"""The ``phi4_mini_flash`` block family (microsoft Phi-4-mini-flash-reasoning,
the SambaY decoder-hybrid-decoder, arXiv:2507.06607): Mamba selective-scan
layers and sliding-window differential attention in a self-decoder, one
full-attention layer whose K/V the whole cross-decoder reads, and a
cross-decoder of gated memory units and cross-attention, on the paged
engine's step contract.

``x0 = embed[token]``.  Every layer is::

    h = x + mixer(LN(x; ln1))
    x' = h + W2(silu(LN(h; ln2) W1) * LN(h; ln2) W3)

with LayerNorm (weight and bias, ``norm_eps``) in f32; after the last layer
``LN(x; ln_f)`` and the head tied to the embedding.  With ``L`` layers and
``half = L / 2`` the mixers are, by layer index ``i``:

- ``i < half + 2``, ``i`` even: Mamba (``mamba``), ``E = expand * d_model``
  channels of ``d_state`` states: ``[u; z] = x W_in``; ``u' = silu(conv(u) +
  conv_b)``, a causal depthwise convolution of ``d_conv`` taps; ``[delta; B;
  C] = u' W_x`` (``dt_rank + 2 d_state``); ``dt = softplus(delta W_dt +
  dt_bias)``; ``a = -exp(A_log)``; ``s_t = exp(dt_t a) * s_{t-1} + (dt_t u'_t)
  B_t^T`` in f32 (:mod:`pathway_tpu.ops.ssm`); ``y_t = C_t^T s_t + D u'_t``;
  the mixer is ``(y * silu(z)) W_out``.  Layer ``half`` is the MEMORY
  layer: its ``y * silu(z)`` is kept as ``M``;
- ``i < half + 1``, ``i`` odd: sliding-window differential attention
  (``sliding``), key ``j`` visible to query ``i`` iff ``i - window < j <=
  i``; ``i = half + 1``: the same over every earlier key (``full``), whose
  K/V is the ONLY full-context cache;
- ``i >= half + 2``, even: a gated memory unit (``gmu``), ``(silu(x W1) *
  M) W2``; odd: cross-attention (``cross``), its own ``W_q`` / ``W_o`` over
  layer ``half + 1``'s K/V, writing no cache.

Differential attention, ``n_heads`` query heads of ``head_dim`` on
``n_kv_heads`` key heads: query heads ``0 .. H/2`` are ``q1`` and the rest
``q2``, key heads ``0 .. KV/2`` are ``k1`` and the rest ``k2``, and the
values are ``KV/2`` heads of ``2 head_dim``, shared by both halves (query
pair ``p`` reads key head ``p // (H / KV)`` of its half and value head
``p // (H / KV)``); no positional encoding (the Mamba layers carry
position)::

    o = softmax(q1 k1^T / sqrt(hd)) v - lam softmax(q2 k2^T / sqrt(hd)) v
    o = RMS(o; subln) * (1 - lam_init)

per pair, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init`` a layer and
``lam_init = 0.8 - 0.6 exp(-0.3 i)``.  The kernels compute both maps of
every query head over the shared values (the paged kernels' ``v_heads``);
the difference and the norm are taken here.

The cross-decoder reads no earlier token's cross-decoder state, so the
tokens that emit nothing need layers ``0 .. half + 1`` alone: a step runs
them over its packed stream and runs layers ``half + 2 ..`` and the head
over the rows' emitting tokens only (``logit_idx``), their cross-attention
queries over the full layer's pool as this step left it (the row's own
chunk included), their memory units on ``M`` of the same tokens.

One function, :func:`_forward`, holds that math for the three step programs
of the ``window_state`` contract (:class:`pathway_tpu.kvcache.windowed
.WindowStateCache`: the full layer's K/V in the paged pool of one layer, the
window layers' in the window pool, each Mamba layer's carried conv inputs
and f32 state in a slot of the arena).  There is no expert layer: the
programs' counter vector counts zero experts, and then the rows a mixed
step ran each decoder over (:data:`COUNTERS`).

Greedy, one device.  Parameters are used in the dtype they come in (the
configuration's: bf16 on the chip; ``A_log``, ``D``, ``dt_bias``, the conv
bias and the lambdas stay f32); no f32 copy is kept or made.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (_carried, _conv_inputs, _fresh_rows, _rms, _swiglu,
                     cast_plan, greedy_steps)
from .encoder import _resolve_dtype

MAMBA, SLIDING, FULL, GMU, CROSS = ("mamba", "sliding_attention",
                                    "full_attention", "gmu", "cross")


@dataclasses.dataclass(frozen=True)
class Phi4MiniFlashConfig:
    vocab_size: int = 200064
    d_model: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64             # hidden_size / num_attention_heads
    d_ff: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2          # a Mamba layer every second layer
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160             # ceil(d_model / 16)
    norm_eps: float = 1e-5
    subln_eps: float = 1e-5
    max_len: int = 262144
    dtype: Any = "auto"  # bf16 on TPU, f32 on CPU (encoder._resolve_dtype)

    family = "phi4_mini_flash"

    def __post_init__(self):
        if self.n_layers % 4 or self.mb_per_layer != 2:
            raise ValueError(
                "a SambaY stack alternates Mamba and attention (mb_per_layer "
                f"2) over a multiple of four layers; got {self.n_layers} "
                f"layers, mb_per_layer {self.mb_per_layer}")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError(
                f"n_heads={self.n_heads} must be a multiple of an even "
                f"n_kv_heads={self.n_kv_heads}: two key halves")

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def rows_only_layers(self) -> tuple:
        """The cross-decoder (its memory units and cross-attention layers):
        the layers a mixed step runs over the rows' emitting tokens alone
        (:func:`_forward`), which the chunk rule bills on the batch's rows
        (:func:`pathway_tpu.obs.memory.step_flops`)."""
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t in (GMU, CROSS))

    @property
    def n_self(self) -> int:
        """Layers every token runs: the self-decoder, the memory layer and
        the full layer, below the cross-decoder."""
        return self.n_layers - len(self.rows_only_layers)

    @property
    def memory_layer(self) -> int:
        """The Mamba layer whose gated scan output the memory units read."""
        return self.n_layers // 2

    @property
    def layer_types(self) -> tuple:
        half, out = self.n_layers // 2, []
        for i in range(self.n_layers):
            even = i % self.mb_per_layer == 0
            if i < half + 2:
                out.append(MAMBA if even else (FULL if i == half + 1
                                               else SLIDING))
            else:
                out.append(GMU if even else CROSS)
        return tuple(out)

    def _of(self, kind: str) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    @property
    def mamba_layers(self) -> tuple:
        return self._of(MAMBA)

    @property
    def window_layers(self) -> tuple:
        return self._of(SLIDING)

    @property
    def full_layers(self) -> tuple:
        return self._of(FULL)

    @property
    def v_heads(self) -> int:
        """Value heads of ``2 head_dim``: one a key pair."""
        return self.n_kv_heads // 2

    def param_count(self) -> int:
        d, E, N, R, K = (self.d_model, self.d_inner, self.d_state,
                         self.dt_rank, self.d_conv)
        hd, H, KV = self.head_dim, self.n_heads, self.n_kv_heads
        diff = 4 * hd + 2 * hd             # the lambdas and subln
        mixer = {
            MAMBA: d * 2 * E + E * K + E + E * (R + 2 * N) + R * E + E
            + N * E + E + E * d,
            SLIDING: d * H * hd + 2 * d * KV * hd + H * hd * d + diff,
            FULL: d * H * hd + 2 * d * KV * hd + H * hd * d + diff,
            GMU: 2 * d * E,
            CROSS: 2 * d * H * hd + diff}
        per_layer = 4 * d + 3 * d * self.d_ff
        return self.vocab_size * d + 2 * d + sum(
            per_layer + mixer[t] for t in self.layer_types)


def lambda_init(layer: int) -> float:
    """``lam_init`` of differential attention at a layer's index."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * layer))


def mamba_init(key, E: int, N: int) -> dict:
    """The published initialisation of a Mamba layer's scan parameters over
    ``E`` channels of ``N`` states, in f32: ``A_log = log(1 .. N)`` a
    channel (laid out (N, E)), ``D = 1``, and ``dt_bias`` the inverse
    softplus of a step drawn log-uniform in [1e-3, 1e-1] a channel."""
    u = jax.random.uniform(key, (E,), jnp.float32)
    dt = jnp.exp(u * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
    return {"A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None],
                (N, E)),
            "D": jnp.ones((E,), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}


def init_phi4_mini_flash_params(cfg: Phi4MiniFlashConfig, rng: jax.Array,
                                dtype=None) -> dict:
    """Random parameters in the layout the step programs read: matrices
    N(0, 1/fan_in) (the output projections of every layer after the first
    times ``1 / sqrt(2 (L - 1))``), embeddings 0.02, LayerNorm weights 1 +-
    0.1 and biases 0.02, the conv N(0, 1/taps) with bias 0.02, the lambdas
    N(0, 0.1^2), the scan parameters as :func:`mamba_init`."""
    dtype = _resolve_dtype(cfg.dtype) if dtype is None else dtype
    d, E, N, R = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.dt_rank
    hd, H, KV, f32 = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, jnp.float32
    keys = iter(jax.random.split(rng, 24 * cfg.n_layers + 4))

    def n(shape, scale, mean=0.0, keep_f32=False):
        x = mean + jax.random.normal(next(keys), shape, f32) * scale
        return x if keep_f32 else x.astype(dtype)

    def mat(*shape, scale=1.0):
        return n(shape, scale / np.sqrt(shape[-2]))

    def norm(prefix):
        return {f"{prefix}_w": n((d,), 0.1, 1.0), f"{prefix}_b": n((d,), 0.02)}

    params: dict = {"embed": n((cfg.vocab_size, d), 0.02), **norm("ln_f"),
                    "layers": []}
    for li, kind in enumerate(cfg.layer_types):
        out = 1.0 if li == 0 else 1.0 / np.sqrt(2.0 * (cfg.n_layers - 1))
        lay = {**norm("ln1"), **norm("ln2"), "w1": mat(d, cfg.d_ff),
               "w3": mat(d, cfg.d_ff), "w2": mat(cfg.d_ff, d, scale=out)}
        if kind == MAMBA:
            lay.update(w_in=mat(d, 2 * E), conv_w=n((E, cfg.d_conv),
                                                    cfg.d_conv ** -0.5),
                       conv_b=n((E,), 0.02, keep_f32=True),
                       w_x=mat(E, R + 2 * N), w_dt=mat(R, E),
                       w_out=mat(E, d, scale=out),
                       **mamba_init(next(keys), E, N))
        elif kind == GMU:
            lay.update(w_gate=mat(d, E), w_mem=mat(E, d, scale=out))
        else:
            lay.update(wq=mat(d, H * hd), wo=mat(H * hd, d, scale=out),
                       subln=n((2 * hd,), 0.1, 1.0),
                       **{k: n((hd,), 0.1, keep_f32=True)
                          for k in ("lq1", "lk1", "lq2", "lk2")})
            if kind != CROSS:
                lay.update(wk=mat(d, KV * hd), wv=mat(d, KV * hd))
        params["layers"].append(lay)
    return params


_F32_LEAVES = ("A_log", "D", "dt_bias", "conv_b", "lq1", "lk1", "lq2", "lk2")


def plan_params(cfg: Phi4MiniFlashConfig, params: dict) -> dict:
    """:func:`~pathway_tpu.models.blocks.cast_plan` with the scan's
    parameters, the conv bias and the lambdas kept f32."""
    return cast_plan(cfg, params, _F32_LEAVES)


def _layer_norm(x, lay: dict, prefix: str, eps: float, dtype=None):
    """LayerNorm in f32 with the layer's weight and bias; the result in
    ``dtype`` (default: x's)."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    c = x32 - mu
    y = c * jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps)
    y = y * lay[f"{prefix}_w"].astype(jnp.float32) \
        + lay[f"{prefix}_b"].astype(jnp.float32)
    return y.astype(dtype or x.dtype)


def _differential(a, lay: dict, layer: int, eps: float, dtype):
    """Both maps of every query head, (N, H, 2 hd) with ``q1``'s heads
    first, to the layer's differential output (N, H * hd) in ``dtype``."""
    H = a.shape[1]
    f32 = jnp.float32
    lam0 = lambda_init(layer)
    lam = jnp.exp(jnp.dot(lay["lq1"], lay["lk1"])) \
        - jnp.exp(jnp.dot(lay["lq2"], lay["lk2"])) + lam0
    o = a[:, :H // 2].astype(f32) - lam * a[:, H // 2:].astype(f32)
    o = _rms(o, lay["subln"], eps, f32) * np.float32(1.0 - lam0)
    return o.reshape(o.shape[0], -1).astype(dtype)


def _mamba_inputs(lay: dict, cfg: Phi4MiniFlashConfig, h, conv_l,
                  slot_of_tok, tok_col, positions):
    """A Mamba layer's scan operands over the stream: ``(u' (T, E), dt
    (T, E), b, c (T, N) f32, z (T, E), carried (T, taps - 1, E): the conv
    inputs each token leaves behind it)``."""
    E, N, R, taps, f32 = (cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv,
                          jnp.float32)
    uz = h @ lay["w_in"]
    u, z = uz[:, :E], uz[:, E:]
    prev = _conv_inputs(u, conv_l[slot_of_tok], tok_col, positions, taps)
    w = lay["conv_w"].astype(f32)
    y = w[:, taps - 1] * u.astype(f32) + lay["conv_b"]
    for j, p in enumerate(prev):
        y = y + w[:, j] * p.astype(f32)
    u1 = y * jax.nn.sigmoid(y)
    xdbc = jnp.dot(u1.astype(h.dtype), lay["w_x"], preferred_element_type=f32)
    dt = jax.nn.softplus(
        jnp.dot(xdbc[:, :R].astype(h.dtype), lay["w_dt"],
                preferred_element_type=f32) + lay["dt_bias"])
    return (u1, dt, xdbc[:, R:R + N], xdbc[:, R + N:], z,
            _carried(prev, u))


def _forward(params: dict, cfg: Phi4MiniFlashConfig, k_pool, v_pool, kw_pool,
             vw_pool, conv, state, tokens, positions, row_tables, row_start,
             row_nvalid, row_token_idx, tok_row, tok_col, slot_blocks,
             slot_offsets, logit_idx, win_tables, row_slot, valid, row_live,
             *, attn: str, decode: bool):
    """One step over a packed stream of T tokens in B rows (the argument
    list of :func:`pathway_tpu.models.decoder.paged_mixed_step` with the
    cache's six arrays, plus ``win_tables`` (B, NB) the rows' window tables,
    ``row_slot`` (B,) their arena slots, ``valid`` (T,) which tokens are
    real and ``row_live`` (B,) which rows are).  ``decode``: every row is
    one token at column 0.  Returns ``(logits (B, V) f32, k_pool, v_pool,
    kw_pool, vw_pool, conv, state, counts (COUNTER_TAIL + COUNTERS,): no
    expert)``."""
    from ..kvcache.paged_attention import (paged_append_attend,
                                           paged_attention, paged_write_rows)
    from ..ops import ssm
    from ..ops.moe import COUNTER_TAIL

    del valid  # no expert layer: padding tokens are never counted
    T, B = tokens.shape[0], logit_idx.shape[0]
    hd, eps, f32 = cfg.head_dim, cfg.norm_eps, jnp.float32
    kernels = attn == "pallas"
    dtype = params["embed"].dtype
    vh = cfg.v_heads
    # the residual stream accumulates in f32; every matmul takes it normed
    # and rounded to the parameters' dtype
    x = params["embed"][tokens].astype(f32)                    # (T, D)
    # a token's window slot: its window table's entry at its position; a
    # token the full pool sends to the null block (padding) goes there too
    win_blocks = jnp.where(
        slot_blocks > 0,
        win_tables[tok_row, positions // kw_pool.shape[2]], 0)
    slot_of_tok = row_slot[tok_row]
    row_first = row_token_idx[:, 0]
    row_fresh = _fresh_rows(row_start)
    packed = (row_token_idx, tok_row, tok_col)
    if not decode:
        items = ssm.chunk_items(row_first, row_fresh, row_nvalid, row_slot,
                                row_live, T, ssm.CHUNK)

    def ffn(x, lay):
        return x + _swiglu(lay, _layer_norm(x, lay, "ln2", eps, dtype)
                           ).astype(f32)

    memory = None
    mi = wi = 0
    for li in range(cfg.n_self):
        kind, lay = cfg.layer_types[li], params["layers"][li]
        h = _layer_norm(x, lay, "ln1", eps, dtype)
        if kind == MAMBA:
            u1, dt, b, c, z, carried = _mamba_inputs(
                lay, cfg, h, conv[mi], slot_of_tok, tok_col, positions)
            a = -jnp.exp(lay["A_log"])
            if decode:
                y, state = ssm.mamba_decode(u1, dt, b, c, a, state, mi,
                                            row_slot, row_fresh,
                                            use_pallas=kernels)
            else:
                y, state = ssm.mamba_mixed(
                    u1, dt, b, c, a, state, mi, items, row_first, row_fresh,
                    row_nvalid, row_slot, row_live, use_pallas=kernels)
            # the row's last token leaves its inputs in the row's slot
            conv = conv.at[mi, row_slot].set(
                carried[logit_idx].astype(conv.dtype))
            g = (y + lay["D"] * u1) * jax.nn.sigmoid(z.astype(f32)) \
                * z.astype(f32)
            if li == cfg.memory_layer:
                memory = g[logit_idx]                           # (B, E)
            x = x + (g.astype(dtype) @ lay["w_out"]).astype(f32)
            mi += 1
        else:
            q = (h @ lay["wq"]).reshape(T, -1, hd)
            k1 = (h @ lay["wk"]).reshape(T, -1, hd)
            v1 = (h @ lay["wv"]).reshape(T, vh, -1)
            if kind == SLIDING:
                pools, tables, blocks = (kw_pool, vw_pool), win_tables, \
                    win_blocks
                layer, extra = wi, {"window": cfg.sliding_window}
            else:
                pools, tables, blocks = (k_pool, v_pool), row_tables, \
                    slot_blocks
                layer, extra = 0, {}
            if kernels and decode:
                a, *pools = paged_append_attend(
                    q[:, None], k1, v1, *pools, tables, row_start + 1, blocks,
                    slot_offsets, layer=layer, use_pallas=True, v_heads=vh,
                    **extra)
                a = a[:, 0]
            else:
                # all rows land before any row's attention gathers
                pools = paged_write_rows(
                    *pools, blocks, slot_offsets, k1, v1, layer=layer,
                    use_pallas=kernels)
                a = paged_attention(
                    q, *pools, tables, start_pos=row_start,
                    n_valid=row_nvalid, packed=packed, layer=layer,
                    use_pallas=kernels, v_heads=vh, **extra)
            if kind == SLIDING:
                kw_pool, vw_pool = pools
                wi += 1
            else:
                k_pool, v_pool = pools
            o = _differential(a, lay, li, cfg.subln_eps, dtype)
            x = x + (o @ lay["wo"]).astype(f32)
        x = ffn(x, lay)
    # the cross-decoder: the rows' emitting tokens alone, each a query at
    # its own position over the full layer's pool as this step left it
    xr = x[logit_idx]                                          # (B, D)
    at = jnp.where(row_live, positions[logit_idx], 0)  # idle: one key
    for li in cfg.rows_only_layers:
        kind, lay = cfg.layer_types[li], params["layers"][li]
        h = _layer_norm(xr, lay, "ln1", eps, dtype)
        if kind == GMU:
            gate = jnp.dot(h, lay["w_gate"], preferred_element_type=f32)
            o = (gate * jax.nn.sigmoid(gate) * memory).astype(dtype)
            xr = xr + (o @ lay["w_mem"]).astype(f32)
        else:
            q = (h @ lay["wq"]).reshape(B, 1, -1, hd)
            a = paged_attention(
                q, k_pool, v_pool, row_tables, start_pos=at,
                n_valid=jnp.ones((B,), jnp.int32), layer=0,
                use_pallas=kernels, v_heads=vh)[:, 0]
            o = _differential(a, lay, li, cfg.subln_eps, dtype)
            xr = xr + (o @ lay["wo"]).astype(f32)
        xr = ffn(xr, lay)
    sel = _layer_norm(xr, params, "ln_f", eps, dtype)
    logits = jax.lax.dot_general(sel, params["embed"],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)
    # no expert counted; then :data:`COUNTERS`, the rows each decoder ran
    # over, as the program's own arrays have them (a mixed step's alone: a
    # decode step's rows all emit)
    ran = (0, 0) if decode else (xr.shape[0], T)
    counts = jnp.concatenate([jnp.zeros((len(COUNTER_TAIL),), jnp.int32),
                              jnp.array(ran, jnp.int32)])
    return logits, k_pool, v_pool, kw_pool, vw_pool, conv, state, counts


# what the programs count after the expert layers' COUNTER_TAIL, each a
# counter of the cache's stats: the rows a mixed step ran layers
# ``half + 2 ..`` and the head over, and the packed tokens it ran the layers
# below over
COUNTERS = ("cross_decoder_rows", "self_decoder_rows")

# the three programs of the window-and-state contract: the rows' window
# tables and their arena slots ride after ``logit_idx``; ``_forward`` is
# found by name at trace time
window_state_mixed_step, window_state_decode_step, \
    window_state_chained_decode = greedy_steps(
        lambda *a, **kw: _forward(*a, **kw), lambda cfg: 0, row_live=True,
        per_row=2, counters=COUNTERS)
