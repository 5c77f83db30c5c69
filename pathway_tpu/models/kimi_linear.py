"""The ``kimi_linear`` block family (moonshotai Kimi Linear): Kimi Delta
Attention and multi-head latent attention as token mixers, SwiGLU and
sparse experts beside a shared expert as feed-forward, on the paged
engine's step contract.  No positional encoding anywhere.

A layer is ``x += mixer(RMS(x; norm_in)); x += ffn(RMS(x; norm_ffn))`` with
RMSNorm (f32) and no bias in any projection; after the last layer
``norm_out`` and the untied ``head``.

- ``kda`` (h the normed input; ``H`` heads of ``dk = dv = kda_head_dim``):
  ``[q~ ; k~ ; v~] = h W_qkv``; each stream through its own depthwise causal
  convolution of ``conv_kernel`` taps (no bias), then SiLU; per head
  ``q = l2norm(q) dk^-0.5``, ``k = l2norm(k)``; per channel the log decay
  ``g = -exp(A_log[head]) softplus(W_fb (W_fa h) + dt_bias)``;
  ``beta = sigmoid(h W_b)``, one a head; the delta rule of
  :mod:`pathway_tpu.ops.kda` on a state of ``dk x dv`` a head;
  ``y = (RMS_dv(o; o_norm) * sigmoid(W_gb (W_ga h))) W_o``, the output
  norm's scale shared by all heads.  What a sequence carries between steps:
  the state (f32) and the last ``conv_kernel - 1`` inputs of the three
  convolutions;
- ``mla``: ``q = h W_q`` (heads of ``qk_nope + qk_rope``);
  ``[c~ ; k_r] = h W_kv_a``; ``c = RMS(c~; kv_norm)``; in the absorbed form
  (the same mathematics as expanding ``[k_nope ; v] = c W_kv_b`` a head)
  the key half of ``W_kv_b`` is folded into the query, every head attends
  the one stored row ``[c ; k_r]`` (scores over ``sqrt(qk_nope + qk_rope)``,
  no rotary on either part), and the value half is applied to the mix of
  ``c``.  What a token leaves in the cache: ``c`` and ``k_r``, once;
- the first ``n_dense_layers`` feed-forwards are ``W2(silu(x W1) * x W3)``;
  the others add a shared SwiGLU expert to ``top_k`` of ``n_experts``
  routed ones (:mod:`pathway_tpu.ops.moe`: sigmoid scores, a bias that
  moves the choice only, weights renormalised and times ``route_scale``).
  Where ``n_held_experts`` is given the layer is one share of an
  expert-parallel deployment: its weights hold the experts ``first_expert
  .. first_expert + n_held_experts`` only, the router keeps its
  ``n_experts`` outputs, and what the absent experts would add is left out.

One function, :func:`_forward`, holds that math for the three step
programs.  The latent rows live in a pool of one array whose layer axis
counts the ``mla`` layers, the ``kda`` layers' state and conv inputs in two
arenas under one slot a sequence
(:class:`pathway_tpu.kvcache.hybrid.StateCache`).  Every program also
returns the expert layers' counter vector, summed: the tokens each held
expert received, then the pairs routed to experts held elsewhere, the grouped
matmul's live rows and tiles and the held experts touched
(:data:`pathway_tpu.ops.moe.COUNTER_TAIL`).

Greedy, one device.  Parameters are used in the dtype they come in (the
configuration's: bf16 on the chip); no f32 copy is kept or made.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .encoder import _resolve_dtype
from .lfm2 import _rms, _swiglu, greedy_ids  # noqa: F401

KDA, MLA = "kda", "mla"
_LANES = 128


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    d_model: int = 2304
    n_heads: int = 32
    kda_head_dim: int = 128
    conv_kernel: int = 4
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 9216
    d_ff_expert: int = 1024
    n_experts: int = 256           # the router's width
    n_held_experts: int | None = None  # experts this share holds; None: all
    first_expert: int = 0          # the first of them
    top_k: int = 8
    n_shared_experts: int = 1
    n_dense_layers: int = 1
    layer_types: tuple = (KDA, KDA, KDA, MLA)
    norm_eps: float = 1e-5
    max_len: int = 1048576
    dtype: Any = "auto"  # bf16 on TPU, f32 on CPU (encoder._resolve_dtype)
    route_norm: bool = True
    route_scale: float = 2.446
    kda_chunk: int = 128           # tokens a work item of the chunked scan

    family = "kimi_linear"

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in (KDA, MLA)]
        if bad:
            raise ValueError(f"unknown layer type(s) {sorted(set(bad))}")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is written down here")
        held = self.held_experts
        if not 0 <= self.first_expert <= self.n_experts - held:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + held} "
                f"are not a share of {self.n_experts}")
        if self.kda_chunk & (self.kda_chunk - 1) or self.kda_chunk < 8:
            raise ValueError("kda_chunk must be a power of two, 8 or more")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kda_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == KDA)

    @property
    def mla_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == MLA)

    @property
    def held_experts(self) -> int:
        return self.n_experts if self.n_held_experts is None \
            else self.n_held_experts

    @property
    def share(self):
        """``expert_ffn``'s ``first_expert``: None where every expert is
        held."""
        return None if self.n_held_experts is None else self.first_expert

    @property
    def kda_width(self) -> int:
        return self.n_heads * self.kda_head_dim

    @property
    def latent_width(self) -> int:
        """Values a token leaves in a latent layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """The stored row: :attr:`latent_width` padded to whole lane tiles
        (576 -> 640 at the published widths: 64 lanes of padding)."""
        return -(-self.latent_width // _LANES) * _LANES

    def param_count(self) -> int:
        d, w, lo = self.d_model, self.kda_width, self.kda_head_dim
        kda = 4 * d * w + 2 * (d * lo + lo * w) + d * self.n_heads \
            + 3 * w * self.conv_kernel + self.n_heads + w + self.kda_head_dim
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        mla = d * self.n_heads * qk + d * self.latent_width \
            + self.kv_lora_rank * self.n_heads \
            * (self.qk_nope_head_dim + self.v_head_dim) \
            + self.n_heads * self.v_head_dim * d + self.kv_lora_rank
        dense = 3 * d * self.d_ff
        moe = (self.held_experts + 1) * 3 * d * self.d_ff_expert \
            + (d + 1) * self.n_experts
        n_moe = max(self.n_layers - self.n_dense_layers, 0)
        return (2 * self.vocab_size * d + d
                + len(self.kda_layers) * kda + len(self.mla_layers) * mla
                + 2 * d * self.n_layers
                + (self.n_layers - n_moe) * dense + n_moe * moe)


def decay_parameters(key, n_heads: int, width: int, *, low=0.955,
                     high=0.9998):
    """``(A_log (n_heads,), dt_bias (width,))`` f32 such that, where the
    low-rank decay projection gives zero, a channel's decay a token
    ``exp(-exp(A_log) softplus(dt_bias))`` is log-uniform in its distance
    from 1 between ``low`` and ``high``: ``A_log = 0`` and ``dt_bias`` the
    inverse softplus of ``-log(decay)``.  With ``W_fb`` drawn at a fifth of
    1/sqrt(fan_in) (:func:`init_kimi_linear_params`) the projection is
    N(0, 0.2^2) a channel and moves the rate by a factor of at most 2.2 at
    four deviations: every decay a token lies between 0.9 and 0.9999."""
    u = jax.random.uniform(key, (width,), jnp.float32)
    rate = -jnp.log(1.0 - jnp.exp(
        np.log(1.0 - low) + u * (np.log(1.0 - high) - np.log(1.0 - low))))
    return jnp.zeros((n_heads,), jnp.float32), jnp.log(jnp.expm1(rate))


def init_kimi_linear_params(cfg: KimiLinearConfig, rng: jax.Array,
                            dtype=None) -> dict:
    """Random parameters in the layout the step programs read: matrices
    N(0, 1/fan_in), embeddings 0.02, norm scales 1 +- 0.1, expert bias 0.02,
    conv taps N(0, 1/taps), the decay's parameters by
    :func:`decay_parameters` (``w_fb`` at a fifth of its fan-in scale).  The output projections (``wo``, ``w2``, the
    shared expert's too) of every layer after the first are scaled by
    ``1 / sqrt(2 (L - 1))`` (the reasoning of
    :func:`pathway_tpu.models.lfm2.init_lfm2_params`)."""
    dtype = _resolve_dtype(cfg.dtype) if dtype is None else dtype
    d, H, w, lo = cfg.d_model, cfg.n_heads, cfg.kda_width, cfg.kda_head_dim
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    keys = iter(jax.random.split(rng, 32 * cfg.n_layers + 4))

    def n(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def mat(*shape, scale=1.0):
        return n(shape, scale / np.sqrt(shape[-2]))

    def norm(width):
        return (1.0 + jax.random.normal(next(keys), (width,), jnp.float32)
                * 0.1).astype(dtype)

    params: dict = {"embed": n((cfg.vocab_size, d), 0.02),
                    "head": mat(d, cfg.vocab_size), "norm_out": norm(d),
                    "layers": []}
    for li, kind in enumerate(cfg.layer_types):
        out = 1.0 if li == 0 else 1.0 / np.sqrt(2.0 * (cfg.n_layers - 1))
        lay = {"norm_in": norm(d), "norm_ffn": norm(d)}
        if kind == KDA:
            a_log, dt_bias = decay_parameters(next(keys), H, w)
            lay.update(
                wqkv=mat(d, 3 * w),
                conv_w=n((3 * w, cfg.conv_kernel),
                         1 / np.sqrt(cfg.conv_kernel)),
                w_fa=mat(d, lo), w_fb=mat(lo, w, scale=0.2), a_log=a_log,
                dt_bias=dt_bias, wb=mat(d, H), w_ga=mat(d, lo),
                w_gb=mat(lo, w), o_norm=norm(lo), wo=mat(w, d, scale=out))
        else:
            lay.update(
                wq=mat(d, H * qk), wkv_a=mat(d, cfg.latent_width),
                kv_norm=norm(cfg.kv_lora_rank),
                wkv_b=mat(cfg.kv_lora_rank,
                          H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                wo=mat(H * cfg.v_head_dim, d, scale=out))
        if li < cfg.n_dense_layers:
            lay.update(w1=mat(d, cfg.d_ff), w3=mat(d, cfg.d_ff),
                       w2=mat(cfg.d_ff, d, scale=out))
        else:
            E, held, F = cfg.n_experts, cfg.held_experts, cfg.d_ff_expert
            lay.update(wg=mat(d, E), w1=mat(held, d, F), w3=mat(held, d, F),
                       w2=mat(held, F, d, scale=out),
                       expert_bias=n((E,), 0.02).astype(jnp.float32),
                       shared={"w1": mat(d, F), "w3": mat(d, F),
                               "w2": mat(F, d, scale=out)})
        params["layers"].append(lay)
    return params


_F32_LEAVES = ("expert_bias", "a_log", "dt_bias")


def plan_params(cfg: KimiLinearConfig, params: dict) -> dict:
    """What the engine dispatches with: the parameters as they are where
    they already have the configuration's dtype, else cast once (the
    router's bias and the decay's parameters stay f32), and ``W_kv_b`` of
    every latent layer cut into the halves the absorbed form applies:
    ``w_kb`` (H, qk_nope, rank), to the queries, and ``w_vb`` (H, rank,
    v_head_dim), to the mix."""
    dtype = _resolve_dtype(cfg.dtype)

    def cast(path, leaf):
        keep = any(getattr(k, "key", None) in _F32_LEAVES for k in path)
        return leaf if keep or leaf.dtype == dtype else leaf.astype(dtype)

    plan = jax.tree_util.tree_map_with_path(cast, params)
    H, nope, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    layers = []
    for kind, lay in zip(cfg.layer_types, plan["layers"]):
        if kind == MLA:
            lay = dict(lay)
            b = lay.pop("wkv_b").reshape(r, H, nope + cfg.v_head_dim)
            lay["w_kb"] = jnp.transpose(b[:, :, :nope], (1, 2, 0))
            lay["w_vb"] = jnp.transpose(b[:, :, nope:], (1, 0, 2))
        layers.append(lay)
    return {**plan, "layers": layers}


# -- the block math -----------------------------------------------------------


def _l2norm(x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + eps)


def _conv_inputs(u, carried, tok_col, positions, taps: int):
    """The ``taps - 1`` inputs before each token of the stream, oldest
    first: from the stream where the token's run holds them, else from the
    row's carried inputs ``carried`` (T, taps - 1, W: the inputs before the
    run's first token, oldest first), and zero before the sequence."""
    col = tok_col[:, None]
    prev = []
    for j in range(taps - 1, 0, -1):
        held = jnp.take_along_axis(
            carried, jnp.clip(taps - 1 - j + tok_col, 0, taps - 2
                              )[:, None, None], axis=1)[:, 0]
        p = jnp.where(col >= j, jnp.roll(u, j, axis=0), held)
        prev.append(jnp.where((positions >= j)[:, None], p, 0))
    return prev


def _beta(lay, h):
    """The delta rule's step size, one a head: (T, H) f32 in (0, 1)."""
    return jax.nn.sigmoid(jnp.dot(h, lay["wb"],
                                  preferred_element_type=jnp.float32))


def _log_decay(lay, h, H: int, dk: int):
    """(T, H, dk) f32 <= 0: ``-exp(A_log[head]) softplus(W_fb (W_fa h) +
    dt_bias)``."""
    f32 = jnp.float32
    f = jnp.dot(h @ lay["w_fa"], lay["w_fb"], preferred_element_type=f32)
    return -jnp.exp(lay["a_log"].astype(f32))[None, :, None] \
        * jax.nn.softplus((f + lay["dt_bias"].astype(f32)).reshape(-1, H, dk))


def _carried(prev: list, u):
    """The conv inputs a token leaves behind it, oldest first: the last
    ``taps - 2`` of those before it, and its own."""
    return jnp.stack(prev[1:] + [u], axis=1)


def _fresh_rows(row_start):
    """Rows whose run is their sequence's first: their state starts from
    zero inside the program, whatever the slot's last owner left."""
    return row_start == 0


def _absorbed_query(qh, w_kb, nope: int, pad: int):
    """The absorbed form's query, a head: ``[q_nope W_kb ; q_rope ; 0]``
    against the stored row ``[c ; k_r ; 0]``."""
    return jnp.concatenate([
        jnp.einsum("thn,hnc->thc", qh[..., :nope], w_kb), qh[..., nope:],
        jnp.zeros(qh.shape[:2] + (pad,), qh.dtype)], -1)


def _kda_inputs(lay, cfg: KimiLinearConfig, h, conv_l, slot_of_tok, tok_col,
                positions):
    """The delta rule's operands of a stream's tokens, and the conv inputs
    each token would leave behind it.  Returns ``(q, k, kb, vb (T, H, dk)
    in h's dtype, g (T, H, dk) f32, gate (T, W) f32, carried (T, taps - 1,
    3 W))``."""
    T, H, dk, taps = h.shape[0], cfg.n_heads, cfg.kda_head_dim, cfg.conv_kernel
    f32 = jnp.float32
    u = h @ lay["wqkv"]                                       # (T, 3 W)
    prev = _conv_inputs(u, conv_l[slot_of_tok], tok_col, positions, taps)
    w = lay["conv_w"].astype(f32)
    y = w[:, taps - 1] * u.astype(f32)
    for j, p in enumerate(prev):
        y = y + w[:, j] * p.astype(f32)
    y = y * jax.nn.sigmoid(y)
    q, k, v = (x.reshape(T, H, dk) for x in jnp.split(y, 3, axis=-1))
    q = _l2norm(q) * np.float32(dk ** -0.5)
    k = _l2norm(k)
    g = _log_decay(lay, h, H, dk)
    beta = _beta(lay, h)
    gate = jnp.dot(h @ lay["w_ga"], lay["w_gb"], preferred_element_type=f32)
    dt = h.dtype
    carried = _carried(prev, u)
    return (q.astype(dt), k.astype(dt), (k * beta[..., None]).astype(dt),
            (v * beta[..., None]).astype(dt), g, gate, carried)


def _forward(params: dict, cfg: KimiLinearConfig, pool, conv, state, tokens,
             positions, row_tables, row_start, row_nvalid, row_token_idx,
             tok_row, tok_col, slot_blocks, slot_offsets, logit_idx,
             row_slot, valid, row_live, *, attn: str, decode: bool):
    """One step over a packed stream of T tokens in B rows (the argument
    list of :func:`pathway_tpu.models.decoder.paged_mixed_step`, with the
    latent pool and the two arenas for the K/V pools, plus ``row_slot``
    (B,) the rows' arena slots, ``valid`` (T,) which tokens are real and
    ``row_live`` (B,) which rows are).  ``decode``: every row is one token
    at column 0.  Returns ``(logits (B, V) f32, pool, conv, state, counts
    (held + 3,): ops/moe.py ``expert_ffn``)``."""
    from ..kvcache.paged_attention import (latent_append_attend,
                                           latent_attention,
                                           latent_write_rows)
    from ..ops import kda
    from ..ops.moe import COUNTER_TAIL, expert_ffn

    T = tokens.shape[0]
    H, eps, f32 = cfg.n_heads, cfg.norm_eps, jnp.float32
    kernels = attn == "pallas"
    dtype = params["embed"].dtype
    nope, rope, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.kv_lora_rank
    lanes = cfg.latent_lanes
    scale = 1.0 / np.sqrt(nope + rope)
    # the residual stream accumulates in f32; every matmul takes it normed
    # and rounded to the parameters' dtype, the router takes it unrounded
    x = params["embed"][tokens].astype(f32)                    # (T, D)
    counts = jnp.zeros((cfg.held_experts + len(COUNTER_TAIL),), jnp.int32)
    slot_of_tok = row_slot[tok_row]
    row_first = row_token_idx[:, 0]
    row_fresh = _fresh_rows(row_start)
    if not decode:
        items = kda.chunk_items(row_first, row_fresh, row_nvalid, row_slot,
                                row_live, T, cfg.kda_chunk)
    ki = mi = 0
    for li, (kind, lay) in enumerate(zip(cfg.layer_types, params["layers"])):
        h = _rms(x, lay["norm_in"], eps, dtype)
        if kind == KDA:
            q, k, kb, vb, g, gate, carried = _kda_inputs(
                lay, cfg, h, conv[ki], slot_of_tok, tok_col, positions)
            if decode:
                o, state = kda.kda_decode(
                    q, k, kb, vb, g, state, ki, row_slot, row_fresh,
                    use_pallas=kernels)
            else:
                o, state = kda.kda_mixed(
                    q, k, kb, vb, g, state, ki, items, row_first, row_fresh,
                    row_nvalid, row_slot, row_live, use_pallas=kernels)
            # the row's last token leaves its inputs in the row's slot
            conv = conv.at[ki, row_slot].set(
                carried[logit_idx].astype(conv.dtype))
            y = (_rms(o, lay["o_norm"], eps, f32).reshape(T, -1)
                 * jax.nn.sigmoid(gate)).astype(dtype)
            x = x + y @ lay["wo"]
            ki += 1
        else:
            qh = (h @ lay["wq"]).reshape(T, H, nope + rope)
            kv = h @ lay["wkv_a"]
            c = _rms(kv[:, :r], lay["kv_norm"], eps)
            row = jnp.concatenate(
                [c, kv[:, r:], jnp.zeros((T, lanes - r - rope), c.dtype)], -1)
            q_abs = _absorbed_query(qh, lay["w_kb"], nope, lanes - r - rope)
            if decode:
                a, pool = latent_append_attend(
                    q_abs[:, None], row, pool, row_tables, row_start + 1,
                    slot_blocks, slot_offsets, scale=scale, layer=mi,
                    use_pallas=kernels)
                a = a[:, 0]
            else:
                # all rows land before any row's attention gathers
                pool = latent_write_rows(pool, slot_blocks, slot_offsets,
                                         row, layer=mi, use_pallas=kernels)
                a = latent_attention(
                    q_abs[row_token_idx], pool, row_tables,
                    start_pos=row_start, n_valid=row_nvalid, scale=scale,
                    layer=mi, use_pallas=kernels)[tok_row, tok_col]
            o = jnp.einsum("thc,hcv->thv", a[..., :r], lay["w_vb"])
            x = x + o.reshape(T, -1).astype(dtype) @ lay["wo"]
            mi += 1
        h32 = _rms(x, lay["norm_ffn"], eps)
        h = h32.astype(dtype)
        if li < cfg.n_dense_layers:
            x = x + _swiglu(lay, h)
        else:
            # with a share: the held experts' part, and the pairs elsewhere
            y, n_tok = expert_ffn(
                h, lay, valid, h_route=h32, top_k=cfg.top_k,
                norm_topk=cfg.route_norm, scale=cfg.route_scale,
                renorm_eps=1e-20, use_pallas=kernels, first_expert=cfg.share)
            x = x + y.astype(f32) + _swiglu(lay["shared"], h).astype(f32)
            counts = counts + n_tok
    sel = _rms(x[logit_idx], params["norm_out"], eps, dtype)   # (B, D)
    logits = jnp.dot(sel, params["head"], preferred_element_type=f32)
    return logits, pool, conv, state, counts


def state_mixed_step(params: dict, cfg: KimiLinearConfig, pool, conv, state,
                     tokens, positions, row_tables, row_start, row_nvalid,
                     row_token_idx, tok_row, tok_col, slot_blocks,
                     slot_offsets, logit_idx, row_slot, *,
                     attn: str = "reference"):
    """The ragged fused step (decode rows and prompt chunks on one packed
    stream) for this family.  A packed token is real where its row's run
    holds it (padding tokens point at row 0, column 0, which is another
    token's place); a row is real where its first token is its own."""
    T, B = tokens.shape[0], row_start.shape[0]
    valid = row_token_idx[tok_row, tok_col] == jnp.arange(T, dtype=jnp.int32)
    first = row_token_idx[:, 0]
    row_live = valid[first] & (tok_row[first] == jnp.arange(B, dtype=jnp.int32))
    return _forward(
        params, cfg, pool, conv, state, tokens, positions, row_tables,
        row_start, row_nvalid, row_token_idx, tok_row, tok_col, slot_blocks,
        slot_offsets, logit_idx, row_slot, valid, row_live, attn=attn,
        decode=False)


def state_decode_step(params: dict, cfg: KimiLinearConfig, pool, conv, state,
                      token, positions, block_tables, slot_blocks,
                      slot_offsets, row_slot, *, attn: str = "reference"):
    """One token a row.  An idle row has the null block first in its table
    and rides slot 0."""
    B = token.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    live = block_tables[:, 0] > 0
    return _forward(
        params, cfg, pool, conv, state, token, positions, block_tables,
        positions, jnp.ones((B,), jnp.int32), rows[:, None], rows,
        jnp.zeros((B,), jnp.int32), slot_blocks, slot_offsets, rows,
        row_slot, live, live, attn=attn, decode=True)


def state_chained_decode(params: dict, cfg: KimiLinearConfig, pool, conv,
                         state, token, positions, block_tables, slot_blocks,
                         slot_offsets, row_slot, *, attn: str = "reference"):
    """K greedy decode steps in one program (``slot_blocks`` /
    ``slot_offsets`` (B, K), the host's pre-extended slots), step t's ids
    feeding step t + 1, the state riding the scan.  Returns ``(ids (B, K),
    pool, conv, state, counts)``."""
    from ..ops.moe import COUNTER_TAIL

    K = slot_blocks.shape[1]
    maxp = cfg.max_len - 1

    def body(carry, xs):
        tok, pl_, cv, st, cnt = carry
        sb, so, t = xs
        logits, pl_, cv, st, n_tok = state_decode_step(
            params, cfg, pl_, cv, st, tok, jnp.minimum(positions + t, maxp),
            block_tables, sb, so, row_slot, attn=attn)
        ids = greedy_ids(logits)
        return (ids, pl_, cv, st, cnt + n_tok), ids

    init = (token.astype(jnp.int32), pool, conv, state,
            jnp.zeros((cfg.held_experts + len(COUNTER_TAIL),), jnp.int32))
    (_last, pool, conv, state, counts), ids = jax.lax.scan(
        body, init, (slot_blocks.T, slot_offsets.T,
                     jnp.arange(K, dtype=jnp.int32)))
    return ids.T, pool, conv, state, counts
