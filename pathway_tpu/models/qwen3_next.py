"""The ``qwen3_next`` block family (Qwen3-Next): gated DeltaNet and gated
full attention as token mixers, a softmax router over sparse experts beside
a sigmoid-gated shared expert as feed-forward, on the paged engine's step
contract.

A layer is ``x += mixer(N(x; norm_in)); x += moe(N(x; norm_ffn))`` with
``N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)``, a ZERO-CENTRED scale, in
f32 (the layer norms, the final norm, the q and k norms), and no bias in
any projection; after the last layer ``norm_out`` and the untied ``head``.

- ``full`` (``H`` query heads over ``KV`` K/V heads of ``hd``):
  ``[q_h ; gate_h] = h W_q`` a head (the first ``hd`` of a head's ``2 hd``
  outputs its query, the others its gate); ``k = h W_k``, ``v = h W_v``;
  ``q_h <- N(q_h; q_norm)``, ``k_j <- N(k_j; k_norm)`` over a head;
  rotate-half rotary on the first ``rotary_dim`` of a head, the others
  pass; ``softmax(q k^T / sqrt(hd)) v``, causal, query head ``h`` on K/V
  head ``h // (H // KV)``; ``(a * sigmoid(gate)) W_o``;
- ``gdn`` (gated DeltaNet; ``Hk`` key heads of ``dk`` feed ``Hv`` value
  heads of ``dv``): ``[q ; k ; v ; z] = h W_qkvz``, ``[b ; a] = h W_ba``;
  ``[q ; k ; v]`` through ONE depthwise causal convolution of
  ``conv_kernel`` taps (no bias), then SiLU; ``q = l2norm(q) dk^-0.5``,
  ``k = l2norm(k)`` a head; value head ``h`` takes ``q, k`` of key head
  ``h // (Hv // Hk)``; ``beta = sigmoid(b)`` and ONE log decay a value head
  and token ``g = -exp(A_log[h]) softplus(a + dt_bias[h])``; the delta rule
  of :mod:`pathway_tpu.ops.kda` (told that the decay is one number a head)
  on a state of ``dk x dv`` a value head; ``y = (N0(o; o_norm) * SiLU(z))
  W_out`` with ``N0`` a plain scale (not zero-centred), one vector of ``dv``
  for all heads.  What a sequence carries between steps: the state (f32)
  and the last ``conv_kernel - 1`` inputs of the convolution;
- every layer's feed-forward: ``p = softmax(h W_g)`` over all ``n_experts``
  in f32, the ``top_k`` largest, weights ``p_e`` over the chosen ones' sum
  (:mod:`pathway_tpu.ops.moe`, ``score="softmax"``), an expert
  ``W2(silu(x W1) * x W3)``; plus ``sigmoid(h w_sg) * shared(h)``.  Where
  ``n_held_experts`` is given the layer is one share of an expert-parallel
  deployment (as :mod:`pathway_tpu.models.kimi_linear`): its weights hold
  the experts ``first_expert .. first_expert + n_held_experts`` only, the
  router keeps its ``n_experts`` outputs, and what the absent experts would
  add is left out.

One function, :func:`_forward`, holds that math for the three step
programs.  The full layers' K/V lives in the paged pool, whose layer axis
counts them alone, the ``gdn`` layers' state and conv inputs in two arenas
under one slot a sequence
(:class:`pathway_tpu.kvcache.hybrid.KVStateCache`).  Every program also
returns the expert layers' counter vector
(:func:`pathway_tpu.ops.moe.expert_ffn`'s), summed.

The ``q, k`` of a key head reach its value heads by a REPEAT before the
kernels, not through the kernels' index maps: ``kb = beta k`` is a value
head's anyway (``beta`` is), so only ``q`` and ``k`` would shrink (2 x 2.2
MB of bf16 a layer of a 528-token step, ~5 us at the HBM roof), and the two
kernels and their jaxprs stay what the per-channel family runs.

Greedy, one device.  Parameters are used in the dtype they come in (the
configuration's: bf16 on the chip); no f32 copy is kept or made.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (_carried, _conv_inputs, _fresh_rows, _gated, _l2norm,
                     _partial_rope, _rms, _swiglu, cast_plan,
                     decay_parameters, greedy_steps)
from .encoder import _resolve_dtype

GDN, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    d_model: int = 2048
    n_heads: int = 16              # query heads of a full-attention layer
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64           # partial_rotary_factor x head_dim
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    conv_kernel: int = 4
    d_ff_expert: int = 512
    d_ff_shared: int = 512
    n_experts: int = 512           # the router's width
    n_held_experts: int | None = None  # experts this share holds; None: all
    first_expert: int = 0          # the first of them
    top_k: int = 10
    layer_types: tuple = (GDN, GDN, GDN, FULL)
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    max_len: int = 262144
    dtype: Any = "auto"  # bf16 on TPU, f32 on CPU (encoder._resolve_dtype)
    gdn_chunk: int = 128           # tokens a work item of the chunked scan

    family = "qwen3_next"

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in (GDN, FULL)]
        if bad:
            raise ValueError(f"unknown layer type(s) {sorted(set(bad))}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads={self.n_heads} must be a multiple of "
                f"n_kv_heads={self.n_kv_heads}")
        if self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError(
                f"gdn_value_heads={self.gdn_value_heads} must be a multiple "
                f"of gdn_key_heads={self.gdn_key_heads}")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"rotary_dim={self.rotary_dim} is no even part "
                             f"of head_dim={self.head_dim}")
        held = self.held_experts
        if not 0 <= self.first_expert <= self.n_experts - held:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + held} "
                f"are not a share of {self.n_experts}")
        if self.gdn_chunk & (self.gdn_chunk - 1) or self.gdn_chunk < 8:
            raise ValueError("gdn_chunk must be a power of two, 8 or more")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def gdn_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == GDN)

    @property
    def full_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == FULL)

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
    def key_width(self) -> int:
        return self.gdn_key_heads * self.gdn_key_dim

    @property
    def value_width(self) -> int:
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def conv_width(self) -> int:
        """The convolution's channels: the ``[q ; k ; v]`` stream."""
        return 2 * self.key_width + self.value_width

    def param_count(self) -> int:
        d, hd, Hv = self.d_model, self.head_dim, self.gdn_value_heads
        gdn = d * (self.conv_width + self.value_width) + d * 2 * Hv \
            + self.conv_width * self.conv_kernel + self.value_width * d \
            + self.gdn_value_dim + 2 * Hv
        full = d * 2 * self.n_heads * hd + 2 * d * self.n_kv_heads * hd \
            + self.n_heads * hd * d + 2 * hd
        moe = 2 * d + d * self.n_experts \
            + self.held_experts * 3 * d * self.d_ff_expert \
            + 3 * d * self.d_ff_shared + d
        return (2 * self.vocab_size * d + d + len(self.gdn_layers) * gdn
                + len(self.full_layers) * full + self.n_layers * moe)


def init_qwen3_next_params(cfg: Qwen3NextConfig, rng: jax.Array,
                           dtype=None) -> dict:
    """Random parameters in the layout the step programs read: matrices
    N(0, 1/fan_in), embeddings 0.02, zero-centred norm scales N(0, 0.1^2)
    (the plain one of the DeltaNet output norm 1 +- 0.1), conv taps N(0,
    1/taps), the decay's parameters by
    :func:`pathway_tpu.models.blocks.decay_parameters` (the ``a`` half
    of ``wba`` at a fifth of its fan-in scale, so that a head's decay a
    token lies between 0.9 and 0.9999).  The output projections (``wo``,
    ``w2``, the shared expert's too) of every layer after the first are
    scaled by ``1 / sqrt(2 (L - 1))`` (the reasoning of
    :func:`pathway_tpu.models.lfm2.init_lfm2_params`)."""
    dtype = _resolve_dtype(cfg.dtype) if dtype is None else dtype
    d, hd, H, KV = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    Hv = cfg.gdn_value_heads
    keys = iter(jax.random.split(rng, 32 * cfg.n_layers + 4))

    def n(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def mat(*shape, scale=1.0):
        return n(shape, scale / np.sqrt(shape[-2]))

    params: dict = {"embed": n((cfg.vocab_size, d), 0.02),
                    "head": mat(d, cfg.vocab_size), "norm_out": n((d,), 0.1),
                    "layers": []}
    for li, kind in enumerate(cfg.layer_types):
        out = 1.0 if li == 0 else 1.0 / np.sqrt(2.0 * (cfg.n_layers - 1))
        lay = {"norm_in": n((d,), 0.1), "norm_ffn": n((d,), 0.1)}
        if kind == GDN:
            a_log, dt_bias = decay_parameters(next(keys), Hv, Hv)
            lay.update(
                wqkvz=mat(d, cfg.conv_width + cfg.value_width),
                wba=jnp.concatenate([mat(d, Hv), mat(d, Hv, scale=0.2)], 1),
                conv_w=n((cfg.conv_width, cfg.conv_kernel),
                         1 / np.sqrt(cfg.conv_kernel)),
                a_log=a_log, dt_bias=dt_bias,
                o_norm=(1.0 + n((cfg.gdn_value_dim,), 0.1).astype(
                    jnp.float32)).astype(dtype),
                wo=mat(cfg.value_width, d, scale=out))
        else:
            lay.update(wq=mat(d, H * 2 * hd), wk=mat(d, KV * hd),
                       wv=mat(d, KV * hd), q_norm=n((hd,), 0.1),
                       k_norm=n((hd,), 0.1), wo=mat(H * hd, d, scale=out))
        E, held, F = cfg.n_experts, cfg.held_experts, cfg.d_ff_expert
        Fs = cfg.d_ff_shared
        lay.update(wg=mat(d, E), w1=mat(held, d, F), w3=mat(held, d, F),
                   w2=mat(held, F, d, scale=out), w_sg=mat(d, 1),
                   shared={"w1": mat(d, Fs), "w3": mat(d, Fs),
                           "w2": mat(Fs, d, scale=out)})
        params["layers"].append(lay)
    return params


def plan_params(cfg: Qwen3NextConfig, params: dict) -> dict:
    """:func:`~pathway_tpu.models.blocks.cast_plan` with the decay's
    parameters kept f32."""
    return cast_plan(cfg, params, ("a_log", "dt_bias"))


# -- the block math -----------------------------------------------------------


def _scale(w):
    """A zero-centred norm scale as it multiplies: ``1 + w``, f32."""
    return 1.0 + w.astype(jnp.float32)


def _zrms(x, w, eps: float, dtype=None):
    """``N(x; w)``: RMSNorm in f32 with the zero-centred scale ``w``."""
    return _rms(x, _scale(w), eps, dtype)


def _key_heads(x, rep: int):
    """(T, Hk, dk) -> (T, Hk * rep, dk): value head ``h`` takes key head
    ``h // rep``."""
    return x if rep == 1 else jnp.repeat(x, rep, axis=1)


def _log_decay(lay, a):
    """(T, Hv) f32 <= 0: ``-exp(A_log[head]) softplus(a + dt_bias[head])``,
    one number a value head and token."""
    f32 = jnp.float32
    return -jnp.exp(lay["a_log"].astype(f32))[None, :] \
        * jax.nn.softplus(a + lay["dt_bias"].astype(f32)[None, :])


def _shared_gate(lay, h):
    """(T, 1) f32 in (0, 1): the shared expert's gate ``sigmoid(h w_sg)``."""
    return jax.nn.sigmoid(jnp.dot(h, lay["w_sg"],
                                  preferred_element_type=jnp.float32))


def _gdn_inputs(lay, cfg: Qwen3NextConfig, h, conv_l, slot_of_tok, tok_col,
                positions):
    """The delta rule's operands of a stream's tokens, and the conv inputs
    each token would leave behind it.  Returns ``(q, k, kb (T, Hv, dk), vb
    (T, Hv, dv) in h's dtype, g (T, Hv) f32, z (T, Hv * dv) f32, carried
    (T, taps - 1, conv_width))``."""
    T, taps, f32 = h.shape[0], cfg.conv_kernel, jnp.float32
    Hk, Hv, dk = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim
    qkvz = h @ lay["wqkvz"]
    u, z = qkvz[:, :cfg.conv_width], qkvz[:, cfg.conv_width:]
    prev = _conv_inputs(u, conv_l[slot_of_tok], tok_col, positions, taps)
    w = lay["conv_w"].astype(f32)
    y = w[:, taps - 1] * u.astype(f32)
    for j, p in enumerate(prev):
        y = y + w[:, j] * p.astype(f32)
    y = y * jax.nn.sigmoid(y)
    kw = cfg.key_width
    q = _l2norm(y[:, :kw].reshape(T, Hk, dk)) * np.float32(dk ** -0.5)
    k = _l2norm(y[:, kw:2 * kw].reshape(T, Hk, dk))
    v = y[:, 2 * kw:].reshape(T, Hv, cfg.gdn_value_dim)
    q, k = _key_heads(q, Hv // Hk), _key_heads(k, Hv // Hk)
    ba = jnp.dot(h, lay["wba"], preferred_element_type=f32)
    beta = jax.nn.sigmoid(ba[:, :Hv])[..., None]
    dt = h.dtype
    return (q.astype(dt), k.astype(dt), (k * beta).astype(dt),
            (v * beta).astype(dt), _log_decay(lay, ba[:, Hv:]),
            z.astype(f32), _carried(prev, u))


def _forward(params: dict, cfg: Qwen3NextConfig, k_pool, v_pool, conv, state,
             tokens, positions, row_tables, row_start, row_nvalid,
             row_token_idx, tok_row, tok_col, slot_blocks, slot_offsets,
             logit_idx, row_slot, valid, row_live, *, attn: str,
             decode: bool):
    """One step over a packed stream of T tokens in B rows (the argument
    list of :func:`pathway_tpu.models.decoder.paged_mixed_step`, with the
    two arenas after the K/V pools, plus ``row_slot`` (B,) the rows' arena
    slots, ``valid`` (T,) which tokens are real and ``row_live`` (B,) which
    rows are).  ``decode``: every row is one token at column 0.  Returns
    ``(logits (B, V) f32, k_pool, v_pool, conv, state, counts (held +
    3,): ops/moe.py ``expert_ffn``)``."""
    from ..kvcache.paged_attention import (paged_append_attend,
                                           paged_attention, paged_write_rows)
    from ..ops import kda
    from ..ops.moe import COUNTER_TAIL, expert_ffn

    T = tokens.shape[0]
    H, hd, eps, f32 = cfg.n_heads, cfg.head_dim, cfg.norm_eps, jnp.float32
    kernels = attn == "pallas"
    dtype = params["embed"].dtype
    # the residual stream accumulates in f32; every matmul takes it normed
    # and rounded to the parameters' dtype, the router takes it unrounded
    x = params["embed"][tokens].astype(f32)                    # (T, D)
    counts = jnp.zeros((cfg.held_experts + len(COUNTER_TAIL),), jnp.int32)
    slot_of_tok = row_slot[tok_row]
    row_first = row_token_idx[:, 0]
    rows = (row_token_idx, tok_row, tok_col)  # the stream's tokens in rows
    row_fresh = _fresh_rows(row_start)
    if not decode:
        items = kda.chunk_items(row_first, row_fresh, row_nvalid, row_slot,
                                row_live, T, cfg.gdn_chunk)
    gi = fi = 0
    for kind, lay in zip(cfg.layer_types, params["layers"]):
        h = _zrms(x, lay["norm_in"], eps, dtype)
        if kind == GDN:
            q, k, kb, vb, g, z, carried = _gdn_inputs(
                lay, cfg, h, conv[gi], slot_of_tok, tok_col, positions)
            if decode:
                o, state = kda.kda_decode(
                    q, k, kb, vb, g, state, gi, row_slot, row_fresh,
                    use_pallas=kernels)
            else:
                o, state = kda.kda_mixed(
                    q, k, kb, vb, g, state, gi, items, row_first, row_fresh,
                    row_nvalid, row_slot, row_live, use_pallas=kernels)
            # the row's last token leaves its inputs in the row's slot
            conv = conv.at[gi, row_slot].set(
                carried[logit_idx].astype(conv.dtype))
            y = (_rms(o, lay["o_norm"], eps, f32).reshape(T, -1)
                 * (z * jax.nn.sigmoid(z))).astype(dtype)
            x = x + y @ lay["wo"]
            gi += 1
        else:
            qg = jnp.dot(h, lay["wq"], preferred_element_type=f32
                         ).reshape(T, H, 2, hd)
            q = _zrms(qg[:, :, 0], lay["q_norm"], eps, dtype)
            k1 = _zrms((h @ lay["wk"]).reshape(T, -1, hd), lay["k_norm"], eps)
            v1 = (h @ lay["wv"]).reshape(T, -1, hd)
            q = _partial_rope(q, positions, cfg.rope_theta, cfg.rotary_dim)
            k1 = _partial_rope(k1, positions, cfg.rope_theta, cfg.rotary_dim)
            if kernels and decode:
                a, k_pool, v_pool = paged_append_attend(
                    q[:, None], k1, v1, k_pool, v_pool, row_tables,
                    row_start + 1, slot_blocks, slot_offsets, layer=fi,
                    use_pallas=True)
                a = a[:, 0]
            else:
                # all rows land before any row's attention gathers
                k_pool, v_pool = paged_write_rows(
                    k_pool, v_pool, slot_blocks, slot_offsets, k1, v1,
                    layer=fi, use_pallas=kernels)
                a = paged_attention(
                    q, k_pool, v_pool, row_tables, start_pos=row_start,
                    n_valid=row_nvalid, packed=rows, layer=fi,
                    use_pallas=kernels)
            o = _gated(a.reshape(T, -1), qg[:, :, 1].reshape(T, -1), dtype)
            x = x + o @ lay["wo"]
            fi += 1
        h32 = _zrms(x, lay["norm_ffn"], eps)
        h = h32.astype(dtype)
        # with a share: the held experts' part, and the pairs elsewhere
        y, n_tok = expert_ffn(
            h, lay, valid, h_route=h32, top_k=cfg.top_k,
            norm_topk=True, renorm_eps=0.0, use_pallas=kernels,
            first_expert=cfg.share, score="softmax")
        x = x + y.astype(f32) \
            + _shared_gate(lay, h) * _swiglu(lay["shared"], h).astype(f32)
        counts = counts + n_tok
    sel = _zrms(x[logit_idx], params["norm_out"], eps, dtype)  # (B, D)
    logits = jnp.dot(sel, params["head"], preferred_element_type=f32)
    return logits, k_pool, v_pool, conv, state, counts


# the three programs of the step contract; ``_forward`` is found by name at
# trace time
kv_state_mixed_step, kv_state_decode_step, kv_state_chained_decode = \
    greedy_steps(lambda *a, **kw: _forward(*a, **kw),
                 lambda cfg: cfg.held_experts, row_live=True)
