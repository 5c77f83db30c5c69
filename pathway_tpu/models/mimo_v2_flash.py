"""The ``mimo_v2_flash`` block family (XiaomiMiMo MiMo-V2-Flash):
sliding-window and full attention layers mixed, each kind with K/V heads of
its own number, keys wider than values, a learned sink in the window
layers' softmax, SwiGLU and sparse experts without a shared one, on the
paged engine's step contract.

``x0 = embed[token]``.  A layer is plain pre-norm::

    x += attention(RMS(x; norm_in)) Wo
    x += ffn(RMS(x; norm_pre_mlp))

with RMSNorm in f32 and no bias anywhere; after the last layer ``norm_out``
and the untied ``head``.

- attention: q (``n_heads`` x ``head_dim``), k (``n_kv`` x ``head_dim``), v
  (``n_kv`` x ``v_head_dim``) times ``value_scale``; ``n_kv`` is
  ``n_kv_heads`` on a ``full_attention`` layer and ``window_kv_heads`` on a
  ``sliding_attention`` one, query head ``h`` on K/V head ``h // (n_heads //
  n_kv)``; rotate-half rotary on the LEADING ``rotary_dim`` of q's and k's
  ``head_dim``, the others pass, with ``rope_theta`` on a full layer and
  ``window_rope_theta`` on a sliding one; scores over ``sqrt(head_dim)``;
  key ``j`` visible to query ``i`` iff ``j <= i`` (full) or ``i - window < j
  <= i`` (sliding); a sliding layer's softmax has one more logit a query
  head, the learned ``sinks`` (f32), whose probability is dropped; the
  output (``n_heads`` x ``v_head_dim``) through ``Wo``; no gate, no q/k
  norm;
- the first ``n_dense_layers`` feed-forwards are ``W2(silu(x W1) * x W3)``;
  the others ``top_k`` of ``n_experts`` routed ones (:mod:`pathway_tpu.ops
  .moe`: sigmoid scores, a bias that moves the choice only, weights
  renormalised over ``sum + 1e-20``) and nothing beside them.  Where
  ``n_held_experts`` is given the layer is one share of an expert-parallel
  deployment (as :mod:`pathway_tpu.models.kimi_linear`): its weights hold
  the experts ``first_expert .. first_expert + n_held_experts`` only, the
  router keeps its ``n_experts`` outputs, and what the absent experts would
  add is left out.

One function, :func:`_forward`, holds that math for the three step programs
of the windowed contract (the argument lists of :mod:`pathway_tpu.models
.afmoe`'s).  The full layers' K/V lives in the paged pool, the window
layers' in the second pool pair of :class:`pathway_tpu.kvcache.windowed
.WindowedCache`, each pool at its own lanes: ``n_kv_heads * head_dim`` keys
beside ``n_kv_heads * v_head_dim`` values, ``window_kv_heads * ...`` in the
window pool.  Every program also returns the expert layers' counter vector,
summed.

Greedy, one device.  Parameters are used in the dtype they come in (the
configuration's: bf16 on the chip; the sinks stay f32); no f32 copy is kept
or made.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (FULL, SLIDING, _partial_rope, _rms, _swiglu, cast_plan,
                     greedy_steps)
from .encoder import _resolve_dtype


@dataclasses.dataclass(frozen=True)
class MimoV2FlashConfig:
    vocab_size: int = 152576
    d_model: int = 4096
    n_heads: int = 64
    n_kv_heads: int = 4            # K/V heads of a full-attention layer
    window_kv_heads: int = 8       # ... of a sliding-window layer
    head_dim: int = 192            # a query's and a key's
    v_head_dim: int = 128
    rotary_dim: int = 64           # int(partial_rotary_factor x head_dim)
    d_ff: int = 16384
    d_ff_expert: int = 2048
    n_experts: int = 256           # the router's width
    n_held_experts: int | None = None  # experts this share holds; None: all
    first_expert: int = 0          # the first of them
    top_k: int = 8
    n_dense_layers: int = 1
    layer_types: tuple = (FULL, SLIDING, SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 128
    rope_theta: float = 5e6
    window_rope_theta: float = 1e4
    value_scale: float = 0.707
    route_scale: float = 1.0
    norm_eps: float = 1e-5
    max_len: int = 262144
    dtype: Any = "auto"  # bf16 on TPU, f32 on CPU (encoder._resolve_dtype)

    family = "mimo_v2_flash"

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in (FULL, SLIDING)]
        if bad:
            raise ValueError(f"unknown layer type(s) {sorted(set(bad))}")
        for name in ("n_kv_heads", "window_kv_heads"):
            if self.n_heads % getattr(self, name):
                raise ValueError(
                    f"n_heads={self.n_heads} must be a multiple of "
                    f"{name}={getattr(self, name)}")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"rotary_dim={self.rotary_dim} is no even part "
                             f"of head_dim={self.head_dim}")
        held = self.held_experts
        if not 0 <= self.first_expert <= self.n_experts - held:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + held} "
                f"are not a share of {self.n_experts}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def full_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == FULL)

    @property
    def window_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == SLIDING)

    @property
    def held_experts(self) -> int:
        return self.n_experts if self.n_held_experts is None \
            else self.n_held_experts

    @property
    def share(self):
        """``expert_ffn``'s ``first_expert``: None where every expert is
        held."""
        return None if self.n_held_experts is None else self.first_expert

    def kv_heads(self, kind: str) -> int:
        return self.window_kv_heads if kind == SLIDING else self.n_kv_heads

    def param_count(self) -> int:
        d, H = self.d_model, self.n_heads

        def attn(kv):
            return d * (H * self.head_dim + kv * self.head_dim
                        + kv * self.v_head_dim) + H * self.v_head_dim * d

        moe = d * self.n_experts + self.n_experts \
            + self.held_experts * 3 * d * self.d_ff_expert
        n_moe = max(self.n_layers - self.n_dense_layers, 0)
        return (2 * self.vocab_size * d + d + self.n_layers * 2 * d
                + len(self.full_layers) * attn(self.n_kv_heads)
                + len(self.window_layers) * (attn(self.window_kv_heads) + H)
                + (self.n_layers - n_moe) * 3 * d * self.d_ff + n_moe * moe)


def init_mimo_v2_flash_params(cfg: MimoV2FlashConfig, rng: jax.Array,
                              dtype=None) -> dict:
    """Random parameters in the layout the step programs read: matrices
    N(0, 1/fan_in), embeddings 0.02, norm scales 1 +- 0.1, expert bias
    0.02, sinks N(1, 1) in f32 on the sliding layers.  The output
    projections (``wo``, ``w2``) of every layer after the first are scaled
    by ``1 / sqrt(2 (L - 1))`` (the reasoning of
    :func:`pathway_tpu.models.lfm2.init_lfm2_params`)."""
    dtype = _resolve_dtype(cfg.dtype) if dtype is None else dtype
    d, hd, hv, H = cfg.d_model, cfg.head_dim, cfg.v_head_dim, cfg.n_heads
    keys = iter(jax.random.split(rng, 16 * cfg.n_layers + 4))

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
        KV = cfg.kv_heads(kind)
        lay = {"norm_in": norm(d), "norm_pre_mlp": norm(d),
               "wq": mat(d, H * hd), "wk": mat(d, KV * hd),
               "wv": mat(d, KV * hv), "wo": mat(H * hv, d, scale=out)}
        if kind == SLIDING:
            lay["sinks"] = 1.0 + jax.random.normal(next(keys), (H,),
                                                   jnp.float32)
        if li < cfg.n_dense_layers:
            lay.update(w1=mat(d, cfg.d_ff), w3=mat(d, cfg.d_ff),
                       w2=mat(cfg.d_ff, d, scale=out))
        else:
            E, held, F = cfg.n_experts, cfg.held_experts, cfg.d_ff_expert
            lay.update(wg=mat(d, E), w1=mat(held, d, F), w3=mat(held, d, F),
                       w2=mat(held, F, d, scale=out),
                       expert_bias=n((E,), 0.02).astype(jnp.float32))
        params["layers"].append(lay)
    return params


def plan_params(cfg: MimoV2FlashConfig, params: dict) -> dict:
    """:func:`~pathway_tpu.models.blocks.cast_plan` with the sinks and the
    router's bias kept f32."""
    return cast_plan(cfg, params, ("sinks", "expert_bias"))


def _values(v, scale: float):
    """``v * attention_value_scale``, in f32, back in v's dtype: what the
    cache holds (the scale on the values and the scale on the mix before
    ``Wo`` are one number)."""
    return (v.astype(jnp.float32) * np.float32(scale)).astype(v.dtype)


def _forward(params: dict, cfg: MimoV2FlashConfig, k_pool, v_pool, kw_pool,
             vw_pool, tokens, positions, row_tables, row_start, row_nvalid,
             row_token_idx, tok_row, tok_col, slot_blocks, slot_offsets,
             logit_idx, win_tables, valid, *, attn: str, decode: bool):
    """One step over a packed stream of T tokens in B rows (the argument
    list of :func:`pathway_tpu.models.afmoe._forward`).  ``decode``: every
    row is one token at column 0, so the layers take the fused
    append+attend kernel.  Returns ``(logits (B, V) f32, k_pool, v_pool,
    kw_pool, vw_pool, counts (held + 5,): ops/moe.py ``expert_ffn``)``."""
    from ..kvcache.paged_attention import (paged_append_attend,
                                           paged_attention, paged_write_rows)
    from ..ops.moe import COUNTER_TAIL, expert_ffn

    T = tokens.shape[0]
    hd, hv, eps, f32 = cfg.head_dim, cfg.v_head_dim, cfg.norm_eps, jnp.float32
    kernels = attn == "pallas"
    dtype = params["embed"].dtype
    # the residual stream accumulates in f32; every matmul takes it normed
    # and rounded to the parameters' dtype, the router takes it unrounded
    x = params["embed"][tokens].astype(f32)                    # (T, D)
    counts = jnp.zeros((cfg.held_experts + len(COUNTER_TAIL),), jnp.int32)
    # a token's window slot: its window table's entry at its position; a
    # token the full pool sends to the null block (padding) goes there too
    win_blocks = jnp.where(
        slot_blocks > 0,
        win_tables[tok_row, positions // kw_pool.shape[2]], 0)
    rows = (row_token_idx, tok_row, tok_col)  # the stream's tokens in rows
    fi = wi = 0
    for li, (kind, lay) in enumerate(zip(cfg.layer_types, params["layers"])):
        h = _rms(x, lay["norm_in"], eps, dtype)
        q = (h @ lay["wq"]).reshape(T, -1, hd)
        k1 = (h @ lay["wk"]).reshape(T, -1, hd)
        v1 = _values(h @ lay["wv"], cfg.value_scale).reshape(T, -1, hv)
        if kind == SLIDING:
            pools, tables, blocks = (kw_pool, vw_pool), win_tables, win_blocks
            layer, theta = wi, cfg.window_rope_theta
            extra = {"window": cfg.sliding_window, "sinks": lay["sinks"]}
        else:
            pools, tables, blocks = (k_pool, v_pool), row_tables, slot_blocks
            layer, theta, extra = fi, cfg.rope_theta, {}
        q = _partial_rope(q, positions, theta, cfg.rotary_dim)
        k1 = _partial_rope(k1, positions, theta, cfg.rotary_dim)
        if kernels and decode:
            a, *pools = paged_append_attend(
                q[:, None], k1, v1, *pools, tables, row_start + 1, blocks,
                slot_offsets, layer=layer, use_pallas=True, **extra)
            a = a[:, 0]
        else:
            # all rows land before any row's attention gathers
            pools = paged_write_rows(
                *pools, blocks, slot_offsets, k1, v1, layer=layer,
                use_pallas=kernels)
            a = paged_attention(
                q, *pools, tables, start_pos=row_start, n_valid=row_nvalid,
                packed=rows, layer=layer, use_pallas=kernels, **extra)
        if kind == SLIDING:
            kw_pool, vw_pool = pools
            wi += 1
        else:
            k_pool, v_pool = pools
            fi += 1
        x = x + a.reshape(T, -1) @ lay["wo"]
        h32 = _rms(x, lay["norm_pre_mlp"], eps)
        h = h32.astype(dtype)
        if li < cfg.n_dense_layers:
            y = _swiglu(lay, h)
        else:
            # with a share: the held experts' part, and the pairs elsewhere
            y, n_tok = expert_ffn(
                h, lay, valid, h_route=h32, top_k=cfg.top_k, norm_topk=True,
                scale=cfg.route_scale, renorm_eps=1e-20, use_pallas=kernels,
                first_expert=cfg.share)
            counts = counts + n_tok
        x = x + y.astype(f32)
    sel = _rms(x[logit_idx], params["norm_out"], eps, dtype)   # (B, D)
    logits = jnp.dot(sel, params["head"], preferred_element_type=f32)
    return logits, k_pool, v_pool, kw_pool, vw_pool, counts


# the three programs of the windowed contract, with ``afmoe``'s argument
# lists; ``_forward`` is found by name at trace time
windowed_mixed_step, windowed_decode_step, windowed_chained_decode = \
    greedy_steps(lambda *a, **kw: _forward(*a, **kw),
                 lambda cfg: cfg.held_experts)
