"""The ``afmoe`` block family (arcee-ai Trinity): sliding-window and full
attention layers mixed, gated attention output, sandwich RMS norms, SwiGLU
and sparse experts beside a shared expert, on the paged engine's step
contract.

``x0 = embed[token] * sqrt(d_model)`` (``mup_enabled``).  A layer is::

    x += RMS(attention(RMS(x; norm_in)); norm_post_attn)
    x += RMS(ffn(RMS(x; norm_pre_mlp)); norm_post_mlp)

with RMSNorm in f32 and no bias anywhere; after the last layer
``norm_out`` and the untied ``head``.

- attention: q and the gate g (n_heads x hd), k and v (n_kv_heads x hd);
  q and k RMS-normalised over hd with one scale vector for all heads; on a
  ``sliding_attention`` layer rotate-half rotary over the whole head and
  key ``j`` visible to query ``i`` iff ``i - window < j <= i``; on a
  ``full_attention`` layer no rotary and ``j <= i``;
  ``softmax(q k^T / sqrt(hd)) v`` with query head ``h`` on K/V head
  ``h // (n_heads // n_kv_heads)``; the output times ``sigmoid(g)``, ``Wo``;
- the first ``n_dense_layers`` feed-forwards are ``W2(silu(x W1) * x W3)``;
  the others add a shared SwiGLU expert to ``top_k`` of ``n_experts``
  routed ones (:mod:`pathway_tpu.ops.moe`: sigmoid scores, a bias that
  moves the choice only, weights renormalised and times ``route_scale``).

One function, :func:`_forward`, holds that math for the three step
programs.  The full layers' K/V lives in the paged pool, whose layer axis
counts them alone; the window layers' in a second pool pair with a block
table of its own, whose blocks go back to their free list behind the
window (:class:`pathway_tpu.kvcache.windowed.WindowedCache`).  Both tables
are indexed by position, so a token's window slot is its window table's
entry at the position its full slot has.  Every program also returns the
expert layers' counter vector, summed (the tokens each expert received,
then :data:`pathway_tpu.ops.moe.COUNTER_TAIL`).

Greedy, one device.  Parameters are used in the dtype they come in (the
configuration's: bf16 on the chip); no f32 copy is kept or made.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (FULL, SLIDING, _gated, _rms, _rope, _swiglu, cast_plan,
                     greedy_steps)
from .encoder import _resolve_dtype


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    d_ff: int = 6144
    d_ff_expert: int = 1024
    n_experts: int = 128
    top_k: int = 8
    n_shared_experts: int = 1
    n_dense_layers: int = 2
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 2048
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    max_len: int = 131072
    dtype: Any = "auto"  # bf16 on TPU, f32 on CPU (encoder._resolve_dtype)
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True

    family = "afmoe"

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in (FULL, SLIDING)]
        if bad:
            raise ValueError(f"unknown layer type(s) {sorted(set(bad))}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads={self.n_heads} must be a multiple of "
                f"n_kv_heads={self.n_kv_heads}")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is written down here")

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

    def param_count(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (3 * self.n_heads + 2 * self.n_kv_heads) + 2 * hd
        dense = 3 * d * self.d_ff
        moe = (self.n_experts + 1) * 3 * d * self.d_ff_expert \
            + (d + 1) * self.n_experts
        n_moe = max(self.n_layers - self.n_dense_layers, 0)
        return (2 * self.vocab_size * d + d
                + self.n_layers * (attn + 4 * d)
                + (self.n_layers - n_moe) * dense + n_moe * moe)


def init_afmoe_params(cfg: AfmoeConfig, rng: jax.Array, dtype=None) -> dict:
    """Random parameters in the layout the step programs read: matrices
    N(0, 1/fan_in), embeddings 0.02, norm scales 1 +- 0.1, expert bias
    0.02.  Every branch leaves through an RMS norm, so its size is that
    norm's scale: the two post-norms of every layer after the first are
    scaled by ``1 / sqrt(2 (L - 1))``, so that the first layer's branches
    build the stream and the others together add as much variance as one
    of them (the reasoning of :func:`pathway_tpu.models.lfm2
    .init_lfm2_params`)."""
    dtype = _resolve_dtype(cfg.dtype) if dtype is None else dtype
    d, hd, H, KV = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    keys = iter(jax.random.split(rng, 24 * cfg.n_layers + 4))

    def n(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def mat(*shape):
        return n(shape, 1.0 / np.sqrt(shape[-2]))

    def norm(width, scale=1.0):
        return ((1.0 + jax.random.normal(next(keys), (width,), jnp.float32)
                 * 0.1) * scale).astype(dtype)

    params: dict = {"embed": n((cfg.vocab_size, d), 0.02),
                    "head": mat(d, cfg.vocab_size), "norm_out": norm(d),
                    "layers": []}
    for li in range(cfg.n_layers):
        out = 1.0 if li == 0 else 1.0 / np.sqrt(2.0 * (cfg.n_layers - 1))
        lay = {"norm_in": norm(d), "norm_post_attn": norm(d, out),
               "norm_pre_mlp": norm(d), "norm_post_mlp": norm(d, out),
               "wq": mat(d, H * hd), "wk": mat(d, KV * hd),
               "wv": mat(d, KV * hd), "wgate": mat(d, H * hd),
               "wo": mat(H * hd, d), "q_norm": norm(hd), "k_norm": norm(hd)}
        if li < cfg.n_dense_layers:
            lay.update(w1=mat(d, cfg.d_ff), w3=mat(d, cfg.d_ff),
                       w2=mat(cfg.d_ff, d))
        else:
            E, F = cfg.n_experts, cfg.d_ff_expert
            lay.update(wg=mat(d, E), w1=mat(E, d, F), w3=mat(E, d, F),
                       w2=mat(E, F, d),
                       expert_bias=n((E,), 0.02).astype(jnp.float32),
                       shared={"w1": mat(d, F), "w3": mat(d, F),
                               "w2": mat(F, d)})
        params["layers"].append(lay)
    return params


def plan_params(cfg: AfmoeConfig, params: dict) -> dict:
    """:func:`~pathway_tpu.models.blocks.cast_plan` with the router's bias
    kept f32."""
    return cast_plan(cfg, params, ("expert_bias",))


def _rotary(kind: str, q, k, positions, theta: float):
    """Rotary on the sliding-window layers; a full-attention layer has no
    positions at all."""
    if kind != SLIDING:
        return q, k
    return _rope(q, positions, theta), _rope(k, positions, theta)


def _forward(params: dict, cfg: AfmoeConfig, k_pool, v_pool, kw_pool, vw_pool,
             tokens, positions, row_tables, row_start, row_nvalid,
             row_token_idx, tok_row, tok_col, slot_blocks, slot_offsets,
             logit_idx, win_tables, valid, *, attn: str, decode: bool):
    """One step over a packed stream of T tokens in B rows (the argument
    list of :func:`pathway_tpu.models.decoder.paged_mixed_step`, plus the
    window pools, ``win_tables`` (B, NB) the rows' window tables and
    ``valid`` (T,) which tokens are real).  ``decode``: every row is one
    token at column 0, so the layers take the fused append+attend kernel.
    Returns ``(logits (B, V) f32, k_pool, v_pool, kw_pool, vw_pool,
    counts (E + 3,):
    ops/moe.py ``expert_ffn``)``."""
    from ..kvcache.paged_attention import (paged_append_attend,
                                           paged_attention, paged_write_rows)
    from ..ops.moe import COUNTER_TAIL, expert_ffn

    T = tokens.shape[0]
    hd, eps, f32 = cfg.head_dim, cfg.norm_eps, jnp.float32
    kernels = attn == "pallas"
    dtype = params["embed"].dtype
    # the residual stream accumulates in f32; every matmul takes it normed
    # and rounded to the parameters' dtype, the router takes it unrounded
    x = params["embed"][tokens].astype(f32)                    # (T, D)
    if cfg.mup_enabled:
        x = x * np.float32(np.sqrt(cfg.d_model))
    counts = jnp.zeros((cfg.n_experts + len(COUNTER_TAIL),), jnp.int32)
    # a token's window slot: its window table's entry at its position; a
    # token the full pool sends to the null block (padding) goes there too
    win_blocks = jnp.where(
        slot_blocks > 0,
        win_tables[tok_row, positions // kw_pool.shape[2]], 0)
    rows = (row_token_idx, tok_row, tok_col)  # the stream's tokens in rows
    fi = wi = 0
    for li, (kind, lay) in enumerate(zip(cfg.layer_types, params["layers"])):
        h = _rms(x, lay["norm_in"], eps, dtype)
        q = _rms((h @ lay["wq"]).reshape(T, -1, hd), lay["q_norm"], eps)
        k1 = _rms((h @ lay["wk"]).reshape(T, -1, hd), lay["k_norm"], eps)
        v1 = (h @ lay["wv"]).reshape(T, -1, hd)
        gate = jnp.dot(h, lay["wgate"], preferred_element_type=f32)
        q, k1 = _rotary(kind, q, k1, positions, cfg.rope_theta)
        if kind == SLIDING:
            pools, tables, blocks = (kw_pool, vw_pool), win_tables, win_blocks
            layer, window = wi, cfg.sliding_window
        else:
            pools, tables, blocks = (k_pool, v_pool), row_tables, slot_blocks
            layer, window = fi, None
        if kernels and decode:
            a, *pools = paged_append_attend(
                q[:, None], k1, v1, *pools, tables, row_start + 1, blocks,
                slot_offsets, layer=layer, use_pallas=True, window=window)
            a = a[:, 0]
        else:
            # all rows land before any row's attention gathers
            pools = paged_write_rows(
                *pools, blocks, slot_offsets, k1, v1, layer=layer,
                use_pallas=kernels)
            a = paged_attention(
                q, *pools, tables, start_pos=row_start, n_valid=row_nvalid,
                packed=rows, layer=layer, use_pallas=kernels, window=window)
        if kind == SLIDING:
            kw_pool, vw_pool = pools
            wi += 1
        else:
            k_pool, v_pool = pools
            fi += 1
        o = _gated(a.reshape(T, -1), gate, dtype)
        x = x + _rms(o @ lay["wo"], lay["norm_post_attn"], eps, f32)
        h32 = _rms(x, lay["norm_pre_mlp"], eps)
        h = h32.astype(dtype)
        if li < cfg.n_dense_layers:
            y = _swiglu(lay, h)
        else:
            y, n_tok = expert_ffn(
                h, lay, valid, h_route=h32, top_k=cfg.top_k,
                norm_topk=cfg.route_norm, scale=cfg.route_scale,
                renorm_eps=1e-20, use_pallas=kernels)
            y = y.astype(f32) + _swiglu(lay["shared"], h).astype(f32)
            counts = counts + n_tok
        x = x + _rms(y, lay["norm_post_mlp"], eps, f32)
    sel = _rms(x[logit_idx], params["norm_out"], eps, dtype)   # (B, D)
    logits = jnp.dot(sel, params["head"], preferred_element_type=f32)
    return logits, k_pool, v_pool, kw_pool, vw_pool, counts


# the three programs of the windowed contract; ``_forward`` is found by name
# at trace time
windowed_mixed_step, windowed_decode_step, windowed_chained_decode = \
    greedy_steps(lambda *a, **kw: _forward(*a, **kw),
                 lambda cfg: cfg.n_experts)
