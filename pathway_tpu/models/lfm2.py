"""The ``lfm2_moe`` block family: gated short convolutions and grouped-query
attention as token mixers, SwiGLU and sparse experts as feed-forward, on
the paged engine's step contract.

A layer is ``x += mixer(norm_op(x)); x += ffn(norm_ffn(x))`` with RMSNorm
(f32), no bias anywhere, and after the last layer ``norm_out`` and the
head (tied to the embedding unless ``head`` is given):

- ``full_attention``: q (n_heads x hd), k and v (n_kv_heads x hd), q and k
  RMS-normalised per head with a learned scale, rotary over the whole head
  (rotate-half) at the token's position, causal softmax(q k^T / sqrt(hd))
  v with query head ``i`` on K/V head ``i // (n_heads // n_kv_heads)``;
- ``conv``: ``(B, C, X) = split3(x W_in)``, ``u = B * X``,
  ``c_t = w[:, 0] u_{t-2} + w[:, 1] u_{t-1} + w[:, 2] u_t`` (``u`` before
  the sequence is 0), ``y = C * c``, ``W_out``.  What a sequence carries
  from one step to the next is ``(u_{t-2}, u_{t-1})``;
- the first ``n_dense_layers`` feed-forwards are ``W2(silu(x W1) * x W3)``;
  the others route every token to ``top_k`` of ``n_experts`` such blocks
  (:mod:`pathway_tpu.ops.moe`).

One function, :func:`_forward`, holds that math for the three step
programs.  The K/V pool's layer axis counts the attention layers only; the
conv layers' carried vectors live in a slot arena ``(conv layers, slots,
2, d_model)`` beside it (:class:`pathway_tpu.kvcache.hybrid.HybridCache`).
A token at position ``p`` reads ``u_{p-1}`` / ``u_{p-2}`` only where those
positions exist, so a slot needs no clearing between sequences.  Every
program also returns the expert layers' counter vector, summed
(``int32[n_experts + 3]``: the tokens each expert received, then
:data:`pathway_tpu.ops.moe.COUNTER_TAIL`).

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

ATTENTION, CONV = "full_attention", "conv"


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 7168
    d_ff_expert: int = 1792
    n_experts: int = 32
    top_k: int = 4
    n_dense_layers: int = 2
    layer_types: tuple = (CONV, CONV, ATTENTION)
    conv_kernel: int = 3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    max_len: int = 128000
    dtype: Any = "auto"  # bf16 on TPU, f32 on CPU (encoder._resolve_dtype)
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    tie_embedding: bool = True

    family = "lfm2"

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in (ATTENTION, CONV)]
        if bad:
            raise ValueError(f"unknown layer type(s) {sorted(set(bad))}")
        if self.n_heads % self.n_kv_heads or self.d_model % self.n_heads:
            raise ValueError(
                f"n_heads={self.n_heads} must divide d_model={self.d_model} "
                f"and be a multiple of n_kv_heads={self.n_kv_heads}")
        if self.conv_kernel != 3:
            raise ValueError("only conv_L_cache = 3 is written down here")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def attn_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == ATTENTION)

    @property
    def conv_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == CONV)

    def param_count(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = 2 * d * d + 2 * d * self.n_kv_heads * hd + 2 * hd
        conv = 4 * d * d + 3 * d
        dense = 3 * d * self.d_ff
        moe = self.n_experts * (3 * d * self.d_ff_expert + d + 1)
        n_moe = max(self.n_layers - self.n_dense_layers, 0)
        return (self.vocab_size * d * (1 if self.tie_embedding else 2) + d
                + len(self.attn_layers) * attn + len(self.conv_layers) * conv
                + (self.n_layers - n_moe) * dense + n_moe * moe
                + 2 * d * self.n_layers)


def init_lfm2_params(cfg: Lfm2Config, rng: jax.Array, dtype=None) -> dict:
    """Random parameters in the layout the step programs read: matrices
    N(0, 1/fan_in), embeddings 0.02, norm scales 1 +- 0.1, expert bias
    0.02, conv taps N(0, 1/3).  The output projections (``w_out``,
    ``wo``, ``w2``) of every layer after the first are scaled by
    ``1 / sqrt(2 (L - 1))``: the first layer's two branches build the
    stream from the token (the embedding's 0.02 is small beside them, and
    has to be under a tied head, or every token would predict itself) and
    the other ``2 (L - 1)`` branches together add as much variance as one
    of them.  With every branch at full size the stream's variance grows
    with the depth and a relative error grows ``(n + g^2) / (n + 1)`` a
    branch (``g`` > 1 the branch's own gain: 2 for SwiGLU, 3 for the gated
    conv): thirteen layers then amplify a rounding error about
    seventy-fold, which no trained checkpoint does."""
    dtype = _resolve_dtype(cfg.dtype) if dtype is None else dtype
    d, hd = cfg.d_model, cfg.head_dim
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
                    "norm_out": norm(d), "layers": []}
    if not cfg.tie_embedding:
        params["head"] = mat(d, cfg.vocab_size)
    for li, kind in enumerate(cfg.layer_types):
        out = 1.0 if li == 0 else 1.0 / np.sqrt(2.0 * (cfg.n_layers - 1))
        lay = {"norm_op": norm(d), "norm_ffn": norm(d)}
        if kind == ATTENTION:
            lay.update(wq=mat(d, d), wk=mat(d, cfg.n_kv_heads * hd),
                       wv=mat(d, cfg.n_kv_heads * hd),
                       wo=mat(d, d, scale=out), q_norm=norm(hd),
                       k_norm=norm(hd))
        else:
            lay.update(w_in=mat(d, 3 * d), conv_w=n((d, 3), 1 / np.sqrt(3)),
                       w_out=mat(d, d, scale=out))
        if li < cfg.n_dense_layers:
            lay.update(w1=mat(d, cfg.d_ff), w3=mat(d, cfg.d_ff),
                       w2=mat(cfg.d_ff, d, scale=out))
        else:
            E, F = cfg.n_experts, cfg.d_ff_expert
            lay.update(wg=mat(d, E), w1=mat(E, d, F), w3=mat(E, d, F),
                       w2=mat(E, F, d, scale=out))
            if cfg.use_expert_bias:
                lay["expert_bias"] = n((E,), 0.02).astype(jnp.float32)
        params["layers"].append(lay)
    return params


def plan_params(cfg: Lfm2Config, params: dict) -> dict:
    """What the engine dispatches with: the parameters as they are where
    they already have the configuration's dtype, else cast once (the
    router's bias stays f32).  No fused or quantized plan for this family
    yet."""
    dtype = _resolve_dtype(cfg.dtype)

    def cast(path, leaf):
        keep = any(getattr(k, "key", None) == "expert_bias" for k in path)
        return leaf if keep or leaf.dtype == dtype else leaf.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, params)


def greedy_ids(logits):
    """The token a row emits: the argmax inside the program, so that only
    ``int32`` ids cross to the host."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# -- the block math -----------------------------------------------------------


def _rms(x, scale, eps: float, dtype=None):
    """RMSNorm in f32; the result in ``dtype`` (default: x's)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype or x.dtype)


def _rope(x, positions, theta: float):
    """Rotate-half rotary over the whole head.  x (T, H, hd); positions
    (T,).  Angles in f32."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]   # (T, 1, hd)
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., hd // 2:], x32[..., : hd // 2]], -1)
    return (x32 * jnp.cos(ang) + rot * jnp.sin(ang)).astype(x.dtype)


def _swiglu(lay, h):
    a = jnp.dot(h, lay["w1"], preferred_element_type=jnp.float32)
    b = jnp.dot(h, lay["w3"], preferred_element_type=jnp.float32)
    return jnp.dot((a * jax.nn.sigmoid(a) * b).astype(h.dtype), lay["w2"])


def _forward(params: dict, cfg: Lfm2Config, k_pool, v_pool, conv, tokens,
             positions, row_tables, row_start, row_nvalid, row_token_idx,
             tok_row, tok_col, slot_blocks, slot_offsets, logit_idx,
             row_slot, valid, *, attn: str, decode: bool):
    """One step over a packed stream of T tokens in B rows (the argument
    list of :func:`pathway_tpu.models.decoder.paged_mixed_step`, plus
    ``row_slot`` (B,) the rows' arena slots and ``valid`` (T,) which
    tokens are real).  ``decode``: every row is one token at column 0, so
    the attention layers take the fused append+attend kernel.  Returns
    ``(logits (B, V) f32, k_pool, v_pool, conv, counts (E + 3,):
    ops/moe.py ``expert_ffn``)``."""
    from ..kvcache.paged_attention import (paged_append_attend,
                                           paged_attention, paged_write_rows)
    from ..ops.moe import COUNTER_TAIL, expert_ffn

    T = tokens.shape[0]
    hd, eps = cfg.head_dim, cfg.norm_eps
    kernels = attn == "pallas"
    dtype = params["embed"].dtype
    # the residual stream accumulates in f32 (twenty-six additions deep a
    # bf16 sum loses the small branches); every matmul takes it normed and
    # rounded to the parameters' dtype, the router takes it unrounded
    x = params["embed"][tokens].astype(jnp.float32)           # (T, D)
    counts = jnp.zeros((cfg.n_experts + len(COUNTER_TAIL),), jnp.int32)
    # where the two vectors before a token come from: the stream for the
    # later tokens of a run, the row's slot for its first two
    slot_of_tok = row_slot[tok_row]
    has1, has2 = (positions >= 1)[:, None], (positions >= 2)[:, None]
    rows = (row_token_idx, tok_row, tok_col)  # the stream's tokens in rows
    ai = ci = 0
    for li, (kind, lay) in enumerate(zip(cfg.layer_types, params["layers"])):
        h = _rms(x, lay["norm_op"], eps, dtype)
        if kind == ATTENTION:
            q = _rope(_rms((h @ lay["wq"]).reshape(T, -1, hd),
                           lay["q_norm"], eps), positions, cfg.rope_theta)
            k1 = _rope(_rms((h @ lay["wk"]).reshape(T, -1, hd),
                            lay["k_norm"], eps), positions, cfg.rope_theta)
            v1 = (h @ lay["wv"]).reshape(T, -1, hd)
            if kernels and decode:
                a, k_pool, v_pool = paged_append_attend(
                    q[:, None], k1, v1, k_pool, v_pool, row_tables,
                    row_start + 1, slot_blocks, slot_offsets, layer=ai,
                    use_pallas=True)
                a = a[:, 0]
            else:
                # all rows land before any row's attention gathers
                k_pool, v_pool = paged_write_rows(
                    k_pool, v_pool, slot_blocks, slot_offsets, k1, v1,
                    layer=ai, use_pallas=kernels)
                a = paged_attention(
                    q, k_pool, v_pool, row_tables, start_pos=row_start,
                    n_valid=row_nvalid, packed=rows, layer=ai,
                    use_pallas=kernels)
            x = x + a.reshape(T, -1).astype(dtype) @ lay["wo"]
            ai += 1
        else:
            bcx = h @ lay["w_in"]
            gate_b, gate_c, xin = jnp.split(bcx, 3, axis=-1)
            u = (gate_b * xin).astype(conv.dtype)             # (T, D)
            s = conv[ci][slot_of_tok]                         # (T, 2, D)
            col = tok_col[:, None]
            prev1 = jnp.where(col >= 1, jnp.roll(u, 1, axis=0), s[:, 1])
            prev2 = jnp.where(col >= 2, jnp.roll(u, 2, axis=0),
                              jnp.where(col == 1, s[:, 1], s[:, 0]))
            prev1 = jnp.where(has1, prev1, 0)
            prev2 = jnp.where(has2, prev2, 0)
            w = lay["conv_w"].astype(jnp.float32)
            c = (w[:, 0] * prev2.astype(jnp.float32)
                 + w[:, 1] * prev1.astype(jnp.float32)
                 + w[:, 2] * u.astype(jnp.float32))
            y = (gate_c.astype(jnp.float32) * c).astype(dtype)
            x = x + y @ lay["w_out"]
            # the row's last token leaves (u_{t-1}, u_t) in its slot
            conv = conv.at[ci, row_slot].set(
                jnp.stack([prev1[logit_idx], u[logit_idx]], axis=1))
            ci += 1
        h32 = _rms(x, lay["norm_ffn"], eps)
        h = h32.astype(dtype)
        if li < cfg.n_dense_layers:
            x = x + _swiglu(lay, h)
        else:
            y, n_tok = expert_ffn(
                h, lay, valid, h_route=h32, top_k=cfg.top_k,
                norm_topk=cfg.norm_topk_prob,
                scale=cfg.routed_scaling_factor, use_pallas=kernels)
            x = x + y
            counts = counts + n_tok
    sel = _rms(x[logit_idx], params["norm_out"], eps, dtype)  # (B, D)
    if "head" in params:
        logits = jnp.dot(sel, params["head"],
                         preferred_element_type=jnp.float32)
    else:
        logits = jax.lax.dot_general(
            sel, params["embed"], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return logits, k_pool, v_pool, conv, counts


def hybrid_mixed_step(params: dict, cfg: Lfm2Config, k_pool, v_pool, conv,
                      tokens, positions, row_tables, row_start, row_nvalid,
                      row_token_idx, tok_row, tok_col, slot_blocks,
                      slot_offsets, logit_idx, row_slot, *,
                      attn: str = "reference"):
    """The ragged fused step (decode rows and prompt chunks on one packed
    stream) for this family.  A packed token is real where its row's run
    holds it: padding tokens point at row 0, column 0, which is another
    token's place."""
    T = tokens.shape[0]
    valid = row_token_idx[tok_row, tok_col] == jnp.arange(T, dtype=jnp.int32)
    return _forward(
        params, cfg, k_pool, v_pool, conv, tokens, positions, row_tables,
        row_start, row_nvalid, row_token_idx, tok_row, tok_col, slot_blocks,
        slot_offsets, logit_idx, row_slot, valid, attn=attn, decode=False)


def hybrid_decode_step(params: dict, cfg: Lfm2Config, k_pool, v_pool, conv,
                       token, positions, block_tables, slot_blocks,
                       slot_offsets, row_slot, *, attn: str = "reference"):
    """One token a row.  An idle row has the null block first in its
    table and rides slot 0."""
    B = token.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    return _forward(
        params, cfg, k_pool, v_pool, conv, token, positions, block_tables,
        positions, jnp.ones((B,), jnp.int32), rows[:, None], rows,
        jnp.zeros((B,), jnp.int32), slot_blocks, slot_offsets, rows,
        row_slot, block_tables[:, 0] > 0, attn=attn, decode=True)


def hybrid_chained_decode(params: dict, cfg: Lfm2Config, k_pool, v_pool,
                          conv, token, positions, block_tables, slot_blocks,
                          slot_offsets, row_slot, *, attn: str = "reference"):
    """K greedy decode steps in one program (``slot_blocks`` /
    ``slot_offsets`` (B, K), the host's pre-extended slots), step t's ids
    feeding step t + 1.  Returns ``(ids (B, K), k_pool, v_pool, conv,
    counts)``."""
    from ..ops.moe import COUNTER_TAIL

    K = slot_blocks.shape[1]
    maxp = cfg.max_len - 1

    def body(carry, xs):
        tok, kp, vp, cv, cnt = carry
        sb, so, t = xs
        logits, kp, vp, cv, n_tok = hybrid_decode_step(
            params, cfg, kp, vp, cv, tok, jnp.minimum(positions + t, maxp),
            block_tables, sb, so, row_slot, attn=attn)
        ids = greedy_ids(logits)
        return (ids, kp, vp, cv, cnt + n_tok), ids

    init = (token.astype(jnp.int32), k_pool, v_pool, conv,
            jnp.zeros((cfg.n_experts + len(COUNTER_TAIL),), jnp.int32))
    (_last, k_pool, v_pool, conv, counts), ids = jax.lax.scan(
        body, init, (slot_blocks.T, slot_offsets.T,
                     jnp.arange(K, dtype=jnp.int32)))
    return ids.T, k_pool, v_pool, conv, counts
