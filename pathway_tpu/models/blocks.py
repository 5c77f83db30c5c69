"""What the routed block families share: their layer math, the plan cast,
and the greedy step contract.

The six routed families (:mod:`~pathway_tpu.models.lfm2`,
:mod:`~pathway_tpu.models.afmoe`, :mod:`~pathway_tpu.models.kimi_linear`,
:mod:`~pathway_tpu.models.qwen3_next`,
:mod:`~pathway_tpu.models.mimo_v2_flash`, and
:mod:`~pathway_tpu.models.phi4_mini_flash`, which has no expert layer and
counts zero experts) each write ONE ``_forward`` over a packed stream;
:func:`greedy_steps` makes the three step programs of the paged engine's
contract from it, and a family module takes every helper it shares from
here, never from another family module.  A family module keeps
a module-level binding of each helper its own ``_forward`` calls, so that a
test may replace one on that family alone.

Greedy, one device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .encoder import _resolve_dtype

FULL, SLIDING = "full_attention", "sliding_attention"


def cast_plan(cfg, params: dict, keep_f32: tuple) -> dict:
    """What the engine dispatches with: the parameters as they are where
    they already have the configuration's dtype, else cast once; the leaves
    named in ``keep_f32`` (a router's bias, a decay's parameters, a sink)
    stay as they are."""
    dtype = _resolve_dtype(cfg.dtype)

    def cast(path, leaf):
        keep = any(getattr(k, "key", None) in keep_f32 for k in path)
        return leaf if keep or leaf.dtype == dtype else leaf.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, params)


def greedy_ids(logits):
    """The token a row emits: the argmax inside the program, so that only
    ``int32`` ids cross to the host."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# -- the layer math -----------------------------------------------------------


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


def _partial_rope(x, positions, theta: float, rot: int):
    """Rotate-half rotary on the first ``rot`` of a head's values, the
    others pass.  x (T, H, hd)."""
    return jnp.concatenate(
        [_rope(x[..., :rot], positions, theta), x[..., rot:]], axis=-1)


def _swiglu(lay, h):
    a = jnp.dot(h, lay["w1"], preferred_element_type=jnp.float32)
    b = jnp.dot(h, lay["w3"], preferred_element_type=jnp.float32)
    return jnp.dot((a * jax.nn.sigmoid(a) * b).astype(h.dtype), lay["w2"])


def _gated(a, gate, dtype):
    """The attention output times the sigmoid of its gate, in f32."""
    return (a.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(dtype)


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


def _carried(prev: list, u):
    """The conv inputs a token leaves behind it, oldest first: the last
    ``taps - 2`` of those before it, and its own."""
    return jnp.stack(prev[1:] + [u], axis=1)


def _fresh_rows(row_start):
    """Rows whose run is their sequence's first: their state starts from
    zero inside the program, whatever the slot's last owner left."""
    return row_start == 0


def decay_parameters(key, n_heads: int, width: int, *, low=0.955,
                     high=0.9998):
    """``(A_log (n_heads,), dt_bias (width,))`` f32 such that, where the
    low-rank decay projection gives zero, a channel's decay a token
    ``exp(-exp(A_log) softplus(dt_bias))`` is log-uniform in its distance
    from 1 between ``low`` and ``high``: ``A_log = 0`` and ``dt_bias`` the
    inverse softplus of ``-log(decay)``.  With ``W_fb`` drawn at a fifth of
    1/sqrt(fan_in) (:func:`~pathway_tpu.models.kimi_linear
    .init_kimi_linear_params`) the projection is N(0, 0.2^2) a channel and
    moves the rate by a factor of at most 2.2 at four deviations: every
    decay a token lies between 0.9 and 0.9999."""
    u = jax.random.uniform(key, (width,), jnp.float32)
    rate = -jnp.log(1.0 - jnp.exp(
        np.log(1.0 - low) + u * (np.log(1.0 - high) - np.log(1.0 - low))))
    return jnp.zeros((n_heads,), jnp.float32), jnp.log(jnp.expm1(rate))


# -- the step contract --------------------------------------------------------


def greedy_steps(forward, experts_counted, row_live: bool = False,
                 per_row: int = 1, counters: tuple = ()):
    """The three step programs of a routed family, ``(mixed, decode,
    chained)``, over ``(params, cfg, *cache arrays, *host arrays, attn=)``.

    ``forward`` takes the argument list of
    :func:`pathway_tpu.models.decoder.paged_mixed_step` with the family's
    cache arrays for the K/V pools and its ``per_row`` per-row arrays (arena
    slots, or window tables, or both: the cache's ``row_extras``) after
    ``logit_idx``, then ``valid`` (T,) which tokens are real and, where
    ``row_live``, ``row_live`` (B,) which rows are; ``decode=True`` where
    every row is one token at column 0.  It returns ``(logits (B, V) f32,
    *cache arrays, counts)``: the expert layers' counter vector of
    ``experts_counted(cfg)`` experts (none, for a family without expert
    layers), :data:`pathway_tpu.ops.moe.COUNTER_TAIL` and the family's own
    ``counters`` (names of the cache's stats; the cache folds them under
    those names).  A family passes a lambda that names its ``_forward``, so
    that the function is found at trace time."""
    n = per_row

    def split(args):
        """``(cache arrays, the five decode arrays, per-row arrays)``."""
        return args[:-5 - n], args[-5 - n:-n], args[-n:]

    def mixed(params: dict, cfg, *args, attn: str = "reference"):
        """The ragged fused step (decode rows and prompt chunks on one
        packed stream).  A packed token is real where its row's run holds it
        (padding tokens point at row 0, column 0, which is another token's
        place); a row is real where its first token is its own."""
        (tokens, _positions, _tables, row_start, _nvalid, row_token_idx,
         tok_row, tok_col) = args[-11 - n:-3 - n]
        T = tokens.shape[0]
        valid = row_token_idx[tok_row, tok_col] \
            == jnp.arange(T, dtype=jnp.int32)
        rows = ()
        if row_live:
            B = row_start.shape[0]
            first = row_token_idx[:, 0]
            rows = (valid[first]
                    & (tok_row[first] == jnp.arange(B, dtype=jnp.int32)),)
        return forward(params, cfg, *args, valid, *rows, attn=attn,
                       decode=False)

    def decode(params: dict, cfg, *args, attn: str = "reference"):
        """One token a row.  An idle row has the null block first in its
        tables and rides slot 0."""
        cache, (token, positions, block_tables, slot_blocks,
                slot_offsets), extras = split(args)
        B = token.shape[0]
        rows = jnp.arange(B, dtype=jnp.int32)
        # where the mask goes among the operations shows in the lowered
        # text: first where it is two masks, after the index arrays where
        # it is ``valid`` alone
        live = block_tables[:, 0] > 0 if row_live else None
        index = (positions, jnp.ones((B,), jnp.int32), rows[:, None], rows,
                 jnp.zeros((B,), jnp.int32))
        masks = (live, live) if row_live else (block_tables[:, 0] > 0,)
        return forward(
            params, cfg, *cache, token, positions, block_tables, *index,
            slot_blocks, slot_offsets, rows, *extras, *masks, attn=attn,
            decode=True)

    def chained(params: dict, cfg, *args, attn: str = "reference"):
        """K greedy decode steps in one program (``slot_blocks`` /
        ``slot_offsets`` (B, K), the host's pre-extended slots; a window
        table holds the chain's blocks already and none is freed inside
        it), step t's ids feeding step t + 1, the cache arrays riding the
        scan.  Returns ``(ids (B, K), *cache arrays, counts)``."""
        from ..ops.moe import COUNTER_TAIL

        cache, (token, positions, block_tables, slot_blocks,
                slot_offsets), extras = split(args)
        K = slot_blocks.shape[1]
        maxp = cfg.max_len - 1

        def body(carry, xs):
            tok, *arrays, cnt = carry
            sb, so, t = xs
            logits, *arrays, n_tok = decode(
                params, cfg, *arrays, tok, jnp.minimum(positions + t, maxp),
                block_tables, sb, so, *extras, attn=attn)
            ids = greedy_ids(logits)
            return (ids, *arrays, cnt + n_tok), ids

        init = (token.astype(jnp.int32), *cache,
                jnp.zeros((experts_counted(cfg) + len(COUNTER_TAIL)
                           + len(counters),), jnp.int32))
        (_last, *cache, counts), ids = jax.lax.scan(
            body, init, (slot_blocks.T, slot_offsets.T,
                         jnp.arange(K, dtype=jnp.int32)))
        return (ids.T, *cache, counts)

    return mixed, decode, chained
