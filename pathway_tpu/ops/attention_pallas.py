"""Pallas TPU flash attention: fused online-softmax attention in VMEM.

Single-chip long-context hot path (SURVEY §5): scores never materialize in
HBM — each (q-block, k-block) tile is a (128,128) MXU matmul whose partial
softmax folds into running (m, l, acc) scratch carried across the innermost
grid dimension (sequential on TPU, so VMEM scratch persists between k
steps).  Complements the sequence-parallel paths in models/attention.py:
ring/Ulysses shard T across chips; this kernel is what each chip runs.

The compiled kernel serves on a TPU backend and the XLA reference
implementation elsewhere; interpret=True exercises the same kernel body on
CPU in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._tiling import pad_to as _pad_to

TILE_Q = 128
TILE_K = 128
_NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  nk: int, causal: bool, t_valid: int, scale: float):
    """Grid: (BH, nQ, nK) — k innermost.  Blocks: q/o (TILE_Q, D);
    k/v (TILE_K, D).  Scratch m/l (TILE_Q, 128) f32, acc (TILE_Q, D) f32."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _update_block():
        # scale uses the TRUE head dim, not the lane-padded one
        s = jax.lax.dot_general(
            q_ref[:], k_ref[:],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (TILE_Q, TILE_K)

        q_pos = iq * TILE_Q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ik * TILE_K + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = k_pos < t_valid  # padding beyond the true sequence
        if causal:
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        s = jnp.where(valid, s, _NEG)

        m_prev = m_ref[:, :1]  # (TILE_Q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # fully-masked rows give exp(_NEG-_NEG)=1
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # skip k-blocks entirely above the diagonal: ~2x less MXU work
        @pl.when(ik * TILE_K <= iq * TILE_Q + TILE_Q - 1)
        def _visible():
            _update_block()
    else:
        _update_block()

    @pl.when(ik == nk - 1)
    def _final():
        denom = jnp.maximum(l_ref[:, :1], 1e-20)
        o_ref[:] = (acc_ref[:] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "t_valid", "d_true", "interpret")
)
def _flash_bhtd(q, k, v, *, causal: bool, t_valid: int | None = None,
                d_true: int | None = None, interpret: bool = False):
    """q/k/v: (BH, T, D) with T, D already padded to tiles."""
    BH, T, D = q.shape
    nq, nk = T // TILE_Q, T // TILE_K
    tv = T if t_valid is None else t_valid
    kernel = functools.partial(
        _flash_kernel, nk=nk, causal=causal, t_valid=tv,
        scale=1.0 / np.sqrt(d_true or D),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((None, TILE_Q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, TILE_K, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, TILE_K, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, TILE_Q, D), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((TILE_Q, 128), jnp.float32),  # m
            pltpu.VMEM((TILE_Q, 128), jnp.float32),  # l
            pltpu.VMEM((TILE_Q, D), jnp.float32),    # acc
        ],
        interpret=interpret,
    )(q, k, v)


def flash_attention(q, k, v, *, causal: bool = False,
                    use_pallas: bool | None = None,
                    interpret: bool | None = None):
    """(B, T, H, D) fused attention; same contract as
    models.attention.reference_attention.

    use_pallas default: compiled kernel on TPU, XLA fallback elsewhere (the
    interpreted kernel is for tests).  interpret default: interpreted off
    TPU; pass False to demand a real Mosaic/Triton compile (bench probes —
    an interpreted T=4096 run would stall for minutes)."""
    backend = jax.default_backend()
    if use_pallas is None:
        use_pallas = backend == "tpu"
    if not use_pallas:
        from ..models.attention import reference_attention

        return reference_attention(q, k, v, causal=causal)
    B, T, H, D = q.shape

    def to_bhtd(x):
        x = jnp.moveaxis(x, 2, 1).reshape(B * H, T, D)
        x = _pad_to(x, 1, max(TILE_Q, TILE_K))
        return _pad_to(x, 2, 128)

    qq, kk, vv = to_bhtd(q), to_bhtd(k), to_bhtd(v)
    out = _flash_bhtd(
        qq, kk, vv, causal=causal, t_valid=T, d_true=D,
        interpret=(backend != "tpu") if interpret is None else interpret,
    )
    out = out[:, :T, :D].reshape(B, H, T, D)
    return jnp.moveaxis(out, 1, 2)
