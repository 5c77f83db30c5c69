"""Pallas TPU kernel for KNN scoring: tiled (Q,d)x(d,N) on the MXU.

Replaces the reference's ndarray scan (brute_force_knn_integration.rs:22-60).
Docs and queries are pre-normalized for cosine; the kernel is a blocked
matmul with f32 accumulation over bf16 inputs, padded to MXU-friendly tiles.
Top-k runs on the scores via lax.top_k (XLA's native implementation).

Kernel selection is one rule (`knn_topk`): the compiled kernel on a TPU
backend, the jnp matmul elsewhere; `use_pallas=True` off-TPU runs the same
kernel body interpreted (tests).  A selected kernel that fails raises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ._tiling import pad_to as _pad_to

TILE_Q = 128
TILE_N = 256


def _scores_kernel(q_ref, m_ref, out_ref):
    # q: (TILE_Q, d) bf16; m: (TILE_N, d) bf16; out: (TILE_Q, TILE_N) f32
    q = q_ref[:]
    m = m_ref[:]
    out_ref[:] = jax.lax.dot_general(
        q, m,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_scores(queries: jax.Array, matrix: jax.Array, *, interpret: bool = False):
    """(Q,d) x (N,d) -> (Q,N) f32 scores via a tiled Pallas matmul."""
    Q0, d = queries.shape
    N0 = matrix.shape[0]
    # f32 inputs keep results identical to the host path (the MXU still
    # pipelines f32 matmuls; switch to bf16 only with a matching host path)
    q = _pad_to(queries.astype(jnp.float32), 0, TILE_Q)
    m = _pad_to(matrix.astype(jnp.float32), 0, TILE_N)
    # lane-align the contraction dim
    q = _pad_to(q, 1, 128)
    m = _pad_to(m, 1, 128)
    Q, dd = q.shape
    N = m.shape[0]

    grid = (Q // TILE_Q, N // TILE_N)
    out = pl.pallas_call(
        _scores_kernel,
        out_shape=jax.ShapeDtypeStruct((Q, N), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_Q, dd), lambda i, j: (i, 0)),
            pl.BlockSpec((TILE_N, dd), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_Q, TILE_N), lambda i, j: (i, j)),
        interpret=interpret,
    )(q, m)
    return out[:Q0, :N0]


def knn_topk(matrix: np.ndarray, queries: np.ndarray, k: int, metric: str = "cos",
             *, use_pallas: bool | None = None):
    """Batched exact KNN: returns (scores (Q,k), indices (Q,k)).

    use_pallas default: the compiled kernel on a TPU backend, the jnp
    matmul elsewhere (an interpreted kernel is only worth running in tests).
    """
    backend = jax.default_backend()
    if use_pallas is None:
        use_pallas = backend == "tpu"
    m = jnp.asarray(matrix)
    q = jnp.asarray(queries)
    if metric == "cos":
        m = m / (jnp.linalg.norm(m, axis=1, keepdims=True) + 1e-12)
        q = q / (jnp.linalg.norm(q, axis=1, keepdims=True) + 1e-12)
        scores = _dispatch_scores(q, m, use_pallas)
    elif metric == "dot":
        scores = _dispatch_scores(q, m, use_pallas)
    else:  # l2sq
        s = _dispatch_scores(q, m, use_pallas)
        scores = (
            2.0 * s
            - jnp.sum(m * m, axis=1)[None, :]
            - jnp.sum(q * q, axis=1)[:, None]
        )
    k = min(k, matrix.shape[0])
    vals, idx = jax.lax.top_k(scores, k)
    return np.asarray(vals), np.asarray(idx)


def _dispatch_scores(q, m, use_pallas: bool):
    if use_pallas:
        return pallas_scores(q, m, interpret=jax.default_backend() != "tpu")
    return q.astype(jnp.float32) @ m.astype(jnp.float32).T
