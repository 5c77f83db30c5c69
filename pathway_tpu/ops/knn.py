"""On-device KNN scoring: matmul + top-k on the accelerator.

Replaces the reference's ndarray brute-force scan
(src/external_integration/brute_force_knn_integration.rs:22-60) with an XLA
matmul that hits the MXU; scores come back to host for merging with the
index's key table.  Batched queries use a single (Q,d)x(d,N) matmul.
"""

from __future__ import annotations

import functools

import numpy as np

@functools.lru_cache(maxsize=1)
def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def to_device(matrix: np.ndarray):
    """Pin an index matrix on the accelerator once; callers cache the result
    and pass it back to device_topk_scores so serving queries don't re-upload
    the corpus (host->HBM transfer per query would dominate TPU latency)."""
    jax, jnp = _jax()
    return jax.device_put(matrix)


@functools.lru_cache(maxsize=8)
def _scores_fn(metric: str):
    jax, jnp = _jax()

    @jax.jit
    def cos(m, q):
        qn = q / (jnp.linalg.norm(q) + 1e-12)
        mn = m / (jnp.linalg.norm(m, axis=1, keepdims=True) + 1e-12)
        return mn @ qn

    @jax.jit
    def cos_prenorm(m, q):
        # matrix rows already L2-normalized (pinned once via to_device);
        # per-query work is one (N,d)@(d,) matmul
        return m @ (q / (jnp.linalg.norm(q) + 1e-12))

    @jax.jit
    def dot(m, q):
        return m @ q

    @jax.jit
    def l2sq(m, q):
        # -(|m|^2 - 2 m.q + |q|^2); matmul form keeps the MXU busy
        return 2.0 * (m @ q) - jnp.sum(m * m, axis=1) - jnp.sum(q * q)

    return {"cos": cos, "cos_prenorm": cos_prenorm, "dot": dot,
            "l2sq": l2sq}[metric]


def device_topk_scores(matrix, query: np.ndarray, metric: str = "cos") -> np.ndarray:
    """Full score vector computed on device.  `matrix` may be a host ndarray
    or a device array previously pinned with to_device (zero-copy reuse)."""
    jax, jnp = _jax()
    m = jnp.asarray(matrix)
    q = jnp.asarray(query)
    return np.asarray(_scores_fn(metric)(m, q))


@functools.lru_cache(maxsize=8)
def _batched_topk_fn(metric: str, k: int):
    jax, jnp = _jax()

    @jax.jit
    def run(m, qs, n_valid):
        if metric == "cos":
            mn = m / (jnp.linalg.norm(m, axis=1, keepdims=True) + 1e-12)
            qn = qs / (jnp.linalg.norm(qs, axis=1, keepdims=True) + 1e-12)
            scores = qn @ mn.T
        elif metric == "dot":
            scores = qs @ m.T
        else:
            scores = (
                2.0 * (qs @ m.T)
                - jnp.sum(m * m, axis=1)[None, :]
                - jnp.sum(qs * qs, axis=1)[:, None]
            )
        # n_valid is a traced scalar: bucket-padded matrices mask their
        # padding rows without a recompile per index version
        scores = jnp.where(
            jnp.arange(m.shape[0])[None, :] < n_valid, scores, -jnp.inf)
        vals, idx = jax.lax.top_k(scores, k)
        return vals, idx

    return run


def batched_topk(matrix: np.ndarray, queries: np.ndarray, k: int,
                 metric: str = "cos", n_valid: int | None = None):
    """(Q,k) top-k values and indices for a batch of queries — one device
    dispatch for the whole micro-batch.  `n_valid` masks bucket padding
    rows (scores forced to -inf)."""
    jax, jnp = _jax()
    nv = int(matrix.shape[0]) if n_valid is None else int(n_valid)
    k = min(k, nv)
    vals, idx = _batched_topk_fn(metric, k)(
        jnp.asarray(matrix), jnp.asarray(queries), nv)
    return np.asarray(vals), np.asarray(idx)


@functools.lru_cache(maxsize=16)
def _single_topk_fn(metric: str, k: int):
    jax, jnp = _jax()

    @jax.jit
    def run(m, q, n_valid):
        if metric == "cos_prenorm":
            scores = m @ (q / (jnp.linalg.norm(q) + 1e-12))
        elif metric == "cos":
            qn = q / (jnp.linalg.norm(q) + 1e-12)
            mn = m / (jnp.linalg.norm(m, axis=1, keepdims=True) + 1e-12)
            scores = mn @ qn
        elif metric == "dot":
            scores = m @ q
        else:  # l2sq
            scores = 2.0 * (m @ q) - jnp.sum(m * m, axis=1) - jnp.sum(q * q)
        scores = jnp.where(jnp.arange(m.shape[0]) < n_valid, scores,
                           -jnp.inf)
        return jax.lax.top_k(scores, k)

    return run


def device_topk(matrix, query: np.ndarray, k: int, metric: str = "cos",
                n_valid: int | None = None):
    """Single-query top-k computed ENTIRELY on device; only the (k,) values
    and indices cross back to the host.  Fetching the full score vector (the
    old device_topk_scores path) costs O(N) device->host bytes per query.
    `n_valid` masks bucket-padding rows."""
    jax, jnp = _jax()
    nv = int(matrix.shape[0]) if n_valid is None else int(n_valid)
    k = min(k, nv)
    vals, idx = _single_topk_fn(metric, k)(matrix, jnp.asarray(query), nv)
    return np.asarray(vals), np.asarray(idx)
