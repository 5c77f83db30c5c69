"""Mesh-sharded brute-force KNN: the index matrix rides the device mesh.

Re-imagination of the reference's single-threaded ndarray scan
(src/external_integration/brute_force_knn_integration.rs:22-60) at v5e-8
scale: the (N, d) matrix is sharded by rows across the mesh's devices
(HBM-resident shards, cached between queries), queries are replicated, and
each device computes a local matmul + top-k; the k candidates per device
are all-gathered over ICI and merged — O(N/n_dev) FLOPs per device and
k*n_dev, not N, bytes on the interconnect.

Padding to a power-of-two row bucket keeps XLA shapes static across
incremental adds (one compile per bucket); padded rows are masked to -inf
INSIDE the kernel via their global row ids, so they can never displace
real (even negative-scoring) neighbors.
"""

from __future__ import annotations

import numpy as np

# (mesh id, axis, k, metric) -> jitted fn; bounded: cleared when oversized
_FNS: dict = {}
_MAX_FNS = 64


def _sharded_topk_fn(mesh, axis: str, k: int, metric: str):
    key = (id(mesh), axis, k, metric)
    fn = _FNS.get(key)
    if fn is not None:
        return fn
    if len(_FNS) > _MAX_FNS:
        _FNS.clear()

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def local_topk(m_shard, qs, n_live):
        # m_shard: (rows/n_dev, d) local rows; qs: (Q, d) replicated;
        # n_live: scalar — rows with global id >= n_live are padding
        rows = m_shard.shape[0]
        offset = jax.lax.axis_index(axis) * rows
        row_ids = offset + jnp.arange(rows)
        if metric == "cos":
            mn = m_shard / (jnp.linalg.norm(m_shard, axis=1, keepdims=True) + 1e-12)
            qn = qs / (jnp.linalg.norm(qs, axis=1, keepdims=True) + 1e-12)
            scores = qn @ mn.T
        elif metric == "dot":
            scores = qs @ m_shard.T
        else:  # l2sq
            scores = (
                2.0 * (qs @ m_shard.T)
                - jnp.sum(m_shard * m_shard, axis=1)[None, :]
                - jnp.sum(qs * qs, axis=1)[:, None]
            )
        scores = jnp.where(row_ids[None, :] < n_live, scores, -jnp.inf)
        kk = min(k, rows)
        vals, idx = jax.lax.top_k(scores, kk)  # (Q, kk) local
        gidx = idx + offset
        all_vals = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
        all_idx = jax.lax.all_gather(gidx, axis, axis=1, tiled=True)
        mvals, mpos = jax.lax.top_k(all_vals, min(k, all_vals.shape[1]))
        midx = jnp.take_along_axis(all_idx, mpos, axis=1)
        return mvals, midx

    fn = jax.jit(
        jax.shard_map(
            local_topk,
            mesh=mesh,
            in_specs=(P(axis, None), P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )
    _FNS[key] = fn
    return fn


def row_bucket(n: int, n_dev: int) -> int:
    """Power-of-two row count >= n, divisible by n_dev (static XLA shapes
    across incremental adds)."""
    b = max(n_dev, 1)
    while b < n:
        b *= 2
    return b + (-b) % n_dev


def shard_matrix(mesh, axis: str, matrix: np.ndarray, bucket: int):
    """Pad to `bucket` rows and lay the matrix out row-sharded on the mesh
    (device-resident; callers cache the result between queries)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n, d = matrix.shape
    if bucket > n:
        padded = np.zeros((bucket, d), matrix.dtype)
        padded[:n] = matrix
    else:
        padded = matrix
    return jax.device_put(padded, NamedSharding(mesh, P(axis, None)))


def sharded_topk_device(mesh, axis: str, device_matrix, queries: np.ndarray,
                        k: int, metric: str, n_live: int):
    """(Q, k) top scores + global row indices over a pre-sharded matrix."""
    import jax.numpy as jnp

    fn = _sharded_topk_fn(mesh, axis, k, metric)
    vals, idx = fn(
        device_matrix,
        np.asarray(queries, np.float32),
        jnp.int32(n_live),
    )
    return np.asarray(vals), np.asarray(idx)


def sharded_topk(mesh, axis: str, matrix: np.ndarray, queries: np.ndarray,
                 k: int, metric: str = "cos"):
    """One-shot convenience (tests/dryrun): shard + search."""
    n_dev = mesh.shape[axis]
    bucket = row_bucket(len(matrix), n_dev)
    dm = shard_matrix(mesh, axis, np.asarray(matrix, np.float32), bucket)
    return sharded_topk_device(mesh, axis, dm, queries, k, metric, len(matrix))
