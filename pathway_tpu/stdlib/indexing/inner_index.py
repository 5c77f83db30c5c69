"""Mutable secondary indexes (reference: src/external_integration/mod.rs:41-49
ExternalIndex trait: add/remove/search; brute_force_knn_integration.rs:22-60;
tantivy_integration.rs).

The vector index keeps vectors in a dense matrix so search is a single
matmul+top-k — numpy on host, jax on TPU when available (ops/knn.py).
"""

from __future__ import annotations

import math
import re
import time as _time
from collections import Counter, defaultdict
from typing import Any, Callable

import numpy as np

from ... import obs


class InnerIndex:
    def add(self, key: int, item: Any, metadata: Any = None) -> None:
        raise NotImplementedError

    def remove(self, key: int) -> None:
        raise NotImplementedError

    def search(self, query: Any, k: int, metadata_filter: str | None = None) -> list[tuple[int, float]]:
        """Returns [(key, score)] with higher score = better."""
        raise NotImplementedError


def _check_metadata(metadata, metadata_filter: str | None) -> bool:
    if metadata_filter is None:
        return True
    from .jmespath_filter import evaluate_filter

    return evaluate_filter(metadata_filter, metadata)


class BruteForceKnn(InnerIndex):
    """Dense exact KNN: one (N,d) matrix, search = matmul + top-k.

    TPU path: when the matrix crosses `device_threshold` rows the matmul+top-k
    is executed with JAX on the accelerator (ops/knn.py), sharded over the
    device mesh by rows.
    """

    def __init__(
        self,
        dimensions: int | None = None,
        *,
        reserved_space: int = 1024,
        metric: str = "cos",
        device_threshold: int = 2048,
        mesh=None,
        mesh_axis: str = "dp",
    ):
        self.dim = dimensions
        self.metric = metric
        self.capacity = max(reserved_space, 16)
        self.matrix: np.ndarray | None = None
        self.keys: list[int] = []
        self.slot_of: dict[int, int] = {}
        self.metadata: dict[int, Any] = {}
        self.n = 0
        self.device_threshold = device_threshold
        # engine-on-mesh: with a jax Mesh the matrix rows shard across
        # devices and search merges per-device top-k (ops/knn_sharded.py)
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self._device_cache = None
        # device-resident mode: slots whose vector lives in a DeviceVecStore
        # (ops/device_store.py) and never crossed to the host.  slot ->
        # (store, batch, row); consolidation gathers them on device.
        self._dev_refs: dict[int, tuple] = {}
        self._version = 0
        self._dev_matrix = None  # (token, device (bucket,d) matrix)
        self._dev_valid = 0      # live rows in the bucketed device matrix
        self._host_mirror = None  # (token, np matrix) for the CPU latency tier
        self._host_mirror_norm = None  # (token, L2-normed matrix) for cos

    def _ensure(self, dim: int) -> None:
        if self.matrix is None:
            self.dim = dim
            self.matrix = np.zeros((self.capacity, dim), dtype=np.float32)

    def add(self, key: int, item: Any, metadata: Any = None) -> None:
        from ...ops.device_store import DeviceVec

        if isinstance(item, DeviceVec):
            # device-resident ingest: record the HBM ref, no host transfer
            self._ensure(item.store.dim)
            if key in self.slot_of:
                slot = self.slot_of[key]
                self._dev_refs[slot] = (item.store, item.batch, item.row_idx)
                self.metadata[key] = metadata
                self._invalidate()
                return
            self._grow_if_full()
            self._dev_refs[self.n] = (item.store, item.batch, item.row_idx)
            self.slot_of[key] = self.n
            self.keys.append(key)
            self.metadata[key] = metadata
            self.n += 1
            self._invalidate()
            return
        vec = np.asarray(item, dtype=np.float32).reshape(-1)
        self._ensure(vec.shape[0])
        if key in self.slot_of:
            slot = self.slot_of[key]
            self.matrix[slot] = vec
            self._dev_refs.pop(slot, None)
            self.metadata[key] = metadata
            self._invalidate()
            return
        self._grow_if_full()
        self.matrix[self.n] = vec
        self.slot_of[key] = self.n
        self.keys.append(key)
        self.metadata[key] = metadata
        self.n += 1
        self._invalidate()

    def _grow_if_full(self) -> None:
        if self.n == self.capacity:
            self.capacity *= 2
            new = np.zeros((self.capacity, self.dim), dtype=np.float32)
            new[: self.n] = self.matrix[: self.n]
            self.matrix = new

    def _invalidate(self) -> None:
        self._device_cache = None
        self._dev_matrix = None
        self._host_mirror = None
        self._host_mirror_norm = None
        self._version += 1

    def remove(self, key: int) -> None:
        slot = self.slot_of.pop(key, None)
        if slot is None:
            return
        last = self.n - 1
        last_key = self.keys[last]
        if slot != last:
            self.matrix[slot] = self.matrix[last]
            last_ref = self._dev_refs.pop(last, None)
            if last_ref is not None:
                self._dev_refs[slot] = last_ref
            else:
                self._dev_refs.pop(slot, None)
            self.keys[slot] = last_key
            self.slot_of[last_key] = slot
        else:
            self._dev_refs.pop(slot, None)
        self.keys.pop()
        self.metadata.pop(key, None)
        self.n = last
        self._invalidate()

    # -- device-resident consolidation ------------------------------------
    @staticmethod
    def _bucket_rows(n: int) -> int:
        """Next power-of-two row bucket (min 256): consolidated matrices
        keep a STATIC shape as the index grows, so the search matmul +
        top-k recompiles only when the bucket steps, not per commit."""
        b = 256
        while b < n:
            b *= 2
        return b

    def _device_matrix(self, prenorm: bool):
        """One (bucket, d) device array over all live slots (zero-padded to
        the row bucket; `self._dev_valid` rows are live), gathered with a
        single dispatch; host rows (if any) are uploaded alongside.  Cached
        until the next mutation."""
        token = (self._version, prenorm)
        if self._dev_matrix is not None and self._dev_matrix[0] == token:
            return self._dev_matrix[1]
        import jax.numpy as jnp

        self._dev_valid = self.n
        stores = {ref[0].id for ref in self._dev_refs.values()}
        single_store = len(stores) == 1
        if single_store and len(self._dev_refs) == self.n and self.n > 0:
            store = next(iter(self._dev_refs.values()))[0]
            refs = [
                (self._dev_refs[s][1], self._dev_refs[s][2])
                for s in range(self.n)
            ]
            m = store.gather(refs, pad_to=self._bucket_rows(self.n))
        else:
            # mixed, host-only, or multi-store: upload host rows, then one
            # gather-and-scatter per distinct DeviceVecStore
            m = jnp.asarray(self.matrix[: self.n])
            if self._dev_refs:
                by_store: dict[int, tuple] = {}
                for s, (store, b, r) in self._dev_refs.items():
                    by_store.setdefault(store.id, (store, []))[1].append(
                        (s, b, r)
                    )
                for store, entries in by_store.values():
                    slots = [s for s, _b, _r in entries]
                    gathered = store.gather(
                        [(b, r) for _s, b, r in entries]
                    )
                    m = m.at[jnp.asarray(slots, jnp.int32)].set(gathered)
        if prenorm:
            m = m / (jnp.linalg.norm(m, axis=1, keepdims=True) + 1e-12)
        self._dev_matrix = (token, m)
        return m

    def host_matrix(self) -> np.ndarray:
        """Host copy of all live vectors for the CPU serving tier — fetched
        once per index version as float16 (halving the device->host bytes)
        and cached."""
        if self._host_mirror is not None and self._host_mirror[0] == self._version:
            return self._host_mirror[1]
        if not self._dev_refs:
            m = self.matrix[: self.n].copy()
        else:
            import jax.numpy as jnp

            dev = self._device_matrix(prenorm=False)
            m = np.asarray(dev.astype(jnp.float16)).astype(
                np.float32)[: self._dev_valid]
        self._host_mirror = (self._version, m)
        return m

    def _scores(self, q: np.ndarray) -> np.ndarray:
        m = self.host_matrix() if self._dev_refs else self.matrix[: self.n]
        if self.metric == "cos":
            qn = q / (np.linalg.norm(q) + 1e-12)
            # shared version-keyed normalized mirror (same cache the
            # tier="cpu" branch uses; _invalidate clears it on mutation) —
            # renormalizing the matrix per query costs ~0.5ms at 4096x384
            if (
                self._host_mirror_norm is None
                or self._host_mirror_norm[0] != self._version
            ):
                mn = m / (np.linalg.norm(m, axis=1, keepdims=True) + 1e-12)
                self._host_mirror_norm = (self._version, mn)
            return self._host_mirror_norm[1] @ qn
        if self.metric == "l2sq":
            return -np.sum((m - q) ** 2, axis=1)
        return m @ q  # dot

    def search_batch(self, queries, k: int) -> list[list[tuple[int, float]]]:
        """Batched search (no metadata filter): one device dispatch for the
        whole micro-batch — Pallas matmul + top-k on TPU.  Below the device
        threshold the per-query numpy path runs so single/batched results
        are identical (both f32)."""
        if self.n == 0:
            return [[] for _ in queries]
        if self._dev_refs:
            # device-resident rows: one batched matmul+top-k dispatch against
            # the consolidated HBM matrix; only (Q, k) results come back
            from ...ops.knn import batched_topk

            qs = np.asarray(
                [np.asarray(q, np.float32).reshape(-1) for q in queries]
            )
            vals, idx = batched_topk(
                self._device_matrix(prenorm=False), qs, k, self.metric,
                n_valid=self._dev_valid,
            )
            return [
                [(self.keys[int(i)], float(v)) for v, i in zip(vi, ii)]
                for vi, ii in zip(vals, idx)
            ]
        if self.n < self.device_threshold:
            return [self.search(q, k) for q in queries]
        qs = np.asarray([np.asarray(q, np.float32).reshape(-1) for q in queries])
        from ...ops.knn_pallas import knn_topk

        vals, idx = knn_topk(self.matrix[: self.n], qs, k, self.metric)
        out = []
        for vi, ii in zip(vals, idx):
            out.append([(self.keys[int(i)], float(v)) for v, i in zip(vi, ii)])
        return out

    def search(self, query: Any, k: int, metadata_filter: str | None = None,
               tier: str = "auto") -> list[tuple[int, float]]:
        """tier: "auto" (device for device-resident/large indexes), "cpu"
        (serving latency tier: host-mirror numpy scan — one small matmul,
        no device round trip), "device" (force the accelerator path)."""
        if self.n == 0:
            return []
        q = np.asarray(query, dtype=np.float32).reshape(-1)
        if tier == "cpu" and metadata_filter is None:
            m = self.host_matrix()
            if self.metric == "cos":
                qn = q / (np.linalg.norm(q) + 1e-12)
                # normalized mirror cached per index version: re-norming the
                # whole matrix per query dominated the r3 serving p50
                if (
                    self._host_mirror_norm is None
                    or self._host_mirror_norm[0] != self._version
                ):
                    mn = m / (np.linalg.norm(m, axis=1, keepdims=True) + 1e-12)
                    self._host_mirror_norm = (self._version, mn)
                mn = self._host_mirror_norm[1]
                scores = mn @ qn
            elif self.metric == "l2sq":
                scores = -np.sum((m - q) ** 2, axis=1)
            else:
                scores = m @ q
            kk = min(k, self.n)
            idx = (
                np.argpartition(-scores, kk - 1)[:kk]
                if kk < self.n else np.arange(self.n)
            )
            order = idx[np.argsort(-scores[idx])]
            return [(self.keys[i], float(scores[i])) for i in order]
        if self._dev_refs and metadata_filter is None:
            # device-resident rows: matmul + top-k in one dispatch, only
            # the (k,) results come back to the host
            from ...ops.knn import device_topk

            prenorm = self.metric == "cos"
            metric = "cos_prenorm" if prenorm else self.metric
            vals, idx = device_topk(
                self._device_matrix(prenorm=prenorm), q, k, metric,
                n_valid=self._dev_valid,
            )
            return [(self.keys[int(i)], float(v)) for v, i in zip(vals, idx)]
        if self.mesh is not None and metadata_filter is None and self.n >= k:
            from ...ops import knn_sharded as ks

            n_dev = self.mesh.shape[self.mesh_axis]
            bucket = ks.row_bucket(self.n, n_dev)
            cache = (self._device_cache or {}).get("mesh")
            if not (
                isinstance(cache, tuple) and cache[0] == ("mesh", bucket, self.n)
            ):
                dm = ks.shard_matrix(
                    self.mesh, self.mesh_axis, self.matrix[: self.n], bucket
                )
                cache = (("mesh", bucket, self.n), dm)
                self._device_cache = {**(self._device_cache or {}),
                                      "mesh": cache}
            vals, idx = ks.sharded_topk_device(
                self.mesh, self.mesh_axis, cache[1], q[None, :],
                min(k, self.n), self.metric, self.n,
            )
            return [
                (self.keys[int(i)], float(v))
                for v, i in zip(vals[0], idx[0])
                if v != -np.inf
            ]
        if self.n >= self.device_threshold:
            try:
                from ...ops.knn import device_topk, to_device

                cache = (self._device_cache or {}).get("single")
                token = ("single", self.n)
                if not (isinstance(cache, tuple) and cache[0] == token):
                    m = self.matrix[: self.n]
                    if self.metric == "cos":
                        # pre-normalize once per index version: serving
                        # queries pay one matmul, not a 6MB renormalize
                        m = m / (
                            np.linalg.norm(m, axis=1, keepdims=True) + 1e-12
                        )
                    cache = (token, to_device(m))
                    self._device_cache = {**(self._device_cache or {}),
                                          "single": cache}
                metric = "cos_prenorm" if self.metric == "cos" else self.metric
                if metadata_filter is None:
                    # top-k on device; only (k,) values/indices fetched
                    vals, idx = device_topk(cache[1], q, k, metric)
                    return [
                        (self.keys[int(i)], float(v))
                        for v, i in zip(vals, idx)
                    ]
                from ...ops.knn import device_topk_scores

                scores = device_topk_scores(cache[1], q, metric)
            except Exception:
                scores = self._scores(q)
        else:
            scores = self._scores(q)
        if metadata_filter is None:
            kk = min(k, self.n)
            idx = np.argpartition(-scores, kk - 1)[:kk] if kk < self.n else np.arange(self.n)
            order = idx[np.argsort(-scores[idx])]
            return [(self.keys[i], float(scores[i])) for i in order]
        out = []
        for i in np.argsort(-scores):
            key = self.keys[i]
            if _check_metadata(self.metadata.get(key), metadata_filter):
                out.append((key, float(scores[i])))
                if len(out) >= k:
                    break
        return out


class IvfKnn(InnerIndex):
    """Inverted-file ANN: the scale tier (reference equivalent: USearch HNSW,
    usearch_integration.rs:21-80 — re-designed for dense-matmul hardware).

    Vectors live in ONE matrix laid out cluster-major by a trained coarse
    quantizer, so probing a cluster is a contiguous-block matmul (zero
    gather, zero pointer chasing — the access pattern HBM/MXU wants).
    Search scores the C centroids (one small matmul), probes the `nprobe`
    best clusters' blocks, and exactly rescores their members.  Mutation is
    incremental: adds append to a per-cluster overflow tail; removes
    tombstone in place; the index re-trains and compacts when it outgrows
    its training set 4x or tombstones exceed 25%.
    """

    def __init__(
        self,
        dimensions: int | None = None,
        *,
        n_clusters: int = 256,
        nprobe: int = 16,
        metric: str = "cos",
        train_min: int = 4096,
        train_sample: int = 50_000,
        seed: int = 0,
        reserved_space: int = 1024,
    ):
        if metric not in ("cos", "dot", "l2sq"):
            raise ValueError(f"unsupported metric {metric!r}")
        self.dim = dimensions
        self.n_clusters = n_clusters
        self.nprobe = nprobe
        self.metric = metric
        self.train_min = train_min
        self.train_sample = train_sample
        self.seed = seed
        self.capacity = max(reserved_space, 16)
        self.matrix: np.ndarray | None = None  # normalized rows for cos
        self.keys: list[int] = []  # slot -> key
        self.slot_of: dict[int, int] = {}
        self.metadata: dict[int, Any] = {}
        self.alive: np.ndarray | None = None  # slot -> live?
        self.n_slots = 0
        self.n = 0  # live count
        self.centroids: np.ndarray | None = None
        self._cent_adj: np.ndarray | None = None  # -||c||^2 for l2sq assignment
        self.sqnorms: np.ndarray | None = None  # per-slot ||v||^2 (l2sq)
        # cluster-major layout: block_bounds[c]:block_bounds[c+1] are cluster
        # c's contiguous slots; later adds land in overflow[c] (slot lists)
        self.block_bounds: np.ndarray | None = None
        self.overflow: list[list[int]] = []
        self._trained_at = 0

    # -- storage ------------------------------------------------------------
    def _norm(self, vec: np.ndarray) -> np.ndarray:
        if self.metric == "cos":
            return vec / (np.linalg.norm(vec) + 1e-12)
        return vec

    def _ensure(self, dim: int) -> None:
        if self.matrix is None:
            self.dim = dim
            self.matrix = np.zeros((self.capacity, dim), dtype=np.float32)
            self.alive = np.zeros(self.capacity, bool)
            if self.metric == "l2sq":
                self.sqnorms = np.zeros(self.capacity, np.float32)

    def _grow(self) -> None:
        self.capacity *= 2
        new = np.zeros((self.capacity, self.dim), dtype=np.float32)
        new[: self.n_slots] = self.matrix[: self.n_slots]
        self.matrix = new
        na = np.zeros(self.capacity, bool)
        na[: self.n_slots] = self.alive[: self.n_slots]
        self.alive = na
        if self.sqnorms is not None:
            ns = np.zeros(self.capacity, np.float32)
            ns[: self.n_slots] = self.sqnorms[: self.n_slots]
            self.sqnorms = ns

    def add(self, key: int, item: Any, metadata: Any = None) -> None:
        vec = self._norm(np.asarray(item, dtype=np.float32).reshape(-1))
        self._ensure(vec.shape[0])
        if key in self.slot_of:
            self.remove(key)
        if self.n_slots == self.capacity:
            self._grow()
        slot = self.n_slots
        self.matrix[slot] = vec
        if self.sqnorms is not None:
            self.sqnorms[slot] = float(vec @ vec)
        self.alive[slot] = True
        self.slot_of[key] = slot
        self.keys.append(key)
        self.metadata[key] = metadata
        self.n_slots += 1
        self.n += 1
        if self.centroids is None:
            if self.n >= self.train_min:
                self._train()
        else:
            c = int(np.argmax(self._assign_scores(vec[None, :])[0]))
            self.overflow[c].append(slot)
            dead = self.n_slots - self.n
            if self.n >= 4 * max(self._trained_at, 1) or (
                self.n_slots > 64 and dead > self.n_slots // 4
            ):
                self._train()

    def remove(self, key: int) -> None:
        slot = self.slot_of.pop(key, None)
        if slot is None:
            return
        self.metadata.pop(key, None)
        self.alive[slot] = False  # tombstone; compaction happens at retrain
        self.n -= 1

    def _assign_scores(self, rows: np.ndarray) -> np.ndarray:
        """(B, C) centroid affinity; for l2sq this ranks by true distance."""
        s = rows @ self.centroids.T
        if self.metric == "l2sq":
            s = 2.0 * s + self._cent_adj[None, :]
        return s

    # -- quantizer ----------------------------------------------------------
    def _train(self) -> None:
        rng = np.random.default_rng(self.seed)
        live = np.flatnonzero(self.alive[: self.n_slots])
        n = len(live)
        if n == 0:
            return
        C = max(1, min(self.n_clusters, n // 8 or 1))
        sample_n = min(n, self.train_sample)
        sample = self.matrix[rng.choice(live, size=sample_n, replace=False)]
        # k-means: random init + a few matmul-assignment iterations
        cent = sample[rng.choice(sample_n, size=C, replace=False)].copy()
        for _ in range(6):
            if self.metric == "l2sq":
                adj = -np.sum(cent * cent, axis=1)
                assign = np.argmax(2.0 * (sample @ cent.T) + adj[None, :], axis=1)
            else:
                assign = np.argmax(sample @ cent.T, axis=1)
            for c in range(C):
                pts = sample[assign == c]
                if len(pts):
                    m = pts.mean(axis=0)
                    if self.metric == "cos":
                        m /= np.linalg.norm(m) + 1e-12
                    cent[c] = m
        self.centroids = cent.astype(np.float32)
        self._cent_adj = -np.sum(cent * cent, axis=1).astype(np.float32)
        # assign all live rows in chunks, then rebuild the matrix
        # cluster-major (compacting tombstones away)
        assigns = np.empty(n, np.int64)
        for s in range(0, n, 65536):
            rows = self.matrix[live[s : s + 65536]]
            assigns[s : s + len(rows)] = np.argmax(self._assign_scores(rows), axis=1)
        order = np.argsort(assigns, kind="stable")
        sorted_live = live[order]
        sorted_assigns = assigns[order]
        new_matrix = np.zeros((max(self.capacity, n), self.dim), np.float32)
        new_matrix[:n] = self.matrix[sorted_live]
        if self.sqnorms is not None:
            ns = np.zeros(len(new_matrix), np.float32)
            ns[:n] = self.sqnorms[sorted_live]
            self.sqnorms = ns
        old_keys = self.keys
        self.keys = [old_keys[s] for s in sorted_live]
        self.slot_of = {k: i for i, k in enumerate(self.keys)}
        self.matrix = new_matrix
        self.capacity = len(new_matrix)
        self.alive = np.zeros(self.capacity, bool)
        self.alive[:n] = True
        self.n_slots = n
        self.n = n
        counts = np.bincount(sorted_assigns, minlength=C)
        self.block_bounds = np.concatenate([[0], np.cumsum(counts)])
        self.overflow = [[] for _ in range(C)]
        self._trained_at = n

    # -- search -------------------------------------------------------------
    def search(self, query, k, metadata_filter=None):
        if self.n == 0:
            return []
        q = self._norm(np.asarray(query, dtype=np.float32).reshape(-1))
        qsq = float(q @ q)

        def _score_rows(rows_2d, sq_1d):
            sc = rows_2d @ q
            if self.metric == "l2sq":
                sc = 2.0 * sc - sq_1d - qsq
            return sc

        if self.centroids is None:
            # untrained: exact scan (small index)
            scores = _score_rows(
                self.matrix[: self.n_slots],
                self.sqnorms[: self.n_slots] if self.sqnorms is not None else None,
            )
            scores[~self.alive[: self.n_slots]] = -np.inf
            slots = np.arange(self.n_slots)
        else:
            cs = self._assign_scores(q[None, :])[0]
            np_probe = min(self.nprobe, len(cs))
            probe = np.argpartition(-cs, np_probe - 1)[:np_probe]
            slot_chunks = []
            score_chunks = []
            bb = self.block_bounds
            for c in probe:
                c = int(c)
                start, end = int(bb[c]), int(bb[c + 1])
                if end > start:
                    block_scores = _score_rows(
                        self.matrix[start:end],
                        self.sqnorms[start:end] if self.sqnorms is not None else None,
                    )
                    a = self.alive[start:end]
                    if not a.all():
                        block_scores = np.where(a, block_scores, -np.inf)
                    score_chunks.append(block_scores)
                    slot_chunks.append(np.arange(start, end))
                ov = self.overflow[c]
                if ov:
                    ov_arr = np.asarray(ov, np.int64)
                    ov_scores = _score_rows(
                        self.matrix[ov_arr],
                        self.sqnorms[ov_arr] if self.sqnorms is not None else None,
                    )
                    a = self.alive[ov_arr]
                    if not a.all():
                        ov_scores = np.where(a, ov_scores, -np.inf)
                    score_chunks.append(ov_scores)
                    slot_chunks.append(ov_arr)
            if not score_chunks:
                return []
            scores = np.concatenate(score_chunks)
            slots = np.concatenate(slot_chunks)
        if metadata_filter is None:
            kk = min(max(k * 4, k), len(scores))
            idx = (
                np.argpartition(-scores, kk - 1)[:kk]
                if kk < len(scores)
                else np.arange(len(scores))
            )
            order = idx[np.argsort(-scores[idx])]
        else:
            # a selective filter must scan past non-matching candidates
            # (BruteForceKnn parity), so rank ALL probed candidates
            order = np.argsort(-scores)
        out = []
        for i in order:
            if scores[i] == -np.inf:
                continue
            key = self.keys[int(slots[i])]
            if metadata_filter is not None and not _check_metadata(
                self.metadata.get(key), metadata_filter
            ):
                continue
            out.append((key, float(scores[i])))
            if len(out) >= k:
                break
        return out


class USearchKnn(BruteForceKnn):
    """API-parity alias: the reference's USearch HNSW
    (usearch_integration.rs:21-80).  Exact search here; the IVF index above
    is the native scale tier."""


class LshKnn(InnerIndex):
    """Locality-sensitive hashing ANN (reference: stdlib/ml/_lsh.py).

    Random-hyperplane buckets; search unions candidate buckets then scores
    exactly — the scalable tier when brute force outgrows HBM."""

    def __init__(self, dimensions: int | None = None, *, n_or: int = 8, n_and: int = 6,
                 bucket_length: float = 1.0, seed: int = 0, metric: str = "cos"):
        self.dim = dimensions
        self.n_or = n_or
        self.n_and = n_and
        self.seed = seed
        self.metric = metric
        self.planes: np.ndarray | None = None
        self.buckets: list[dict[bytes, set]] = [defaultdict(set) for _ in range(n_or)]
        self.vectors: dict[int, np.ndarray] = {}
        self.metadata: dict[int, Any] = {}

    def _ensure(self, dim: int) -> None:
        if self.planes is None:
            rng = np.random.default_rng(self.seed)
            self.planes = rng.normal(size=(self.n_or, self.n_and, dim)).astype(np.float32)
            self.dim = dim

    def _hashes(self, vec: np.ndarray) -> list[bytes]:
        bits = (np.einsum("oad,d->oa", self.planes, vec) > 0)
        return [bits[i].tobytes() for i in range(self.n_or)]

    def add(self, key: int, item: Any, metadata: Any = None) -> None:
        vec = np.asarray(item, dtype=np.float32).reshape(-1)
        self._ensure(vec.shape[0])
        if key in self.vectors:
            self.remove(key)
        self.vectors[key] = vec
        self.metadata[key] = metadata
        for i, h in enumerate(self._hashes(vec)):
            self.buckets[i][h].add(key)

    def remove(self, key: int) -> None:
        vec = self.vectors.pop(key, None)
        if vec is None:
            return
        self.metadata.pop(key, None)
        for i, h in enumerate(self._hashes(vec)):
            self.buckets[i][h].discard(key)

    def search(self, query, k, metadata_filter=None):
        if not self.vectors:
            return []
        q = np.asarray(query, dtype=np.float32).reshape(-1)
        self._ensure(q.shape[0])
        cands: set[int] = set()
        for i, h in enumerate(self._hashes(q)):
            cands |= self.buckets[i].get(h, set())
        if not cands:
            cands = set(self.vectors.keys())
        scored = []
        qn = q / (np.linalg.norm(q) + 1e-12)
        for key in cands:
            if metadata_filter is not None and not _check_metadata(
                self.metadata.get(key), metadata_filter
            ):
                continue
            v = self.vectors[key]
            if self.metric == "cos":
                s = float(v @ qn / (np.linalg.norm(v) + 1e-12))
            else:
                s = float(-np.sum((v - q) ** 2))
            scored.append((key, s))
        scored.sort(key=lambda t: -t[1])
        return scored[:k]


_TOKEN_RE = re.compile(r"\w+")


def _tokenize(text: str) -> list[str]:
    return [t.lower() for t in _TOKEN_RE.findall(text or "")]


class TantivyBM25(InnerIndex):
    """BM25 full-text index (reference: tantivy_integration.rs) — host-side
    inverted index with Okapi BM25 scoring."""

    def __init__(self, *, k1: float = 1.2, b: float = 0.75, **kwargs):
        self.k1, self.b = k1, b
        self.postings: dict[str, dict[int, int]] = defaultdict(dict)
        self.doc_len: dict[int, int] = {}
        self.metadata: dict[int, Any] = {}
        self.total_len = 0

    def add(self, key: int, item: Any, metadata: Any = None) -> None:
        if key in self.doc_len:
            self.remove(key)
        toks = _tokenize(item if isinstance(item, str) else str(item))
        counts = Counter(toks)
        for tok, c in counts.items():
            self.postings[tok][key] = c
        self.doc_len[key] = len(toks)
        self.total_len += len(toks)
        self.metadata[key] = metadata

    def remove(self, key: int) -> None:
        n = self.doc_len.pop(key, None)
        if n is None:
            return
        self.total_len -= n
        self.metadata.pop(key, None)
        for tok in list(self.postings.keys()):
            self.postings[tok].pop(key, None)
            if not self.postings[tok]:
                del self.postings[tok]

    def search(self, query, k, metadata_filter=None):
        if not self.doc_len:
            return []
        toks = _tokenize(query if isinstance(query, str) else str(query))
        n_docs = len(self.doc_len)
        avg_len = self.total_len / n_docs if n_docs else 1.0
        scores: dict[int, float] = defaultdict(float)
        for tok in toks:
            plist = self.postings.get(tok)
            if not plist:
                continue
            idf = math.log(1 + (n_docs - len(plist) + 0.5) / (len(plist) + 0.5))
            for key, tf in plist.items():
                dl = self.doc_len[key]
                scores[key] += idf * tf * (self.k1 + 1) / (
                    tf + self.k1 * (1 - self.b + self.b * dl / avg_len)
                )
        out = [
            (key, s)
            for key, s in scores.items()
            if metadata_filter is None or _check_metadata(self.metadata.get(key), metadata_filter)
        ]
        out.sort(key=lambda t: -t[1])
        return out[:k]


class HybridIndex(InnerIndex):
    """Reciprocal-rank fusion over sub-indexes (reference: hybrid_index.py:14).

    `weights` scales each sub-index's RRF contribution (w_i / (k + rank)),
    letting a caller down-weight a weaker retriever so fusion dominates
    both components instead of averaging toward the worse one; the default
    (all 1.0) is the reference's plain RRF.  A ZERO weight disables the
    sub-index completely — no adds, no removals, no probes — so callers
    (HybridIndexFactory) can also skip computing its items: a tuned-out
    retriever costs nothing at either index or query time (round-12)."""

    def __init__(self, inner_indexes: list[InnerIndex], *, k: float = 60.0,
                 weights: list[float] | None = None):
        self.inner = inner_indexes
        self.k = k
        if weights is not None and len(weights) != len(inner_indexes):
            raise ValueError("weights must match inner_indexes length")
        self.weights = weights or [1.0] * len(inner_indexes)

    def add(self, key, item, metadata=None):
        # item is a tuple: one entry per sub-index
        for idx, it, w in zip(self.inner, item, self.weights):
            if w == 0.0:
                continue  # disabled tier: its item may be raw/unembedded
            idx.add(key, it, metadata)

    def remove(self, key):
        for idx, w in zip(self.inner, self.weights):
            if w == 0.0:
                continue
            idx.remove(key)

    def search(self, query, k, metadata_filter=None):
        """Round-11: each sub-index probe and the RRF fusion (rerank)
        land as spans, so a hybrid `query_p50_ms` regression names its
        stage (dense probe vs BM25 probe vs fuse) instead of hiding in
        one aggregate number."""
        fused: dict[int, float] = defaultdict(float)
        for idx, q, w in zip(self.inner, query, self.weights):
            if w == 0.0:
                continue
            t0 = _time.perf_counter()
            matches = idx.search(q, k * 2, metadata_filter)
            obs.record_span("index.probe", t0, _time.perf_counter(),
                            kind=type(idx).__name__, k=k * 2)
            for rank, (key, _score) in enumerate(matches):
                fused[key] += w / (self.k + rank + 1)
        t0 = _time.perf_counter()
        out = sorted(fused.items(), key=lambda t: -t[1])[:k]
        obs.record_span("index.fuse", t0, _time.perf_counter(),
                        candidates=len(fused), k=k)
        return out
