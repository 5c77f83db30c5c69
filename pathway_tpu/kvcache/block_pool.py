"""Fixed-size HBM block pool for paged KV caching.

One K and one V array hold the entire cache for every live sequence:
``(n_layers, num_blocks, block_size, n_kv_heads * head_dim)`` — a token's
heads side by side on ONE minor axis, head-major.  This module is the one
place that knows the shape; everything else derives it from ``pool.k``
or from ``(n_heads, head_dim)``.  Why fused: the chip tiles the two
minor axes of an array (8 x 128 words), and a ``(block_size, 1280)``
block fills whole tiles, so XLA keeps the pool row-major — the layout
the attention kernels read it in.  With heads and head_dim as separate
minor axes (20, 64) they did not fill a tile, XLA chose a layout of its
own for the pool, and every step program converted both pools to the
kernels' layout and back: four pool-sized copies a dispatch and 7.3 GB
of temporaries (PERF.md, PR 26).

Sequences address the pool through per-sequence block tables (ordered
lists of physical block ids); the attention op gathers blocks through the
table (paged_attention.py) and decode writes land at ``(block, offset)``
slots.

Allocation is a free-list pop; blocks are refcounted so full prompt
blocks can be shared between sequences (prefix_cache.py) and sequence
forks are copy-on-write: a fork shares every block of its parent, and the
first append into a shared tail block copies it first (the parent's bytes
are never mutated).  When the pool is exhausted the engine preempts a
victim — lowest priority class first, most recent arrival within a class
— frees its blocks, and re-queues the sequence for recompute-prefill.

Physical block 0 is reserved as the null block: padded block-table
entries and padded batch rows write/read there, so scatter/gather never
needs a branch for invalid rows (the results are masked out).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from .backend import CacheBackend


# live pools by metrics name: a second concurrent pool must not collide
# with (and corrupt) an existing pool's stats block — it gets a "#n"
# suffix instead.  WeakValue so a discarded pool frees its name, letting
# a REBUILT pool of the same name keep its monotonic counters.
_LIVE_POOLS: "weakref.WeakValueDictionary[str, BlockPool]" = (
    weakref.WeakValueDictionary()
)
_LIVE_POOLS_LOCK = threading.Lock()


def _cow_copy_fn(pool_arr, src, dst):
    """pool_arr[:, dst] = pool_arr[:, src] with the buffer donated —
    an in-place one-block copy, not an O(pool) clone."""
    return pool_arr.at[:, dst].set(pool_arr[:, src])


def _make_cow_copy():
    # Round-14: registered in the device cost observatory like every
    # other serving-path program (COW copies show up in the profile)
    try:
        from ..obs.profiler import profiled_jit

        return profiled_jit("pw.cow_copy", _cow_copy_fn, donate_argnums=(0,))
    except Exception:  # pragma: no cover - import-order edge
        return functools.partial(jax.jit, donate_argnums=(0,))(_cow_copy_fn)


_cow_copy = _make_cow_copy()


class PoolExhausted(RuntimeError):
    """Not enough free blocks; caller should evict prefix blocks or preempt."""

    def __init__(self, message: str = "KV block pool exhausted",
                 needed: int = 0, free: int = 0):
        super().__init__(message)
        self.needed = needed
        self.free = free


@dataclasses.dataclass
class SequenceState:
    """Host-side bookkeeping for one live sequence in the pool."""

    seq_id: int
    block_ids: list[int]
    n_tokens: int
    priority: int = 1  # serve.admission.Priority value: lower = more urgent
    arrival: int = 0  # pool-local admission counter (preemption tie-break)

    def num_blocks(self) -> int:
        return len(self.block_ids)


class BlockPool(CacheBackend):
    """Refcounted block allocator over stacked per-layer K/V pool arrays —
    the PAGED implementation of the Round-16 engine↔cache contract
    (backend.py)."""

    cache_kind = "paged"
    #: a second pool array for the values; a latent pool's stored row is key
    #: and value both (``v`` is then None)
    value_pool = True
    supports_fork = True
    supports_prefix = True
    supports_preemption = True

    def __init__(self, *, num_blocks: int, block_size: int, n_layers: int,
                 n_heads: int, head_dim: int, dtype=jnp.float32,
                 name: str = "kvcache", mesh=None, tp_axis: str = "tp",
                 v_head_dim: int | None = None):
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is reserved)")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        # a V head of another width than a K head's (None: the same): the V
        # pool's minor axis is n_heads * v_head_dim
        self.v_head_dim = self.head_dim if v_head_dim is None \
            else int(v_head_dim)
        self.dtype = dtype
        shape = (n_layers, num_blocks, block_size, n_heads * head_dim)
        v_shape = shape[:3] + (n_heads * self.v_head_dim,)
        # Round-9 tensor parallelism: with a mesh, the K/V arrays are laid
        # out [L, NB, BS, n_kv_heads/tp * hd] PER SHARD via NamedSharding on
        # the fused axis (head-major, so each shard keeps its own heads,
        # contiguous) — N x aggregate KV HBM across the mesh.  Block
        # tables, the free list, refcounts and every piece of allocation
        # bookkeeping below stay host-side and replicated: a block id means
        # the same (head-split) physical block on every shard, so the
        # allocator logic is untouched by sharding.
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.tp = 1
        if mesh is not None:
            self.tp = int(mesh.shape[tp_axis])
            if self.n_heads % self.tp:
                raise ValueError(
                    f"cannot shard the KV pool: n_kv_heads={self.n_heads} "
                    f"% tp={self.tp} != 0. Legal tp values: "
                    f"{[t for t in range(1, self.n_heads + 1) if self.n_heads % t == 0]}"
                )
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(mesh, P(None, None, None, tp_axis))

            def zeros(dims):
                return jax.jit(lambda: jnp.zeros(dims, dtype),
                               out_shardings=sharding)()

            self.k = zeros(shape)
            self.v = zeros(v_shape) if self.value_pool else None
        else:
            self.k = jnp.zeros(shape, dtype)
            self.v = jnp.zeros(v_shape, dtype) if self.value_pool else None
        # block 0 reserved: never allocated, target of padded writes
        self._free: list[int] = list(range(num_blocks - 1, 0, -1))
        self._ref = np.zeros(num_blocks, np.int32)
        self._seqs: dict[int, SequenceState] = {}
        self._arrival = itertools.count()
        self._lock = threading.RLock()
        from ..serve.metrics import kv_stats

        with _LIVE_POOLS_LOCK:
            unique, n = name, 1
            while unique in _LIVE_POOLS:
                unique = f"{name}#{n}"
                n += 1
            name = unique
            _LIVE_POOLS[name] = self
        self.name = name

        # the stats registry is process-global and never pruned: hand it a
        # weakref-backed gauge so a discarded pool (and its large K/V
        # arrays) can still be garbage collected
        wref = weakref.ref(self)

        def _in_use() -> int:
            pool = wref()
            return 0 if pool is None else pool.blocks_in_use

        self.stats = kv_stats(
            name, blocks_in_use_fn=_in_use, blocks_total=num_blocks - 1,
            shards=self.tp, shard_hbm_bytes=self.per_shard_bytes,
        )

    def retire(self) -> None:
        """Release this pool's registry name immediately (Round-13: a
        supervised engine restart rebuilds a same-name pool while the old
        object may still be transiently pinned by the failure traceback —
        without this, the replacement would get a '#1' suffix and a fresh
        stats block instead of re-attaching to the monotonic counters)."""
        with _LIVE_POOLS_LOCK:
            if _LIVE_POOLS.get(self.name) is self:
                del _LIVE_POOLS[self.name]

    # -- capacity ----------------------------------------------------------
    @property
    def per_shard_bytes(self) -> int:
        """K + V HBM held by EACH shard (the whole pool when tp=1)."""
        total = int(self.k.size) + (int(self.v.size) if self.value_pool
                                    else 0)
        return total * self.k.dtype.itemsize // self.tp

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        # excludes the reserved null block
        return (self.num_blocks - 1) - len(self._free)

    def blocks_for(self, n_tokens: int) -> int:
        # 0 tokens -> 0 blocks: a token-less sequence owns nothing, and its
        # first append_slot opens the first block (reserving one up front
        # would strand it — appends always open at the 0-offset boundary)
        return -(-n_tokens // self.block_size)

    def sequence(self, seq_id: int) -> SequenceState:
        return self._seqs[seq_id]

    def sequences(self) -> list[SequenceState]:
        return list(self._seqs.values())

    # -- allocation --------------------------------------------------------
    def _pop_free(self) -> int:
        b = self._free.pop()
        self._ref[b] = 1
        return b

    def incref(self, block_id: int) -> None:
        with self._lock:
            if self._ref[block_id] <= 0:
                raise ValueError(f"incref on free block {block_id}")
            self._ref[block_id] += 1

    def decref(self, block_id: int) -> None:
        with self._lock:
            if self._ref[block_id] <= 0:
                raise ValueError(f"double free of block {block_id}")
            self._ref[block_id] -= 1
            if self._ref[block_id] == 0:
                self._free.append(block_id)

    def refcount(self, block_id: int) -> int:
        return int(self._ref[block_id])

    def allocate(self, seq_id: int, n_tokens: int, *,
                 shared_blocks: list[int] | tuple = (),
                 priority: int = 1) -> SequenceState:
        """Register a sequence holding ``n_tokens`` tokens: leading
        ``shared_blocks`` (full blocks already resident, e.g. a prefix-cache
        hit — each gets a new reference) plus freshly allocated blocks for
        the remainder.  Raises :class:`PoolExhausted` (without side
        effects) when the free list cannot cover the fresh blocks."""
        with self._lock:
            if seq_id in self._seqs:
                raise ValueError(f"sequence {seq_id} already allocated")
            total = self.blocks_for(n_tokens)
            n_shared = len(shared_blocks)
            if n_shared * self.block_size > n_tokens:
                raise ValueError(
                    f"{n_shared} shared blocks cover more than "
                    f"{n_tokens} tokens"
                )
            fresh = total - n_shared
            if fresh > len(self._free):
                raise PoolExhausted(
                    f"need {fresh} blocks, {len(self._free)} free",
                    needed=fresh, free=len(self._free),
                )
            for b in shared_blocks:
                self.incref(b)
            block_ids = list(shared_blocks) + [
                self._pop_free() for _ in range(fresh)
            ]
            state = SequenceState(
                seq_id=seq_id, block_ids=block_ids, n_tokens=n_tokens,
                priority=priority, arrival=next(self._arrival),
            )
            self._seqs[seq_id] = state
            return state

    def _cow_block(self, block_id: int) -> int:
        """Copy-on-write: materialize a private copy of a shared block.
        Device copy across every layer; the source keeps its other refs.
        The pool buffer is donated to the jitted copy so XLA updates one
        block in place instead of cloning the whole pool per COW."""
        new = self._pop_free()
        self.k = _cow_copy(self.k, block_id, new)
        self.v = _cow_copy(self.v, block_id, new)
        self.decref(block_id)
        self.stats.record_cow()
        return new

    def append_slot(self, seq_id: int) -> tuple[int, int]:
        """Reserve the write slot for the sequence's next token: returns
        ``(block_id, offset)`` and advances ``n_tokens``.  Allocates a new
        block at a block boundary; copies a shared tail block first (COW)
        so writes never touch blocks other sequences still reference.
        Raises :class:`PoolExhausted` with no state change when a needed
        block cannot be allocated."""
        return self.extend_slots(seq_id, 1)[0]

    def extend_slots(self, seq_id: int, k: int) -> list[tuple[int, int]]:
        """Pre-extend a sequence by ``k`` write slots in one call — the
        Round-10 chained-decode contract: the engine reserves a whole
        chain's slots BEFORE dispatch, so the device program can scatter
        K tokens' K/V without any host round trip in between.

        Returns the ``k`` ``(block_id, offset)`` slots in append order
        and advances ``n_tokens`` by ``k``.  ATOMIC: the needed block
        count (a COW of a shared tail + one fresh block per crossed
        boundary) is checked up front, and :class:`PoolExhausted` is
        raised with NO state change when the free list cannot cover it —
        so a failed chain reservation leaves the sequence exactly as it
        was (the engine then evicts/preempts and retries).

        Invariant note (check_invariants): reserved-but-not-yet-written
        slots count toward ``n_tokens`` immediately — the table/token
        partition invariant covers in-flight chains the same way it
        covered the single reserved slot of a per-step round."""
        if k <= 0:
            return []
        with self._lock:
            seq = self._seqs[seq_id]
            offset0 = seq.n_tokens % self.block_size
            need = -(-(offset0 + k) // self.block_size) - (1 if offset0 else 0)
            if offset0 and self._ref[seq.block_ids[-1]] > 1:
                need += 1  # COW of the shared tail block
            if need > len(self._free):
                raise PoolExhausted(
                    f"need {need} blocks, {len(self._free)} free",
                    needed=need, free=len(self._free),
                )
            slots: list[tuple[int, int]] = []
            for _ in range(k):
                offset = seq.n_tokens % self.block_size
                if offset == 0:
                    seq.block_ids.append(self._pop_free())
                else:
                    tail = seq.block_ids[-1]
                    if self._ref[tail] > 1:
                        seq.block_ids[-1] = self._cow_block(tail)
                seq.n_tokens += 1
                slots.append((seq.block_ids[-1], offset))
            return slots

    def truncate_slots(self, seq_id: int, k: int) -> None:
        """Roll back the sequence's last ``k`` reserved slots — the
        inverse of :meth:`extend_slots` for slots whose writes turned out
        to be garbage (Round-18 speculative verify: the rejected tail of
        a draft run is rolled back so the pool never holds phantom KV).

        ``n_tokens`` shrinks by ``k`` and blocks past the new span are
        released; the table/token invariant (``check_invariants``) holds
        on exit.  Stale bytes may linger inside the surviving tail block
        past the new ``n_tokens`` — harmless, exactly like a freed
        block's bytes: every read is masked to the live positions and
        the next ``extend_slots`` overwrites them in place.  Only roll
        back slots reserved by THIS sequence's own ``extend_slots`` (the
        engine never truncates into prefix-shared history)."""
        if k <= 0:
            return
        with self._lock:
            seq = self._seqs[seq_id]
            if k > seq.n_tokens:
                raise ValueError(
                    f"cannot roll back {k} slots: sequence {seq_id} "
                    f"holds {seq.n_tokens} tokens"
                )
            seq.n_tokens -= k
            keep = self.blocks_for(seq.n_tokens)
            while len(seq.block_ids) > keep:
                self.decref(seq.block_ids.pop())

    def fork(self, parent_id: int, child_id: int, *,
             priority: int | None = None) -> SequenceState:
        """Child shares every parent block (refcounted); diverging appends
        copy-on-write, so the parent's bytes are preserved."""
        with self._lock:
            parent = self._seqs[parent_id]
            if child_id in self._seqs:
                raise ValueError(f"sequence {child_id} already allocated")
            for b in parent.block_ids:
                self.incref(b)
            child = SequenceState(
                seq_id=child_id, block_ids=list(parent.block_ids),
                n_tokens=parent.n_tokens,
                priority=parent.priority if priority is None else priority,
                arrival=next(self._arrival),
            )
            self._seqs[child_id] = child
            return child

    def free_sequence(self, seq_id: int) -> None:
        """Release the sequence's references; blocks whose refcount reaches
        0 return to the free list (prefix-cached blocks survive on the
        cache's own reference until evicted)."""
        with self._lock:
            seq = self._seqs.pop(seq_id)
            for b in seq.block_ids:
                self.decref(b)

    # -- suspend / resume (backend contract; tiering.SessionStore) ---------
    def suspend_host(self, seq_id: int, context_tokens) -> tuple[dict | None,
                                                                 int]:
        """Gather the sequence's context blocks to host memory and free
        them from the pool.  The host buffers keep the power-of-two
        padded gather width (O(log max_blocks) compiled variants), and
        the returned byte charge is the PADDED buffer size — what the
        process actually holds, not the logical block span."""
        from .tiering import _pad_width, _tier_gather

        nb = self.blocks_for(len(context_tokens))
        if nb == 0:
            self.free_sequence(seq_id)
            return None, 0
        with self._lock:
            blocks = self._seqs[seq_id].block_ids[:nb]
        pad = _pad_width(nb)
        padded = np.zeros(pad, np.int32)
        padded[:nb] = blocks
        idx = jnp.asarray(padded)
        k_host = np.asarray(_tier_gather(self.k, idx))
        v_host = np.asarray(_tier_gather(self.v, idx))
        self.free_sequence(seq_id)
        payload = {"k": k_host, "v": v_host, "nb": nb}
        return payload, int(k_host.nbytes) + int(v_host.nbytes)

    def resume_host(self, payload: dict, slot_ids) -> None:
        """Scatter a suspended payload into freshly allocated blocks.
        Padded lanes target block 0 — the designated garbage sink — so
        one compiled scatter serves every session length."""
        from .tiering import _tier_scatter

        nb = int(payload["nb"])
        pad = int(payload["k"].shape[1])
        table = np.zeros(pad, np.int32)
        table[:nb] = list(slot_ids)[:nb]
        idx = jnp.asarray(table)
        self.k = _tier_scatter(self.k, idx, jnp.asarray(payload["k"]))
        self.v = _tier_scatter(self.v, idx, jnp.asarray(payload["v"]))

    # -- what the engine's step programs take and give back ----------------
    def device_state(self) -> tuple:
        """The donated device arrays a step program takes after the
        parameters, in order."""
        return (self.k, self.v)

    def set_device_state(self, k, v) -> None:
        """What the program returned after its ids, in the same order."""
        self.k, self.v = k, v

    # -- preemption --------------------------------------------------------
    def preempt(self, *, exclude: set | frozenset = frozenset()
                ) -> SequenceState | None:
        """Evict one victim to free blocks: the lowest-priority class first
        (highest numeric Priority value), most recent arrival within the
        class.  The victim's blocks are released and its state returned so
        the engine can re-queue it for recompute-prefill.  None when every
        live sequence is excluded."""
        with self._lock:
            candidates = [
                s for s in self._seqs.values() if s.seq_id not in exclude
            ]
            if not candidates:
                return None
            victim = max(candidates, key=lambda s: (s.priority, s.arrival))
            self.free_sequence(victim.seq_id)
            self.stats.record_preemption()
            return victim

    # -- device-facing views -----------------------------------------------
    def block_table(self, seq_id: int, width: int) -> np.ndarray:
        """(width,) int32 table padded with the null block."""
        seq = self._seqs[seq_id]
        if len(seq.block_ids) > width:
            raise ValueError(
                f"sequence {seq_id} spans {len(seq.block_ids)} blocks "
                f"> table width {width}"
            )
        table = np.zeros(width, np.int32)
        table[: len(seq.block_ids)] = seq.block_ids
        return table

    # -- verification ------------------------------------------------------
    def check_invariants(self, external_refs: dict[int, int] | None = None
                         ) -> None:
        """Assert allocator consistency (tests + fuzz): the free list and
        refcounts exactly partition the pool, and every reference is
        accounted for by a sequence table or ``external_refs`` (e.g. the
        prefix cache's own holds)."""
        with self._lock:
            free = list(self._free)
            assert len(free) == len(set(free)), "duplicate free-list entry"
            assert 0 not in free, "reserved block 0 on the free list"
            for b in free:
                assert self._ref[b] == 0, f"free block {b} has refs"
            counted = np.zeros(self.num_blocks, np.int64)
            for seq in self._seqs.values():
                assert len(seq.block_ids) == len(set(seq.block_ids)), (
                    f"sequence {seq.seq_id} table references a block twice"
                )
                assert len(seq.block_ids) == self.blocks_for(seq.n_tokens) or (
                    seq.n_tokens == 0 and not seq.block_ids
                ), f"sequence {seq.seq_id} table/token-count mismatch"
                for b in seq.block_ids:
                    counted[b] += 1
            for b, n in (external_refs or {}).items():
                counted[b] += n
            mismatched = [
                b for b in range(1, self.num_blocks)
                if counted[b] != self._ref[b]
            ]
            assert not mismatched, (
                f"refcount mismatch on blocks {mismatched[:8]}: "
                f"counted {[int(counted[b]) for b in mismatched[:8]]} vs "
                f"ref {[int(self._ref[b]) for b in mismatched[:8]]}"
            )
            in_use = sum(1 for b in range(1, self.num_blocks)
                         if self._ref[b] > 0)
            assert in_use + len(free) == self.num_blocks - 1, (
                "free list + in-use blocks do not partition the pool"
            )
