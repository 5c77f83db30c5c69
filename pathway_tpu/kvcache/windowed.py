"""Two block tables for one sequence: full-attention layers keep every
key, sliding-window layers forget.

A block family that mixes the two (models/afmoe.py) keeps the full
layers' K/V in the paged :class:`BlockPool` as it is - its layer axis
counts the full layers only, a sequence's ``block_ids`` grow with the
context - and the window layers' K/V in a second pool pair beside it,
``(window layers, window blocks, block_size, window_heads * head_dim)``
(and ``window_heads * v_head_dim`` for the values), with a free list of its
own.  The window pool's geometry is its own: ``window_heads`` K/V heads
where the full pool has ``n_heads`` (None: as many), each pool billed at its
own width (``pool_part_bytes``: the four arrays by name).  Both tables
are indexed by position (``table[p // block_size]``), so the kernels index
them alike; a window
table's entries wholly behind the window point at the null block once
their block has gone back to the free list.

The freeing rule.  ``next`` is the first position the sequence has yet to
compute.  A query at position ``i`` sees the keys ``i - window < j <= i``,
so no query from ``next`` on sees a position ``<= next - window``: a block
is freed when every position in it is that old.  ``next`` moves when a
round reserves its rows (:meth:`reserve_chunk` for a prompt's chunk,
:meth:`extend_slots` for decode rows and chains), and blocks are freed in
:meth:`after_sync`, when every dispatched program has finished - never
inside a chain, and a chunk still finds every key its first column sees.

The window pool is sized exactly: a sequence holds at most
``ceil((window + round_tokens) / block_size) + 2`` blocks
(:func:`window_seq_blocks`; ``round_tokens`` the most a round adds to one
sequence), and at most ``max_seqs`` sequences live, so it never runs dry.
The full pool is the scarce one: it takes the engine's share of HBM and
preempts as :class:`BlockPool` does; a victim gives back both tables and
is rebuilt by recompute.

Prefix sharing, fork, host tiering and rolled-back slots are off: a
shared block may be freed behind one sequence's window while another
still reads it (ROADMAP B3).
"""

from __future__ import annotations

import weakref

import jax.numpy as jnp
import numpy as np

from .backend import ExpertCounts, UnsupportedCacheOp
from .block_pool import BlockPool, PoolExhausted, SequenceState


def window_seq_blocks(window: int, round_tokens: int, block_size: int) -> int:
    """Window blocks one sequence can hold at once: the blocks that reach
    into its window, plus those the next round's tokens open, plus the two
    partial blocks at the ends."""
    return -(-(int(window) + int(round_tokens)) // int(block_size)) + 2


def window_pool_blocks(window: int, round_tokens: int, block_size: int,
                       max_seqs: int) -> int:
    """Blocks of the window pool, the null block included."""
    return int(max_seqs) * window_seq_blocks(window, round_tokens,
                                             block_size) + 1


class WindowedCache(ExpertCounts, BlockPool):
    cache_kind = "windowed"
    supports_fork = False
    supports_prefix = False

    def __init__(self, *, window: int, window_layers: int, round_tokens: int,
                 max_seqs: int, window_heads: int | None = None,
                 **pool_kwargs):
        if window < 1 or window_layers < 1 or pool_kwargs["n_layers"] < 1:
            raise ValueError(
                "a windowed cache holds at least one full and one window "
                "layer, and a window of at least one position")
        if pool_kwargs.get("mesh") is not None:
            raise ValueError("the window pool has no sharded layout")
        self.window = int(window)
        self.max_seqs = int(max_seqs)
        bs = int(pool_kwargs["block_size"])
        self.window_blocks = window_pool_blocks(window, round_tokens, bs,
                                                max_seqs)
        heads = int(pool_kwargs["n_heads"] if window_heads is None
                    else window_heads)
        hd = int(pool_kwargs["head_dim"])
        shape = (int(window_layers), self.window_blocks, bs)
        dtype = pool_kwargs.get("dtype", jnp.float32)
        # before the pool registers its stats: per_shard_bytes reads them
        self.kw = jnp.zeros(shape + (heads * hd,), dtype)
        self.vw = jnp.zeros(
            shape + (heads * int(pool_kwargs.get("v_head_dim") or hd),),
            dtype)
        self._wfree: list[int] = list(range(self.window_blocks - 1, 0, -1))
        self._wtable: dict[int, list[int]] = {}  # by position // block_size
        self._wnext: dict[int, int] = {}   # first position yet to compute
        self._wdead: dict[int, int] = {}   # leading entries already freed
        super().__init__(**pool_kwargs)
        wref = weakref.ref(self)
        self.stats.window_blocks_total = self.window_blocks - 1
        self.stats._window_blocks_in_use_fn = lambda: (
            0 if wref() is None else wref().window_blocks_in_use)
        self.stats.pool_part_bytes = self.pool_part_bytes

    # -- capacity ----------------------------------------------------------
    @property
    def window_bytes(self) -> int:
        return (int(self.kw.size) + int(self.vw.size)) \
            * self.kw.dtype.itemsize

    @property
    def pool_part_bytes(self) -> dict:
        """The four pool arrays' bytes, each at its own width."""
        return {part: int(a.size) * a.dtype.itemsize for part, a in (
            ("full_k", self.k), ("full_v", self.v),
            ("window_k", self.kw), ("window_v", self.vw))}

    @property
    def per_shard_bytes(self) -> int:
        return super().per_shard_bytes + self.window_bytes

    @property
    def window_blocks_in_use(self) -> int:
        return (self.window_blocks - 1) - len(self._wfree)

    def window_table(self, seq_id: int) -> list[int]:
        return self._wtable[seq_id]

    # -- allocation --------------------------------------------------------
    def allocate(self, seq_id: int, n_tokens: int, *,
                 shared_blocks: list[int] | tuple = (),
                 priority: int = 1) -> SequenceState:
        """The full layers' blocks for ``n_tokens`` now; the window layers'
        as the prompt's chunks are reserved (a long prompt never holds its
        whole length there)."""
        if shared_blocks:
            raise UnsupportedCacheOp(
                "a windowed sequence cannot start from shared blocks: a "
                "shared block may be freed behind another sequence's window")
        with self._lock:
            if len(self._seqs) >= self.max_seqs:
                raise PoolExhausted(
                    f"the window pool is sized for {self.max_seqs} "
                    "sequences", needed=0, free=len(self._free))
            state = super().allocate(seq_id, n_tokens, priority=priority)
            self._wtable[seq_id] = []
            self._wnext[seq_id] = self._wdead[seq_id] = 0
            return state

    def _window_need(self, seq_id: int, end: int) -> int:
        """Window blocks still to claim for the positions below ``end``;
        raises where the free list cannot give them (with the engine's
        rounds: unreachable by the pool's sizing)."""
        need = max(self.blocks_for(end) - len(self._wtable[seq_id]), 0)
        if need > len(self._wfree):
            raise PoolExhausted(
                f"need {need} window blocks, {len(self._wfree)} free",
                needed=need, free=len(self._wfree))
        return need

    def _window_reserve(self, seq_id: int, end: int) -> None:
        need = self._window_need(seq_id, end)
        self._wtable[seq_id].extend(self._wfree.pop() for _ in range(need))
        self.stats.record_window_blocks(allocated=need)
        self._wnext[seq_id] = max(self._wnext[seq_id], int(end))

    def reserve_chunk(self, seq_id: int, end: int) -> None:
        """A round is about to compute the sequence's positions below
        ``end`` (a prompt's chunk): window blocks for them."""
        with self._lock:
            self._window_reserve(seq_id, end)

    def extend_slots(self, seq_id: int, k: int) -> list[tuple[int, int]]:
        """Both tables grow by ``k`` slots or neither does.  The slots
        returned are the full pool's; a window slot is the window table's
        entry at the same position, the same offset."""
        if k <= 0:
            return []
        with self._lock:
            end = self._seqs[seq_id].n_tokens + k
            self._window_need(seq_id, end)  # raises before either grows
            slots = super().extend_slots(seq_id, k)
            self._window_reserve(seq_id, end)
            return slots

    def truncate_slots(self, seq_id: int, k: int) -> None:
        raise UnsupportedCacheOp(
            "WindowedCache does not roll slots back: a block behind the "
            "window may be gone")

    def free_sequence(self, seq_id: int) -> None:
        with self._lock:
            super().free_sequence(seq_id)
            held = [b for b in self._wtable.pop(seq_id) if b]
            self._wfree.extend(held)
            self.stats.record_window_blocks(freed=len(held))
            del self._wnext[seq_id], self._wdead[seq_id]

    def fork(self, parent_id, child_id, *, priority=None):
        raise UnsupportedCacheOp("WindowedCache does not support fork")

    def suspend_host(self, seq_id, context_tokens):
        raise UnsupportedCacheOp(
            "WindowedCache does not support host tiering")

    def resume_host(self, payload, slot_ids):
        raise UnsupportedCacheOp(
            "WindowedCache does not support host tiering")

    # -- freeing behind the window ------------------------------------------
    def dead_blocks(self, nxt: int) -> int:
        """Leading blocks of a window table no query from position ``nxt``
        on can see: every position in them is ``<= nxt - window``."""
        return max((nxt - self.window + 1) // self.block_size, 0)

    def after_sync(self) -> None:
        """Every program dispatched so far has finished: free the window
        blocks behind each sequence's window, and fold the programs'
        tokens-per-expert counts."""
        with self._lock:
            freed = 0
            for seq_id, table in self._wtable.items():
                dead = min(self.dead_blocks(self._wnext[seq_id]), len(table))
                for i in range(self._wdead[seq_id], dead):
                    self._wfree.append(table[i])
                    table[i] = 0
                    freed += 1
                self._wdead[seq_id] = max(self._wdead[seq_id], dead)
            if freed:
                self.stats.record_window_blocks(freed=freed)
        self.fold_expert_counts()

    # -- what the engine's step programs take and give back ----------------
    def device_state(self) -> tuple:
        return (self.k, self.v, self.kw, self.vw)

    def set_device_state(self, k, v, kw, vw, counts) -> None:
        self.k, self.v, self.kw, self.vw = k, v, kw, vw
        self.keep_expert_counts(counts)

    def row_extras(self, seq_ids, n_rows: int, table_blocks: int = 0) -> tuple:
        """(n_rows, table_blocks) int32: each row's window table, by
        position; the null block behind the window, past the reserved
        positions and for the rows past ``seq_ids``."""
        tables = np.zeros((n_rows, table_blocks), np.int32)
        for i, seq_id in enumerate(seq_ids):
            table = self._wtable[seq_id]
            tables[i, : len(table)] = table
        return (tables,)

    # -- verification ------------------------------------------------------
    def check_invariants(self, external_refs=None) -> None:
        super().check_invariants(external_refs)
        with self._lock:
            assert set(self._wtable) == set(self._seqs), (
                "a live sequence without a window table, or a table "
                "without its sequence")
            held = [b for t in self._wtable.values() for b in t if b]
            free = list(self._wfree)
            assert 0 not in free, "the null block on the window free list"
            assert len(set(held + free)) == len(held) + len(free), (
                "a window block is held twice or held and free")
            assert sorted(held + free) == list(range(1, self.window_blocks)), (
                "held and free window blocks do not partition the pool")
            for seq_id, table in self._wtable.items():
                dead = self._wdead[seq_id]
                assert not any(table[:dead]) and all(table[dead:]), (
                    f"sequence {seq_id}: freed window entries are not "
                    "exactly its leading ones")
                assert dead <= self.dead_blocks(self._wnext[seq_id]), (
                    f"sequence {seq_id}: a window block was freed that a "
                    "query may still see")
                assert len(table) == self.blocks_for(self._wnext[seq_id]), (
                    f"sequence {seq_id}: window table / reserved positions "
                    "mismatch")
