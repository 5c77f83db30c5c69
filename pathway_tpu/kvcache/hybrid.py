"""Two kinds of state for one sequence: K/V blocks and a conv slot.

A hybrid block family (models/lfm2.py) mixes tokens by attention in some
layers and by a gated short convolution in the others.  The attention
layers' K/V grows with the context and lives in the paged pool, whose
layer axis counts the attention layers only; a conv layer carries a fixed
``(u_{t-2}, u_{t-1})`` per sequence, which lives in a slot arena
``(conv layers, slots + 1, 2, width)`` beside the pool.  Slot 0 is the
null slot that padded rows ride, as block 0 is the null block.

:class:`HybridCache` is the paged :class:`BlockPool` with that arena: a
sequence is given its blocks and its slot together or not at all, loses
both when it is released or preempted, and is rebuilt by recompute over
prompt + emitted like any preempted sequence.  A slot is not cleared
between sequences: the step programs read ``u_{p-1}`` / ``u_{p-2}`` of a
token at position ``p`` only where those positions exist, so what an
earlier sequence left in a slot is never read.

Prefix sharing, fork and host tiering are off: a shared or resumed block
would skip the tokens that build the conv state (it would take a snapshot
of the state at every block boundary; ROADMAP B4).

:class:`StateCache` (PR 33) is the same arena with a second kind of slot
under the same index: a matrix state a head, ``(state layers, slots + 1,
heads, dk, dv)`` in f32, for the layers of a linear-attention family that
mix tokens by a delta rule (models/kimi_linear.py), beside a conv slot of
the carried inputs of their short convolutions; and with a LATENT pool in
the place of K and V: one array ``(latent layers, blocks, block size,
lanes)`` whose row is key and value both (``v`` is None).  Unlike a conv
slot a matrix state is summed into, so what an earlier sequence left in a
slot must not be read: the step programs start a sequence's first chunk
from zero themselves (``row_start == 0``; no host-side clear, which would
cost a dispatch), a chunk's padded tokens leave the state as it was, and a
preempted sequence rebuilds it by recompute like any other.

:class:`KVStateCache` (PR 38) is that state cache with ``value_pool`` on: a
plain K/V pool (keys AND values of a family's full-attention layers,
models/qwen3_next.py) where the latent pool stood, ``device_state() = (k,
v, conv, state)``.  Everything else - blocks and slot together or not at
all, free, preemption, ``row_extras``, ``after_sync``, the invariants - is
the class's it inherits from.
"""

from __future__ import annotations

import weakref

import jax.numpy as jnp
import numpy as np

from .backend import ExpertCounts, UnsupportedCacheOp
from .block_pool import BlockPool, PoolExhausted, SequenceState


class HybridCache(ExpertCounts, BlockPool):
    cache_kind = "hybrid"
    supports_fork = False
    supports_prefix = False

    def __init__(self, *, conv_layers: int, conv_width: int,
                 conv_slots: int, conv_taps: int = 2, **pool_kwargs):
        if conv_slots < 1:
            raise ValueError("conv_slots must be >= 1 (slot 0 is reserved)")
        self.conv_slots = int(conv_slots)
        # before the pool registers its stats: per_shard_bytes reads it
        self.conv = jnp.zeros(
            (int(conv_layers), self.conv_slots + 1, int(conv_taps),
             int(conv_width)), pool_kwargs.get("dtype", jnp.float32))
        self._free_slots: list[int] = list(range(self.conv_slots, 0, -1))
        self._slot_of: dict[int, int] = {}
        super().__init__(**pool_kwargs)
        wref = weakref.ref(self)
        self.stats.conv_slots_total = self.conv_slots
        self.stats._conv_slots_in_use_fn = lambda: (
            0 if wref() is None else wref().slots_in_use)

    # -- capacity ----------------------------------------------------------
    @property
    def conv_bytes(self) -> int:
        return int(self.conv.size) * self.conv.dtype.itemsize

    @property
    def per_shard_bytes(self) -> int:
        return super().per_shard_bytes + self.conv_bytes

    @property
    def slots_in_use(self) -> int:
        return self.conv_slots - len(self._free_slots)

    def slot(self, seq_id: int) -> int:
        return self._slot_of[seq_id]

    # -- allocation: blocks and slot, both or neither ----------------------
    def allocate(self, seq_id: int, n_tokens: int, *,
                 shared_blocks: list[int] | tuple = (),
                 priority: int = 1) -> SequenceState:
        if shared_blocks:
            raise UnsupportedCacheOp(
                "a hybrid sequence cannot start from shared blocks: the "
                "tokens they hold would not pass through its conv state")
        with self._lock:
            if not self._free_slots:
                raise PoolExhausted(
                    f"all {self.conv_slots} conv slots are taken",
                    needed=0, free=len(self._free))
            state = super().allocate(seq_id, n_tokens, priority=priority)
            self._slot_of[seq_id] = self._free_slots.pop()
            return state

    def free_sequence(self, seq_id: int) -> None:
        with self._lock:
            super().free_sequence(seq_id)
            self._free_slots.append(self._slot_of.pop(seq_id))

    def fork(self, parent_id, child_id, *, priority=None):
        raise UnsupportedCacheOp("HybridCache does not support fork")

    def suspend_host(self, seq_id, context_tokens):
        raise UnsupportedCacheOp("HybridCache does not support host tiering")

    def resume_host(self, payload, slot_ids):
        raise UnsupportedCacheOp("HybridCache does not support host tiering")

    # -- what the engine's step programs take and give back ----------------
    def device_state(self) -> tuple:
        return (self.k, self.v, self.conv)

    def set_device_state(self, k, v, conv, counts) -> None:
        self.k, self.v, self.conv = k, v, conv
        self.keep_expert_counts(counts)

    def row_extras(self, seq_ids, n_rows: int, table_blocks: int = 0) -> tuple:
        """(n_rows,) int32: each row's conv slot, the null slot for the
        rows past ``seq_ids``."""
        slots = np.zeros(n_rows, np.int32)
        for i, seq_id in enumerate(seq_ids):
            slots[i] = self._slot_of[seq_id]
        return (slots,)

    def after_sync(self) -> None:
        """The tokens-per-expert counts of the programs that have finished
        (``moe_routed_pairs`` / ``moe_tokens_per_expert``)."""
        self.fold_expert_counts()

    # -- verification ------------------------------------------------------
    def check_invariants(self, external_refs=None) -> None:
        super().check_invariants(external_refs)
        with self._lock:
            held = list(self._slot_of.values())
            free = list(self._free_slots)
            assert set(self._slot_of) == set(self._seqs), (
                "a live sequence without a conv slot, or a slot without "
                "its sequence")
            assert len(set(held + free)) == len(held) + len(free), (
                "a conv slot is held twice or held and free")
            assert sorted(held + free) == list(range(1, self.conv_slots + 1)), (
                "held and free conv slots do not partition the arena")


class StateCache(HybridCache):
    """A latent pool, and one slot a sequence in two arenas: the carried
    conv inputs and the matrix states of the delta-rule layers."""

    cache_kind = "latent_state"
    value_pool = False

    def __init__(self, *, state_heads: int, state_dk: int, state_dv: int,
                 conv_layers: int, conv_slots: int, **kwargs):
        # before the pool registers its stats: per_shard_bytes reads it
        self.state = jnp.zeros(
            (int(conv_layers), int(conv_slots) + 1, int(state_heads),
             int(state_dk), int(state_dv)), jnp.float32)
        super().__init__(conv_layers=conv_layers, conv_slots=conv_slots,
                         **kwargs)
        wref = weakref.ref(self)
        self.stats.state_slots_total = self.conv_slots
        self.stats._state_slots_in_use_fn = lambda: (
            0 if wref() is None else wref().slots_in_use)

    @property
    def state_bytes(self) -> int:
        return int(self.state.size) * self.state.dtype.itemsize

    @property
    def per_shard_bytes(self) -> int:
        return super().per_shard_bytes + self.state_bytes

    def device_state(self) -> tuple:
        pools = (self.k, self.v) if self.value_pool else (self.k,)
        return (*pools, self.conv, self.state)

    def set_device_state(self, *arrays) -> None:
        *pools, self.conv, self.state, counts = arrays
        self.k, self.v = pools if self.value_pool else (pools[0], None)
        self.keep_expert_counts(counts)


class KVStateCache(StateCache):
    """A K/V pool, and one slot a sequence in the two arenas."""

    cache_kind = "kv_state"
    value_pool = True
