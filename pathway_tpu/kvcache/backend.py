"""The engine↔cache contract.

The engine (engine.py: admission, rounds, restart, sessions) programs
against :class:`CacheBackend`, not against a layout; how a sequence's
decode state lives in HBM is the backend's.  Six kinds serve cells:

- ``"paged"`` — :class:`~pathway_tpu.kvcache.block_pool.BlockPool`: K/V
  blocks for every layer, addressed through per-sequence block tables;
  prefix sharing, copy-on-write fork, preemption, host tiering.
- ``"hybrid"`` — :class:`~pathway_tpu.kvcache.hybrid.HybridCache`: the
  same K/V blocks for a model's attention layers and, beside them, one
  conv slot a sequence for its short-conv layers.  Preemption only: a
  shared, forked or resumed block would skip the tokens that build the
  conv state.
- ``"latent_state"`` — :class:`~pathway_tpu.kvcache.hybrid.StateCache`:
  a latent pool (one array: a token's stored row is key and value both)
  for a model's latent-attention layers and, in the slot arena, a matrix
  state a head beside the carried conv inputs for its delta-rule layers.
  Preemption only, as the hybrid kind.
- ``"kv_state"`` — :class:`~pathway_tpu.kvcache.hybrid.KVStateCache`:
  the state kind with a plain K/V pool in the latent pool's place: keys
  AND values of a model's full-attention layers beside the conv slot and
  the matrix state of its delta-rule layers, one slot and one block table a
  sequence.  Preemption only, as the state kind.
- ``"windowed"`` — :class:`~pathway_tpu.kvcache.windowed.WindowedCache`:
  the K/V blocks of a model's full-attention layers and, in a second pool
  with a block table of its own, those of its sliding-window layers, whose
  blocks go back to their free list once they lie behind the window.
  Preemption only: a shared block could be freed behind one sequence's
  window while another still reads it.
- ``"window_state"`` — :class:`~pathway_tpu.kvcache.windowed
  .WindowStateCache`: the windowed kind's two pools and tables beside the
  state kind's slot arena (carried conv inputs and an f32 state a scan
  layer), one slot a sequence.  Preemption only, as both.

A backend owns:

- **slot lifecycle**: ``allocate`` / ``extend_slots`` / ``append_slot``
  / ``free_sequence`` — how a sequence claims device memory (K/V blocks
  that grow with the context; for the hybrid kind a conv slot with them,
  claimed and released in the same call);
- **device state**: ``device_state()`` / ``set_device_state(...)`` — the
  arrays a step program takes after the params and gives back (donated),
  and ``row_extras`` — further per-row host arrays the programs take
  (the hybrid kind: the rows' conv slots; the windowed kind: the rows'
  window tables);
- **byte accounting**: ``per_shard_bytes`` — what the backend pins in
  each tensor-parallel shard's HBM, the number ``obs/memory.py
  hbm_plan`` charges;
- **suspend/resume**: ``suspend_host`` / ``resume_host`` — the
  device↔host copies behind
  :class:`~pathway_tpu.kvcache.tiering.SessionStore`.  The payload is
  backend-opaque; the store only charges its byte size (power-of-two
  padded block gathers for the paged kind) and keys it by session;
- **invariants**: ``check_invariants`` — the backend-specific
  consistency sweep (refcount conservation; for the hybrid kind also
  slot conservation).  Engine-owned invariants (admission ordering,
  emit counts, watchdog state) stay in the engine and are NOT part of
  this contract.

Backend-optional capabilities — prefix sharing, copy-on-write ``fork``,
preemption-by-eviction — are declared via ``supports_*`` flags and
raise :class:`UnsupportedCacheOp` where absent; the engine consults the
flags before relying on them.

``make_backend(kind, ...)`` is the construction seam: the engine builds
its cache through it (and REBUILDS through it on supervised restart)
with the geometry its block family names (models/families.py
``cache_kind`` / ``cache_kwargs``).
"""

from __future__ import annotations

import abc
import importlib


class UnsupportedCacheOp(NotImplementedError):
    """An optional capability (fork/preempt/prefix) the backend does not
    implement — engines must consult ``supports_*`` before calling."""


class CacheBackend(abc.ABC):
    """Abstract engine↔cache contract.  See the module docstring for
    which side owns which invariant."""

    #: "paged" | "hybrid" | "windowed" | "latent_state" | "kv_state" |
    #: "window_state" — the factory key
    cache_kind: str = "abstract"
    #: positions a sliding-window layer's query sees (itself included);
    #: None: every layer keeps every key
    window: int | None = None
    #: optional capabilities the paged engine consults
    supports_fork: bool = False
    supports_prefix: bool = False
    supports_preemption: bool = False

    # -- slot lifecycle ----------------------------------------------------
    @abc.abstractmethod
    def allocate(self, seq_id, n_tokens: int, *, shared_blocks=(),
                 priority: int = 1):
        """Claim device memory for a new sequence of ``n_tokens``.
        Raises the backend's capacity error with NO partial side effects
        when it cannot."""

    @abc.abstractmethod
    def extend_slots(self, seq_id, k: int) -> list[int]:
        """Grow the sequence by ``k`` decode slots, atomically; returns
        the ``(block, offset)`` slots the next ``k`` tokens land in."""

    def append_slot(self, seq_id) -> int:
        return self.extend_slots(seq_id, 1)[0]

    @abc.abstractmethod
    def free_sequence(self, seq_id) -> None:
        """Release the sequence's device memory."""

    @abc.abstractmethod
    def sequence(self, seq_id):
        """The live per-sequence record (``.block_ids``, ``.n_tokens``,
        ``.priority``, ``.arrival``)."""

    @abc.abstractmethod
    def sequences(self):
        """Iterable of live seq_ids."""

    # -- byte accounting (obs/memory.py hbm_plan) --------------------------
    @property
    @abc.abstractmethod
    def per_shard_bytes(self) -> int:
        """Bytes this backend pins in EACH tensor-parallel shard's HBM."""

    # -- suspend / resume (tiering.SessionStore) ---------------------------
    @abc.abstractmethod
    def suspend_host(self, seq_id, context_tokens) -> tuple[dict, int]:
        """Copy the sequence's decode state to host memory and free its
        device allocation.  Returns ``(payload, nbytes)`` where
        ``payload`` is backend-opaque and ``nbytes`` is the HOST bytes
        the store must charge — the real buffer size, padding
        included."""

    @abc.abstractmethod
    def resume_host(self, payload: dict, slot_ids) -> None:
        """Scatter a suspended payload back into freshly allocated
        ``slot_ids`` (the ``.block_ids`` of the resuming sequence)."""

    # -- invariants --------------------------------------------------------
    @abc.abstractmethod
    def check_invariants(self, external_refs=None) -> None:
        """Raise AssertionError on any backend-internal inconsistency."""

    # -- optional capabilities ---------------------------------------------
    def fork(self, parent_id, child_id, *, priority=None):
        raise UnsupportedCacheOp(
            f"{type(self).__name__} does not support fork"
        )

    def preempt(self, *, exclude=frozenset()):
        raise UnsupportedCacheOp(
            f"{type(self).__name__} does not support preemption"
        )

    def retire(self) -> None:
        """Unregister from metrics; default no-op."""

    # -- per-row state beside the block tables -----------------------------
    def row_extras(self, seq_ids, n_rows: int, table_blocks: int = 0) -> tuple:
        """Further ``(n_rows, ...)`` arrays a step program takes after the
        paged ones, one entry a batch row in ``seq_ids``' order (a hybrid
        cache: the rows' conv slots; a windowed cache: the rows' window
        tables, ``table_blocks`` wide as the block tables are).  None by
        default."""
        return ()

    def reserve_chunk(self, seq_id, end: int) -> None:
        """A round is about to compute the sequence's prompt positions
        below ``end``.  ``allocate`` has claimed whatever grows with the
        whole prompt; a cache that claims by progress instead (a windowed
        cache's window blocks) does so here.  Default no-op."""

    def after_sync(self) -> None:
        """Every program dispatched so far has finished: fold what the
        programs counted on the device into the stats.  Default no-op."""


class ExpertCounts:
    """What the step programs of a routed family count on the device, one
    ``int32`` vector a program (ops/moe.py ``expert_ffn``, summed over the
    expert layers): the tokens each held
    expert received, then ``COUNTER_TAIL`` (pairs routed to experts held
    elsewhere, the grouped matmul's live rows in units of 16, held experts
    with at least one pair, the passes counted, the kernel's live row tiles
    whatever their height), then the family's own ``counters`` (a stat's
    name each; none by default).  Kept as they come back, read
    after the next sync (:meth:`fold_expert_counts`, the vector's one
    reader) into the cache's stats."""

    _expert_counts: tuple = ()  # the programs' counts not yet read back

    def __init__(self, *, counters: tuple = (), **kwargs):
        self.counters = tuple(counters)
        super().__init__(**kwargs)

    def keep_expert_counts(self, counts) -> None:
        try:
            counts.copy_to_host_async()
        except Exception:  # noqa: BLE001 - optional fast path (CPU arrays)
            pass
        self._expert_counts = (*self._expert_counts, counts)

    def fold_expert_counts(self) -> None:
        import numpy as np

        from ..ops.moe import COUNTER_TAIL

        pending, self._expert_counts = self._expert_counts, ()
        tail = COUNTER_TAIL + self.counters
        n = len(tail)
        for counts in pending:
            counts = np.asarray(counts)
            self.stats.record_moe(counts[:-n], zip(tail, counts[-n:]))


# a cache kind: the module (of this package) and the class that hold it,
# imported on first use (they import the ABC from here)
_KINDS = {"paged": ("block_pool", "BlockPool"),
          "hybrid": ("hybrid", "HybridCache"),
          "windowed": ("windowed", "WindowedCache"),
          "latent_state": ("hybrid", "StateCache"),
          "kv_state": ("hybrid", "KVStateCache"),
          "window_state": ("windowed", "WindowStateCache")}


def make_backend(kind: str, **kwargs) -> CacheBackend:
    """Construct a cache backend by kind — the seam engines build (and
    restart-rebuild) their cache through.  ``"paged"`` →
    :class:`~pathway_tpu.kvcache.block_pool.BlockPool`; ``"hybrid"`` →
    :class:`~pathway_tpu.kvcache.hybrid.HybridCache` (K/V blocks for the
    attention layers and a conv slot, one sequence); ``"windowed"`` →
    :class:`~pathway_tpu.kvcache.windowed.WindowedCache` (a second pool and
    table for the sliding-window layers); ``"latent_state"`` / ``"kv_state"``
    → :class:`~pathway_tpu.kvcache.hybrid.StateCache` /
    :class:`~pathway_tpu.kvcache.hybrid.KVStateCache` (a latent pool, or a
    K/V pool, beside a conv slot and a matrix state a sequence);
    ``"window_state"`` → :class:`~pathway_tpu.kvcache.windowed
    .WindowStateCache` (the windowed pools beside that slot arena)."""
    if kind not in _KINDS:
        raise ValueError(f"unknown cache backend {kind!r}; known: "
                         f"{', '.join(_KINDS)}")
    module, cls = _KINDS[kind]
    return getattr(importlib.import_module(f"{__package__}.{module}"),
                   cls)(**kwargs)
