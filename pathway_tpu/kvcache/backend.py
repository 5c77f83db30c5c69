"""The engine↔cache contract (Round-16).

Until this round the decode engines programmed directly against
:class:`~pathway_tpu.kvcache.block_pool.BlockPool` — the paged layout
was the only cache scheme, so the contract between "engine" (admission,
scheduling, restart, sessions) and "cache" (how a sequence's decode
state lives in HBM) existed only implicitly, as the set of BlockPool
methods the engine happened to call.  ROADMAP item 4's constant-memory
decode family needs a SECOND scheme — a fixed-size recurrent state per
sequence (statecache.py) — so the contract becomes explicit here.

:class:`CacheBackend` is that contract.  A backend owns:

- **slot lifecycle**: ``allocate`` / ``extend_slots`` / ``append_slot``
  / ``free_sequence`` — how a sequence claims device memory.  For the
  paged backend slots are KV blocks and extension is real growth; for
  the state backend a "slot" is the sequence's single fixed-size state
  row and extension past allocation is a no-op by construction.
- **byte accounting**: ``per_shard_bytes`` (what the backend pins in
  each tensor-parallel shard's HBM, the number ``obs/memory.py
  hbm_plan`` charges) and ``state_bytes_per_seq`` (the per-sequence
  footprint — block-count-dependent for paged, a constant for state).
- **suspend/resume**: ``suspend_host`` / ``resume_host`` — the
  device↔host copies behind
  :class:`~pathway_tpu.kvcache.tiering.SessionStore`.  The payload is
  backend-opaque; the store only charges its byte size and keys it by
  session.  The paged payload grows with context (power-of-two padded
  block gathers); the state payload is ONE fixed-size array, which is
  what makes session resume O(1) in context length.
- **invariants**: ``check_invariants`` — the backend-specific
  consistency sweep (refcount conservation for paged; slot-bitmap
  conservation for state).  Engine-owned invariants (admission
  ordering, emit counts, watchdog state) stay in the engine and are NOT
  part of this contract.

Backend-optional capabilities — prefix sharing, copy-on-write ``fork``,
preemption-by-eviction — are declared via ``supports_*`` flags and
raise :class:`UnsupportedCacheOp` by default; the paged engine consults
the flags before relying on them.

``make_backend(kind, ...)`` is the construction seam: engines build
their cache through it (and REBUILD through it on supervised restart),
so tests can run the existing paged identity suite through the
extracted interface unchanged.
"""

from __future__ import annotations

import abc
from typing import Callable


class UnsupportedCacheOp(NotImplementedError):
    """An optional capability (fork/preempt/prefix) the backend does not
    implement — engines must consult ``supports_*`` before calling."""


class CacheBackend(abc.ABC):
    """Abstract engine↔cache contract.  See the module docstring for
    which side owns which invariant."""

    #: "paged" | "state" | ... — the factory key and metrics family
    cache_kind: str = "abstract"
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
        the slot ids (paged: new block ids; state: the fixed slot,
        repeated — growth is free)."""

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

    def state_bytes_per_seq(self, n_tokens: int) -> int:
        """Device bytes one sequence of ``n_tokens`` occupies (global
        across shards).  Paged: grows with the block span.  State: a
        constant — the property the capacity headline is computed
        from."""
        raise UnsupportedCacheOp(
            f"{type(self).__name__} does not account per-sequence bytes"
        )

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
    def row_extras(self, seq_ids, n_rows: int) -> tuple:
        """Further ``(n_rows,)`` arrays a step program takes after the
        paged ones, one entry a batch row in ``seq_ids``' order (a hybrid
        cache: the rows' conv slots).  None by default."""
        return ()

    def after_sync(self) -> None:
        """Every program dispatched so far has finished: fold what the
        programs counted on the device into the stats.  Default no-op."""


_BACKENDS: dict[str, Callable] = {}


def register_backend(kind: str, factory: Callable) -> None:
    _BACKENDS[kind] = factory


def make_backend(kind: str, **kwargs) -> CacheBackend:
    """Construct a cache backend by kind — the seam engines build (and
    restart-rebuild) their cache through.  ``"paged"`` →
    :class:`~pathway_tpu.kvcache.block_pool.BlockPool`; ``"state"`` →
    :class:`~pathway_tpu.kvcache.statecache.StateCache`; ``"hybrid"`` →
    :class:`~pathway_tpu.kvcache.hybrid.HybridCache` (K/V blocks for the
    attention layers and a conv slot, one sequence)."""
    if kind not in _BACKENDS:
        # lazy registration avoids import cycles: block_pool/statecache
        # import nothing from here at module scope except the ABC
        if kind == "paged":
            from .block_pool import BlockPool

            register_backend("paged", BlockPool)
        elif kind == "state":
            from .statecache import StateCache

            register_backend("state", StateCache)
        elif kind == "hybrid":
            from .hybrid import HybridCache

            register_backend("hybrid", HybridCache)
        else:
            raise ValueError(
                f"unknown cache backend {kind!r}; "
                f"registered: {sorted(_BACKENDS)} + builtin: paged, state, "
                "hybrid"
            )
    return _BACKENDS[kind](**kwargs)
