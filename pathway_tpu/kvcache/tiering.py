"""Host-RAM session tier for the paged KV cache (Round-15).

A serving front holds millions of conversations, but almost all of them
are IDLE between user turns — keeping every session's K/V resident in
HBM caps the session count at the pool size.  :class:`SessionStore`
suspends a finished request's context blocks to host memory (one device
gather + copy) and resumes the session's next turn by re-scattering
them into freshly allocated pool blocks — so an idle session costs host
bytes, not HBM blocks, and the next turn skips recomputing its entire
history prefill.

Correctness leans on the engine's existing divert rule: resumed
positions are marked ``n_diverted`` exactly like prefix-cache hits, so
chunk writes for already-resident positions go to the null block while
the attention gather reads the re-scattered bytes through the table.
Token identity is untouched — a resume produces bit-identical K/V to
the suspend-time pool state, and a store miss simply falls back to the
normal recompute prefill.

Residency is budgeted the Round-14 way: :meth:`residency_ledger`
computes, from an ``obs.memory.hbm_plan`` ledger, how many sessions
stay resident at a FIXED HBM budget with and without the host tier —
the ``sessions_resident_at_fixed_hbm`` bench row.

Shape discipline: gathers and scatters pad the block list to the next
power of two with the null block, so a store serves every session
length through O(log max_blocks) compiled programs instead of one per
block count.  Padded scatter lanes write into block 0 — the pool's
designated garbage sink — which is safe by construction.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict


def _make_tier_programs():
    try:
        from ..obs.profiler import profiled_jit

        gather = profiled_jit(
            "pw.kv_tier_suspend", lambda pool_arr, idx: pool_arr[:, idx]
        )
        scatter = profiled_jit(
            "pw.kv_tier_resume",
            lambda pool_arr, idx, vals: pool_arr.at[:, idx].set(vals),
            donate_argnums=(0,),
        )
        return gather, scatter
    except Exception:  # pragma: no cover - import-order edge
        import jax

        return (
            jax.jit(lambda pool_arr, idx: pool_arr[:, idx]),
            jax.jit(
                lambda pool_arr, idx, vals: pool_arr.at[:, idx].set(vals),
                donate_argnums=(0,),
            ),
        )


_tier_gather, _tier_scatter = _make_tier_programs()


def _pad_width(nb: int) -> int:
    """Next power of two >= nb: bounds the compiled gather/scatter
    variants at O(log max_blocks_per_seq)."""
    return 1 << max(nb - 1, 0).bit_length() if nb > 1 else 1


class _SessionEntry:
    __slots__ = ("session_id", "tokens", "payload", "nbytes", "t_suspend")

    def __init__(self, session_id, tokens, payload, nbytes):
        self.session_id = session_id
        self.tokens = tokens  # the context tokens the stored state covers
        # backend-opaque host state (paged: padded K/V block gathers)
        self.payload = payload
        # the REAL host buffer size, padding included — Round-16 fix:
        # charging the logical block bytes of a padded gather's view
        # under-counted the budget by up to 2x (the view's base buffer
        # holds the power-of-two width either way)
        self.nbytes = int(nbytes)
        self.t_suspend = time.perf_counter()


class SessionStore:
    """LRU host-RAM store of suspended sessions' KV blocks.

    Engine-agnostic and shareable: every replica of a fleet points at
    ONE store, so a session suspended on replica A resumes on replica B
    (same model config => same pool block layout) — the tier doubles as
    the fleet's session-mobility layer.
    """

    def __init__(self, *, host_budget_bytes: int | None = None,
                 name: str = "sessions"):
        self.name = name
        self.host_budget_bytes = (
            int(host_budget_bytes) if host_budget_bytes else None
        )
        self._sessions: "OrderedDict[object, _SessionEntry]" = OrderedDict()
        self._bytes = 0
        self._lock = threading.RLock()
        # counters the fleet metrics/dashboard surface
        self.n_suspends = 0
        self.n_resumes = 0
        self.n_misses = 0
        self.n_evictions = 0
        self.resumed_tokens = 0
        self.resume_ms: list[float] = []  # bounded sample ring
        try:  # surface pathway_kv_tier_* on /metrics + OTLP
            from ..serve.metrics import register_session_store

            register_session_store(self)
        except Exception:  # pragma: no cover - import-order edge
            pass

    # -- introspection -----------------------------------------------------
    def __len__(self) -> int:
        with self._lock:
            return len(self._sessions)

    @property
    def host_bytes(self) -> int:
        return self._bytes

    def stats(self) -> dict:
        with self._lock:
            samples = sorted(self.resume_ms)
            p99 = (
                samples[min(len(samples) - 1,
                            int(0.99 * len(samples)))]
                if samples else 0.0
            )
            return {
                "suspended_sessions": len(self._sessions),
                "host_bytes": self._bytes,
                "host_budget_bytes": self.host_budget_bytes,
                "suspends": self.n_suspends,
                "resumes": self.n_resumes,
                "misses": self.n_misses,
                "evictions": self.n_evictions,
                "resumed_tokens": self.resumed_tokens,
                "resume_ms_p99": p99,
            }

    # -- suspend / resume --------------------------------------------------
    def match(self, session_id, tokens) -> "_SessionEntry | None":
        """The stored entry IF its context is a non-empty prefix of this
        turn's admitted tokens (the app sent the running conversation
        back, as chat protocols do).  A diverged entry — the app edited
        history — is dropped: resuming it would attend through K/V of
        tokens that no longer exist."""
        with self._lock:
            ent = self._sessions.get(session_id)
            if ent is None:
                self.n_misses += 1
                return None
            n = len(ent.tokens)
            if 0 < n <= len(tokens) and list(tokens[:n]) == ent.tokens:
                self._sessions.move_to_end(session_id)
                return ent
            del self._sessions[session_id]
            self._bytes -= ent.nbytes
            self.n_misses += 1
            return None

    def suspend(self, session_id, pool, seq_id, context_tokens) -> int:
        """Copy the sequence's decode state to host RAM and free its
        device allocation, through the backend contract
        (``CacheBackend.suspend_host``).  ``context_tokens`` are the
        tokens the state actually covers (admitted + fed-back emitted);
        for the paged backend blocks past their span — chain
        pre-extension garbage — are NOT copied.  Returns the number of
        context tokens stored (0 = nothing worth storing; the sequence
        is freed either way)."""
        tokens = [int(t) for t in context_tokens]
        payload, nbytes = pool.suspend_host(seq_id, tokens)
        if payload is None:
            return 0
        ent = _SessionEntry(session_id, tokens, payload, nbytes)
        with self._lock:
            old = self._sessions.pop(session_id, None)
            if old is not None:
                self._bytes -= old.nbytes
            self._sessions[session_id] = ent
            self._bytes += ent.nbytes
            self.n_suspends += 1
            self._evict_over_budget()
        return len(tokens)

    def resume_into(self, pool, entry, block_ids) -> int:
        """Scatter a suspended session's state into the freshly
        allocated ``block_ids`` (the engine allocated for the FULL new
        prompt, which the stored context prefixes), through
        ``CacheBackend.resume_host``.  Returns the number of resident
        tokens — the engine's ``n_diverted``."""
        t0 = time.perf_counter()
        pool.resume_host(entry.payload, block_ids)
        ms = (time.perf_counter() - t0) * 1e3
        with self._lock:
            self.n_resumes += 1
            self.resumed_tokens += len(entry.tokens)
            self.resume_ms.append(ms)
            if len(self.resume_ms) > 4096:
                del self.resume_ms[:2048]
        return len(entry.tokens)

    def drop(self, session_id) -> bool:
        with self._lock:
            ent = self._sessions.pop(session_id, None)
            if ent is None:
                return False
            self._bytes -= ent.nbytes
            return True

    def clear(self) -> None:
        with self._lock:
            self._sessions.clear()
            self._bytes = 0

    def _evict_over_budget(self) -> None:
        # caller holds the lock; LRU whole-session eviction (an evicted
        # session is not lost — its next turn recomputes, exactly the
        # paged-only behaviour)
        if self.host_budget_bytes is None:
            return
        while self._bytes > self.host_budget_bytes and len(self._sessions) > 1:
            _sid, ent = self._sessions.popitem(last=False)
            self._bytes -= ent.nbytes
            self.n_evictions += 1

    # -- residency accounting ----------------------------------------------
    def residency_ledger(self, plan, *, session_tokens: int,
                         host_budget_bytes: int | None = None) -> dict:
        """How many sessions stay RESIDENT (resumable without recompute)
        at the plan's fixed HBM budget, paged-only vs tiered — computed
        from the ``hbm_plan`` ledger, not sampled.  Paged-only residency
        is bounded by pool blocks; the tier adds host-budget/bytes-per-
        session on top, at zero extra HBM."""
        bs = int(plan.block_size)
        nb_sess = max(-(-int(session_tokens) // bs), 1)
        usable_blocks = max(int(plan.num_blocks) - 1, 0)
        paged_only = usable_blocks // nb_sess
        # host bytes per suspended session: the same per-block K/V bytes
        # the plan charges HBM (global across tp shards: the host copy
        # gathers full heads), for the session's block span
        per_block = int(plan.per_block_bytes) * max(int(plan.tp), 1)
        per_session_host = nb_sess * per_block
        budget = (
            host_budget_bytes if host_budget_bytes is not None
            else self.host_budget_bytes
        )
        if budget is None:
            # unbounded store: report what the CURRENT contents prove
            host_sessions = len(self._sessions)
        else:
            host_sessions = int(budget) // max(per_session_host, 1)
        tiered = paged_only + host_sessions
        return {
            "hbm_budget_bytes": plan.budget_bytes,
            "hbm_total_bytes": plan.total_bytes,
            "session_tokens": int(session_tokens),
            "blocks_per_session": nb_sess,
            "bytes_per_session_host": per_session_host,
            "paged_only_sessions": paged_only,
            "host_tier_sessions": host_sessions,
            "sessions_resident": tiered,
            "residency_gain": tiered / max(paged_only, 1),
        }
