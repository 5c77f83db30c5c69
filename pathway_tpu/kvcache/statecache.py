"""Constant-memory decode: the fixed-size state backend + its engine.

Round-16.  The paged engine's per-sequence cost GROWS with context —
every decoded token appends K/V, so HBM caps live sessions at
``pool_bytes / context_bytes`` and suspend/resume copies scale with the
conversation.  The SSD decoder family (models/decoder.py ``ssd_*``)
replaces attention with a gated linear-attention recurrence whose whole
decode state is ONE fixed-size tensor per sequence: ``[n_layers,
n_heads, head_dim, head_dim]``, independent of context length.

:class:`StateCache` is the :class:`~pathway_tpu.kvcache.backend.
CacheBackend` that manages those states: a stacked ``[L, max_slots, H,
hd, hd]`` device array (sharded on the head axis under tensor
parallelism, like the K/V pool), with SLOT allocation instead of block
tables — a sequence owns exactly one slot for its whole life, so there
is no growth, no copy-on-write, no preemption-by-eviction: a slot
either exists or is suspended.  Slot 0 is reserved as the null garbage
sink (mirroring the paged pool's block 0): padding rows in every
dispatch target it, so scatters never branch on row validity.

Suspend/resume through the fleet-shared
:class:`~pathway_tpu.kvcache.tiering.SessionStore` is ONE fixed-size
gather/scatter per session (``pw.state_suspend`` / ``pw.state_resume``)
— resume latency is O(1) in context length, where the paged tier's
padded block copies grow with the conversation.  That, plus the
constant HBM footprint, is the capacity headline bench.py commits as
``ssd.live_sessions_at_fixed_hbm_vs_paged``.

:class:`StateDecodeEngine` serves the SSD family with the SAME serving
surface as :class:`~pathway_tpu.kvcache.engine.PagedDecodeEngine` —
continuous batching, chunked prefill riding a mixed-dispatch token
budget, chained multi-step decode, device-side (sampled) heads,
watchdog + supervised restart, session tiering, degrade/failover hooks
— by BORROWING the paged engine's surface methods unbound (admission
ordering, delivery semantics, the failure domain and the sampling-array
plumbing are cache-agnostic; reimplementing them would fork the
semantics the fleet and scheduler tests pin).  Only the cache-specific
mechanics are defined here: slot admission, the three ``pw.ssd_*``
dispatch shapes, and restart-rebuild through ``make_backend("state")``.

One recurrence-specific correction to the paged playbook: a chained
scan cannot let a finished row keep stepping (the paged chain parks
surplus writes in the null block, but a recurrent state has no null to
absorb updates), so the chained programs carry per-row budgets and the
EOS id and FREEZE finished rows in-scan — keeping every suspended
state exactly equal to ``context + emitted[:-1]``, the same coverage
rule the paged tier pins.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults, obs
from .backend import CacheBackend, UnsupportedCacheOp, make_backend
from .block_pool import PoolExhausted, SequenceState
from .engine import (PagedDecodeEngine, _Active, _Request,  # noqa: F401
                     _WatchdogSync, resolve_tp)

# live caches by metrics name — same contract as block_pool._LIVE_POOLS:
# a second concurrent cache gets a "#n" suffix; a discarded one frees
# its name so a restart-rebuilt cache re-attaches to monotonic counters
_LIVE_CACHES: "weakref.WeakValueDictionary[str, StateCache]" = (
    weakref.WeakValueDictionary()
)
_LIVE_CACHES_LOCK = threading.Lock()


def _make_state_programs():
    """The fixed-shape suspend/resume pair: ONE (L, H, hd, hd) gather or
    scatter per session, whatever its context length — the O(1)-resume
    property the round's latency bench pins."""
    try:
        from ..obs.profiler import profiled_jit

        gather = profiled_jit(
            "pw.state_suspend", lambda state, slot: state[:, slot]
        )
        scatter = profiled_jit(
            "pw.state_resume",
            lambda state, slot, vals: state.at[:, slot].set(vals),
            donate_argnums=(0,),
        )
        clear = profiled_jit(
            "pw.state_clear",
            lambda state, slot: state.at[:, slot].set(0.0),
            donate_argnums=(0,),
        )
        return gather, scatter, clear
    except Exception:  # pragma: no cover - import-order edge
        return (
            jax.jit(lambda state, slot: state[:, slot]),
            jax.jit(
                lambda state, slot, vals: state.at[:, slot].set(vals),
                donate_argnums=(0,),
            ),
            jax.jit(
                lambda state, slot: state.at[:, slot].set(0.0),
                donate_argnums=(0,),
            ),
        )


_state_gather, _state_scatter, _state_clear = _make_state_programs()


class StateCache(CacheBackend):
    """Slot allocator over the stacked SSD recurrent-state array — the
    constant-memory implementation of the engine↔cache contract."""

    cache_kind = "state"
    supports_fork = False
    supports_prefix = False
    supports_preemption = False

    def __init__(self, *, max_slots: int, n_layers: int, n_heads: int,
                 head_dim: int, dtype=jnp.float32, name: str = "statecache",
                 mesh=None, tp_axis: str = "tp", block_size: int = 16):
        if max_slots < 2:
            raise ValueError("max_slots must be >= 2 (slot 0 is reserved)")
        self.max_slots = int(max_slots)
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        self.dtype = dtype
        # the paged pool's block granularity has no meaning here, but the
        # attribute is part of the backend's serving surface: fleet
        # affinity routing hashes prompts in block_size chains, and
        # keeping the knob lets one routing config serve mixed fleets
        self.block_size = int(block_size)
        shape = (self.n_layers, self.max_slots, self.n_heads,
                 self.head_dim, self.head_dim)
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.tp = 1
        if mesh is not None:
            self.tp = int(mesh.shape[tp_axis])
            if self.n_heads % self.tp:
                raise ValueError(
                    f"cannot shard the state cache: n_heads={self.n_heads}"
                    f" % tp={self.tp} != 0"
                )
            from ..parallel.mesh import ssd_state_sharding

            zeros = jax.jit(
                lambda: jnp.zeros(shape, dtype),
                out_shardings=ssd_state_sharding(mesh),
            )
            self.state = zeros()
        else:
            self.state = jnp.zeros(shape, dtype)
        # slot 0 reserved: never allocated, target of padded dispatch rows
        self._free: list[int] = list(range(self.max_slots - 1, 0, -1))
        self._seqs: dict[int, SequenceState] = {}
        self._arrival = 0
        self._lock = threading.RLock()
        from ..serve.metrics import kv_stats, state_stats

        with _LIVE_CACHES_LOCK:
            unique, n = name, 1
            while unique in _LIVE_CACHES:
                unique = f"{name}#{n}"
                n += 1
            name = unique
            _LIVE_CACHES[name] = self
        self.name = name
        wref = weakref.ref(self)

        def _in_use() -> int:
            cache = wref()
            return 0 if cache is None else cache.slots_in_use

        # engine-generic counters (TTFT, chains, restarts, host gap)
        # live on the shared KV stats block — the engine records through
        # pool.stats regardless of backend; slot occupancy doubles as
        # the blocks gauge there
        self.stats = kv_stats(
            name, blocks_in_use_fn=_in_use,
            blocks_total=self.max_slots - 1, shards=self.tp,
            shard_hbm_bytes=self.per_shard_bytes,
        )
        # the Round-16 pathway_state_* family: slot occupancy and
        # suspend/resume traffic for THIS backend specifically
        self.state_stats = state_stats(
            name, slots_in_use_fn=_in_use,
            slots_total=self.max_slots - 1,
            state_bytes_per_seq=self.state_bytes_per_seq(1),
        )

    def retire(self) -> None:
        """Release the registry name immediately (supervised restart
        rebuilds a same-name cache while the failure traceback may still
        pin the old object)."""
        with _LIVE_CACHES_LOCK:
            if _LIVE_CACHES.get(self.name) is self:
                del _LIVE_CACHES[self.name]

    # -- capacity ----------------------------------------------------------
    @property
    def per_shard_bytes(self) -> int:
        """State bytes held by EACH shard (whole array when tp=1)."""
        return int(self.state.size) * self.state.dtype.itemsize // self.tp

    def state_bytes_per_seq(self, n_tokens: int = 1) -> int:
        """A CONSTANT — the whole point.  One slot's global bytes:
        ``L x H x hd x hd x itemsize``, with no context-length term."""
        return (self.n_layers * self.n_heads * self.head_dim
                * self.head_dim * self.state.dtype.itemsize)

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def slots_in_use(self) -> int:
        # excludes the reserved null slot
        return (self.max_slots - 1) - len(self._free)

    # the paged stats gauge name; same quantity here
    blocks_in_use = slots_in_use

    def sequence(self, seq_id: int) -> SequenceState:
        return self._seqs[seq_id]

    def sequences(self) -> list[SequenceState]:
        return list(self._seqs.values())

    # -- slot lifecycle ----------------------------------------------------
    def allocate(self, seq_id: int, n_tokens: int, *,
                 shared_blocks=(), priority: int = 1) -> SequenceState:
        """Claim ONE slot for a new sequence — ``block_ids`` is the
        single-element ``[slot]`` so engine and SessionStore code paths
        (``resume_into(pool, entry, block_ids)``) stay uniform across
        backends.  Raises :class:`PoolExhausted` with no side effects
        when every slot is live."""
        if shared_blocks:
            raise UnsupportedCacheOp(
                "StateCache does not support shared (prefix) slots"
            )
        with self._lock:
            if seq_id in self._seqs:
                raise ValueError(f"sequence {seq_id} already allocated")
            if not self._free:
                raise PoolExhausted(
                    "state cache has no free slot", needed=1, free=0,
                )
            slot = self._free.pop()
            # a fresh sequence MUST start from the zero state: unlike a
            # paged block (every position overwritten by prefill), the
            # recurrence ACCUMULATES onto the slot — a reused slot would
            # fold the previous occupant's context into the new sequence
            self.state = _state_clear(
                self.state, jnp.asarray(np.int32(slot))
            )
            self._arrival += 1
            state = SequenceState(
                seq_id=seq_id, block_ids=[slot], n_tokens=int(n_tokens),
                priority=priority, arrival=self._arrival,
            )
            self._seqs[seq_id] = state
            return state

    def extend_slots(self, seq_id: int, k: int) -> list[tuple[int, int]]:
        """Growth is free: the fixed slot absorbs every decode step.
        Advances the token count and returns the slot ``k`` times (the
        ``(slot, 0)`` tuple shape the paged contract uses)."""
        if k <= 0:
            return []
        with self._lock:
            seq = self._seqs[seq_id]
            seq.n_tokens += k
            return [(seq.block_ids[0], 0)] * k

    def free_sequence(self, seq_id: int) -> None:
        with self._lock:
            seq = self._seqs.pop(seq_id)
            self._free.append(seq.block_ids[0])

    # -- suspend / resume (backend contract; tiering.SessionStore) ---------
    def suspend_host(self, seq_id: int,
                     context_tokens) -> tuple[dict | None, int]:
        """ONE fixed-size gather to host, whatever the context length;
        the charged bytes ARE the buffer bytes (no padding — the state
        shape never varies, so there is nothing to pad)."""
        if len(context_tokens) == 0:
            self.free_sequence(seq_id)
            return None, 0
        with self._lock:
            slot = self._seqs[seq_id].block_ids[0]
        host = np.asarray(
            _state_gather(self.state, jnp.asarray(np.int32(slot)))
        )
        self.free_sequence(seq_id)
        self.state_stats.record_suspend()
        return {"s": host}, int(host.nbytes)

    def resume_host(self, payload: dict, slot_ids) -> None:
        slot = int(list(slot_ids)[0])
        self.state = _state_scatter(
            self.state, jnp.asarray(np.int32(slot)),
            jnp.asarray(payload["s"]),
        )
        self.state_stats.record_resume()

    # -- verification ------------------------------------------------------
    def check_invariants(self, external_refs=None) -> None:
        """Slot-bitmap conservation: the free list and the live
        sequences' slots exactly partition {1..max_slots-1}, one slot
        per sequence, slot 0 never allocated."""
        with self._lock:
            free = list(self._free)
            assert len(free) == len(set(free)), "duplicate free-list entry"
            assert 0 not in free, "reserved slot 0 on the free list"
            held: list[int] = []
            for seq in self._seqs.values():
                assert len(seq.block_ids) == 1, (
                    f"sequence {seq.seq_id} holds {len(seq.block_ids)} "
                    "slots (must be exactly 1)"
                )
                assert seq.block_ids[0] != 0, (
                    f"sequence {seq.seq_id} holds the reserved null slot"
                )
                held.append(seq.block_ids[0])
            assert len(held) == len(set(held)), (
                "two sequences hold the same slot"
            )
            assert not (set(held) & set(free)), (
                "live slot also on the free list"
            )
            assert len(held) + len(free) == self.max_slots - 1, (
                "free list + live slots do not partition the cache"
            )


class StateDecodeEngine:
    """Continuous-batching generation over :class:`StateCache` + the
    SSD decoder programs.  Public surface mirrors
    :class:`~pathway_tpu.kvcache.engine.PagedDecodeEngine` exactly —
    most of it IS the paged engine's methods, borrowed unbound (see the
    module docstring for why); this class defines only the
    cache-specific mechanics."""

    # cache-agnostic surface, borrowed verbatim: admission ordering,
    # delivery/failure semantics, sampling plumbing, sync accounting.
    # The chained-round driver is borrowed too — only _dispatch_chain
    # (the dispatch shape) differs underneath it.
    generate = PagedDecodeEngine.generate
    serve_batch = PagedDecodeEngine.serve_batch
    generate_batch = PagedDecodeEngine.generate_batch
    _run_loop = PagedDecodeEngine._run_loop
    _loop_body = PagedDecodeEngine._loop_body
    _admit_arrivals = PagedDecodeEngine._admit_arrivals
    _requeue = PagedDecodeEngine._requeue
    _fail_all = PagedDecodeEngine._fail_all
    _wrap_failure = PagedDecodeEngine._wrap_failure
    _try_degrade = PagedDecodeEngine._try_degrade
    _emit = PagedDecodeEngine._emit
    _phase = PagedDecodeEngine._phase
    _sync_host = PagedDecodeEngine._sync_host
    _note_sync = PagedDecodeEngine._note_sync
    _note_dispatch = PagedDecodeEngine._note_dispatch
    _record_dispatch = PagedDecodeEngine._record_dispatch
    _sampling_arrays = PagedDecodeEngine._sampling_arrays
    _is_done = PagedDecodeEngine._is_done
    _can_chain = PagedDecodeEngine._can_chain
    _chain_headroom = PagedDecodeEngine._chain_headroom
    _chained_rounds = PagedDecodeEngine._chained_rounds
    _scan_chain = PagedDecodeEngine._scan_chain

    def __init__(self, cfg, params, *, max_slots: int = 64,
                 num_blocks: int | None = None,
                 max_batch_size: int = 8, prefill_chunk: int = 16,
                 chain_steps: int = 8, stop_token: int | None = None,
                 tp: int | None = None, name: str = "state_decoder",
                 block_size: int = 16,
                 watchdog_timeout_s: float | None = None,
                 max_restarts: int | None = None,
                 degrade_fn: Callable | None = None,
                 hbm_budget_bytes: int | None = None,
                 hbm_fit: str = "reject",
                 session_store=None):
        from ..models.decoder import ssd_augment_params
        from ..models.encoder import _resolve_dtype

        if num_blocks is not None:
            # the paged engine's capacity knob, accepted as an alias so
            # one fleet/bench config ports across cache kinds (a paged
            # BLOCK and a state SLOT are both "one capacity unit")
            max_slots = int(num_blocks)

        self.cfg = cfg
        self.name = name
        self.max_batch_size = int(max_batch_size)
        self.stop_token = stop_token
        self.tp = resolve_tp(cfg, tp)
        self.mesh = None
        # one checkpoint serves both families: a dense-decoder pytree
        # without the SSD decay projections is grafted deterministically
        # (seed 0) BEFORE sharding, so every engine/replica/restart sees
        # identical w_a/b_a
        if "w_a" not in params["layers"][0]:
            params = ssd_augment_params(params, cfg)
        if self.tp > 1:
            from ..parallel.mesh import shard_decoder_params, tp_mesh

            self.mesh = tp_mesh(self.tp)
            params = shard_decoder_params(params, self.mesh)
        self.params = params
        head_dim = cfg.d_model // cfg.n_heads
        dtype = _resolve_dtype(cfg.dtype)
        per_seq = (cfg.n_layers * cfg.n_heads * head_dim * head_dim
                   * np.dtype(np.float32 if dtype is None else dtype)
                   .itemsize)
        from ..obs import memory as obs_memory

        if hbm_fit not in ("reject", "clamp", "off"):
            raise ValueError(
                f"hbm_fit={hbm_fit!r} is not one of 'reject', 'clamp', "
                "'off'"
            )
        # the same pre-flight ledger as the paged engine, with the
        # Round-16 constant-memory cache term: num_blocks is the SLOT
        # count and context length does not appear
        self.hbm_plan = obs_memory.hbm_plan(
            cfg, num_blocks=int(max_slots), block_size=int(block_size),
            max_batch_size=self.max_batch_size,
            chain_steps=max(1, int(chain_steps)),
            prefill_chunk=int(prefill_chunk), tp=self.tp, dtype=dtype,
            params=params, budget_bytes=hbm_budget_bytes,
            reference_attn=False, state_bytes_per_seq=per_seq,
        )
        if self.hbm_plan.budget_bytes is not None \
                and not self.hbm_plan.fits and hbm_fit != "off":
            clamped = (
                self.hbm_plan.max_fitting_num_blocks()
                if hbm_fit == "clamp" else None
            )
            if clamped is not None and clamped >= 2:
                import logging

                logging.getLogger(__name__).warning(
                    "engine %s does not fit HBM at max_slots=%d; "
                    "clamping to %d (budget %.1fMB, %s)",
                    name, int(max_slots), clamped,
                    self.hbm_plan.budget_bytes / 1048576,
                    self.hbm_plan.budget_source,
                )
                max_slots = clamped
                self.hbm_plan = self.hbm_plan.with_(num_blocks=clamped)
            else:
                raise ValueError(self.hbm_plan.reject_message())
        self._pool_kwargs = dict(
            max_slots=int(max_slots), n_layers=cfg.n_layers,
            n_heads=cfg.n_heads, head_dim=head_dim, dtype=dtype,
            name=name, mesh=self.mesh, block_size=int(block_size),
        )
        self.pool = make_backend("state", **self._pool_kwargs)
        if watchdog_timeout_s is None:
            env_wd = os.environ.get("PW_ENGINE_WATCHDOG_S")
            watchdog_timeout_s = float(env_wd) if env_wd else None
        self.watchdog_timeout_s = (
            watchdog_timeout_s if watchdog_timeout_s
            and watchdog_timeout_s > 0 else None
        )
        if max_restarts is None:
            max_restarts = int(os.environ.get("PW_ENGINE_MAX_RESTARTS", "0")
                               or 0)
        self.max_restarts = max(0, int(max_restarts))
        self.degrade_fn = degrade_fn
        self.session_store = session_store
        self._sampled: dict | None = None
        self._watchdog = (
            _WatchdogSync(f"pw-watchdog-{name}")
            if self.watchdog_timeout_s else None
        )
        self._t_failure: float | None = None
        # the recurrence has no positional table, so a sequence's length
        # is unbounded by the cache — only max_new/EOS close requests
        # (the borrowed capacity checks compare against infinity)
        self.max_seq_tokens = float("inf")
        self.prefill_chunk = max(1, int(prefill_chunk))
        # packed token budget of one mixed round: every decode row costs
        # one token, the rest is chunk headroom — same budget rule as
        # the paged ragged step, so prefill chunks stream without
        # stalling in-flight decodes
        self.mixed_tokens = self.max_batch_size + self.prefill_chunk
        self.chain_steps = max(1, int(chain_steps))
        self._t_device_idle: float | None = None
        self._t_dispatch: float | None = None
        self._dispatch_kind = "step"
        # speculative decoding is a paged-cache feature (proposals need
        # extend_slots/truncate_slots); the borrowed round driver and
        # generate_batch flush read this, so it must exist — always off
        self._spec = None
        self._run_ctx: tuple = (obs.new_trace_id(), 0)
        self._seq_counter = 0
        self._lock = threading.RLock()
        # no prefix sharing in this backend; the borrowed run loop still
        # clears the (always-empty) map
        self._inflight_prefix: dict = {}
        _cfg = cfg
        _mesh = self.mesh

        def _step_fn(p, state, token, slots):
            from ..models.decoder import ssd_decode_step, ssd_decode_step_tp

            if _mesh is not None:
                return ssd_decode_step_tp(p, _cfg, _mesh, state, token,
                                          slots)
            out, state = ssd_decode_step(p, _cfg, state, token, slots)
            return jnp.argmax(out, axis=-1).astype(jnp.int32), state

        def _mixed_fn(p, state, tokens, n_valid, slots):
            from ..models.decoder import ssd_mixed_step, ssd_mixed_step_tp

            if _mesh is not None:
                return ssd_mixed_step_tp(p, _cfg, _mesh, state, tokens,
                                         n_valid, slots)
            out, state = ssd_mixed_step(p, _cfg, state, tokens, n_valid,
                                        slots)
            return jnp.argmax(out, axis=-1).astype(jnp.int32), state

        def _chained_fn(p, state, token, slots, steps, rem, stop_tok):
            from ..models.decoder import (ssd_chained_decode,
                                          ssd_chained_decode_tp)

            if _mesh is not None:
                return ssd_chained_decode_tp(p, _cfg, _mesh, state, token,
                                             slots, steps, rem, stop_tok)
            return ssd_chained_decode(p, _cfg, state, token, slots, steps,
                                      rem, stop_tok)

        # state donated: every step consumes the array in place.  THREE
        # static shapes cover the whole greedy workload — (B,) decode,
        # (B, C) mixed, (B, K) chained — pinned by the round's
        # zero-recompile guard
        from ..obs.profiler import profiled_jit

        self._step = profiled_jit(
            "pw.ssd_decode_step", _step_fn, donate_argnums=(1,)
        )
        self._mixed = profiled_jit(
            "pw.ssd_mixed_step", _mixed_fn, donate_argnums=(1,)
        )
        self._chained = profiled_jit(
            "pw.ssd_chained_decode", _chained_fn, donate_argnums=(1,)
        )

    def _sampled_programs(self) -> dict:
        """The pw.ssd_*_sampled programs, built on FIRST sampled request
        (greedy-only workloads compile exactly the greedy set)."""
        if self._sampled is not None:
            return self._sampled
        from ..obs.profiler import profiled_jit

        _cfg, _mesh = self.cfg, self.mesh

        def _step_fn(p, state, token, slots, temp, tk, tpp, seed, emit):
            from ..models.decoder import (ssd_decode_step_sampled,
                                          ssd_decode_step_sampled_tp)

            if _mesh is not None:
                return ssd_decode_step_sampled_tp(
                    p, _cfg, _mesh, state, token, slots, temp, tk, tpp,
                    seed, emit,
                )
            return ssd_decode_step_sampled(
                p, _cfg, state, token, slots, temp, tk, tpp, seed, emit,
            )

        def _mixed_fn(p, state, tokens, n_valid, slots, temp, tk, tpp,
                      seed, emit):
            from ..models.decoder import (ssd_mixed_step_sampled,
                                          ssd_mixed_step_sampled_tp)

            if _mesh is not None:
                return ssd_mixed_step_sampled_tp(
                    p, _cfg, _mesh, state, tokens, n_valid, slots, temp,
                    tk, tpp, seed, emit,
                )
            return ssd_mixed_step_sampled(
                p, _cfg, state, tokens, n_valid, slots, temp, tk, tpp,
                seed, emit,
            )

        def _chained_fn(p, state, token, slots, steps, rem, stop_tok,
                        temp, tk, tpp, seed, emit0):
            from ..models.decoder import (ssd_chained_decode_sampled,
                                          ssd_chained_decode_sampled_tp)

            if _mesh is not None:
                return ssd_chained_decode_sampled_tp(
                    p, _cfg, _mesh, state, token, slots, steps, rem,
                    stop_tok, temp, tk, tpp, seed, emit0,
                )
            return ssd_chained_decode_sampled(
                p, _cfg, state, token, slots, steps, rem, stop_tok, temp,
                tk, tpp, seed, emit0,
            )

        self._sampled = {
            "step": profiled_jit(
                "pw.ssd_decode_step_sampled", _step_fn, donate_argnums=(1,)
            ),
            "mixed": profiled_jit(
                "pw.ssd_mixed_step_sampled", _mixed_fn, donate_argnums=(1,)
            ),
            "chained": profiled_jit(
                "pw.ssd_chained_decode_sampled", _chained_fn,
                donate_argnums=(1,),
            ),
        }
        return self._sampled

    # -- failure domain ----------------------------------------------------
    def _restart(self, running, pending, err_name: str, err_text: str,
                 attempt: int) -> None:
        """Rebuild the failure domain: fresh StateCache through the
        backend factory, then every in-flight request rejoins the queue
        carrying its emitted tokens — re-admission recomputes the
        recurrence over prompt + emitted, token-identical by the same
        guarantee the paged restart pins."""
        import logging

        self._t_failure = time.perf_counter()
        t0 = self._t_failure
        survivors = [act.req for act in running]
        running.clear()
        for req in survivors:
            self._requeue(pending, req)
        old_pool = self.pool
        old_pool.retire()
        try:
            self.pool = None
            self.pool = make_backend("state", **self._pool_kwargs)
        except BaseException:
            self.pool = old_pool
            raise
        self._t_device_idle = None
        self._t_dispatch = None
        rebuild_s = time.perf_counter() - t0
        self.pool.stats.record_engine_restart(rebuild_s)
        obs.event(
            "engine.restart", ctx=self._run_ctx, attempt=attempt,
            error=err_name, rebuild_s=round(rebuild_s, 4),
            inflight=len(survivors),
        )
        logging.getLogger(__name__).warning(
            "engine restart #%d after %s: %s — state cache rebuilt in "
            "%.3fs, re-admitting %d in-flight sequence(s) by recompute",
            attempt, err_name, err_text, rebuild_s, len(survivors),
        )

    # -- admission ---------------------------------------------------------
    def _try_admit(self, req: _Request, running, pending, deliver) -> str:
        """Claim one slot and queue the (untrimmed — the recurrence has
        no length cap) prompt for chunked streaming.  A session hit
        resumes the suspended state into the fresh slot and prefill
        continues from the first uncovered token: unlike the paged
        divert rule there is NO recompute of resident positions — the
        recurrence would double-fold them — so a stored context that
        covers the ENTIRE new prompt is treated as a miss (chat turns
        always extend the context, making that edge recompute-only)."""
        if req.max_new - len(req.emitted) <= 0:
            deliver(req)
            return "done"
        tokens = req.prompt + req.emitted
        if not tokens:
            tokens = [4]
        n = len(tokens)
        self._seq_counter += 1
        seq_id = self._seq_counter
        sess_entry = None
        if req.session is not None and self.session_store is not None:
            sess_entry = self.session_store.match(req.session, tokens)
        try:
            state = self.pool.allocate(seq_id, n, priority=req.priority)
        except PoolExhausted:
            if running:
                return "wait"
            deliver(req, RuntimeError(
                f"state cache ({self.pool.max_slots - 1} slots) has no "
                "free slot"
            ))
            return "failed"
        act = _Active(seq_id, req)
        act.tokens = tokens
        act.admitted = tokens
        if sess_entry is not None and len(sess_entry.tokens) < n:
            resident = self.session_store.resume_into(
                self.pool, sess_entry, state.block_ids
            )
            act.n_filled = resident
            act.n_diverted = resident
        req.note_admitted(time.perf_counter())
        running.append(act)
        return "admitted"

    def _release_seq(self, act: _Active) -> None:
        """Completion-time release; a session-tagged request SUSPENDS
        its fixed-size state instead (one gather, O(1) in context).
        Coverage rule identical to paged: the final emitted token was
        output, never fed back, so the state covers admitted + emitted
        minus the last."""
        req = act.req
        store = self.session_store
        if (store is not None and req.session is not None
                and act.admitted is not None):
            emitted = [int(t) for t in req.emitted[act.emit_base:]]
            context = list(act.admitted) + emitted[:-1]
            try:
                store.suspend(req.session, self.pool, act.seq_id, context)
                return
            except Exception:  # noqa: BLE001 - tiering is best-effort
                import logging

                logging.getLogger(__name__).warning(
                    "session suspend failed for %r; freeing slot",
                    req.session, exc_info=True,
                )
        self.pool.free_sequence(act.seq_id)

    def _slot(self, act: _Active) -> int:
        return self.pool.sequence(act.seq_id).block_ids[0]

    # -- stepping ----------------------------------------------------------
    def _step_round(self, running, pending, deliver, poll=None,
                    stop=None) -> None:
        """One engine step: the chained program when the queue is quiet
        (borrowed adaptive-K policy), else the mixed chunk program when
        any prefill is streaming, else the 1-token decode program."""
        if self._can_chain(running, pending):
            if self._chained_rounds(running, pending, deliver, poll, stop):
                return
            if not running:
                return
        if any(a.tokens is not None for a in running):
            self._mixed_round(running, deliver)
        elif running:
            self._decode_round(running, deliver)

    def _decode_round(self, running, deliver) -> None:
        B = self.max_batch_size
        token = np.zeros(B, np.int32)
        slots = np.zeros(B, np.int32)  # idle rows target the null slot
        acts = list(running)
        for i, act in enumerate(acts):
            token[i] = act.req.emitted[-1]
            slots[i] = self._slot(act)
        samp = self._sampling_arrays(
            [(i, a.req) for i, a in enumerate(acts)], B
        )
        faults.fire("engine.dispatch.step")
        self._note_dispatch("step")
        t_disp = self._t_dispatch
        if samp is None:
            prog = self._step
            with obs.trace_annotation("pw.ssd_decode_step"):
                ids, self.pool.state = prog(
                    self.params, self.pool.state, jnp.asarray(token),
                    jnp.asarray(slots),
                )
        else:
            prog = self._sampled_programs()["step"]
            with obs.trace_annotation("pw.ssd_decode_step_sampled"):
                ids, self.pool.state = prog(
                    self.params, self.pool.state, jnp.asarray(token),
                    jnp.asarray(slots), *map(jnp.asarray, samp),
                )
        ids = self._sync_host(ids)
        t_sync1 = time.perf_counter()
        self._note_sync()
        self._record_dispatch(prog, t_disp, t_sync1, items=len(acts))
        self.pool.stats.record_chain(
            steps=1, slots=len(acts), emitted=len(acts)
        )
        for i, act in enumerate(acts):
            self._emit(act.req, int(ids[i]))
            if self._is_done(act.req, act.seq_id):
                running.remove(act)
                self._release_seq(act)
                deliver(act.req)

    def _mixed_round(self, running, deliver) -> None:
        """Decode rows (one token each) and prefill chunk rows (a run
        of up to ``prefill_chunk`` tokens) share one (B, C) dispatch
        under the ``mixed_tokens`` budget — a long prompt streams in
        chunks without stalling in-flight decodes, exactly the paged
        ragged-step scheduling with a dense per-row layout (the chunk
        form's masked matmuls want rectangular runs)."""
        B = self.max_batch_size
        C = self.prefill_chunk
        tokens = np.zeros((B, C), np.int32)
        n_valid = np.zeros(B, np.int32)  # 0 = idle row: exact no-op
        slots = np.zeros(B, np.int32)
        budget = self.mixed_tokens
        rows: list[tuple[_Active, int, int]] = []  # (act, row, filled|-1)
        row = 0
        for act in running:  # decode rows ride every round
            if act.tokens is not None:
                continue
            tokens[row, 0] = act.req.emitted[-1]
            n_valid[row] = 1
            slots[row] = self._slot(act)
            rows.append((act, row, -1))
            row += 1
            budget -= 1
        for act in running:  # chunk rows fill the remaining budget
            if act.tokens is None:
                continue
            if row >= B or budget <= 0:
                break  # later chunks wait a round (FIFO — no starvation)
            s = act.n_filled
            e = min(s + C, len(act.tokens), s + budget)
            if e <= s:
                continue
            nv = e - s
            tokens[row, :nv] = act.tokens[s:e]
            n_valid[row] = nv
            slots[row] = self._slot(act)
            rows.append((act, row, e))
            row += 1
            budget -= nv
        if not rows:  # pragma: no cover - admission guarantees a row
            raise RuntimeError("mixed round produced no rows")
        samp = self._sampling_arrays(
            [(r, act.req) for act, r, _f in rows], B
        )
        faults.fire("engine.dispatch.mixed")
        self._note_dispatch("mixed")
        t_disp = self._t_dispatch
        for act, _row, filled in rows:
            if filled >= 0:
                act.req.note_chunk(t_disp)
        if samp is None:
            prog = self._mixed
            with obs.trace_annotation("pw.ssd_mixed_step"):
                ids, self.pool.state = prog(
                    self.params, self.pool.state, jnp.asarray(tokens),
                    jnp.asarray(n_valid), jnp.asarray(slots),
                )
        else:
            prog = self._sampled_programs()["mixed"]
            with obs.trace_annotation("pw.ssd_mixed_step_sampled"):
                ids, self.pool.state = prog(
                    self.params, self.pool.state, jnp.asarray(tokens),
                    jnp.asarray(n_valid), jnp.asarray(slots),
                    *map(jnp.asarray, samp),
                )
        ids = self._sync_host(ids)
        t_sync1 = time.perf_counter()
        self._note_sync()
        self._record_dispatch(prog, t_disp, t_sync1,
                              items=int(n_valid.sum()))
        self.pool.stats.record_mixed_step(len(rows))
        n_decode = sum(1 for _a, _r, f in rows if f < 0)
        if n_decode:
            self.pool.stats.record_chain(
                steps=1, slots=n_decode, emitted=n_decode
            )
        self.pool.stats.record_prefill_chunks(
            sum(1 for _a, _r, f in rows if f >= 0)
        )
        for act, row, filled in rows:
            if filled < 0:  # decode row
                act.req.n_mixed += 1
                self._emit(act.req, int(ids[row]))
            else:
                obs.record_span("engine.prefill_chunk", t_disp, t_sync1,
                                ctx=act.req.ctx, start=act.n_filled,
                                end=filled)
                act.n_filled = filled
                if filled < len(act.tokens):
                    continue  # mid-prefill: this row's id is garbage
                act.tokens = None
                self._emit(act.req, int(ids[row]))
            if self._is_done(act.req, act.seq_id):
                running.remove(act)
                self._release_seq(act)
                deliver(act.req)

    def _dispatch_chain(self, running, pending):
        """Dispatch ONE K-step scan over every decode row.  No slot
        pre-extension exists to fail, so (unlike paged) this never
        preempts; per-row budgets + the EOS id ride INTO the program so
        finished rows freeze in-scan (see the module docstring).
        Returns ``(acts, kreal, ids, t_disp, prog)`` for the borrowed
        double-buffered chain driver."""
        K = self.chain_steps
        B = self.max_batch_size
        token = np.zeros(B, np.int32)
        slots = np.zeros(B, np.int32)
        rem = np.zeros(B, np.int32)  # idle rows: budget 0, fully frozen
        acts: list[_Active] = []
        kreal: list[int] = []
        for i, act in enumerate(running):
            token[i] = act.req.emitted[-1]
            slots[i] = self._slot(act)
            r = min(K, max(act.req.max_new - len(act.req.emitted), 1))
            rem[i] = r
            acts.append(act)
            kreal.append(r)
        stop_val = acts[0].req.stop_token  # uniform across a run
        samp = self._sampling_arrays(
            [(i, a.req) for i, a in enumerate(acts)], B
        )
        faults.fire("engine.dispatch.chain")
        self._note_dispatch("chain")
        t_disp = self._t_dispatch
        base = (
            self.params, self.pool.state, jnp.asarray(token),
            jnp.asarray(slots), jnp.arange(K, dtype=jnp.int32),
            jnp.asarray(rem),
            jnp.asarray(np.int32(-1 if stop_val is None else stop_val)),
        )
        if samp is None:
            prog = self._chained
            with obs.trace_annotation("pw.ssd_chain_dispatch"):
                ids, self.pool.state = prog(*base)
        else:
            prog = self._sampled_programs()["chained"]
            with obs.trace_annotation("pw.ssd_chain_dispatch_sampled"):
                ids, self.pool.state = prog(*base, *map(jnp.asarray, samp))
        try:
            ids.copy_to_host_async()
        except Exception:  # noqa: BLE001 - optional fast path
            pass
        return acts, kreal, ids, t_disp, prog
