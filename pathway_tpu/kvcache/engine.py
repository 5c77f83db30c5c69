"""Continuous-batching greedy generation over the paged KV cache.

The dense serving path (models/host_decoder.py `serving_executor`) was
pinned to ``max_batch_size=1`` because the KV cache was per-instance
mutable state.  Here the cache is the shared BlockPool, so the engine
decodes MANY sequences per device step:

- admission (Round-8, chunked): a request's prompt is matched against
  the prefix cache (shared leading blocks are mapped instead of
  re-stored AND re-computed — chunked prefill starts after them), fresh
  blocks are allocated for the remainder, and the prompt then streams
  through the RAGGED fused step in block-aligned chunks
  (the block family's ``mixed`` program, models/families.py): each engine
  step carries the in-flight decode rows (1 token each) plus one
  ``prefill_chunk``-token chunk per admitting sequence in ONE dispatch,
  so a 1k-token arrival never stalls running decodes behind a
  monolithic whole-bucket prefill (head-of-line blocking at step
  boundaries); ``prefill_chunk`` left unset is chosen with the other
  shapes (obs/memory.choose_engine_config): as wide as the HBM ledger,
  the prompt cap and the chip's ridge allow, since a step streams every
  weight whatever it carries; two blocks where no budget or no device
  roof resolves (the CPU);
- decode: every running sequence advances one token per dispatch with
  per-sequence positions/block tables (the dense path's
  one-scalar-position design is what forced batch 1).  Rounds with no
  chunk in flight dispatch the cheap 1-token-per-row program; rounds
  with admissions dispatch the mixed program — two static shapes total,
  compiled once each (no per-bucket prefill ladder);
- device-side sampling: greedy argmax runs INSIDE the jitted step; only
  ``[B]`` int32 token ids cross the device->host boundary per round
  (the done-mask is a host compare on those ids), shrinking the
  per-token sync by ~vocab x vs shipping ``[B, vocab]`` logits;
- chained decode (Round-10): when the queue is quiet the engine chains
  up to ``chain_steps`` greedy steps into ONE device program
  (lax.scan feeding step t's ids into step t+1, KV scattered in-loop
  into host-PRE-EXTENDED block tables) and syncs once per chain on a
  ``[B, K]`` ids array; rounds are double-buffered — chain N+1 is
  dispatched before chain N's completion callbacks/polling run, so
  host bookkeeping overlaps device execution.  K adapts back to 1
  whenever arrivals or preemption are pending (admission semantics
  unchanged); emitted tokens truncate at EOS/max_new host-side with
  the per-step done rule, so greedy output is token-identical;
- continuous batching: between steps the engine polls its scheduler for
  new arrivals and admits them into the in-flight batch (step-boundary
  admission, serve/scheduler.py `poll_inflight`).  N same-round
  arrivals ride the SAME mixed dispatch — their first tokens all come
  from that dispatch's device-side argmax, one dispatch, not N;
- preemption: when the pool is exhausted, refcount-0 prefix blocks are
  evicted first; if that is not enough a victim sequence (lowest
  priority class, most recent arrival — mid-prefill sequences
  included) is preempted — blocks freed, request re-queued — and later
  re-admitted by recompute-prefill over ``prompt + tokens_emitted_so_
  far`` (token-identical to never having been preempted: the
  recomputed prefill's next-token logits equal the decode path's).

Shapes are static per compile: steps are padded to ``max_batch_size``
rows x ``prefill_chunk`` columns (idle rows/columns write to the
reserved null block), per the TPU static-shape rule.
"""

from __future__ import annotations

import math
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults, obs
from .backend import make_backend
from .block_pool import BlockPool, PoolExhausted  # noqa: F401 - re-export
from .packing import RoundLayout
from .paged_attention import (query_layout, query_tile_columns,
                              span_blocks, window_pairs)
from .prefix_cache import PrefixCache


# the registry names of a family's step programs (obs/profiler.py; the
# ``_sampled`` / ``_i8`` suffixes are added to these)
_PROGRAM_NAMES = {"step": "pw.decode_step", "mixed": "pw.mixed_step",
                  "chained": "pw.chained_decode"}


class EngineHungError(RuntimeError):
    """A device dispatch exceeded the engine watchdog deadline: the
    program is presumed wedged (driver hang, deadlocked collective, a
    chaos `hang`).  The engine treats it exactly like a failed dispatch
    — trace dump, then supervised restart when budget remains."""


class _WatchdogSync:
    """Deadline-bounded device->host sync.

    A blocked ``block_until_ready()`` or ``np.asarray(device_array)``
    cannot be interrupted from Python, so both waits of a sync run on a
    persistent helper thread and the engine thread waits with a timeout.
    On expiry the helper is ORPHANED (it parks on the wedged wait;
    daemon, so it never blocks exit) and the next sync spawns a fresh one
    — the restarted engine's new pool makes the wedged program's eventual
    result irrelevant."""

    def __init__(self, name: str = "pw-engine-watchdog"):
        self._name = name
        self._thread: threading.Thread | None = None
        self._inbox = None

    def _spawn(self) -> None:
        import queue as _q

        self._inbox = _q.Queue()
        self._thread = threading.Thread(
            target=self._loop, args=(self._inbox,), daemon=True,
            name=self._name,
        )
        self._thread.start()

    @staticmethod
    def _loop(inbox) -> None:
        while True:
            job = inbox.get()
            if job is None:
                return  # orphaned after a timeout: wind down
            fn, box = job
            try:
                box["result"] = fn()
            except BaseException as exc:  # noqa: BLE001 - re-raised below
                box["error"] = exc
            box["done"].set()

    def run(self, fn: Callable, timeout_s: float):
        if self._thread is None or not self._thread.is_alive():
            self._spawn()
        box: dict = {"done": threading.Event(), "result": None, "error": None}
        self._inbox.put((fn, box))
        if not box["done"].wait(timeout_s):
            # the helper is stuck inside fn(); abandon it (a None
            # sentinel stops it if fn ever returns) and fail typed
            self._inbox.put(None)
            self._thread = None
            raise EngineHungError(
                f"device dispatch still blocked after {timeout_s}s "
                "(watchdog deadline)"
            )
        if box["error"] is not None:
            raise box["error"]
        return box["result"]


class _RoundPhase(obs.phase):
    """One phase of an engine round (:func:`pathway_tpu.obs.phase`: the
    same name in a device trace's host plane and in the flight recorder,
    on the engine-run trace) that also adds its time to the pool's
    ``round_s`` counter under ``key``."""

    __slots__ = ("stats", "key")

    def __init__(self, stats, key: str, name: str, ctx, attrs: dict):
        super().__init__(name, ctx, **attrs)
        self.stats = stats
        self.key = key

    def __exit__(self, exc_type, exc, tb):
        super().__exit__(exc_type, exc, tb)
        self.stats.record_round(self.key, self.t1 - self.t0)


def _roomy(fn, *args):
    """``fn(*args)`` from a frame that needs a data-stack chunk of its own.

    CPython keeps a thread's frames in 16 KiB chunks and frees a chunk the
    moment its first frame returns, so a hot loop whose callees straddle a
    chunk boundary maps and unmaps a chunk on every call.  jax lowers the
    step programs ~100 frames beneath the engine loop: whether that loop
    straddles a boundary depends on how many locals the engine's own
    frames hold, and when it does the first dispatch of a program lowers
    in 3.8 s instead of 1.4 (PERF.md, PR 25: 3.5 s of set-up moved by a
    refactor that touched no device work).  This frame asks for 32,768
    slots, so it opens a 512 KiB chunk and leaves half of it to everything
    the engine loop calls: no boundary beneath it."""
    return fn(*args)


_roomy.__code__ = _roomy.__code__.replace(co_stacksize=1 << 15)


def _norm_sampling(s) -> tuple | None:
    """Normalize a sampling spec (dict or 4-tuple) to the canonical
    ``(temperature, top_k, top_p, seed)`` tuple the device programs
    consume, or None for pure greedy.  A spec with temperature=0 is KEPT
    (not folded to greedy): it still routes through the sampled program,
    where the per-row jnp.where pins it to the exact greedy tokens —
    that degeneration is part of the contract and stays testable."""
    if s is None:
        return None
    if isinstance(s, dict):
        return (float(s.get("temperature", 1.0)), int(s.get("top_k", 0)),
                float(s.get("top_p", 1.0)), int(s.get("seed", 0)))
    t, k, p, seed = s
    return (float(t), int(k), float(p), int(seed))


def _payload_extras(r) -> tuple[int, dict | None]:
    """Parse the optional tail of a request/payload tuple: after
    ``(prompt, max_new)`` may come a priority (int/str) and/or an options
    dict (``sampling``/``session``/``on_token``), in either slot —
    ``(p, n)``, ``(p, n, prio)``, ``(p, n, opts)`` and ``(p, n, prio,
    opts)`` all parse; existing 2/3-tuple callers are untouched."""
    priority: Any = 1
    opts = None
    for el in r[2:4]:
        if isinstance(el, dict):
            opts = el
        elif el is not None:
            priority = el
    return priority, opts


class _Request:
    __slots__ = ("prompt", "max_new", "priority", "stop_token", "emitted",
                 "index", "on_done", "on_error", "t_arrival", "span", "ctx",
                 "sampling", "session", "on_token", "t_admit", "t_chunk0",
                 "t_first", "t_requeue", "in_prefill", "n_chunks",
                 "n_rounds", "n_skipped", "n_first", "n_chains", "n_mixed")

    def __init__(self, prompt, max_new: int, *, priority: int = 1,
                 stop_token: int | None = None, index: int | None = None,
                 on_done: Callable | None = None,
                 on_error: Callable | None = None,
                 trace: tuple | None = None,
                 sampling=None, session: str | None = None,
                 on_token: Callable | None = None, emitted=None):
        self.prompt = list(prompt)
        self.max_new = int(max_new)
        self.priority = int(priority)
        self.stop_token = stop_token
        # `emitted` pre-populates already-produced tokens (fleet failover
        # re-admission): the request CONTINUES — admission recomputes
        # prompt + emitted and the emit-index seed schedule resumes at
        # len(emitted), so sampled output stays bit-identical across the
        # handoff.  max_new counts the TOTAL including these.
        self.emitted: list[int] = (
            [int(t) for t in emitted] if emitted else []
        )
        self.index = index
        self.on_done = on_done
        self.on_error = on_error
        # Round-15 serving-front fields: `sampling` is the normalized
        # (temperature, top_k, top_p, seed) tuple or None for greedy;
        # `session` names a KV tiering session (kvcache/tiering.py);
        # `on_token` streams each emitted token to the transport as it
        # lands (io/http.py SSE) — best-effort, exceptions are swallowed
        self.sampling = _norm_sampling(sampling)
        self.session = session
        self.on_token = on_token
        # request-scoped tracing: the root span is opened the moment the
        # engine learns about the request (its trace id is minted here
        # unless the serving path already carries one — e.g. an
        # X-Pathway-Trace header through scheduler submit()) and finished
        # at delivery; the lifecycle spans below parent under it
        self.span = obs.start_span(
            "engine.request", ctx=trace,
            prompt_tokens=len(self.prompt), max_new=self.max_new,
        )
        self.ctx = self.span.ctx
        self.t_arrival = self.span.t0
        # lifecycle marks (one span per transition, none per token):
        # arrival -> engine.pending -> admitted -> engine.prefill_wait ->
        # first chunk dispatched -> engine.prefill -> first token ->
        # engine.decode -> done.  The three before the first token tile
        # [t_arrival, t_first], so their sum IS the recorded TTFT.
        self.t_admit: float | None = None    # this admission
        self.t_chunk0: float | None = None   # its first chunk's dispatch
        self.t_first: float | None = None    # first token (TTFT closed)
        self.t_requeue: float | None = None  # preempted at
        self.in_prefill = False
        self.n_chunks = self.n_rounds = self.n_skipped = 0
        self.n_first = self.n_chains = self.n_mixed = 0

    def note_admitted(self, now: float) -> None:
        """Admission: ``engine.pending`` closes (pool-full waits included).
        A preempted request's later admissions are marked ``readmit``."""
        if self.t_admit is None:
            obs.record_span("engine.pending", self.t_arrival, now,
                            ctx=self.ctx)
        else:
            obs.record_span("engine.pending", self.t_requeue, now,
                            ctx=self.ctx, readmit=True)
            self.t_chunk0 = now  # a re-admission has no prefill_wait span
        self.t_admit = now
        self.in_prefill = True
        self.n_chunks = self.n_rounds = self.n_skipped = 0

    def note_chunk(self, t_disp: float) -> None:
        """A round that carries one of this request's prompt chunks was
        dispatched at ``t_disp``; the first closes ``engine.prefill_wait``."""
        if self.t_chunk0 is None:
            obs.record_span("engine.prefill_wait", self.t_admit, t_disp,
                            ctx=self.ctx)
            self.t_chunk0 = t_disp
        self.n_chunks += 1
        self.n_rounds += 1

    def note_prefill_end(self, now: float, **attrs) -> None:
        """``engine.prefill`` closes: at the first token after this
        admission, or (``preempted=True``) when a preemption cut it."""
        if self.t_chunk0 is None:  # cut before any chunk: it only waited
            obs.record_span("engine.prefill_wait", self.t_admit, now,
                            ctx=self.ctx)
            self.t_chunk0 = now
        if self.t_requeue is not None:
            attrs["readmit"] = True
        obs.record_span("engine.prefill", self.t_chunk0, now, ctx=self.ctx,
                        chunks=self.n_chunks, rounds=self.n_rounds,
                        rounds_skipped=self.n_skipped, **attrs)
        self.in_prefill = False

    def note_requeued(self, now: float) -> None:
        """Preempted (or restarted) back into the queue."""
        if self.in_prefill:
            self.note_prefill_end(now, preempted=True)
        self.t_requeue = now

    def finish(self, outcome: str) -> None:
        """Delivery: ``engine.decode`` (first token -> done) and the root
        close.  ``finish()`` of a span is idempotent, so a double delivery
        records once."""
        if self.span.t1 is not None:
            return
        attrs = {"outcome": outcome, "emitted": len(self.emitted)}
        if self.t_first is not None:
            obs.record_span("engine.decode", self.t_first,
                            time.perf_counter(), ctx=self.ctx,
                            tokens=len(self.emitted) - self.n_first,
                            chains=self.n_chains,
                            mixed_rounds=self.n_mixed)
            attrs["ttft_s"] = self.t_first - self.t_arrival
        self.span.finish(**attrs)


class _Active:
    __slots__ = ("seq_id", "req", "tokens", "n_filled", "n_diverted",
                 "prefix_keys", "wait_writer", "admitted", "emit_base")

    def __init__(self, seq_id: int, req: _Request):
        self.seq_id = seq_id
        self.req = req
        # chunked-prefill state: `tokens` is the full (trimmed) prompt
        # still being streamed in; None once prefill completes
        self.tokens: list[int] | None = None
        # the trimmed token list this sequence was admitted with — kept
        # past prefill completion (unlike `tokens`) so session suspension
        # (kvcache/tiering.py) knows which tokens the resident K/V covers
        self.admitted: list[int] | None = None
        # len(req.emitted) at admission: tokens emitted AFTER admission
        # are the ones whose K/V landed in THIS allocation's blocks (the
        # session-suspend coverage rule needs the split)
        self.emit_base = len(req.emitted)
        self.n_filled = 0
        self.n_diverted = 0  # positions < this are prefix-shared blocks
        self.prefix_keys: list | None = None
        # set when the shared leading blocks belong to another sequence
        # whose chunked prefill is STILL WRITING them: our chunks are
        # gated on that writer's progress (same-dispatch writes are
        # visible, so lockstep rows usually cost zero extra rounds)
        self.wait_writer: "_Active | None" = None


def build_engine(cfg, params, fallback_msg: str, logger_name: str,
                 engine_cls=None, **kwargs):
    """Construct a decode engine (:class:`PagedDecodeEngine` by default,
    or ``engine_cls``).

    The serial tier behind the callers (JaxDecoderLM.paged_engine,
    Int8DecoderHost.paged_engine) is the CPU's: on a CPU backend an
    engine that cannot be built logs at INFO and returns None, and the
    caller keeps its serial loop.  On a TPU backend the engine IS the
    serving path, so the failure raises with its cause attached — an HBM
    misfit or a compiler refusal must not read as a working server."""
    cls = engine_cls or PagedDecodeEngine
    try:
        return cls(cfg, params, **kwargs)
    except Exception as exc:  # noqa: BLE001 - the CPU's serial tier works
        what = "paged KV" if cls is PagedDecodeEngine else cls.__name__
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                f"{what} decode engine cannot be built on the TPU backend"
            ) from exc
        import logging

        logging.getLogger(logger_name).info(
            "%s decode engine unavailable (%s); %s", what, exc, fallback_msg,
        )
        return None


def resolve_tp(cfg, tp: int | None) -> int:
    """Resolve the tensor-parallel degree for a decoder config.

    ``tp=None`` (auto) picks all local devices on a TPU backend —
    stepping down to the largest degree that divides both ``n_heads``
    (the KV heads) and ``vocab_size`` — and 1 on the CPU fallback, where
    virtual shards share one core and collectives only add overhead.  An
    EXPLICIT ``tp`` is validated loudly instead
    (:func:`pathway_tpu.parallel.mesh.validate_decoder_tp`): requesting
    an impossible shard is a configuration error, not a preference."""
    n_dev = len(jax.devices())
    d_ff = getattr(cfg, "d_ff", None)
    if tp is None:
        if jax.default_backend() != "tpu":
            return 1
        from ..parallel.mesh import legal_tp_values

        legal = legal_tp_values(cfg.n_heads, cfg.vocab_size, n_dev, d_ff)
        return max(legal) if legal else 1
    tp = int(tp)
    from ..parallel.mesh import validate_decoder_tp

    validate_decoder_tp(cfg.n_heads, cfg.vocab_size, tp, n_dev, d_ff)
    return tp


class PagedDecodeEngine:
    """Batched greedy decoding through BlockPool + PrefixCache."""

    def __init__(self, cfg, params, *, num_blocks: int | None = None,
                 block_size: int | None = None,
                 max_blocks_per_seq: int | None = None,
                 max_batch_size: int | None = None,
                 seq_buckets=(64, 256, 1024),
                 prefix_sharing: bool = True, stop_token: int | None = None,
                 attn: str | None = None,
                 prefill_chunk: int | None = None, tp: int | None = None,
                 chain_steps: int | None = None,
                 quantize: str | None = None,
                 name: str = "paged_decoder",
                 watchdog_timeout_s: float | None = None,
                 max_restarts: int | None = None,
                 degrade_fn: Callable | None = None,
                 hbm_budget_bytes: int | None = None,
                 hbm_fit: str = "reject",
                 session_store=None,
                 speculative=None):
        from ..models.encoder import _resolve_dtype

        self.cfg = cfg
        self.stop_token = stop_token
        if attn is None:
            attn = "pallas" if jax.default_backend() == "tpu" else "reference"
        self.attn = attn
        # the block family (models/families.py), read from the
        # configuration: it says which math a round runs and what state a
        # sequence keeps; what it cannot do yet it refuses here, by name
        from ..models.families import step_family

        self.family = step_family(cfg)
        self.family.unsupported(
            tp=tp, quantize=quantize, speculative=speculative,
            session_store=session_store)
        # Round-9 tensor parallelism: tp > 1 lays the K/V pool out over a
        # (dp=1, tp) mesh (n_kv_heads/tp per shard — N x aggregate KV HBM)
        # and shard_maps every step program; tp == 1 keeps the EXACT
        # single-device round-8 programs (no mesh, no shard_map wrapper)
        # (an unasked tp takes every local chip; a family without sharded
        # programs has refused an asked one above and runs on one)
        self.tp = resolve_tp(cfg, tp) if self.family.tensor_parallel else 1
        self.mesh = None
        if self.tp > 1:
            from ..parallel.mesh import tp_mesh

            self.mesh = tp_mesh(self.tp)
        # Round-17: the engine dispatches the FUSED DECODE PLAN, not the
        # raw checkpoint pytree — Q/K/V folded into one gemm per layer,
        # the vocab head pre-transposed where that wins, and (with
        # quantize="int8") matmul weights quantized to int8 with
        # per-output-channel scales (models/decoder.plan_decode_params).
        # The plan is a pure function of (params, tp, quantize), so a
        # supervised restart or a fleet replica rebuilding from the same
        # checkpoint reproduces it — and its tokens — exactly.
        self.quantize = quantize
        self.base_params = params
        plan = self.family.plan(cfg, params, tp=self.tp, quantize=quantize)
        if self.tp > 1:
            from ..parallel.mesh import shard_decoder_params

            plan = shard_decoder_params(plan, self.mesh)
        self.params = plan
        # Round-14 pre-flight HBM fit (obs/memory.py): params + KV pool +
        # step-temp watermark must fit the budget BEFORE any allocation —
        # an unfittable (num_blocks, chain_steps, max_batch) is rejected
        # (or, with hbm_fit="clamp", its pool shrunk) at construction
        # with the budget and the largest fitting alternative named,
        # instead of OOMing at first dispatch.  With no budget resolvable
        # (the CPU fallback, no env override) the ledger is still
        # computed but nothing is enforced.
        # Round-17: shapes the caller leaves unset are CHOSEN from the
        # same ledger's what-ifs (obs/memory.choose_engine_config) — the
        # ledger sees the decode plan's own leaves, so an int8 plan's
        # weights are billed at their true byte width and the freed HBM
        # goes to the pool.  auto_config records what was chosen and why.
        from ..obs import memory as obs_memory

        if hbm_fit not in ("reject", "clamp", "off"):
            raise ValueError(
                f"hbm_fit={hbm_fit!r} is not one of 'reject', 'clamp', "
                "'off'"
            )
        auto = obs_memory.choose_engine_config(
            cfg, params=self.params, tp=self.tp,
            dtype=_resolve_dtype(cfg.dtype),
            budget_bytes=hbm_budget_bytes,
            reference_attn=(self.attn != "pallas"),
            prefill_chunk=prefill_chunk, num_blocks=num_blocks,
            block_size=block_size, max_batch_size=max_batch_size,
            chain_steps=chain_steps, seq_buckets=seq_buckets,
        )
        num_blocks = auto["num_blocks"]
        block_size = auto["block_size"]
        chain_steps = auto["chain_steps"]
        prefill_chunk = auto["prefill_chunk"]
        self.max_batch_size = int(auto["max_batch_size"])
        self.auto_config = {
            "chosen": auto["chosen"], "source": auto["source"],
            "num_blocks": num_blocks, "block_size": block_size,
            "max_batch_size": self.max_batch_size,
            "chain_steps": chain_steps,
            "chunk_source": auto["chunk_source"], "quantize": quantize,
        }
        # re-constructibility guarantee: the ledger below is built FRESH
        # from the resolved shapes (not reused from the chooser), so the
        # fit verdict the engine enforces is exactly what anyone
        # re-running hbm_plan with these numbers would get
        self.hbm_plan = obs_memory.hbm_plan(
            cfg, num_blocks=int(num_blocks), block_size=int(block_size),
            max_batch_size=self.max_batch_size,
            chain_steps=max(1, int(chain_steps)),
            prefill_chunk=prefill_chunk, tp=self.tp,
            dtype=_resolve_dtype(cfg.dtype), params=self.params,
            budget_bytes=hbm_budget_bytes,
            reference_attn=(self.attn != "pallas"),
        )
        if set(auto["chosen"]) - {"prefill_chunk"} \
                and self.hbm_plan.budget_bytes is not None:
            assert self.hbm_plan.fits, (
                "auto-chosen engine config must re-construct as fitting: "
                + self.hbm_plan.reject_message()
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
                    "engine %s does not fit HBM at num_blocks=%d; "
                    "clamping to %d (budget %.1fMB, %s)",
                    name, int(num_blocks), clamped,
                    self.hbm_plan.budget_bytes / 1048576,
                    self.hbm_plan.budget_source,
                )
                num_blocks = clamped
                self.hbm_plan = self.hbm_plan.with_(num_blocks=clamped)
            else:
                raise ValueError(self.hbm_plan.reject_message())
        # the shapes of a round, before the cache is built: a cache may
        # size itself by the most one round adds to a sequence
        bs = int(block_size)
        cap = min((num_blocks - 1) * bs, cfg.max_len)
        if max_blocks_per_seq is None:
            max_blocks_per_seq = -(-min(cfg.max_len, cap) // bs)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self.max_seq_tokens = min(self.max_blocks_per_seq * bs, cfg.max_len)
        # prompt buckets: block-aligned, capped at what one table can
        # span (the cap rounded DOWN to a block multiple); the largest
        # entry caps a prompt
        bucket_cap = max((self.max_seq_tokens // bs) * bs, bs)
        buckets = sorted({
            min(-(-b // bs) * bs, bucket_cap) for b in seq_buckets
        })
        self.seq_buckets = buckets or [bucket_cap]
        # chunk width: block-aligned (so chunk writes cover whole blocks
        # except the prompt's tail).  Left unset it was chosen above
        # (obs/memory.choose_engine_config) as wide as the ledger, the
        # prompt cap and the chip's ridge allow: a step streams every
        # weight whatever it carries, so its tokens ride on bytes paid
        # anyway, and a decode row that rides it waits that one longer
        # step in place of several short ones; two blocks where no budget
        # or no device roof resolves (the CPU)
        self.prefill_chunk = max(bs, min(-(-int(prefill_chunk) // bs) * bs,
                                         bucket_cap))
        # packed token budget of one ragged dispatch: every decode row
        # costs one token, the rest is chunk headroom — so the mixed
        # program's cost scales with B + chunk, never B x chunk
        self.mixed_tokens = self.max_batch_size + self.prefill_chunk
        # (what runs: the chosen or given chunk in whole blocks, no wider
        # than the largest bucket)
        self.auto_config["prefill_chunk"] = self.prefill_chunk
        # Round-10 device-resident multi-step decode: when the queue is
        # quiet (no pending admissions, no mid-prefill chunks) the engine
        # chains up to `chain_steps` greedy steps into ONE dispatch and
        # syncs once per chain on a [B, K] ids array — K adapts back to 1
        # the moment arrivals or preemption are pending, so TTFT and the
        # step-boundary admission semantics are unchanged
        self.chain_steps = max(1, int(chain_steps))
        # Round-13 failure domain: the pool's constructor args are kept so
        # a supervised restart can rebuild it from scratch (a failed or
        # hung dispatch may have consumed the donated K/V arrays).
        # Round-16: construction goes through the cache-backend factory
        # (backend.py) — the engine programs against the CacheBackend
        # contract, with BlockPool as its paged implementation.
        self._pool_kwargs = dict(
            num_blocks=num_blocks, block_size=block_size,
            dtype=_resolve_dtype(cfg.dtype), name=name, mesh=self.mesh,
            **self.family.cache_kwargs(
                cfg, self.max_batch_size,
                max(self.prefill_chunk, self.chain_steps)),
        )
        self.pool = make_backend(self.family.cache_kind, **self._pool_kwargs)
        # keys one grid step of the paged kernels spans (a lane tile: eight
        # blocks of 16), for ``kv_key_lanes`` on ``pw.round.build``
        lanes = self.pool.k.shape[-1] // self.tp
        self._span_keys = bs * span_blocks(bs, self.max_blocks_per_seq, lanes)
        # how a mixed step's ragged or latent calls lay the rows out on the
        # full pool (``query_layout``'s ``(P, N)``: N kernel rows of P query
        # columns; a latent pool has no V), and the query columns the live
        # tiles of a row cover, by its valid columns 0 .. chunk (the kernels'
        # own tile rule, on a shard's query heads and pool lanes), for
        # ``kv_query_slots`` and ``kv_query_tile_cols``
        B, C, H = self.max_batch_size, self.prefill_chunk, cfg.n_heads // self.tp
        hd, hd_v = self._pool_kwargs["head_dim"], \
            self._pool_kwargs.get("v_head_dim")
        dtype = self._pool_kwargs["dtype"]
        keys = self.max_blocks_per_seq * bs
        self._query_layout = query_layout(
            self.mixed_tokens, B, C, H, hd, lanes, dtype,
            Dv=0 if self.pool.v is None else self.pool.v.shape[-1] // self.tp,
            keys=keys, v_heads=self.family.attn_v_heads(cfg))
        cols = self._query_layout[0]
        self._query_tile_cols = query_tile_columns(
            np.arange(C + 1), C, H, hd, lanes, dtype, hd_v=hd_v, cols=cols)
        # a kernel row past the rows' pieces runs one column's first tile
        self._idle_tile_cols = int(query_tile_columns(
            [1], C, H, hd, lanes, dtype, hd_v=hd_v, cols=cols)[0])
        # on a windowed cache whose window is narrower than a chunk: the
        # window pool's geometry and layout as ``window_pairs`` takes them,
        # for ``kv_window_band_pairs`` / ``kv_window_span_pairs``
        self._window_pairs = None
        window = self.pool.window
        if window is not None and window < C:
            wk, wv, hd = self.pool.kw.shape[-1], self.pool.vw.shape[-1], \
                self.pool.head_dim
            self._window_pairs = dict(
                C=C, H=cfg.n_heads, hd=hd, D=wk, dtype=self.pool.kw.dtype,
                window=window,
                span=bs * span_blocks(bs, self.max_blocks_per_seq,
                                      math.gcd(wk, wv)),
                hd_v=None if self.pool.v_head_dim == hd
                else self.pool.v_head_dim,
                cols=query_layout(self.mixed_tokens, B, C, cfg.n_heads, hd,
                                  wk, self.pool.kw.dtype, Dv=wv,
                                  keys=keys)[0])
        # a cache that cannot share blocks (the hybrid one: a shared block
        # would skip the tokens that build the conv state) runs without a
        # prefix cache, whatever was asked for
        self._prefix_sharing = bool(prefix_sharing) \
            and self.pool.supports_prefix
        self.prefix = PrefixCache(self.pool) if self._prefix_sharing \
            else None
        # watchdog + supervised restart (Round-13): a dispatch blocked
        # past watchdog_timeout_s raises EngineHungError; any engine
        # failure with restart budget left rebuilds the pool and
        # re-admits every in-flight sequence by recompute over
        # prompt + emitted — token-identical to an uninterrupted run
        # (the same guarantee preemption-recompute already pins).  When
        # the budget is exhausted, requests fail with a typed
        # EngineFailedError — or complete through `degrade_fn(prompt,
        # n_remaining, emitted)`, the degrade-to-host-tier handoff.
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
        # Round-15 KV session tiering (kvcache/tiering.py SessionStore):
        # requests carrying a `session` id suspend their blocks to host
        # RAM at completion and resume by re-scatter at the next turn
        # (resume rides the chunk divert rule).
        self.session_store = session_store
        # Round-15 sampled program variants — built LAZILY on the first
        # sampled request (_sampled_programs), so a greedy-only workload
        # compiles exactly the greedy set and nothing else
        self._sampled: dict | None = None
        self._watchdog = (
            _WatchdogSync(f"pw-watchdog-{name}")
            if self.watchdog_timeout_s else None
        )
        # failure timestamp: set when a restartable failure is caught,
        # cleared by the first token emitted after the restart — the
        # failure -> first-recovered-token MTTR the bench reports
        self._t_failure: float | None = None
        # host-gap accounting: perf_counter of the last device->host sync
        # (the device has nothing queued past it) — the next dispatch
        # closes the window and counts it (see _note_sync/_note_dispatch)
        self._t_device_idle: float | None = None
        self._t_dispatch: float | None = None
        self._dispatch_kind = "step"
        self._run_ctx: tuple = (obs.new_trace_id(), 0)
        self._seq_counter = 0
        self._lock = threading.RLock()
        # chain key -> (writer _Active, physical block) for blocks an
        # in-flight chunked prefill is still writing: same-round arrivals
        # with a common prefix map these immediately (the HBM saving and
        # the compute skip) and lockstep their chunks behind the writer.
        # Per-run state (reset by _run_loop); the engine lock serializes
        # runs, so one map on self is safe
        self._inflight_prefix: dict = {}
        # the step programs are the family's (models/families.py); every
        # one samples INSIDE the jitted program, so only [B] int32 ids (not
        # [B, vocab] logits) cross the device->host boundary per round.
        # Two static shapes cover the whole workload — the (B,) decode
        # program and the (B, prefill_chunk) mixed program — so a
        # bucket-ladder workload compiles exactly twice (pinned by
        # tests/test_ragged_step.py's recompile guard); the chained
        # program's (B, chain_steps) shape is static too, so the whole
        # multi-step hot loop is ONE additional compile (K=1 rounds reuse
        # the plain step program)
        # (kind, sampled) -> the layout of that program's packed operand
        # (_layout): a round's arrays cross in one buffer, one transfer
        self._layouts: dict = {}
        greedy = self._programs(sampled=False)
        self._step = greedy["step"]
        self._mixed = greedy["mixed"]
        self._chained = greedy["chained"]
        # Round-18 speculative decoding (kvcache/speculative.py): a
        # drafter proposes up to K tokens per row, ONE ragged verify
        # dispatch checks them all, and the greedy accept rule keeps the
        # emitted stream token-identical to non-speculative decode.  The
        # verify program is built lazily on the first speculative round
        # (like the sampled variants), so speculative=off engines compile
        # nothing extra.  Resolution may bill a draft model's HBM against
        # this engine's ledger and must therefore run AFTER hbm_plan.
        self._verify = None
        from .speculative import resolve_speculative

        self._spec = resolve_speculative(speculative, self)

    @property
    def _prog_suffix(self) -> str:
        return "_i8" if self.quantize == "int8" else ""

    # -- the step programs: jitted here, named by the family -----------------
    def _programs(self, sampled: bool) -> dict:
        """One table of the family's step programs (``step``, ``mixed``,
        ``chained``), jitted with the cache's arrays donated: every step
        consumes them in place.  Round-14: every program registers in the
        device cost observatory — compile wall/provenance at first
        lowering, FLOPs/bytes introspection, and the dispatch->sync
        windows the sync sites attribute per program (obs/profiler.py).
        Round-17: int8 engines register under distinct ``_i8`` names, so
        the observatory ranks the two weight paths separately and
        CompileWatch pins each variant's compile count."""
        from ..obs.profiler import profiled_jit

        sfx = ("_sampled" if sampled else "") + self._prog_suffix
        table = self.family.programs(self.cfg, self.attn, self.mesh,
                                     sampled=sampled)
        return {
            kind: profiled_jit(f"{_PROGRAM_NAMES[kind]}{sfx}",
                               self._layout(kind, sampled).program(fn),
                               donate_argnums=donated)
            for kind, (fn, donated) in table.items()
        }

    def _layout(self, kind: str, sampled: bool = False) -> RoundLayout:
        """The layout of one step program's packed operand: each jitted
        program takes ``(params, *cache arrays, packed)`` and cuts the
        family's operands out of ``packed`` (kvcache/packing.py).  Fixed
        by the program's first round (:meth:`_h2d`)."""
        layout = self._layouts.get((kind, sampled))
        if layout is None:
            layout = self._layouts[(kind, sampled)] = RoundLayout()
        return layout

    def _sampled_programs(self) -> dict:
        """The pw.*_sampled programs (Round-15), built on FIRST use: each
        takes five (B,) arrays after its greedy twin's —
        temperature/top_k/top_p/seed/emit-index.  Greedy-only workloads
        never call this, so the sampled variants are the ONLY programs
        sampling adds — the zero-extra-compiles pin of the round."""
        if self._sampled is None:
            self._sampled = self._programs(sampled=True)
        return self._sampled

    def _sampling_arrays(self, entries, B: int):
        """Per-row sampling arrays (numpy; the caller transfers them) for
        one dispatch, or None when EVERY row is greedy (the round then
        uses the greedy program — no sampled compile).  ``entries``:
        (row_index, _Request) pairs.  Greedy rows riding a sampled
        dispatch get temperature=0, which the device head pins to the
        exact argmax."""
        if not any(req.sampling is not None for _i, req in entries):
            return None
        temp = np.zeros(B, np.float32)
        top_k = np.zeros(B, np.int32)
        top_p = np.ones(B, np.float32)
        seed = np.zeros(B, np.int32)
        emit = np.zeros(B, np.int32)
        for i, req in entries:
            emit[i] = len(req.emitted)
            if req.sampling is not None:
                t, k, p, s = req.sampling
                temp[i], top_k[i], top_p[i], seed[i] = t, k, p, s
        return (temp, top_k, top_p, seed, emit)

    # -- Round-18: speculative verify program ------------------------------
    def _verify_program(self):
        """The jitted verify program, built on FIRST speculative use: the
        family's ragged ``mixed`` program given a FLATTENED ``(B*C,)``
        logit index — one argmax per packed query position instead of one
        per row, so the host can compare every draft token against the
        target model's own next-token choice.  Shapes are static
        (``T = B * (k+1)`` tokens, ``C = k+1`` queries/row, ``B =
        max_batch_size``), so the program compiles exactly once per
        engine — the zero-recompile pin of the round."""
        if self._verify is None:
            from ..obs.profiler import profiled_jit

            fn, donated = self.family.programs(
                self.cfg, self.attn, self.mesh)["mixed"]
            self._verify = profiled_jit(
                f"pw.verify_step{self._prog_suffix}",
                self._layout("verify").program(fn),
                donate_argnums=donated)
        return self._verify

    def _call(self, prog, packed):
        """Run a step program on the cache's device arrays and the round's
        packed operand, and put back what it returns after its ids (the
        donated arrays, and for a hybrid cache the device's counters)."""
        pool = self.pool
        ids, *state = prog(self.params, *pool.device_state(), packed)
        pool.set_device_state(*state)
        return ids

    def _record_dispatch(self, prog, t_disp, t_end, items: int) -> None:
        """Attribute one dispatch->sync window to ``prog``'s registry
        record.  Guarded getattr: tests (and the bench's stall spies)
        re-wrap the step attributes with plain closures, which simply
        drop the attribution."""
        rec = getattr(prog, "record_dispatch", None)
        if rec is not None and t_disp is not None:
            rec(t_end - t_disp, t_end=t_end, items=items)

    # -- public API --------------------------------------------------------
    def generate(self, prompt_ids, max_new: int, *,
                 stop_token: int | None = None) -> list[int]:
        """Single-sequence convenience wrapper over :meth:`generate_batch`."""
        return self.generate_batch([(list(prompt_ids), max_new)],
                                   stop_token=stop_token)[0]

    def serve_batch(self, reqs, scheduler=None) -> list[list[int]]:
        """``batch_fn`` adapter for serve.scheduler.RequestScheduler: reqs
        are ``(prompt_ids, n_new)`` payloads — an optional third element
        carries the submit-time priority class into preemption decisions
        (host_decoder.generate_scheduled threads it through; payloads
        without one decode at NORMAL).  When the owning scheduler is
        passed, new arrivals are admitted into the in-flight batch at step
        boundaries via its ``poll_inflight`` hook — true continuous
        batching instead of batch-at-a-time coalescing."""
        import functools

        poll = None
        if scheduler is not None:
            def poll(n):
                items = []
                for w in scheduler.poll_inflight(n):
                    items.append((
                        # extras past (prompt, n_new) — the Round-15
                        # options dict (sampling/session/on_token) —
                        # ride along for _admit_arrivals to parse
                        (list(w.payload[0]), int(w.payload[1]))
                        + tuple(w.payload[2:4]),
                        int(w.priority),
                        functools.partial(scheduler.complete_inflight, w),
                        functools.partial(scheduler.fail_inflight, w),
                        # request-scoped trace context rides along so the
                        # engine's spans parent under the submit() root
                        getattr(w, "trace", None),
                    ))
                return items
        def _prio(v) -> int:
            try:
                return int(v)
            except (TypeError, ValueError):
                from ..serve.admission import Priority

                return int(Priority.parse(v))

        # request-scoped tracing: the scheduler exposes the batch's
        # waiters while batch_fn runs, so each payload's engine spans
        # join the trace its submit() minted (size-bucket padding repeats
        # the last payload past the waiter list — those get fresh traces)
        traces = []
        if scheduler is not None:
            traces = [
                getattr(w, "trace", None)
                for w in getattr(scheduler, "_inflight_waiters", ()) or ()
            ]

        def _norm(r):
            priority, opts = _payload_extras(r)
            base = (list(r[0]), int(r[1]), _prio(priority))
            return base + (opts,) if opts is not None else base

        return self.generate_batch(
            [_norm(r) for r in reqs],
            poll=poll,
            return_exceptions=True,
            traces=traces,
        )

    def generate_batch(self, requests, *, poll: Callable | None = None,
                       stop_token: int | None = None,
                       return_exceptions: bool = False,
                       traces: Sequence | None = None) -> list[list[int]]:
        """Greedy-decode a batch of ``(prompt_ids, max_new)`` requests (an
        optional third element is a serve.admission.Priority value; a
        trailing dict element carries per-request options —
        ``sampling=(temperature, top_k, top_p, seed)`` or the dict form,
        ``session=<id>`` for KV tiering, ``on_token=<callable>`` for
        per-token streaming).

        ``poll(n)``, when given, is called at every step boundary and may
        return up to ``n`` newly arrived ``(payload, priority, on_done,
        on_error)`` tuples to admit into the in-flight batch; their results
        flow through the callbacks instead of the returned list.

        ``return_exceptions=True`` places a per-request exception in that
        request's result slot instead of raising after the loop — one
        undecodable request must not throw away the rest of the batch's
        completed decodes (serve_batch relies on this; the scheduler maps
        exception results back to their individual callers).
        """
        stop = self.stop_token if stop_token is None else stop_token
        pending: deque[_Request] = deque()
        for i, r in enumerate(requests):
            prompt, max_new = r[0], r[1]
            priority, opts = _payload_extras(r)
            opts = opts or {}
            pending.append(_Request(
                prompt, max_new, priority=priority, stop_token=stop, index=i,
                trace=traces[i] if traces and i < len(traces) else None,
                sampling=opts.get("sampling"), session=opts.get("session"),
                on_token=opts.get("on_token"), emitted=opts.get("emitted"),
            ))
        results: list[Any] = [None] * len(requests)
        errors: list[tuple[int, BaseException]] = []
        outstanding = {"n": len(requests)}  # batch-origin work still open

        def deliver(req: _Request, err: BaseException | None = None) -> None:
            # delivery closes the request's root span (and its
            # engine.decode span); idempotent on a double delivery
            req.finish("error" if err is not None else "done")
            if req.on_done is None and req.on_error is None:
                outstanding["n"] -= 1
            if err is not None:
                if req.on_error is not None:
                    req.on_error(err)
                elif return_exceptions:
                    results[req.index] = err
                else:
                    errors.append((req.index, err))
            elif req.on_done is not None:
                req.on_done(list(req.emitted))
            else:
                results[req.index] = list(req.emitted)

        if poll is not None:
            # stop admitting NEW arrivals once every batch-origin request
            # has delivered: their callers are blocked on this function's
            # return, and a sustained arrival stream must not starve them
            # past the (bounded) tail of already-admitted work
            inner_poll = poll

            def poll(n):  # noqa: F811 - deliberate bounded wrapper
                return inner_poll(n) if outstanding["n"] > 0 else []

        with self._lock:
            running = _roomy(self._run_loop, pending, deliver, poll, stop)
            assert not running
        if self._spec is not None:
            # batch end: the controller's measured (drafter, K) aggregate
            # lands in the cost store as a pw.spec_tier row — the prior
            # speculative="auto" arbitrates from at the next engine build
            self._spec.flush()
        if errors:
            raise errors[0][1]
        return results

    # -- main loop ---------------------------------------------------------
    def _run_loop(self, pending, deliver, poll, stop):
        running: list[_Active] = []
        self._inflight_prefix.clear()
        # a dangling idle mark from the PREVIOUS batch's last sync would
        # bill the whole inter-batch wait to this batch's first dispatch
        # (and a dangling failure mark would record the inter-batch wall
        # clock as a bogus engine-recovery MTTR sample)
        self._t_device_idle = None
        self._t_dispatch = None
        self._t_failure = None
        # engine-run trace: device-busy / host-gap / sync spans for this
        # run group under one root (requests keep their own traces)
        run_span = obs.start_span(
            "engine.run", ctx=(obs.new_trace_id(), 0), pool=self.pool.name,
        )
        self._run_ctx = run_span.ctx
        attempts_left = self.max_restarts
        while True:
            try:
                self._loop_body(running, pending, deliver, poll, stop)
                break
            except BaseException as exc:
                self._inflight_prefix.clear()
                # supervised restart (Round-13): with budget left, a
                # failed/hung dispatch rebuilds the pool and re-admits
                # every in-flight sequence by recompute over
                # prompt + emitted — the exact preemption-recompute path,
                # so recovered output is token-identical to an
                # uninterrupted run
                if attempts_left > 0 and isinstance(exc, Exception):
                    attempts_left -= 1
                    try:
                        obs.recorder().dump_on_failure("engine_failure", exc)
                    except Exception:  # noqa: BLE001
                        pass
                    err_name, err_text = type(exc).__name__, str(exc)
                    # the traceback's frames hold locals referencing the
                    # dead pool; drop it so the rebuild can release the
                    # old K/V arrays (and reclaim the pool's stats name)
                    exc.__traceback__ = None
                    try:
                        self._restart(
                            running, pending, err_name, err_text,
                            attempt=self.max_restarts - attempts_left,
                        )
                        continue
                    except BaseException as rexc:  # noqa: BLE001
                        exc = rexc  # rebuild failed: budget is moot
                # always-on flight recorder: the run span is closed with
                # its error FIRST (so the dump shows the failed engine
                # run), then the dump is written BEFORE the failure
                # deliveries so _wrap_failure attaches THIS failure's
                # dump path to every typed error (the 503 body points an
                # operator at the right file) — only the per-request
                # delivery-outcome spans land after the dump
                run_span.finish(error=type(exc).__name__)
                try:
                    obs.recorder().dump_on_failure("engine_failure", exc)
                except Exception:  # noqa: BLE001 - never mask the error
                    pass
                self._fail_all(running, pending, deliver, exc)
                if not isinstance(exc, Exception):
                    raise  # KeyboardInterrupt/SystemExit must propagate
                # every request was delivered a per-request outcome above
                # (typed EngineFailedError, or a degrade completion) —
                # batch-origin callers see the typed error through the
                # normal errors/results path, so re-raising the raw
                # exception here would only destroy successfully degraded
                # results
                break
        run_span.finish()
        return running

    # -- failure domain (Round-13) -----------------------------------------
    def _restart(self, running, pending, err_name: str, err_text: str,
                 attempt: int) -> None:
        """Rebuild the failure domain: fresh BlockPool + PrefixCache
        (the old pool's donated arrays may be consumed or backing a
        wedged program), then every in-flight request rejoins the queue
        carrying its emitted tokens — admission recomputes prefill over
        prompt + emitted, token-identical by the preemption guarantee."""
        import logging

        self._t_failure = time.perf_counter()
        t0 = self._t_failure
        survivors = [act.req for act in running]
        running.clear()
        # requeue the survivors BEFORE attempting the rebuild: if the
        # rebuild itself fails (e.g. device OOM while the wedged old
        # program still pins HBM), the terminal _fail_all must still see
        # every in-flight request — orphaning them would hang their
        # waiters until timeout
        for req in survivors:
            self._requeue(pending, req)
        # release the dead pool BEFORE constructing its replacement so
        # the metrics name (and its monotonic counters) re-attach
        self.prefix = None
        old_pool = self.pool
        old_pool.retire()
        try:
            self.pool = None
            self.pool = make_backend(self.family.cache_kind,
                                     **self._pool_kwargs)
        except BaseException:
            # keep a pool object attached: the terminal path still reads
            # .stats (degrade accounting) and frees sequences through it
            self.pool = old_pool
            raise
        self.prefix = (
            PrefixCache(self.pool) if self._prefix_sharing else None
        )
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
            "engine restart #%d after %s: %s — pool rebuilt in %.3fs, "
            "re-admitting %d in-flight sequence(s) by recompute",
            attempt, err_name, err_text, rebuild_s, len(survivors),
        )

    def _fail_all(self, running, pending, deliver, exc: BaseException) -> None:
        """Terminal failure: fail (or degrade) EVERYTHING still in
        flight before propagating — requests admitted via poll_inflight
        are owned by this engine, and leaving their waiters unset would
        hang submit() callers until timeout with a misleading deadline
        error.  With a ``degrade_fn``, each request is handed to the
        cheaper tier instead (the serve degrade hook); waiters that
        cannot degrade fail with a typed EngineFailedError carrying the
        flight-recorder dump path."""
        # terminal: no recovery is coming, so no first-token may close a
        # recovery window against this failure timestamp
        self._t_failure = None
        for act in running:
            try:
                self.pool.free_sequence(act.seq_id)
            except Exception:  # noqa: BLE001 - best-effort cleanup
                pass
        reqs = [act.req for act in running] + list(pending)
        running.clear()
        pending.clear()
        wrapped = self._wrap_failure(exc)
        # degrade only on real engine failures: a KeyboardInterrupt /
        # SystemExit must propagate promptly, not block on minutes of
        # serial host decode first
        degrade = self.degrade_fn is not None and isinstance(exc, Exception)
        for req in reqs:
            if degrade and self._try_degrade(req, deliver):
                continue
            deliver(req, wrapped)

    def _wrap_failure(self, exc: BaseException):
        from ..serve.admission import EngineFailedError

        dump = getattr(obs.recorder(), "last_dump_path", None)
        budget = (
            f" after {self.max_restarts} restart(s)" if self.max_restarts
            else ""
        )
        return EngineFailedError(
            f"decode engine failed{budget}: {type(exc).__name__}: {exc}",
            retry_after_s=5.0, trace_id=self._run_ctx[0], dump_path=dump,
        )

    def _try_degrade(self, req: _Request, deliver) -> bool:
        """Degrade-to-host-tier handoff: complete one stranded request
        through ``degrade_fn(prompt, n_remaining, emitted)`` (the serial
        tier).  Tokens already emitted by the dead engine are kept —
        the degrade tier continues the sequence, it does not restart it.

        A degrade_fn accepting a ``req`` keyword gets the full _Request
        (the fleet failover hook: a peer replica needs the sampling spec,
        session id and streaming callback to continue the request
        token-identically); such a hook forwards streaming itself, so
        on_token is NOT re-fired for the tokens it returns."""
        import inspect
        import logging

        try:
            remaining = req.max_new - len(req.emitted)
            if remaining > 0 and (
                req.stop_token is None
                or req.stop_token not in req.emitted
            ):
                takes_req = False
                try:
                    takes_req = "req" in inspect.signature(
                        self.degrade_fn
                    ).parameters
                except (TypeError, ValueError):
                    pass
                if takes_req:
                    extra = self.degrade_fn(
                        list(req.prompt), remaining, list(req.emitted),
                        req=req,
                    )
                else:
                    extra = self.degrade_fn(
                        list(req.prompt), remaining, list(req.emitted)
                    )
                for t in list(extra)[:remaining]:
                    req.emitted.append(int(t))
                    if not takes_req and req.on_token is not None:
                        try:
                            req.on_token(int(t))
                        except Exception:  # noqa: BLE001
                            pass
                    if req.stop_token is not None \
                            and int(t) == req.stop_token:
                        break  # same EOS truncation as _scan_chain
        except Exception as dexc:  # noqa: BLE001 - fall back to failing
            logging.getLogger(__name__).warning(
                "degrade tier failed for a stranded request (%s); "
                "failing it typed instead", dexc,
            )
            return False
        # delivery happens OUTSIDE the try: a raising on_done callback
        # must propagate (as on the normal path), not convert an
        # already-delivered success into a second on_error delivery
        obs.event("engine.degraded", ctx=req.ctx, emitted=len(req.emitted))
        self.pool.stats.record_engine_degrade()
        deliver(req)
        return True

    def _admit_arrivals(self, running, pending, poll, stop) -> None:
        """Step-boundary admission of newly arrived requests into the
        pending queue (the chained path also calls this in its overlap
        window, so arrivals discovered mid-chain adapt the NEXT round
        back to K=1)."""
        if poll is None or len(running) >= self.max_batch_size:
            return
        budget = self.max_batch_size - len(running) - len(pending)
        for item in (poll(budget) if budget > 0 else ()):
            payload, priority, on_done, on_error = item[:4]
            # an optional 5th element is the request's trace context
            # (serve_batch's poll wrapper supplies it; bare 4-tuples from
            # direct poll= callers mint a fresh trace at admission)
            trace = item[4] if len(item) > 4 else None
            _p, opts = _payload_extras(payload)
            opts = opts or {}
            # priority-ordered like _requeue: an urgent arrival
            # must not queue behind a lower-priority victim
            self._requeue(pending, _Request(
                payload[0], payload[1], priority=priority,
                stop_token=stop, on_done=on_done, on_error=on_error,
                trace=trace, sampling=opts.get("sampling"),
                session=opts.get("session"), on_token=opts.get("on_token"),
                emitted=opts.get("emitted"),
            ))

    def _loop_body(self, running, pending, deliver, poll, stop):
        while pending or running:
            with self._phase("pw.round.admit") as ph:
                self._admit_arrivals(running, pending, poll, stop)
                admitted = 0
                while pending and len(running) < self.max_batch_size:
                    req = pending[0]
                    t0a = time.perf_counter()
                    status = self._try_admit(req, running, pending, deliver)
                    if status != "wait":
                        # "wait" recurs every round while the pool is
                        # full — recording each retry would flood the ring
                        # (and the request's trace) with duplicates; the
                        # blocked time is inside engine.pending
                        obs.record_span("engine.admission", t0a,
                                        time.perf_counter(), ctx=req.ctx,
                                        outcome=status)
                    if status == "wait":
                        break
                    admitted += status == "admitted"
                    pending.popleft()
                ph.set(admitted=admitted, pending=len(pending))
            if not running:
                # nothing admitted implies nothing pending either:
                # _try_admit only returns "wait" while others run, and the
                # admission loop above drains pending otherwise
                break
            self._step_round(running, pending, deliver, poll, stop)
        return running

    def _readmit_len(self, req: _Request) -> int:
        """How many tokens _try_admit would prefill for this request right
        now (its capacity-trim rule, before the bucket cap)."""
        total = len(req.prompt) + len(req.emitted)
        remaining = req.max_new - len(req.emitted)
        if total + remaining > self.max_seq_tokens:
            return max(self.max_seq_tokens - remaining, 1)
        return total

    def _requeue(self, pending, req: _Request) -> None:
        """Put a preemption victim back in line by PRIORITY class: ahead
        of strictly-lower-priority work, behind equal-or-higher — a
        victim must not leapfrog an urgent arrival (priority inversion)
        nor lose its place to later same-class requests."""
        if req.t_admit is not None:  # a victim, not a new arrival
            req.note_requeued(time.perf_counter())
        idx = next(
            (i for i, r in enumerate(pending) if r.priority > req.priority),
            len(pending),
        )
        pending.insert(idx, req)

    def _note_sync(self) -> None:
        """A device->host sync just returned with nothing queued behind
        it: the device is idle until the next dispatch.  Every dispatch
        site calls :meth:`_note_dispatch` to close (and count) the
        window, so ``pathway_kv_host_gap_seconds_total`` measures exactly
        the host-on-critical-path time the device spends waiting; on the
        double-buffered chained path the bookkeeping that runs AFTER the
        next dispatch is correctly excluded.  The counter is all there
        is: the round's phases (``pw.round.deliver`` + ``admit`` +
        ``build`` between the two calls, ``h2d`` + the program call +
        ``sync`` + ``d2h`` from dispatch to here) are the spans."""
        self._t_device_idle = time.perf_counter()
        self.pool.after_sync()

    def _note_dispatch(self, kind: str = "step") -> None:
        """A dispatch begins: closes the host-gap window :meth:`_note_sync`
        opened, and keeps the instant (``_t_dispatch``: the prefill-chunk
        and chain spans start there) and the program's ``kind`` (what the
        round's ``pw.round.sync`` / ``pw.round.d2h`` will say)."""
        now = time.perf_counter()
        if self._t_device_idle is not None:
            self.pool.stats.record_host_gap(now - self._t_device_idle)
            self._t_device_idle = None
        self._t_dispatch = now
        self._dispatch_kind = kind

    def _emit(self, req: _Request, token_id: int) -> None:
        """Record one emitted token; the FIRST token of a request closes
        its time-to-first-token window (preemption does not reopen it —
        a victim re-admitted mid-decode already emitted)."""
        req.emitted.append(token_id)
        if req.on_token is not None:
            # per-token streaming (Round-15): best-effort — a broken
            # stream consumer must not take the whole batch down with it
            try:
                req.on_token(token_id)
            except Exception:  # noqa: BLE001
                import logging

                logging.getLogger(__name__).warning(
                    "on_token callback failed; continuing decode",
                    exc_info=True,
                )
        first = len(req.emitted) == 1
        if req.in_prefill or first:
            # one clock reading closes engine.prefill and the TTFT window,
            # so pending + prefill_wait + prefill is the recorded value
            now = time.perf_counter()
            if req.in_prefill:
                req.note_prefill_end(now)
            if req.t_first is None:
                req.t_first, req.n_first = now, len(req.emitted)
            if first:
                self.pool.stats.record_ttft(now - req.t_arrival)
        if self._t_failure is not None:
            # first token after a supervised restart: the
            # failure -> first-recovered-token window (engine_restart_s)
            self.pool.stats.record_engine_recovery(
                time.perf_counter() - self._t_failure
            )
            self._t_failure = None

    def _phase(self, name: str, **attrs) -> _RoundPhase:
        """A phase of this run's rounds: ``pw.round.<key>``, or a program
        call under the program's own ``pw.*`` name (key ``dispatch``)."""
        key = name[9:] if name.startswith("pw.round.") else "dispatch"
        return _RoundPhase(self.pool.stats, key, name, self._run_ctx, attrs)

    def _h2d(self, kind: str, sampled: bool, host: tuple):
        """The step's numpy arrays to the device in ONE transfer, packed
        by the layout of the program they are for (``pw.round.h2d``:
        ``arrays`` the arrays a round is made of, ``transfers`` what
        crossed)."""
        self.pool.stats.record_h2d(len(host), 1)
        with self._phase("pw.round.h2d", arrays=len(host),
                         transfers=1) as ph:
            packed = self._layout(kind, sampled).pack(host)
            ph.set(bytes=packed.nbytes)
            return jnp.asarray(packed)

    def _sync_host(self, dev_array) -> np.ndarray:
        """Device->host sync as two sibling phases on the engine thread,
        each watchdog-bounded when configured.  ``pw.round.sync`` lasts
        from the program call's return until the result is ready ON THE
        DEVICE: the launch, the program's own device time and the wake-up.
        The `engine.sync` fault point lives inside it, so a chaos `hang`
        wedges exactly where a stuck device program would; ``perf_ns`` is
        the clock anchor (this clock's reading at the annotation's start
        on a device trace's clock).  ``pw.round.d2h`` is the pull of the
        ready result into numpy: the readback's tail on a mixed or step
        round, near nothing on the chained path, whose copy started at
        dispatch.  Both say the ``kind`` of the program they wait for."""
        kind = self._dispatch_kind

        def ready():
            faults.fire("engine.sync")
            dev_array.block_until_ready()

        with self._phase("pw.round.sync", perf_ns=time.perf_counter_ns(),
                         kind=kind):
            self._bounded(ready)
        with self._phase("pw.round.d2h", kind=kind, bytes=dev_array.nbytes):
            return self._bounded(lambda: np.asarray(dev_array))

    def _bounded(self, wait: Callable):
        """``wait()``, under the watchdog's deadline when one is set."""
        if self._watchdog is None:
            return wait()
        return self._watchdog.run(wait, self.watchdog_timeout_s)

    # -- admission ---------------------------------------------------------
    def _try_admit(self, req: _Request, running, pending, deliver) -> str:
        """Allocate one request.  Returns "admitted", "done" (a request
        for no tokens), "failed" (undecodable — delivered as an error), or
        "wait" (pool full while other sequences run).

        Admission allocates the sequence's blocks and queues the prompt
        for streaming through the ragged mixed step — NO device work
        happens here, so an arrival can never stall the in-flight
        batch."""
        if req.max_new - len(req.emitted) <= 0:
            # zero-token request: the dense path returns nothing, so must we
            deliver(req)
            return "done"
        if self.family.greedy_only and req.sampling is not None:
            deliver(req, ValueError(
                f"the {self.family.name} block family decodes greedily: "
                "it has no sampled step programs"))
            return "failed"
        tokens = req.prompt + req.emitted
        limit = self.max_seq_tokens
        remaining = req.max_new - len(req.emitted)
        if len(tokens) + remaining > limit:
            # keep the most recent context that still leaves room for every
            # new token (JaxDecoderLM.generate's trimming rule)
            tokens = tokens[-max(limit - remaining, 1):]
        if len(tokens) > self.seq_buckets[-1]:
            # prefill must fit the largest bucket even when the table could
            # span more (max_seq_tokens bounds the TOTAL, growth included)
            tokens = tokens[-self.seq_buckets[-1]:]
        if not tokens:
            tokens = [4]
        n = len(tokens)
        self._seq_counter += 1
        seq_id = self._seq_counter
        # Round-15 session tiering: a session-tagged
        # request resumes its suspended K/V from the host tier instead of
        # going through the prefix cache — sessions are PRIVATE
        # continuity (one conversation's history), not shared prefixes,
        # so the cross-request sharing machinery (and its in-flight
        # writer gates) is deliberately bypassed for them
        sess_entry = None
        use_session = (
            req.session is not None and self.session_store is not None
        )
        if use_session:
            sess_entry = self.session_store.match(req.session, tokens)
        state = None
        attempt = 0
        writer = None
        while state is None:
            shared, keys = ([], [])
            writer = None
            if self.prefix is not None and not use_session:
                # sharing is safe even when it covers EVERY prompt block:
                # full blocks are never decode-write targets (appends open
                # a fresh block at the boundary) and chunk writes for the
                # shared positions are diverted to the null block.  Only
                # the first match records hit/miss stats (below, in-flight
                # blocks included) — eviction retries re-match the same
                # admission
                shared, keys = self.prefix.match(tokens, record=False)
                # extend the match into blocks an IN-FLIGHT chunked
                # prefill is still writing: the physical sharing (and
                # compute skip) starts NOW; our chunks gate on the
                # writer's progress.  One writer only — chaining
                # across writers would need a multi-way gate for
                # marginal benefit
                for key in keys[len(shared):]:
                    ent = self._inflight_prefix.get(key)
                    if ent is None or (
                        writer is not None and ent[0] is not writer
                    ):
                        break
                    writer = ent[0]
                    shared.append(ent[1])
                if attempt == 0:
                    hits = len(shared)
                    if hits:
                        self.pool.stats.record_prefix_hit(hits)
                    if len(keys) - hits:
                        self.pool.stats.record_prefix_miss(
                            len(keys) - hits
                        )
            attempt += 1
            try:
                state = self.pool.allocate(
                    seq_id, n, shared_blocks=shared, priority=req.priority,
                )
            except PoolExhausted as exc:
                freed = 0
                if self.prefix is not None:
                    freed = self.prefix.evict(exc.needed - exc.free)
                if freed:
                    continue  # re-match: eviction may have dropped `shared`
                if running:
                    return "wait"
                # nothing running and nothing evictable: every engine-owned
                # sequence is freed, so preempt() can only reclaim a stray
                # registered through direct pool use — retry if it did
                if self.pool.preempt() is None:
                    deliver(req, RuntimeError(
                        f"KV pool ({self.pool.num_blocks - 1} blocks of "
                        f"{self.pool.block_size}) cannot hold a "
                        f"{n}-token sequence"
                    ))
                    return "failed"
        act = _Active(seq_id, req)
        act.tokens = tokens
        act.admitted = tokens
        if use_session:
            resident = 0
            if sess_entry is not None:
                resident = self.session_store.resume_into(
                    self.pool, sess_entry, state.block_ids
                )
            # resumed positions ride the chunk divert rule exactly
            # like prefix-shared blocks: their K/V is already
            # resident, so chunk writes for pos < n_diverted go to
            # the null block — but the prompt's LAST token always
            # recomputes to produce the next-token logits
            act.n_filled = min(resident, n - 1)
            act.n_diverted = resident
            req.note_admitted(time.perf_counter())
            running.append(act)
            return "admitted"
        # prefix-shared leading blocks need no recompute: their K/V
        # is already (or will be, gated on the writer) resident, so
        # chunking starts after them, but at least the prompt's LAST
        # token must run to produce the next-token logits
        shared_tokens = len(shared) * self.pool.block_size
        act.n_filled = min(shared_tokens, n - 1)
        act.n_diverted = shared_tokens
        act.wait_writer = writer
        # cache registration happens only when the last chunk lands
        # (K/V written); until then our OWN unshared full blocks go
        # into the in-flight map so same-round arrivals can share
        # them under the progress gate
        act.prefix_keys = keys
        if self.prefix is not None:
            for key, blk in zip(keys[len(shared):],
                                state.block_ids[len(shared):len(keys)]):
                self._inflight_prefix.setdefault(key, (act, blk))
        req.note_admitted(time.perf_counter())
        running.append(act)
        return "admitted"

    def _release_seq(self, act: _Active) -> None:
        """Completion-time release of a finished sequence's blocks.  A
        session-tagged request (session_store attached)
        SUSPENDS instead: its context K/V — the admitted tokens plus
        every emitted-and-fed-back token — is copied to the host tier so
        the session's next turn resumes by re-scatter rather than
        recompute.  The final emitted token was never written to the
        pool (it is output, not input), so coverage stops one short."""
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
                    "session suspend failed for %r; freeing blocks",
                    req.session, exc_info=True,
                )
        self.pool.free_sequence(act.seq_id)

    def _is_done(self, req: _Request, seq_id: int) -> bool:
        if len(req.emitted) >= req.max_new:
            return True
        if req.stop_token is not None and req.emitted[-1] == req.stop_token:
            return True
        # capacity: the next token's position must fit the table + pos_embed
        return self.pool.sequence(seq_id).n_tokens >= self.max_seq_tokens

    # -- stepping ----------------------------------------------------------
    def _step_round(self, running, pending, deliver, poll=None,
                    stop=None) -> None:
        """One engine step = ONE device program over the ragged in-flight
        batch: decode rows (a reserved write slot each) plus prefill-chunk
        runs sharing the ``mixed_tokens`` budget.  Rounds with no chunk in
        flight dispatch the cheaper 1-token-per-row program — or, when the
        queue is quiet, the Round-10 CHAINED program: up to ``chain_steps``
        greedy steps per dispatch with host bookkeeping overlapped against
        device execution (one sync per chain, not per token)."""
        if self._spec is not None and self._spec_round(running, pending,
                                                       deliver):
            return
        if self._can_chain(running, pending):
            if self._chained_rounds(running, pending, deliver, poll, stop):
                return
            if not running:
                return  # every row was preempted into pending; re-admit
        with self._phase("pw.round.build") as ph:
            victims: list[_Active] = []
            reserved = self._reserve_slots(running, pending, victims)
            if victims:
                # a preempted mid-prefill WRITER strands any sharer still
                # reading through its half-written blocks — cascade those
                # back to the queue too (recompute restores them)
                self._cascade_preempt(victims, running, pending)
            # chunk membership is decided AFTER slot reservation:
            # reservation may preempt a mid-prefill sequence, which must
            # then not be dispatched this round
            chunks = [a for a in running if a.tokens is not None]
            if chunks:
                step = self._build_mixed(reserved, chunks, ph)
            elif reserved:
                step = self._build_decode(reserved, ph)
            else:
                ph.set(kind="none", rows=0, tokens=0, budget=0, waiting=0)
        if chunks:
            self._mixed_round(step, running, deliver)
        elif reserved:
            self._decode_round(step, reserved, running, deliver)

    # -- Round-18: speculative draft + verify rounds -----------------------
    def _spec_round(self, running, pending, deliver) -> bool:
        """One speculative round: the drafter proposes up to K tokens per
        decode row, ONE ragged verify dispatch pushes every row's last
        emitted token plus its proposals through the mixed-step kernel
        (C = k+1 queries/row, per-position argmax), and the greedy accept
        rule emits the longest prefix where draft == target argmax plus
        the free bonus token — TOKEN-IDENTICAL to non-speculative decode.
        Unlike the chain, this round stays multi-token while arrivals are
        PENDING: admission still happens at step boundaries (the loop
        body polls before every round), so TTFT semantics are unchanged
        and only this round's bounded latency is added.

        Returns True when a verify dispatch ran; False falls through to
        the chain/step/mixed paths — no decode rows, chunk rows in
        flight, sampled rows (they ride K=1 unchanged this round), or no
        usable proposals (the zero-accept worst case thereby degrades to
        plain chained throughput, not below it)."""
        with self._phase("pw.round.build", kind="verify") as ph:
            built = self._build_verify(running, pending, ph)
        if not isinstance(built, tuple):
            return built
        return self._verify_round(*built, running, deliver)

    def _build_verify(self, running, pending, ph):
        """Draft, reserve and pack one verify dispatch (inside the
        caller's ``pw.round.build``).  False: no verify this round (see
        :meth:`_spec_round`); True: every row was preempted into pending;
        else ``(host arrays, rows, prop_of)``."""
        spec = self._spec
        if any(a.tokens is not None for a in running):
            return False  # mid-prefill chunks stream through mixed
        if any(a.req.sampling is not None for a in running):
            return False
        acts = list(running)
        if not acts:
            return False
        pool = self.pool
        # per-row draft budget BEFORE reservation: a row needs k_i + 1
        # slots (proposals + the bonus token), and never more than its
        # remaining emit/capacity budget
        k_of: dict[int, int] = {}
        ctx_of: dict[int, list[int]] = {}
        ks = []
        for a in acts:
            seq = pool.sequence(a.seq_id)
            rem = min(a.req.max_new - len(a.req.emitted),
                      self.max_seq_tokens - seq.n_tokens)
            k_of[id(a)] = max(0, min(spec.k, rem - 1))
            base_ctx = (list(a.admitted) if a.admitted is not None
                        else list(a.req.prompt))
            ctx_of[id(a)] = base_ctx + [
                int(t) for t in a.req.emitted[a.emit_base:]
            ]
            ks.append(k_of[id(a)])
        if faults.fire("engine.draft") == "drop":
            return False  # chaos: drafting suppressed, plain paths serve
        t_d0 = time.perf_counter()
        proposals = spec.propose_batch([ctx_of[id(a)] for a in acts], ks)
        obs.record_span("engine.draft", t_d0, time.perf_counter(),
                        ctx=self._run_ctx)
        prop_of = {
            id(a): [int(t) for t in p][:k_of[id(a)]]
            for a, p in zip(acts, proposals)
        }
        if not any(prop_of.values()):
            return False  # nothing proposed: fall through (chain/step)
        victims: list[_Active] = []
        reserved = self._reserve_slots(
            running, pending, victims,
            k_for=lambda a: len(prop_of.get(id(a), ())) + 1,
        )
        if victims:
            self._cascade_preempt(victims, running, pending)
        if not reserved:
            return True  # every row preempted into pending; re-admit
        # token-packed verify arrays: row i owns packed positions
        # [i*C, i*C + nv_i) — static T = B*C regardless of acceptance,
        # so the verify program never respecializes.  Pad rows/tokens
        # follow the mixed-round convention: zeros -> the null block 0
        # garbage sink, results discarded host-side.
        C = spec.k + 1
        B = self.max_batch_size
        T = B * C
        NB = self.max_blocks_per_seq
        tokens = np.zeros(T, np.int32)
        positions = np.zeros(T, np.int32)
        sb = np.zeros(T, np.int32)
        so = np.zeros(T, np.int32)
        row_tables = np.zeros((B, NB), np.int32)
        row_start = np.zeros(B, np.int32)
        row_nvalid = np.ones(B, np.int32)
        row_token_idx = np.zeros((B, C), np.int32)
        tok_row = np.zeros(T, np.int32)
        tok_col = np.zeros(T, np.int32)
        logit_idx = np.zeros(T, np.int32)
        rows: list[tuple[_Active, int, int]] = []
        for i, (act, slots) in enumerate(reserved):
            nv = len(slots)
            base = i * C
            seq = pool.sequence(act.seq_id)
            prop = prop_of.get(id(act), [])
            tokens[base:base + nv] = [act.req.emitted[-1]] + prop
            start = seq.n_tokens - nv  # extend_slots already advanced
            positions[base:base + nv] = np.arange(start, start + nv)
            for t, (blk, off) in enumerate(slots):
                sb[base + t] = blk
                so[base + t] = off
            row_tables[i, : len(seq.block_ids)] = seq.block_ids
            row_start[i] = start
            row_nvalid[i] = nv
            cols = np.minimum(np.arange(C), nv - 1)
            row_token_idx[i, :] = base + cols
            run = np.arange(base, base + nv)
            tok_row[run] = i
            tok_col[run] = np.arange(nv)
            logit_idx[base:base + C] = base + cols
            rows.append((act, i, nv))
        ph.set(rows=len(rows), tokens=sum(nv for _a, _r, nv in rows),
               budget=T, waiting=0)
        return (tokens, positions, row_tables, row_start, row_nvalid,
                row_token_idx, tok_row, tok_col, sb, so, logit_idx), \
            rows, prop_of

    def _verify_round(self, host, rows, prop_of, running, deliver) -> bool:
        spec = self._spec
        pool = self.pool
        C = spec.k + 1
        faults.fire("engine.dispatch.verify")
        self._note_dispatch("verify")
        t_disp = self._t_dispatch
        dev = self._h2d("verify", False, host)
        prog = self._verify_program()
        with self._phase("pw.verify_step"):
            ids = self._call(prog, dev)
        ids = self._sync_host(ids)
        with self._phase("pw.round.deliver") as ph:
            t_sync1 = time.perf_counter()
            self._note_sync()
            # greedy accept scan: packed position base+c holds the target's
            # argmax AFTER consuming input token c (c=0: the row's last
            # emitted token — always valid; c>=1: draft c-1).  Output c is
            # the true greedy token iff every input before it matched, so we
            # emit until the input feeding the NEXT position diverges; the
            # first mismatching position still yields one correct token (the
            # free bonus).  Causality makes later garbage inputs harmless.
            n_proposed = sum(nv - 1 for _a, _r, nv in rows)
            n_accepted = 0
            n_emitted = 0
            done: list[_Active] = []
            for act, i, nv in rows:
                base = i * C
                req = act.req
                prop = prop_of.get(id(act), [])
                emitted_n = 0
                finished = False
                for c in range(nv):
                    self._emit(req, int(ids[base + c]))
                    emitted_n += 1
                    n_emitted += 1
                    if len(req.emitted) >= req.max_new or (
                        req.stop_token is not None
                        and req.emitted[-1] == req.stop_token
                    ):
                        finished = True
                        break
                    if c < nv - 1 and prop[c] != int(ids[base + c]):
                        break  # draft refuted: later positions are phantom
                n_accepted += emitted_n - 1
                # roll back the rejected tail NOW: the pool must never hold
                # phantom K/V past the round (written coverage stays exactly
                # "every emitted token but the last", the engine invariant)
                rollback = nv - emitted_n
                if rollback:
                    pool.truncate_slots(act.seq_id, rollback)
                # capacity is judged AFTER rollback — the pre-extended
                # n_tokens must not close a request its budget keeps open
                if not finished and pool.sequence(
                        act.seq_id).n_tokens >= self.max_seq_tokens:
                    finished = True
                obs.record_span("engine.verify", t_disp, t_sync1, ctx=req.ctx,
                                k=nv - 1, accepted=emitted_n - 1)
                if finished:
                    done.append(act)
            self._record_dispatch(prog, t_disp, t_sync1, items=n_emitted)
            pool.stats.record_spec(
                proposed=n_proposed, accepted=n_accepted, emitted=n_emitted,
            )
            for act in done:
                running.remove(act)
                self._release_seq(act)
                deliver(act.req)
                # a finished stream is drafter training data (the n-gram
                # drafter's cross-request chain-hash table learns from it)
                base_ctx = (list(act.admitted) if act.admitted is not None
                            else list(act.req.prompt))
                spec.note_release(base_ctx + [
                    int(t) for t in act.req.emitted[act.emit_base:]
                ])
            spec.note_round(n_proposed, n_accepted, n_emitted,
                            ms=(t_sync1 - t_disp) * 1000.0)
            ph.set(emitted=n_emitted, finished=len(done))
        return True

    # -- Round-10: device-resident chained decode --------------------------
    def _can_chain(self, running, pending) -> bool:
        """Adaptive-K policy: chain only when the queue is QUIET — no
        pending admissions (arrivals and preemption victims force the
        round back to K=1 so step-boundary admission/TTFT semantics are
        unchanged), no mid-prefill chunk rows (those stream through the
        ragged mixed step), and at least one row with >= 2 tokens of
        budget left (an all-tail batch just runs the plain step)."""
        if self.chain_steps <= 1 or pending or not running:
            return False
        if any(a.tokens is not None for a in running):
            return False
        return self._chain_headroom(running) >= 2

    def _chain_headroom(self, running) -> int:
        out = 0
        for a in running:
            seq = self.pool.sequence(a.seq_id)
            out = max(out, min(a.req.max_new - len(a.req.emitted),
                               self.max_seq_tokens - seq.n_tokens))
        return out

    def _dispatch_chain(self, running, pending):
        """Pre-extend every decode row's block table by its chain budget
        and dispatch ONE K-step device program.  Returns ``(acts, kreal,
        ids, t_disp, prog)`` with ``ids`` the un-synced [B, K] device
        array (its host copy is started asynchronously), or None when
        nothing could be reserved (every row was preempted into
        pending)."""
        K = self.chain_steps
        pool = self.pool

        def k_for(act):
            seq = pool.sequence(act.seq_id)
            rem = min(act.req.max_new - len(act.req.emitted),
                      self.max_seq_tokens - seq.n_tokens)
            # rows with less budget than K still ride the chain: their
            # surplus steps write to the null block and their post-budget
            # ids are truncated host-side (wasted compute bounded by K)
            return min(K, max(rem, 1))

        with self._phase("pw.round.build") as ph:
            victims: list[_Active] = []
            reserved = self._reserve_slots(running, pending, victims,
                                           k_for=k_for)
            if victims:
                self._cascade_preempt(victims, running, pending)
            if not reserved:
                ph.set(kind="chain", rows=0, tokens=0, budget=0, waiting=0)
                return None
            B = self.max_batch_size
            NB = self.max_blocks_per_seq
            token = np.zeros(B, np.int32)
            positions = np.zeros(B, np.int32)
            sb = np.zeros((B, K), np.int32)
            so = np.zeros((B, K), np.int32)
            bt = np.zeros((B, NB), np.int32)
            acts: list[_Active] = []
            kreal: list[int] = []
            for i, (act, slots) in enumerate(reserved):
                seq = pool.sequence(act.seq_id)
                token[i] = act.req.emitted[-1]
                # extend_slots already advanced n_tokens by len(slots): the
                # chain's first token writes at the first reserved position
                positions[i] = seq.n_tokens - len(slots)
                for t, (blk, off) in enumerate(slots):
                    sb[i, t] = blk
                    so[i, t] = off
                bt[i, : len(seq.block_ids)] = seq.block_ids
                acts.append(act)
                kreal.append(len(slots))
            # the per-row PRNG key rides the scan carry; emit0 is the
            # row's absolute emit index at the chain's first step, so a
            # chain of K tokens lands bit-identically to K single steps
            samp = self._sampling_arrays(
                [(i, act.req) for i, act in enumerate(acts)], B
            )
            ph.set(kind="chain", rows=len(acts), tokens=sum(kreal),
                   budget=B * K, waiting=0)
            self._note_keys(ph, [int(positions[i]) + 1 + t
                                 for i, k in enumerate(kreal)
                                 for t in range(k)])
            self._note_state(ph, len(acts), step_rows=sum(kreal))
            extras = self._row_extras([a.seq_id for a in acts], ph)
        host = (token, positions, bt, sb, so) + extras
        faults.fire("engine.dispatch.chain")
        self._note_dispatch("chain")
        t_disp = self._t_dispatch
        dev = self._h2d("chained", samp is not None,
                        host if samp is None else host + samp)
        prog = self._chained if samp is None \
            else self._sampled_programs()["chained"]
        with self._phase("pw.chain_dispatch" if samp is None
                         else "pw.chain_dispatch_sampled"):
            ids = self._call(prog, dev)
        try:
            # start the device->host copy NOW so it overlaps the chain's
            # tail and the host's bookkeeping; np.asarray later just
            # collects it instead of blocking on a cold transfer
            ids.copy_to_host_async()
        except Exception:  # noqa: BLE001 - optional fast path (CPU arrays)
            pass
        return acts, kreal, ids, t_disp, prog

    def _scan_chain(self, acts, kreal, ids_np, running
                    ) -> tuple[list[_Active], int]:
        """Truncating emit of one synced chain: each row's ids are taken
        in order until EOS / max_new / capacity closes the request (the
        per-step done rule, applied token by token — so the emitted
        stream is token-identical to K separate rounds).  Returns the
        finished rows and the total emitted-token count."""
        done: list[_Active] = []
        n_emitted = 0
        for i, act in enumerate(acts):
            if not any(a is act for a in running):
                continue  # preempted after dispatch; results are void
            req = act.req
            finished = False
            for t in range(kreal[i]):
                self._emit(req, int(ids_np[i, t]))
                n_emitted += 1
                if len(req.emitted) >= req.max_new or (
                    req.stop_token is not None
                    and req.emitted[-1] == req.stop_token
                ):
                    finished = True
                    break
            if not finished and self.pool.sequence(
                    act.seq_id).n_tokens >= self.max_seq_tokens:
                finished = True
            if finished:
                done.append(act)
        return done, n_emitted

    def _chained_rounds(self, running, pending, deliver, poll, stop) -> bool:
        """The Round-10 hot loop: double-buffered chained rounds.

        The blocking per-token sync is gone — each iteration dispatches
        chain N+1 (its input token is chain N's last emitted id, already
        on the host from the ONE [B, K] sync) BEFORE doing chain N's
        heavy bookkeeping: completion callbacks, scheduler polling and
        metrics run in the overlap window while the device executes
        chain N+1.  The loop drops back to the per-step path (returns)
        the moment anything disturbs the quiet window: an arrival, a
        preemption, a finished row that leaves no chainable headroom."""
        inflight = self._dispatch_chain(running, pending)
        if inflight is None:
            return False
        while True:
            # overlap: poll the scheduler while the chain runs — an
            # arrival discovered here lands in pending and adapts the
            # NEXT round to K=1 (this chain is the bounded latency cost)
            with self._phase("pw.round.admit") as ph:
                self._admit_arrivals(running, pending, poll, stop)
                ph.set(admitted=0, pending=len(pending))
            acts, kreal, ids_dev, t_disp, prog = inflight
            # ONE sync per K-token chain: the host-blocked-on-device
            # window, then the pull of ids already on their way
            ids_np = self._sync_host(ids_dev)
            with self._phase("pw.round.deliver") as ph:
                t_sync1 = time.perf_counter()
                self._note_sync()
                # per-request chain spans: the dispatch->sync window each
                # row rode, under the REQUEST's trace (k = its chain depth)
                for i, act in enumerate(acts):
                    act.req.n_chains += 1
                    obs.record_span("engine.chain", t_disp, t_sync1,
                                    ctx=act.req.ctx, k=kreal[i])
                done, n_emitted = self._scan_chain(acts, kreal, ids_np,
                                                   running)
                self._record_dispatch(prog, t_disp, t_sync1,
                                      items=n_emitted)
                for act in done:
                    running.remove(act)
                    self._release_seq(act)
                ph.set(emitted=n_emitted, finished=0)
            nxt = None
            # with a drafter armed, the chain is the FALLBACK, not the
            # hot loop: return after one dispatch so _step_round offers
            # every round to the drafter (emitted tokens between rounds
            # are exactly what the n-gram drafter learns from)
            if running and not pending and self._spec is None \
                    and self._chain_headroom(running) >= 2:
                try:
                    nxt = self._dispatch_chain(running, pending)
                except BaseException:
                    # the overlapped dispatch failed AFTER chain N's
                    # finished rows left `running` but BEFORE their
                    # deliveries below ran — deliver them now or the
                    # failure path (restart or fail-all) loses completed
                    # requests it can no longer see
                    for act in done:
                        deliver(act.req)
                    raise
            # overlap: chain N's completion bookkeeping runs while the
            # device executes chain N+1 (the _note_sync/_note_dispatch
            # pair above already closed the device-idle window, so this
            # work is correctly NOT counted as host gap) — a second
            # pw.round.deliver, AFTER chain N+1's build/h2d/dispatch
            with self._phase("pw.round.deliver", emitted=0,
                             finished=len(done)):
                for act in done:
                    deliver(act.req)
                self.pool.stats.record_chain(
                    steps=self.chain_steps,
                    slots=len(acts) * self.chain_steps, emitted=n_emitted,
                )
            if nxt is None:
                return True
            inflight = nxt

    def _note_keys(self, ph, contexts, queries=None) -> None:
        """What a round's calls of the paged kernels attend, on
        ``pw.round.build`` and in the pool's counters: ``kv_keys``, the
        live rows' context lengths summed (a chain's rows once a step),
        and ``kv_key_lanes``, the key lanes their live grid steps span
        (every context rounded up to whole spans).  With a windowed cache
        those are the full layers'; for a sliding-window layer
        ``kv_window_keys``, the keys the rows' queries see there
        (``queries``: a row's query columns, default one: the window of
        its first column to its last key), and ``kv_window_ctx_keys``,
        what they would see with no window (``kv_keys`` again)."""
        span = self._span_keys
        keys = sum(contexts)
        lanes = sum(-(-c // span) for c in contexts) * span
        ph.set(kv_keys=keys, kv_key_lanes=lanes)
        self.pool.stats.record_attended_keys(keys, lanes)
        window = self.pool.window
        if window is not None:
            seen = sum(min(c, window + q - 1) for c, q in zip(
                contexts, queries or [1] * len(contexts)))
            ph.set(kv_window_keys=seen, kv_window_ctx_keys=keys)
            self.pool.stats.record_window_keys(seen, keys)

    def _note_query_cols(self, ph, row_nvalid) -> None:
        """What a mixed round's calls of the ragged kernels (the latent
        kernel on a latent pool) run on the full pool, on
        ``pw.round.build``: ``kv_query_cols``, the live query columns of ALL
        the step's rows (an idle row has one: the kernel runs it);
        ``kv_query_slots``, the query slots the dispatch lays out for them
        (:func:`query_layout`, which lays the K/V pools and the latent pool
        out alike: N kernel rows of P columns, B x C where it keeps the
        rows), also in the pool's counters; and
        ``kv_query_tile_cols``, the columns the kernel rows' live tiles
        cover (:func:`query_tile_columns`; a kernel row past the rows'
        pieces runs one column's).  A decode step's or a chain's rows are
        one column and one tile each: nothing to count."""
        P, N = self._query_layout
        spare = N - int((-(-row_nvalid // P)).sum())
        slots = N * P
        ph.set(kv_query_cols=int(row_nvalid.sum()), kv_query_slots=slots,
               kv_query_tile_cols=int(
                   self._query_tile_cols[row_nvalid].sum())
               + spare * self._idle_tile_cols)
        self.pool.stats.record_query_slots(slots)

    def _note_window_pairs(self, ph, row_start, row_nvalid) -> None:
        """On a windowed cache whose window is narrower than a chunk, what
        a sliding-window layer's ragged call sees and computes for the
        round's live rows, on ``pw.round.build`` and in the pool's
        counters, from shapes alone (:func:`window_pairs`):
        ``kv_window_band_pairs``, the query-key pairs inside the window,
        and ``kv_window_span_pairs``, the pairs the kernel's live column
        tiles times its live spans compute for them."""
        if self._window_pairs is None:
            return
        band, run = window_pairs(row_start, row_nvalid, **self._window_pairs)
        ph.set(kv_window_band_pairs=band, kv_window_span_pairs=run)
        self.pool.stats.record_window_pairs(band, run)

    def _note_write_blocks(self, ph, slot_blocks) -> None:
        """``kv_write_blocks`` on a mixed round's ``pw.round.build`` and in
        the pool's counters: the distinct pool blocks the round's tokens
        land in (the null block once, where tokens are diverted to it),
        which is what the K/V writer moves a layer and pool."""
        blocks = int(np.unique(slot_blocks).size)
        ph.set(kv_write_blocks=blocks)
        self.pool.stats.record_write_blocks(blocks)

    def _row_extras(self, seq_ids: list, ph) -> tuple:
        """The cache's own per-row arrays of a step, in row order (a
        hybrid cache: the rows' conv slots, noted on ``pw.round.build`` as
        ``conv_rows``; a windowed cache: the rows' window tables)."""
        extras = self.pool.row_extras(seq_ids, self.max_batch_size,
                                      self.max_blocks_per_seq)
        if extras and getattr(self.pool, "conv_slots", 0):
            ph.set(conv_rows=len(seq_ids))
        return extras

    def _note_state(self, ph, rows: int, *, chunk_tokens: int = 0,
                    step_rows: int = 0, resets: int = 0) -> None:
        """With a cache of matrix-state slots (kvcache/hybrid.py
        StateCache), on ``pw.round.build``: ``state_rows``, the rows that
        carry a state slot; ``kda_chunk_tokens``, the tokens that go through
        the chunked scan (rows of two tokens or more); ``kda_step_rows``,
        the rows that go through the one-token recurrence (a chain's rows
        once a step); and in the pool's counters ``kda_state_resets``, the
        first chunks that start a slot's state from zero."""
        if getattr(self.pool, "state", None) is None:
            return
        ph.set(state_rows=rows, kda_chunk_tokens=chunk_tokens,
               kda_step_rows=step_rows)
        if resets:
            self.pool.stats.record_state_resets(resets)

    def _build_decode(self, reserved, ph) -> tuple:
        """The numpy arrays of one 1-token-per-row step (inside the
        caller's ``pw.round.build``): ``(host arrays, sampled?)``."""
        B = self.max_batch_size
        NB = self.max_blocks_per_seq
        token = np.zeros(B, np.int32)
        positions = np.zeros(B, np.int32)
        sb = np.zeros(B, np.int32)
        so = np.zeros(B, np.int32)
        bt = np.zeros((B, NB), np.int32)
        for i, (act, slots) in enumerate(reserved):
            blk, off = slots[0]
            seq = self.pool.sequence(act.seq_id)
            token[i] = act.req.emitted[-1]
            positions[i] = seq.n_tokens - 1  # append_slot already advanced
            sb[i] = blk
            so[i] = off
            bt[i, : len(seq.block_ids)] = seq.block_ids
        samp = self._sampling_arrays(
            [(i, act.req) for i, (act, _s) in enumerate(reserved)], B
        )
        ph.set(kind="step", rows=len(reserved), tokens=len(reserved),
               budget=B, waiting=0)
        self._note_keys(ph, [int(p) + 1 for p in positions[:len(reserved)]])
        self._note_state(ph, len(reserved), step_rows=len(reserved))
        host = (token, positions, bt, sb, so) + self._row_extras(
            [act.seq_id for act, _s in reserved], ph)
        return (host if samp is None else host + samp), samp is not None

    def _decode_round(self, step, reserved, running, deliver) -> None:
        host, sampled = step
        faults.fire("engine.dispatch.step")
        self._note_dispatch("step")
        t_disp = self._t_dispatch
        dev = self._h2d("step", sampled, host)
        prog = self._sampled_programs()["step"] if sampled else self._step
        with self._phase("pw.decode_step_sampled" if sampled
                         else "pw.decode_step"):
            ids = self._call(prog, dev)
        ids = self._sync_host(ids)
        with self._phase("pw.round.deliver") as ph:
            t_sync1 = time.perf_counter()
            self._note_sync()
            self._record_dispatch(prog, t_disp, t_sync1,
                                  items=len(reserved))
            # a per-step round IS a K=1 chain: recording it keeps the
            # pathway_kv_chain_steps histogram's le=1 bucket meaningful —
            # admission pressure forcing K back to 1 is visible there
            self.pool.stats.record_chain(
                steps=1, slots=len(reserved), emitted=len(reserved)
            )
            finished = 0
            for i, (act, _slot) in enumerate(reserved):
                self._emit(act.req, int(ids[i]))
                if self._is_done(act.req, act.seq_id):
                    running.remove(act)
                    self._release_seq(act)
                    deliver(act.req)
                    finished += 1
            ph.set(emitted=len(reserved), finished=finished)

    def _build_mixed(self, reserved, chunks, ph) -> tuple:
        """The numpy arrays of the ragged fused step over a token-PACKED
        stream (inside the caller's ``pw.round.build``): decode rows
        contribute one token each, chunk rows a run of prompt tokens,
        sharing a ``mixed_tokens`` budget — so the dispatch's cost scales
        with the live token count (B + chunk headroom), never
        B x chunk.  Returns ``(host arrays, sampled?, rows, t)``."""
        B = self.max_batch_size
        C = self.prefill_chunk
        T = self.mixed_tokens
        NB = self.max_blocks_per_seq
        bs = self.pool.block_size
        tokens = np.zeros(T, np.int32)
        positions = np.zeros(T, np.int32)
        sb = np.zeros(T, np.int32)
        so = np.zeros(T, np.int32)
        row_tables = np.zeros((B, NB), np.int32)
        row_start = np.zeros(B, np.int32)
        row_nvalid = np.ones(B, np.int32)
        row_token_idx = np.zeros((B, C), np.int32)
        tok_row = np.zeros(T, np.int32)
        tok_col = np.zeros(T, np.int32)
        logit_idx = np.zeros(B, np.int32)
        rows: list[tuple[_Active, int, int]] = []  # (act, row, n_filled|-1)
        t = 0
        row = 0
        for act, slots in reserved:
            blk, off = slots[0]
            seq = self.pool.sequence(act.seq_id)
            tokens[t] = act.req.emitted[-1]
            positions[t] = seq.n_tokens - 1  # append_slot already advanced
            sb[t] = blk
            so[t] = off
            row_tables[row, : len(seq.block_ids)] = seq.block_ids
            row_start[row] = positions[t]
            row_token_idx[row, :] = t  # one valid column
            tok_row[t] = row
            logit_idx[row] = t
            rows.append((act, row, -1))
            t += 1
            row += 1
        proj: dict[int, int] = {}  # this round's projected n_filled
        for act in chunks:
            budget = T - t
            if budget <= 0 or row >= B:
                break  # later chunks wait a round (FIFO — no starvation)
            seq = self.pool.sequence(act.seq_id)
            s = act.n_filled
            e = min(s + C, len(act.tokens), s + budget)
            w = act.wait_writer
            if w is not None:
                if w.tokens is None:
                    # the writer finished: the whole shared region is
                    # resident, the gate is moot forever after
                    act.wait_writer = None
                else:
                    # our queries up to e read every position < min(e,
                    # n_diverted) of the shared region; the writer must
                    # have written them by THIS dispatch (its same-round
                    # run counts: per layer, all T tokens' K/V scatters
                    # land before any token's attention gathers)
                    wp = proj.get(id(w), w.n_filled)
                    if min(e, act.n_diverted) > wp:
                        e = min(e, wp)
                    if e <= s:
                        continue  # no safe progress: writer lags a round
            nv = e - s
            self.pool.reserve_chunk(act.seq_id, e)
            pos = np.arange(s, e)
            tokens[t:t + nv] = act.tokens[s:e]
            positions[t:t + nv] = pos
            blocks = np.asarray(seq.block_ids, np.int32)
            # prefix-shared leading blocks already hold the right K/V:
            # divert their writes to the null block — a live sequence may
            # be attending through them right now; the gather still READS
            # the shared blocks' resident bytes through the table
            sb[t:t + nv] = np.where(pos < act.n_diverted, 0,
                                    blocks[pos // bs])
            so[t:t + nv] = pos % bs
            row_tables[row, : len(seq.block_ids)] = seq.block_ids
            row_start[row] = s
            row_nvalid[row] = nv
            run = np.arange(t, t + nv)
            row_token_idx[row, :nv] = run
            row_token_idx[row, nv:] = t + nv - 1  # pad cols: masked anyway
            tok_row[run] = row
            tok_col[run] = np.arange(nv)
            logit_idx[row] = t + nv - 1
            rows.append((act, row, e))
            proj[id(act)] = e
            t += nv
            row += 1
        if not rows:
            # unreachable by construction: gate dependencies are acyclic
            # and rooted at an ungated writer, so at least one chunk run
            # always dispatches — fail loudly rather than spin
            raise RuntimeError(
                "ragged step produced no rows (gated chunk cycle?)"
            )
        # sampling rides per ROW: only rows emitting a token this round
        # matter (decode rows; a chunk row's mid-prefill logits are
        # discarded host-side either way, and its completing chunk's
        # first token uses emit = len(emitted), same as a decode row)
        samp = self._sampling_arrays(
            [(r, act.req) for act, r, _f in rows], B
        )
        # rows still in prefill that got no token this round: before their
        # first chunk they wait (engine.prefill_wait), after it they skip
        chunked = {id(a) for a, _r, f in rows if f >= 0}
        waiting = [a for a in chunks if id(a) not in chunked]
        for a in waiting:
            if a.req.t_chunk0 is not None:
                a.req.n_skipped += 1
                a.req.n_rounds += 1
        # chunk_rows / chunk_tokens: the rows that carry a prompt chunk and
        # their tokens (the rest of `tokens` is one a decode row)
        ph.set(kind="mixed", rows=len(rows), tokens=t, budget=T,
               waiting=len(waiting), chunk_rows=len(chunked),
               chunk_tokens=t - (len(rows) - len(chunked)))
        self._note_keys(ph, [int(c) for c in
                             row_start[:row] + row_nvalid[:row]],
                        [int(q) for q in row_nvalid[:row]])
        self._note_query_cols(ph, row_nvalid)
        self._note_window_pairs(ph, row_start[:row], row_nvalid[:row])
        self._note_write_blocks(ph, sb[:t])
        runs = row_nvalid[:row]
        self._note_state(
            ph, row, chunk_tokens=int(runs[runs >= 2].sum()),
            step_rows=int((runs == 1).sum()),
            resets=int(((runs >= 2) & (row_start[:row] == 0)).sum()))
        host = (tokens, positions, row_tables, row_start, row_nvalid,
                row_token_idx, tok_row, tok_col, sb, so, logit_idx) \
            + self._row_extras([act.seq_id for act, _r, _f in rows], ph)
        return (host if samp is None else host + samp), samp is not None, \
            rows, t

    def _mixed_round(self, step, running, deliver) -> None:
        """One dispatch serves decode rows and prompt chunks alike; only
        the [B] argmaxed ids come back."""
        host, sampled, rows, t = step
        faults.fire("engine.dispatch.mixed")
        self._note_dispatch("mixed")
        t_disp = self._t_dispatch
        for act, _row, filled in rows:
            if filled >= 0:
                act.req.note_chunk(t_disp)
        dev = self._h2d("mixed", sampled, host)
        prog = self._sampled_programs()["mixed"] if sampled else self._mixed
        with self._phase("pw.mixed_step_sampled" if sampled
                         else "pw.mixed_step"):
            ids = self._call(prog, dev)
        ids = self._sync_host(ids)
        with self._phase("pw.round.deliver") as ph:
            t_sync1 = time.perf_counter()
            self._note_sync()
            self._record_dispatch(prog, t_disp, t_sync1, items=t)
            self.pool.stats.record_mixed_step(len(rows))
            self.pool.stats.record_mixed_tokens(t, self.mixed_tokens)
            n_decode = sum(1 for _a, _r, f in rows if f < 0)
            if n_decode:
                # mixed rounds advance decode rows one token: a K=1 entry
                # in the chain histogram (adaptive-K observability)
                self.pool.stats.record_chain(
                    steps=1, slots=n_decode, emitted=n_decode
                )
            self.pool.stats.record_prefill_chunks(len(rows) - n_decode)
            emitted = finished = 0
            for act, row, filled in rows:
                if filled < 0:  # decode row
                    act.req.n_mixed += 1
                    self._emit(act.req, int(ids[row]))
                else:
                    # the chunk's ride through this ragged dispatch, on
                    # the request's trace: [start, end) positions streamed
                    obs.record_span("engine.prefill_chunk", t_disp, t_sync1,
                                    ctx=act.req.ctx, start=act.n_filled,
                                    end=filled)
                    act.n_filled = filled
                    if filled < len(act.tokens):
                        continue  # mid-prefill: the row's logits are garbage
                    # prefill complete — register the prompt's full blocks
                    # for sharing only NOW that their K/V is actually
                    # written (registering at admission would hand
                    # still-empty blocks to a concurrent request), then
                    # emit the first token from the dispatch's device-side
                    # argmax
                    if self.prefix is not None and act.prefix_keys:
                        self.prefix.insert(
                            act.prefix_keys,
                            self.pool.sequence(act.seq_id).block_ids,
                        )
                    self._drop_inflight_keys(act)
                    act.tokens = None
                    act.prefix_keys = None
                    self._emit(act.req, int(ids[row]))
                emitted += 1
                if self._is_done(act.req, act.seq_id):
                    running.remove(act)
                    self._release_seq(act)
                    deliver(act.req)
                    finished += 1
            ph.set(emitted=emitted, finished=finished)

    def _drop_inflight_keys(self, act: _Active) -> None:
        """Remove `act`'s registrations from the in-flight prefix map
        (prefill completed -> the cache owns them now; or preempted ->
        they are gone)."""
        if self._inflight_prefix:
            self._inflight_prefix = {
                k: v for k, v in self._inflight_prefix.items()
                if v[0] is not act
            }

    def _cascade_preempt(self, victims, running, pending) -> None:
        """A preempted mid-prefill writer strands every sharer whose
        shared region it had not finished writing: requeue those for
        recompute too (transitively — a sharer can itself be a writer
        for its unshared tail).  Safety is judged by the WRITER's
        progress, not the sharer's: a sharer starts with ``n_filled ==
        n_diverted`` (chunking begins after the shared region) yet has
        read nothing until its first chunk runs.  Once the writer wrote
        past ``n_diverted`` (or finished prefill entirely), the region
        is resident and the sharer's own references keep those blocks
        alive regardless of the writer's fate."""
        queue = list(victims)
        while queue:
            w = queue.pop()
            self._drop_inflight_keys(w)
            for act in list(running):
                if act.wait_writer is not w:
                    continue
                if w.tokens is None or w.n_filled >= act.n_diverted \
                        or act.tokens is None:
                    # region fully written (a completed sharer implies it
                    # too — its gate required the writer to pass the
                    # region before the last chunk could run)
                    act.wait_writer = None
                else:
                    running.remove(act)
                    self.pool.free_sequence(act.seq_id)
                    self.pool.stats.record_preemption()
                    self._requeue(pending, act.req)
                    queue.append(act)

    def _reserve_slots(self, running, pending, victims=None, k_for=None
                       ) -> list[tuple[_Active, list[tuple[int, int]]]]:
        """Reserve write slots per running DECODE sequence (mid-prefill
        sequences own their blocks already and need none), resolving pool
        exhaustion by prefix eviction first, preemption second.  Victims
        are only taken from sequences that have NOT yet reserved this
        round (a reserved slot is already in the outgoing device arrays);
        mid-prefill sequences are legitimate victims — their recompute
        re-streams the same chunks.

        ``k_for(act)`` gives the number of slots to pre-extend per row
        (the Round-10 chain reservation; default 1), atomically via
        BlockPool.extend_slots — so preemption, when it happens, happens
        at a CHAIN boundary with no half-reserved row."""
        reserved: list[tuple[_Active, list[tuple[int, int]]]] = []
        survivors = list(running)
        idx = 0
        while idx < len(survivors):
            act = survivors[idx]
            if act.tokens is not None:
                idx += 1  # mid-prefill: no decode slot this round
                continue
            try:
                slots = self.pool.extend_slots(
                    act.seq_id, k_for(act) if k_for is not None else 1
                )
            except PoolExhausted as exc:
                if self.prefix is not None and self.prefix.evict(
                    max(exc.needed - exc.free, 1)
                ) > 0:
                    continue
                # never preempt a sequence whose RE-ADMISSION prefill would
                # not fit the largest bucket (it would have to truncate,
                # breaking token identity) — such sequences are
                # preempt-immune.  The length is the admission trim math,
                # not the raw prompt: a long prompt already trimmed at
                # admission re-admits at the same (suffix-consistent) size
                bucket_cap = self.seq_buckets[-1]
                exclude = {a.seq_id for a, _ in reserved} | {
                    a.seq_id for a in survivors
                    if self._readmit_len(a.req) > bucket_cap
                }
                victim = self.pool.preempt(exclude=exclude)
                if victim is None:
                    raise RuntimeError(
                        "KV pool exhausted with nothing left to preempt; "
                        "increase num_blocks"
                    )
                vact = next(
                    (a for a in survivors if a.seq_id == victim.seq_id),
                    None,
                )
                if vact is None:
                    # the victim was a stray registered through direct pool
                    # use, not one of ours: its blocks are freed, retry
                    continue
                survivors.remove(vact)
                running.remove(vact)
                if victims is not None:
                    victims.append(vact)
                # preemption-with-recompute: the request rejoins the queue
                # carrying its emitted tokens; re-admission prefills over
                # prompt + emitted (the last emitted token's K/V was never
                # written, so recompute is the only correct resumption).
                # Trim consistency makes this token-identical: admission
                # keeps the last (limit - max_new) + len(emitted) tokens,
                # exactly the originally-admitted suffix plus everything
                # emitted since
                self._requeue(pending, vact.req)
                continue  # same idx: list shifted or retry current
            reserved.append((act, slots))
            idx += 1
        return reserved
