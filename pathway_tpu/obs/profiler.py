"""Device cost observatory: the program registry + per-dispatch profiles.

`decode_mfu` sits at ~0.02 against a measured roofline and nothing said
WHICH of the many small jitted programs eats the step — PR 6's tracer
attributes time to phases (queue/prefill/device/sync/host), not to
device programs.  This module is the per-PROGRAM instrument
(Round-14), in the spirit of compile-time introspection from "Memory
Safe Computations with XLA" (arxiv 2206.14148):

- **Registry**: every jit entry point on the serving/data path is
  wrapped with :func:`profiled_jit`.  The wrapper detects compiles via
  the jit cache size (two ~0.07us probes per call — the hot path costs
  well under a microsecond), and at every compile records a
  :class:`CompileEvent`: program name, the static shape bucket (arg
  shapes/dtypes), compile wall time, and a stack summary — so the
  zero-recompile guards name the offender instead of saying
  "count != 0".
- **Cost/memory introspection**: each (program, bucket) record keeps
  the abstract argument shapes, so XLA's ``cost_analysis()`` (FLOPs,
  bytes accessed — a re-LOWER, no second compile) and
  ``memory_analysis()`` (temp/argument/output bytes — this one DOES
  pay an AOT compile, so it is strictly on-demand) can be computed
  lazily when ``/debug/profile`` or the HBM ledger asks.
- **Per-dispatch profiles**: the engine hangs its dispatch->sync
  windows (the same windows its ``jax.profiler.TraceAnnotation("pw.*")``
  call sites bracket) off the wrapper via :meth:`ProfiledFunction.
  record_dispatch`; a bounded reservoir per (program, bucket) feeds
  measured ms, achieved FLOPs/s, arithmetic intensity and roofline
  placement — the ranked "which kernel to fuse first" table.
- **Surfaces**: ``/debug/profile`` JSON (MetricsServer + every
  PathwayWebserver + the dashboard app), ``pathway_xla_*``
  Prometheus/OTLP metrics, Perfetto counter tracks in flight-recorder
  dumps, and ``cli.py profile`` for the ranked table from a terminal.

The registry is process-global and monotonic: tests snapshot
``total_compiles()`` and assert ``compile_events(since=n)`` stays
empty across a warm second pass.
"""

from __future__ import annotations

import json
import os
import threading
import time
import traceback
from collections import deque

# bounded per-(program, bucket) dispatch samples: sized so a bench
# window's dispatches (a few hundred at most) never evict mid-window —
# window_fracs over a longer horizon than the reservoir undercounts
_RESERVOIR = 1024
_STACK_DEPTH = 6  # app frames kept per compile event


def _is_arrayish(x) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype")


def _leaf_sig(leaf):
    if _is_arrayish(leaf):
        return (str(leaf.dtype), tuple(leaf.shape))
    return ("lit", repr(leaf)[:32])


def _sig_one(a):
    """Signature of ONE argument: arrays by shape/dtype, pytrees by their
    flattened leaf signatures, everything else by a bounded repr.  Only
    computed on the compile path (cache growth), never per dispatch."""
    if _is_arrayish(a):
        return _leaf_sig(a)
    if isinstance(a, (dict, list, tuple)):
        import jax

        leaves = jax.tree_util.tree_leaves(a)
        return ("tree", len(leaves), tuple(_leaf_sig(l) for l in leaves))
    return ("lit", repr(a)[:32])


def _signature(args, kwargs) -> tuple:
    parts = [_sig_one(a) for a in args]
    for k in sorted(kwargs):
        parts.append((k, _sig_one(kwargs[k])))
    return tuple(parts)


def _bucket_label(args, kwargs) -> str:
    """Human-readable short form of the bucket for tables/metrics:
    ``f32[8,112]+tree(194)+i32[8]`` — pytrees collapse to a leaf count
    (the params dict would otherwise be 200 shapes long)."""
    def one(a):
        if _is_arrayish(a):
            dt = str(a.dtype)
            dt = {"float32": "f32", "int32": "i32", "bfloat16": "bf16",
                  "float16": "f16", "int8": "i8", "bool": "b1",
                  "float64": "f64", "int64": "i64"}.get(dt, dt)
            return f"{dt}[{','.join(str(d) for d in a.shape)}]"
        if isinstance(a, (dict, list, tuple)):
            import jax

            return f"tree({len(jax.tree_util.tree_leaves(a))})"
        return repr(a)[:16]

    parts = [one(a) for a in args]
    parts += [f"{k}={one(kwargs[k])}" for k in sorted(kwargs)]
    out = "+".join(parts)
    return out if len(out) <= 160 else out[:157] + "..."


def _abstract(x):
    """ShapeDtypeStruct tree of an argument — holds NO buffers, so a
    compile event can be re-lowered for cost analysis long after the
    (possibly donated) concrete arrays are gone."""
    import jax

    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype)
        if _is_arrayish(l) else l,
        x,
    )


def _stack_summary() -> list[str]:
    """The last few APPLICATION frames of the triggering call (profiler
    and jax internals dropped) — the recompile provenance."""
    frames = traceback.extract_stack()
    keep = [
        f for f in frames
        if "obs/profiler" not in f.filename.replace("\\", "/")
        and os.sep + "jax" + os.sep not in f.filename
    ]
    return [
        f"{os.path.basename(f.filename)}:{f.lineno} in {f.name}"
        for f in keep[-_STACK_DEPTH:]
    ]


class CompileEvent:
    """One observed XLA compile: which program, what shapes triggered it,
    how long it took, and where the call came from."""

    __slots__ = ("seq", "program", "bucket", "label", "compile_s",
                 "redundant", "stack", "t_wall")

    def __init__(self, seq: int, program: str, bucket: tuple, label: str,
                 compile_s: float, redundant: bool, stack: list[str]):
        self.seq = seq
        self.program = program
        self.bucket = bucket
        self.label = label
        self.compile_s = compile_s
        # True when this (program, bucket) had already compiled once in
        # this process (another engine instance of the same config, or a
        # genuine cache-lost recompile) — redundant compilation work
        self.redundant = redundant
        self.stack = stack
        self.t_wall = time.time()

    def describe(self) -> str:
        kind = "RECOMPILE" if self.redundant else "compile"
        lines = [
            f"{kind} #{self.seq}: {self.program} [{self.label}] "
            f"({self.compile_s:.3f}s)",
            "  triggering args: " + self.label,
        ]
        lines += [f"    {frame}" for frame in self.stack]
        return "\n".join(lines)

    def as_dict(self) -> dict:
        return {
            "seq": self.seq, "program": self.program, "bucket": self.label,
            "compile_s": round(self.compile_s, 4),
            "redundant": self.redundant, "stack": list(self.stack),
        }


class ProgramRecord:
    """Everything known about one (program, shape bucket): compile cost,
    lazily-materialized XLA cost/memory analysis, and the bounded
    dispatch-timing reservoir."""

    __slots__ = ("program", "bucket", "label", "n_compiles",
                 "compile_s_total", "_wrapper_ref", "_abs_args",
                 "_abs_kwargs", "analysis", "_analysis_failed", "mem",
                 "_mem_failed", "reservoir", "dispatch_s_total",
                 "dispatches", "items_total", "calls")

    def __init__(self, program: str, bucket: tuple, label: str):
        self.program = program
        self.bucket = bucket
        self.label = label
        self.n_compiles = 0
        self.compile_s_total = 0.0
        self._wrapper_ref = None  # weakref to the owning ProfiledFunction
        self._abs_args = None
        self._abs_kwargs = None
        self.analysis: dict | None = None  # {"flops", "bytes_accessed"}
        self._analysis_failed = False
        self.mem: dict | None = None  # {"temp", "argument", "output"} bytes
        self._mem_failed = False
        # (t_end_perf_counter, duration_s, items) — items is the caller's
        # unit (tokens for decode programs) so tokens/s falls out
        self.reservoir: deque = deque(maxlen=_RESERVOIR)
        self.dispatch_s_total = 0.0
        self.dispatches = 0
        self.items_total = 0
        self.calls = 0

    # -- lazy XLA introspection -------------------------------------------
    def _lowered(self):
        wrapper = self._wrapper_ref() if self._wrapper_ref else None
        if wrapper is None or self._abs_args is None:
            return None
        return wrapper._jit.lower(*self._abs_args, **self._abs_kwargs)

    def try_analyze(self) -> dict | None:
        """FLOPs / bytes accessed via XLA's HLO cost analysis on the
        re-LOWERED module (tracing only — no second compile).  Cached;
        a failure is cached too so a broken program cannot be re-traced
        on every scrape."""
        if self.analysis is not None or self._analysis_failed:
            return self.analysis
        try:
            lowered = self._lowered()
            if lowered is None:
                self._analysis_failed = True
                return None
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):  # some versions: per device
                ca = ca[0] if ca else {}
            ca = ca or {}
            self.analysis = {
                "flops": float(ca.get("flops") or 0.0) or None,
                "bytes_accessed": (
                    float(ca.get("bytes accessed") or 0.0) or None
                ),
            }
        except Exception:  # noqa: BLE001 - introspection must never raise
            self._analysis_failed = True
            return None
        return self.analysis

    def try_memory(self) -> dict | None:
        """temp/argument/output bytes via ``memory_analysis()``.  This
        pays an AOT compile of the program (XLA will not hand out the
        dispatch cache's executable), so it is strictly on-demand —
        ``/debug/profile?memory=1`` and the HBM ledger, never a scrape."""
        if self.mem is not None or self._mem_failed:
            return self.mem
        try:
            lowered = self._lowered()
            if lowered is None:
                self._mem_failed = True
                return None
            with _own_compiles():
                ma = lowered.compile().memory_analysis()
            self.mem = {
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "argument_bytes": int(
                    getattr(ma, "argument_size_in_bytes", 0)
                ),
                "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            }
        except Exception:  # noqa: BLE001
            self._mem_failed = True
            return None
        return self.mem

    # -- dispatch stats ----------------------------------------------------
    def ms_percentile(self, q: float) -> float | None:
        if not self.reservoir:
            return None
        durs = sorted(d for _t, d, _i in self.reservoir)
        idx = min(int(q * len(durs)), len(durs) - 1)
        return durs[idx] * 1e3

    def as_row(self, peak_flops=None, membw=None, memory: bool = False,
               analyze: bool = True) -> dict:
        analysis = self.try_analyze() if analyze else self.analysis
        mem = self.try_memory() if memory else self.mem
        flops = (analysis or {}).get("flops")
        nbytes = (analysis or {}).get("bytes_accessed")
        ms_p50 = self.ms_percentile(0.5)
        achieved = (
            flops / (ms_p50 / 1e3) if flops and ms_p50 else None
        )
        ai = flops / nbytes if flops and nbytes else None
        row = {
            "program": self.program,
            "bucket": self.label,
            "n_compiles": self.n_compiles,
            "compile_s": round(self.compile_s_total, 4),
            "calls": self.calls,
            "dispatches": self.dispatches,
            "dispatch_s_total": round(self.dispatch_s_total, 4),
            "dispatch_ms_p50": round(ms_p50, 4) if ms_p50 else None,
            "dispatch_ms_min": (
                round(min(d for _t, d, _i in self.reservoir) * 1e3, 4)
                if self.reservoir else None
            ),
            "items_total": self.items_total,
            "flops": flops,
            "bytes_accessed": nbytes,
            "arithmetic_intensity": round(ai, 3) if ai else None,
            "achieved_flops_per_s": (
                round(achieved, 1) if achieved else None
            ),
        }
        if mem:
            row["memory"] = dict(mem)
        # roofline placement: where this program sits against the
        # machine's peak-FLOPs / memory-bandwidth roof
        if peak_flops and achieved:
            row["mfu"] = round(achieved / peak_flops, 5)
        if peak_flops and membw and ai:
            ridge = peak_flops / membw
            attainable = min(peak_flops, ai * membw)
            row["roofline"] = {
                "bound": "memory" if ai < ridge else "compute",
                "ridge_ai": round(ridge, 2),
                "attainable_flops_per_s": round(attainable, 1),
                "attained_frac": (
                    round(achieved / attainable, 4) if achieved else None
                ),
            }
        return row


class ProgramRegistry:
    """Process-global table of profiled device programs."""

    def __init__(self):
        self._lock = threading.RLock()
        self._records: dict[tuple, ProgramRecord] = {}
        self._events: list[CompileEvent] = []
        self._n_compiles = 0
        self._n_redundant = 0

    # -- recording ---------------------------------------------------------
    def record_compile(self, wrapper, args, kwargs,
                       compile_s: float) -> ProgramRecord:
        import weakref

        sig = _signature(args, kwargs)
        label = _bucket_label(args, kwargs)
        key = (wrapper.program, sig)
        with self._lock:
            rec = self._records.get(key)
            redundant = rec is not None and rec.n_compiles > 0
            if rec is None:
                rec = self._records[key] = ProgramRecord(
                    wrapper.program, sig, label
                )
            rec.n_compiles += 1
            rec.compile_s_total += compile_s
            rec._wrapper_ref = weakref.ref(wrapper)
            if rec._abs_args is None:
                try:
                    rec._abs_args = _abstract(args)
                    rec._abs_kwargs = _abstract(kwargs)
                except Exception:  # noqa: BLE001 - analysis degrades only
                    rec._abs_args = None
                    rec._abs_kwargs = {}
            self._n_compiles += 1
            if redundant:
                self._n_redundant += 1
            self._events.append(CompileEvent(
                self._n_compiles, wrapper.program, sig, label, compile_s,
                redundant, _stack_summary(),
            ))
            # failure loops could otherwise grow the event list without
            # bound; the registry keeps the newest few thousand
            if len(self._events) > 4096:
                del self._events[:1024]
        return rec

    def record_dispatch(self, program: str, key: tuple | None,
                        duration_s: float, t_end: float,
                        items: int | None) -> None:
        with self._lock:
            if key is not None:
                rec = self._records.get(key)
            else:
                # a wrapper called at several shapes: aggregate under a
                # program-level pseudo bucket
                rec = self._records.get((program, ("*",)))
                if rec is None:
                    rec = self._records[(program, ("*",))] = ProgramRecord(
                        program, ("*",), "*"
                    )
            if rec is None:
                return
            rec.reservoir.append((t_end, duration_s, items or 0))
            rec.dispatch_s_total += duration_s
            rec.dispatches += 1
            if items:
                rec.items_total += items

    # -- reading -----------------------------------------------------------
    def total_compiles(self) -> int:
        with self._lock:
            return self._n_compiles

    def compile_events(self, since: int = 0) -> list[CompileEvent]:
        """Events with seq > ``since`` (pair with :meth:`total_compiles`
        for a begin/end guard)."""
        with self._lock:
            return [e for e in self._events if e.seq > since]

    def records(self) -> list[ProgramRecord]:
        with self._lock:
            return list(self._records.values())

    def totals(self) -> dict:
        with self._lock:
            recs = list(self._records.values())
            return {
                "n_device_programs": len(
                    {(r.program, r.bucket) for r in recs if r.n_compiles}
                ),
                "n_compiles": self._n_compiles,
                "recompiles_total": self._n_redundant,
                "compile_s_total": round(
                    sum(r.compile_s_total for r in recs), 4
                ),
                "dispatch_s_total": round(
                    sum(r.dispatch_s_total for r in recs), 4
                ),
            }

    def max_temp_bytes(self, prefix: str = "", cached_only: bool = True,
                       bucket_contains: str | None = None) -> int | None:
        """Largest known temp watermark over matching programs — the HBM
        ledger's measured input.  ``cached_only`` (default) never
        triggers the AOT compile memory analysis costs.
        ``bucket_contains`` restricts the match to records whose bucket
        label carries the substring (the HBM ledger passes the pool
        shape, so one engine's fit check is never contaminated by
        another model's watermark)."""
        best = None
        for rec in self.records():
            if prefix and not rec.program.startswith(prefix):
                continue
            if bucket_contains and bucket_contains not in rec.label:
                continue
            mem = rec.mem if cached_only else rec.try_memory()
            if mem and mem.get("temp_bytes") is not None:
                best = max(best or 0, mem["temp_bytes"])
        return best

    def window_fracs(self, t0: float, t1: float) -> dict[str, float]:
        """Per-PROGRAM share of a wall-clock window (perf_counter
        timeline): how much of the window each program's dispatch->sync
        intervals covered.  The bench's ``decode_kernel_fracs`` over the
        best chained window — the 0.0197 aggregate MFU decomposed.
        Bounded by the per-record reservoir: a window containing more
        than ``_RESERVOIR`` dispatches of one program undercounts that
        program (evicted samples read as idle time) — keep queried
        windows short relative to the dispatch rate."""
        wall = max(t1 - t0, 1e-9)
        out: dict[str, float] = {}
        for rec in self.records():
            tot = 0.0
            for t_end, dur, _items in list(rec.reservoir):
                s0, s1 = t_end - dur, t_end
                if s1 <= t0 or s0 >= t1:
                    continue
                tot += min(s1, t1) - max(s0, t0)
            if tot > 0:
                out[rec.program] = out.get(rec.program, 0.0) + tot / wall
        return out

    # -- summary / export --------------------------------------------------
    def summary(self, *, peak_flops=None, membw=None, analyze: bool = True,
                memory: bool = False) -> dict:
        if analyze and peak_flops is None:
            peak_flops = measured_peak_flops()
        if analyze and membw is None:
            membw = measured_membw()
        rows = [
            r.as_row(peak_flops=peak_flops, membw=membw, memory=memory,
                     analyze=analyze)
            for r in self.records()
        ]
        rows.sort(key=lambda r: -(r["dispatch_s_total"] or 0.0))
        events = self.compile_events()
        return {
            "peak_flops_per_s": peak_flops,
            "membw_bytes_per_s": membw,
            "peak_source": _roofline()["source"] if analyze else None,
            **self.totals(),
            "programs": rows,
            "recompile_events": [
                e.as_dict() for e in events if e.redundant
            ][-32:],
        }


_REGISTRY = ProgramRegistry()

# process-wide backend-compile counter via jax.monitoring: counts EVERY
# XLA compile, including jits not wrapped with profiled_jit — the
# breadth the zero-recompile guards need (the registry adds the named
# provenance for wrapped programs).  Installed lazily; the listener
# costs one string compare per monitoring event.  ``suspended`` masks
# the observatory's OWN deliberate compiles (the roofline probes, the
# on-demand memory_analysis AOT compile) so a /debug/profile scrape
# racing a CompileWatch guard cannot fail it spuriously — best-effort:
# a REAL compile on another thread during that brief window is missed.
_BACKEND_COMPILES = {"n": 0, "installed": False, "suspended": 0}


def _install_backend_compile_counter() -> None:
    if _BACKEND_COMPILES["installed"]:
        return
    _BACKEND_COMPILES["installed"] = True
    try:
        from jax import monitoring as _mon

        def _listener(name, _dur, **_kw):
            if name == "/jax/core/compile/backend_compile_duration" \
                    and not _BACKEND_COMPILES["suspended"]:
                _BACKEND_COMPILES["n"] += 1

        _mon.register_event_duration_secs_listener(_listener)
    except Exception:  # noqa: BLE001 - breadth degrades, registry remains
        pass


class _own_compiles:
    """Context manager masking the observatory's own compiles from the
    backend-compile counter."""

    def __enter__(self):
        _BACKEND_COMPILES["suspended"] += 1

    def __exit__(self, *exc):
        _BACKEND_COMPILES["suspended"] -= 1


def total_backend_compiles() -> int:
    """Lifetime count of ALL XLA backend compiles in this process
    (wrapped or not).  0-until-installed: call this once BEFORE the
    workload you want guarded (CompileWatch does)."""
    _install_backend_compile_counter()
    return _BACKEND_COMPILES["n"]


def registry() -> ProgramRegistry:
    return _REGISTRY


class ProfiledFunction:
    """A jitted function that registers its compiled programs.

    Drop-in for ``jax.jit(fn, **jit_kwargs)``: same call signature, same
    donation semantics (the wrapper retains only abstract shapes, never
    buffers).  Compile detection is two jit-cache-size probes around the
    call; all heavy work (signatures, stack capture, lowering) happens
    only on the compile path.
    """

    def __init__(self, program: str, fn, **jit_kwargs):
        import jax

        self.program = program
        self._jit = jax.jit(fn, **jit_kwargs)
        self._cache_size = getattr(self._jit, "_cache_size", None)
        self._seen_sigs: set | None = None if self._cache_size else set()
        self.calls = 0
        # the single (program, bucket) key when exactly one bucket has
        # compiled through this wrapper (the engine's static-shape case);
        # False once a second bucket appears (per-bucket attribution of
        # dispatch timings then degrades to the program level)
        self._key: tuple | None | bool = None
        # perf_counter at the end of the newest compile: dispatch windows
        # that overlap a compile are COLD (compile wall inside them) and
        # would poison the warm-latency reservoir
        self._last_compile_end = 0.0

    # jax.jit API passthroughs used by the registry / AOT paths
    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        self.calls += 1
        if self._cache_size is not None:
            n0 = self._cache_size()
            t0 = time.perf_counter()
            out = self._jit(*args, **kwargs)
            if self._cache_size() > n0:
                self._on_compile(args, kwargs, time.perf_counter() - t0)
            return out
        # fallback (no _cache_size hook): signature-tracked, slower
        sig = _signature(args, kwargs)
        if sig in self._seen_sigs:
            return self._jit(*args, **kwargs)
        t0 = time.perf_counter()
        out = self._jit(*args, **kwargs)
        self._seen_sigs.add(sig)
        self._on_compile(args, kwargs, time.perf_counter() - t0)
        return out

    def _on_compile(self, args, kwargs, compile_s: float) -> None:
        self._last_compile_end = time.perf_counter()
        rec = _REGISTRY.record_compile(self, args, kwargs, compile_s)
        key = (rec.program, rec.bucket)
        if self._key is None:
            self._key = key
        elif self._key is not False and self._key != key:
            self._key = False
        # cost analysis runs EAGERLY on the compile path (a re-lower is
        # a fraction of the compile that just happened) so FLOPs/bytes
        # survive the wrapper: records only hold weakrefs, and a
        # discarded engine's programs must still report on
        # /debug/profile.  memory_analysis stays strictly on-demand —
        # it pays a full AOT compile.  PW_PROFILER_EAGER_COST=0 opts out.
        if os.environ.get("PW_PROFILER_EAGER_COST", "1") != "0":
            rec.try_analyze()

    def record_dispatch(self, duration_s: float, *, t_end: float | None = None,
                        items: int | None = None) -> None:
        """Attribute one dispatch->sync window to this program (the
        engine calls this after its ``pw.round.d2h`` returned).
        ``t_end`` is the window's perf_counter end so window queries
        (``window_fracs``) line up with the flight recorder.  Windows
        overlapping a compile are dropped — they measure XLA, not the
        kernel."""
        end = t_end if t_end is not None else time.perf_counter()
        if end - duration_s < self._last_compile_end:
            return
        key = self._key if isinstance(self._key, tuple) else None
        _REGISTRY.record_dispatch(self.program, key, duration_s, end, items)
        rec = _REGISTRY._records.get(key) if key else None
        if rec is not None:
            rec.calls = self.calls

    def probe_overhead(self, reps: int = 20000) -> float:
        """Measured per-call cost of the wrapper's FAST-PATH bookkeeping
        (cache probe + counter), excluding the jit call itself — the
        noise-immune per-event number the overhead guard multiplies by
        the event count (tests/test_profiler.py)."""
        cs = self._cache_size or (lambda: 0)
        t0 = time.perf_counter()
        for _ in range(reps):
            self.calls += 1
            n0 = cs()
            t_call = time.perf_counter()  # the per-call timestamp probe
            if cs() > n0:  # pragma: no cover - never true in the probe
                pass
            del t_call
        per = (time.perf_counter() - t0) / reps
        self.calls -= reps
        return per


def profiled_jit(program: str, fn, **jit_kwargs) -> ProfiledFunction:
    """``jax.jit(fn, **jit_kwargs)`` that registers its compiled programs
    in the device cost observatory under ``program``."""
    return ProfiledFunction(program, fn, **jit_kwargs)


# -- machine roofline (lazy, cached) ----------------------------------------

# Published per-chip peaks, keyed by jax's ``device_kind``.  On a TPU
# backend the roofline comes from this table and nowhere else: a kind
# that is not listed yields no peak (``mfu`` stays null, with the reason
# in the summary) rather than a probe's guess.
TPU_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}

_PROBE_CACHE: dict = {}
_PROBE_LOCK = threading.Lock()


def _best_of_3(f, a) -> float:
    with _own_compiles():
        f(a).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        f(a).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def _roofline() -> dict:
    """``{"peak", "membw", "source"}`` of the active backend, resolved
    once per process.  TPU: the :data:`TPU_PEAKS` row of the device's
    kind (bf16 peak — the serving dtype), or nulls with the reason as
    ``source``.  CPU: a best-of-3 jitted 1024^3 f32 matmul and a 32MB
    copy (read+write counted), ~100ms together."""
    with _PROBE_LOCK:
        if "roof" in _PROBE_CACHE:
            return _PROBE_CACHE["roof"]
        import jax
        import jax.numpy as jnp
        import numpy as np

        if jax.default_backend() == "tpu":
            kind = jax.devices()[0].device_kind
            row = TPU_PEAKS.get(kind)
            roof = (
                {"peak": row["bf16_flops_per_s"],
                 "membw": row["hbm_bytes_per_s"],
                 "source": f"{row['source']} ({kind}, bf16)"}
                if row else
                {"peak": None, "membw": None,
                 "source": f"device_kind {kind!r} is not in TPU_PEAKS"}
            )
        else:
            n = 1024
            a = jnp.asarray(
                np.random.default_rng(0).standard_normal((n, n)),
                jnp.float32,
            )
            t_mm = _best_of_3(jax.jit(lambda x: x @ x), a)
            m = 8 * 1024 * 1024  # 32MB f32
            t_cp = _best_of_3(jax.jit(lambda x: x + 1.0),
                              jnp.zeros((m,), jnp.float32))
            roof = {"peak": 2.0 * n ** 3 / t_mm, "membw": 2.0 * 4 * m / t_cp,
                    "source": "measured on this host (f32 matmul, 32MB copy)"}
        _PROBE_CACHE["roof"] = roof
        return roof


def measured_peak_flops() -> float | None:
    """Peak FLOP/s of the active backend — the denominator for
    per-program MFU (see :func:`_roofline` for where it comes from)."""
    return _roofline()["peak"]


def measured_membw() -> float | None:
    """Memory bandwidth of the active backend — the roofline's ridge
    point (see :func:`_roofline`)."""
    return _roofline()["membw"]


# -- surfaces ---------------------------------------------------------------

def profile_dump(params: dict | None = None) -> str:
    """The ``/debug/profile`` endpoint body (MetricsServer, every
    PathwayWebserver, the dashboard app): the registry summary as JSON.
    ``?memory=1`` additionally materializes ``memory_analysis()`` per
    program (pays one AOT compile each — first hit only)."""
    params = params or {}
    include_memory = str(params.get("memory", "")) in ("1", "true", "yes")
    return json.dumps(
        _REGISTRY.summary(memory=include_memory), default=str,
    )


def render_prometheus_lines() -> list[str]:
    """``pathway_xla_*`` Prometheus lines (appended to the serving
    metrics surface).  Uses cached analysis only — a scrape must never
    trigger lowering or compiles."""
    recs = _REGISTRY.records()
    if not recs:
        return []
    totals = _REGISTRY.totals()
    lines = [
        "# TYPE pathway_xla_programs gauge",
        f"pathway_xla_programs {totals['n_device_programs']}",
        "# TYPE pathway_xla_compiles_total counter",
        "# TYPE pathway_xla_recompiles_total counter",
        f"pathway_xla_recompiles_total {totals['recompiles_total']}",
        "# TYPE pathway_xla_compile_seconds_total counter",
        "# TYPE pathway_xla_dispatches_total counter",
        "# TYPE pathway_xla_dispatch_seconds_total counter",
        "# TYPE pathway_xla_program_flops gauge",
        "# TYPE pathway_xla_program_mfu gauge",
    ]
    # never probe on a scrape
    peak = (_PROBE_CACHE.get("roof") or {}).get("peak")
    for rec in recs:
        lbl = f'program="{rec.program}",bucket="{rec.label}"'
        lines.append(
            f"pathway_xla_compiles_total{{{lbl}}} {rec.n_compiles}"
        )
        lines.append(
            f"pathway_xla_compile_seconds_total{{{lbl}}} "
            f"{rec.compile_s_total:.4f}"
        )
        lines.append(
            f"pathway_xla_dispatches_total{{{lbl}}} {rec.dispatches}"
        )
        lines.append(
            f"pathway_xla_dispatch_seconds_total{{{lbl}}} "
            f"{rec.dispatch_s_total:.4f}"
        )
        flops = (rec.analysis or {}).get("flops")
        if flops:
            lines.append(f"pathway_xla_program_flops{{{lbl}}} {flops:.0f}")
            ms = rec.ms_percentile(0.5)
            if ms and peak:
                lines.append(
                    f"pathway_xla_program_mfu{{{lbl}}} "
                    f"{flops / (ms / 1e3) / peak:.5f}"
                )
    return lines


def otlp_points(now_ns: str) -> list[dict]:
    """``pathway.xla`` OTLP data points (merged into the engine's
    metrics push)."""
    points = []
    for rec in _REGISTRY.records():
        attrs = [
            {"key": "program", "value": {"stringValue": rec.program}},
            {"key": "bucket", "value": {"stringValue": rec.label}},
        ]
        for key, val in (("compiles", rec.n_compiles),
                         ("dispatches", rec.dispatches)):
            points.append({
                "asInt": str(val), "timeUnixNano": now_ns,
                "attributes": attrs + [
                    {"key": "counter", "value": {"stringValue": key}}
                ],
            })
        for key, val in (("compile_s", rec.compile_s_total),
                         ("dispatch_s", rec.dispatch_s_total)):
            points.append({
                "asDouble": val, "timeUnixNano": now_ns,
                "attributes": attrs + [
                    {"key": "counter", "value": {"stringValue": key}}
                ],
            })
    return points


def counter_events(epoch_perf: float, pid: int) -> list[dict]:
    """Chrome-trace COUNTER events ("ph": "C") from the dispatch
    reservoirs — per-program counter tracks in every flight-recorder
    dump, so Perfetto shows kernel cost next to the span timeline."""
    events = []
    for rec in _REGISTRY.records():
        name = f"pw.xla.{rec.program}"
        for t_end, dur, _items in list(rec.reservoir):
            events.append({
                "name": name, "ph": "C",
                "ts": round((t_end - epoch_perf) * 1e6, 3),
                "pid": pid,
                "args": {"dispatch_ms": round(dur * 1e3, 4)},
            })
    events.sort(key=lambda e: e["ts"])
    return events


def publish_to_costdb(db=None, *, peak_flops=None) -> int:
    """Push every record with measured dispatches into the persistent
    cost store (obs/costdb.py) — the substrate the auto-planner
    (ROADMAP item 5) queries.  Returns the number of entries written."""
    from . import costdb as _costdb

    if db is None:
        db = _costdb.default_db()
    if peak_flops is None:
        peak_flops = (_PROBE_CACHE.get("roof") or {}).get("peak")
    n = 0
    for rec in _REGISTRY.records():
        ms = rec.ms_percentile(0.5)
        if ms is None:
            continue
        flops = (rec.analysis or {}).get("flops")
        mfu = (
            flops / (ms / 1e3) / peak_flops
            if flops and peak_flops else None
        )
        db.observe(
            rec.program, rec.label, ms=ms,
            flops=flops,
            bytes=(rec.analysis or {}).get("bytes_accessed"),
            mfu=round(mfu, 5) if mfu else None,
            extra={"dispatches": rec.dispatches,
                   "compile_s": round(rec.compile_s_total, 4)},
        )
        n += 1
    return n


def profile_diff(before: dict, after: dict) -> list[dict]:
    """Per-program deltas between two ``/debug/profile`` snapshots (the
    dicts ``registry().summary()`` returns, or their JSON round-trips).

    One row per (program, bucket) present in EITHER snapshot — a program
    only in ``after`` is new (a fused/int8 variant that didn't exist
    before), one only in ``before`` was retired; both read off the same
    table.  Rows carry before/after/delta for the reviewable movers —
    p50 dispatch ms, MFU, and share of total dispatch seconds — sorted
    by |share delta| then |ms delta| so the biggest shift leads."""
    def _index(snap):
        progs = (snap or {}).get("programs") or []
        return {((r.get("program") or "?"), r.get("bucket")): r
                for r in progs}

    def _share(rows):
        tot = sum(r.get("dispatch_s_total") or 0.0 for r in rows.values())
        return tot or 1.0

    b_rows, a_rows = _index(before), _index(after)
    b_tot, a_tot = _share(b_rows), _share(a_rows)
    out = []
    for key in sorted(set(b_rows) | set(a_rows), key=str):
        b, a = b_rows.get(key), a_rows.get(key)

        def _get(row, field):
            return row.get(field) if row else None

        def _delta(field):
            x, y = _get(b, field), _get(a, field)
            return round(y - x, 5) if x is not None and y is not None \
                else None

        b_share = ((b or {}).get("dispatch_s_total") or 0.0) / b_tot
        a_share = ((a or {}).get("dispatch_s_total") or 0.0) / a_tot
        out.append({
            "program": key[0],
            "bucket": key[1],
            "status": ("new" if b is None
                       else "gone" if a is None else "both"),
            "ms_p50_before": _get(b, "dispatch_ms_p50"),
            "ms_p50_after": _get(a, "dispatch_ms_p50"),
            "ms_p50_delta": _delta("dispatch_ms_p50"),
            "mfu_before": _get(b, "mfu"),
            "mfu_after": _get(a, "mfu"),
            "mfu_delta": _delta("mfu"),
            "share_before": round(b_share, 4),
            "share_after": round(a_share, 4),
            "share_delta": round(a_share - b_share, 4),
        })
    out.sort(key=lambda r: (-abs(r["share_delta"]),
                            -abs(r["ms_p50_delta"] or 0.0)))
    return out
