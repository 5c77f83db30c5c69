"""Reader ``module_gap_ms``: how long the device sat idle before a
program, from the device's own clock.

``XLA Modules`` of the first device holds one event a program run.  The
gap of a module is the time between the end of the module before it on
that line and its own start; the reading is the **median** gap, in
milliseconds, of the modules whose name matches ``pattern`` (the module
that *ends* the gap).  The median and not the mean: a wave's boundary (the
scheduler's linger, a closed loop's turn-around) is a gap of another kind,
and the idle share of the run already carries the total.  Device events
against device events: the host plane's clock does not enter.  No trace,
or fewer than ``min_count`` (8) such gaps: no reading.

The module also pairs the engine's program calls (host plane) with the
modules they launched, for ``sync_overhang_ms`` and for
:func:`causal_clock_bounds`.  A call is paired with the module whose start
is nearest its own return, which is unambiguous while rounds (9 ms and
more) are longer than the distance between the two planes' clocks (1-2 ms
in a run) plus a launch: ``MAX_PAIR_S`` is half the shortest round."""

from __future__ import annotations

import bisect
import re
import statistics

from benchmark.trace_reduce import MODULES_LINE, op_name

MIN_COUNT = 8
MAX_PAIR_S = 4.5e-3
CALL = re.compile(
    r"^pw\.(mixed_step|decode_step|chain_dispatch|verify_step)(_sampled)?$")
SYNC, D2H = "pw.round.sync", "pw.round.d2h"


def modules(trace) -> list:
    """``(name, start, end)`` of the first device's programs, in order."""
    if not trace.planes:
        return []
    return sorted(((op_name(n), s, e) for n, s, e in
                   trace.device_lines[trace.planes[0]].get(MODULES_LINE, ())),
                  key=lambda m: m[1])


def gaps(mods: list, pattern: str) -> dict:
    """Index of a module that matches -> the idle time before it."""
    rx = re.compile(pattern)
    return {i: max(mods[i][1] - mods[i - 1][2], 0.0)
            for i in range(1, len(mods)) if rx.search(mods[i][0])}


def read(params: dict, run) -> float | None:
    if run.trace is None:
        return None
    found = gaps(modules(run.trace), params["pattern"])
    if len(found) < params.get("min_count", MIN_COUNT):
        return None
    return 1e3 * statistics.median(found.values())


def dispatches(trace, mods: list) -> list:
    """One record a program call of the host plane, in order: ``call``,
    ``sync`` and ``d2h`` (each ``(start, end)``; ``d2h`` None where the
    program's sync still holds the pull), ``host`` (the summed duration of
    the ``pw.*`` spans between the sync before and this call: readback,
    deliver, admit, build, h2d), ``name`` of the call, and ``module``: the
    index into ``mods`` (:func:`modules`) of the module it launched, or
    None."""
    starts = [m[1] for m in mods]
    out: list = []
    host, cur = 0.0, None
    for name, s, e in trace.host_spans:
        if CALL.match(name):
            cur = {"name": name, "call": (s, e), "sync": None, "d2h": None,
                   "host": host, "module": None}
            out.append(cur)
            host = 0.0
        elif name == SYNC:
            if cur is not None and cur["sync"] is None:
                cur["sync"] = (s, e)
            host = 0.0
        else:
            if name == D2H and cur is not None and cur["sync"] is not None \
                    and cur["d2h"] is None:
                cur["d2h"] = (s, e)
            host += e - s
    taken: set = set()
    for d in out:
        at = d["call"][1]
        i = bisect.bisect_left(starts, at)
        near = min((j for j in (i - 1, i) if 0 <= j < len(mods)),
                   key=lambda j: abs(starts[j] - at), default=None)
        if near is not None and near not in taken \
                and abs(starts[near] - at) <= MAX_PAIR_S:
            d["module"] = near
            taken.add(near)
    return out


def whole(trace) -> tuple:
    """``(modules, dispatches)``: the dispatches that have their sync and
    their module.  One without, anywhere but at the two ends of the trace
    (where the window cuts a round), means the pairing cannot be trusted:
    the second is then None."""
    mods = modules(trace)
    ds = dispatches(trace, mods)
    ok = [d["sync"] is not None and d["module"] is not None for d in ds]
    if not all(ok[1:-1]):
        return mods, None
    return mods, [d for d, good in zip(ds, ok) if good]


def causal_clock_bounds(trace) -> tuple | None:
    """``(low, high)`` seconds: where the device plane's clock minus the
    host plane's must lie.  A module cannot start before its call starts,
    nor end after its sync returns, so over every paired dispatch
    ``max(module end - sync end) <= offset <= min(module start - call
    start)``.  Negative where the device's events read early (a module
    that seems to start before its call).  The interval is as wide as the
    shortest call + launch plus the shortest wake-up seen.  No metric
    reads it; ``trace_reduce.idle_gaps`` would have to subtract it."""
    mods, ds = whole(trace)
    if not ds:
        return None
    return (max(mods[d["module"]][2] - d["sync"][1] for d in ds),
            min(mods[d["module"]][1] - d["call"][0] for d in ds))
