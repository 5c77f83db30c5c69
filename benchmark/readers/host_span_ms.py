"""Reader ``host_span_ms``: host milliseconds per dispatch of the engine's
own phases, from the device trace's host plane.

``run.trace.host_spans`` holds the ``pw.*`` ``TraceAnnotation`` events of
the trace (on the device's clock).  The summed duration of the events
whose name matches ``pattern`` is divided by the number of ``pw.round.sync``
events (one per dispatch).  No trace, no ``pw.round.sync`` event (a
program without the phases) or no matching event: no reading.

:func:`clock_anchors` reads the anchors that tie the program's
``perf_counter`` to the trace's clock: every ``pw.round.sync`` event
carries ``perf_ns``, ``time.perf_counter_ns()`` at its start."""

from __future__ import annotations

import re
import statistics

SYNC = "pw.round.sync"


def read(params: dict, run) -> float | None:
    if run.trace is None:
        return None
    rx = re.compile(params["pattern"])
    spans = run.trace.host_spans
    n_sync = sum(name == SYNC for name, _s, _e in spans)
    durs = [e - s for name, s, e in spans if rx.search(name)]
    if not n_sync or not durs:
        return None
    return 1e3 * sum(durs) / n_sync


def clock_anchors(profile) -> list:
    """(perf_counter seconds, trace seconds) of every anchored event of a
    profile as ``trace_reduce.load`` gives it."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SYNC:
                    perf_ns = dict(ev.stats).get("perf_ns")
                    if perf_ns is not None:
                        out.append((perf_ns * 1e-9, ev.start_ns * 1e-9))
    return sorted(out)


def perf_to_trace(anchors: list) -> tuple:
    """(offset, spread): ``trace seconds = perf_counter seconds - offset``
    by the median anchor, and the distance between the extreme anchors."""
    offs = [p - t for p, t in anchors]
    return statistics.median(offs), max(offs) - min(offs)
