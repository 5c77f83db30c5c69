"""Reader ``trace_event_ms``: device milliseconds per matching event.

``line`` (``XLA Modules`` for whole programs, ``XLA Ops`` for operations)
and ``pattern`` (a regular expression on the event's name) choose the
events; the sum of their device durations is divided by their number,
times ``per_info`` (a constant of the system, such as the decode steps one
chained program runs) when given.  No matching event: no reading."""

from __future__ import annotations


def read(params: dict, run) -> float | None:
    if run.trace is None:
        return None
    durs = run.trace.events(params["line"], params["pattern"])
    if not durs:
        return None
    per = run.info[params["per_info"]] if "per_info" in params else 1
    return 1e3 * sum(durs) / (len(durs) * per)
