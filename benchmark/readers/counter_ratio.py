"""Reader ``counter_ratio``: ``scale * num / den`` of two counters' growth
over the window (``"over": "window"``, the default) or over the traced part
of it (``"over": "trace"``).  ``num`` / ``den`` name counters of the system
(``engine.*``, ``encoder.*`` ...) or of the generator (``client.*``).
Nothing to divide by: no reading."""

from __future__ import annotations


def read(params: dict, run) -> float | None:
    c = run.trace_counters if params.get("over") == "trace" else run.counters
    if c is None:
        return None
    num, den = c.get(params["num"]), c.get(params["den"])
    if num is None or not den:
        return None
    return params.get("scale", 1.0) * num / den
