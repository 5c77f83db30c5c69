"""Reader ``client_percentile``: the ``p``-th percentile of one list of
samples (``sample``), times ``scale``.  Samples come from the generator's
clock (``ttft_s``, ``retrieve_s`` ...) or from the system (``engine.*``).
All samples of the window count, and a request that never answered reads
``inf``; fewer than ``min_count`` samples, or a percentile that falls among
those that never answered: no reading."""

from __future__ import annotations

import math


def percentile(values: list, p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    v = sorted(values)
    k = (len(v) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    if k == lo or math.isinf(v[hi]):  # no arithmetic on inf
        return v[lo] if k == lo else v[hi]
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def read(params: dict, run) -> float | None:
    v = run.samples.get(params["sample"])
    if not v or len(v) < params.get("min_count", 1):
        return None
    value = params.get("scale", 1.0) * percentile(v, params["p"])
    return value if math.isfinite(value) else None
