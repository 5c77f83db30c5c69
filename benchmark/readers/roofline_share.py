"""Reader ``roofline_share``: 100 x (least time the chip could take for
the work the traffic asked of a kernel) / (the kernel's device time).

``line`` / ``pattern`` find the kernel's events in the trace; ``least`` is
a function of ``benchmark/flops.py`` that takes the configuration's shape
(``shape``, a key of the system's constants), the contexts of the tokens
decoded and the prompts (length, share) prefilled while the trace ran, the
cache's item size and the chip's peaks.  No kernel event: no reading."""

from __future__ import annotations

from benchmark import flops
from benchmark.readers import work_between


def read(params: dict, run) -> float | None:
    if run.peaks is None:  # a rehearsal off the chip has no peak
        return None
    if run.trace is None or run.trace_window is None:
        return None
    kernel_s = sum(run.trace.events(params["line"], params["pattern"]))
    if kernel_s <= 0:
        return None
    dec, pre = work_between(run, params, *run.trace_window)
    least = getattr(flops, params["least"])(
        run.info[params["shape"]], dec, pre, run.info["kv_itemsize"],
        run.peaks)
    return 100.0 * least["least_s"] / kernel_s
