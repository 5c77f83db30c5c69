"""Reader ``roofline_share_of``: as ``roofline_share`` (100 x least time
for the work the traffic asked of a kernel / the kernel's device time),
with ``least`` taken from the module the metric file names (``module``)
and the item size from the system's constant ``itemsize`` names.  No
kernel event (a program without the kernel), no trace, no peak: no
reading."""

from __future__ import annotations

from benchmark.readers import work_between
from benchmark.readers.mfu_of import counting_module


def read(params: dict, run) -> float | None:
    mod = counting_module(params)
    if mod is None or run.peaks is None or run.trace is None \
            or run.trace_window is None or params["shape"] not in run.info:
        return None
    kernel_s = sum(run.trace.events(params["line"], params["pattern"]))
    if kernel_s <= 0:
        return None
    dec, pre = work_between(run, params, *run.trace_window)
    least = getattr(mod, params["least"])(
        run.info[params["shape"]], dec, pre, run.info[params["itemsize"]],
        run.peaks)
    return 100.0 * least["least_s"] / kernel_s
