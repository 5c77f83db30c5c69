"""Reader ``mfu``: the whole step's share of the chip's bf16 peak, over the
traced stretch of the window.

The work: the tokens decoded while the trace ran, each at its context
(``flops``), and the part of every prompt prefilled then (``prompt_flops``),
functions of ``benchmark/flops.py`` over the shape ``shape``.  The time:
the trace's own, from the first to the last operation on the device.
Both over the peak of ``peaks.json`` times the chips used.  The work is
counted from what the clients received, so a kernel taken off the path
cannot silence it; the host's clock only says which tokens fell into the
traced stretch.  No trace: no reading."""

from __future__ import annotations

from benchmark import flops
from benchmark.readers import work_between


def read(params: dict, run) -> float | None:
    if run.peaks is None:  # a rehearsal off the chip has no peak
        return None
    if run.trace is None or run.trace_window is None:
        return None
    s0, s1 = run.trace.device_span()
    if s1 - s0 <= 0:
        return None
    dec, pre = work_between(run, params, *run.trace_window)
    fn = getattr(flops, params["flops"])
    prompt_fn = getattr(flops, params["prompt_flops"])
    shape = run.info[params["shape"]]
    total = sum(fn(shape, c) for c in dec) \
        + sum(share * prompt_fn(shape, p) for p, share in pre)
    if total <= 0:
        return None
    return 100.0 * total / ((s1 - s0) * run.peaks["bf16_flops_per_s"]
                            * run.chips)
