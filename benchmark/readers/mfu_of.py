"""Reader ``mfu_of``: as ``mfu`` (the whole step's share of the chip's bf16
peak over the traced stretch, the work counted from what the clients
received), with the counting functions taken from the module the metric
file names (``module``, beside ``benchmark/flops.py``) - a block family
counts its own operations.  No trace, no peak, no such module: no
reading."""

from __future__ import annotations

import importlib

from benchmark.readers import work_between


def counting_module(params: dict):
    try:
        return importlib.import_module("benchmark." + params["module"])
    except ImportError:
        return None


def read(params: dict, run) -> float | None:
    mod = counting_module(params)
    if mod is None or run.peaks is None or run.trace is None \
            or run.trace_window is None or params["shape"] not in run.info:
        return None
    s0, s1 = run.trace.device_span()
    if s1 - s0 <= 0:
        return None
    dec, pre = work_between(run, params, *run.trace_window)
    shape = run.info[params["shape"]]
    fn = getattr(mod, params["flops"])
    prompt_fn = getattr(mod, params["prompt_flops"])
    total = sum(fn(shape, c) for c in dec) \
        + sum(share * prompt_fn(shape, p) for p, share in pre)
    if total <= 0:
        return None
    return 100.0 * total / ((s1 - s0) * run.peaks["bf16_flops_per_s"]
                            * run.chips)
