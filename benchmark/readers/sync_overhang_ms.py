"""Reader ``sync_overhang_ms``: what the host waited in ``pw.round.sync``
beyond the program's own device time, and what of a round's device gap no
span of the program names.  Durations only: device events against device
events, host spans against host spans, so the distance between the two
planes' clocks cannot move either number (it enters only where a call is
paired with its module, ``module_gap_ms.dispatches``).

Over the dispatches whose call matches ``call`` and whose module matches
``pattern`` (the mixed rounds):

- default: the median of ``dur(pw.round.sync) - dur(module)``, clipped at
  0: the launch after the call returned, plus the wake-up.
- ``"mode": "residual"``: the median of ``abs(gap - (host + call +
  overhang))``: ``gap`` the device's idle time before the module
  (``module_gap_ms.gaps``), ``host`` the spans between the sync before and
  the call (the readback ``pw.round.d2h``, deliver, admit, build, h2d),
  ``call`` the program call, ``overhang`` the number above for this
  dispatch.  What is left lies in no span.  The absolute value: an
  over-count shows as well as a hole.

It reads the *narrowed* ``pw.round.sync`` (ready on the device, the pull
its own phase ``pw.round.d2h``): on a trace where a mixed dispatch has no
``pw.round.d2h`` the sync still holds the pull, launch + tail under this
name would be another number, and there is no reading.  Nor is there one
without a trace, with fewer than ``min_count`` (8) such dispatches, or
with a call between two others that finds no module."""

from __future__ import annotations

import re
import statistics

from benchmark.readers import module_gap_ms as G


def read(params: dict, run) -> float | None:
    if run.trace is None:
        return None
    mods, ds = G.whole(run.trace)
    if ds is None:
        return None
    call, module = re.compile(params["call"]), re.compile(params["pattern"])
    mixed = [d for d in ds if call.search(d["name"])
             and module.search(mods[d["module"]][0])]
    if len(mixed) < params.get("min_count", G.MIN_COUNT) \
            or any(d["d2h"] is None for d in mixed):
        return None

    def overhang(d) -> float:
        _n, s, e = mods[d["module"]]
        return max((d["sync"][1] - d["sync"][0]) - (e - s), 0.0)

    if params.get("mode") != "residual":
        return 1e3 * statistics.median(overhang(d) for d in mixed)
    gap = G.gaps(mods, params["pattern"])
    left = [abs(gap[d["module"]] - (d["host"] + d["call"][1] - d["call"][0]
                                    + overhang(d)))
            for d in mixed if d["module"] in gap]
    if len(left) < params.get("min_count", G.MIN_COUNT):
        return None
    return 1e3 * statistics.median(left)
