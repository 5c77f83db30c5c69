"""From a profiler trace (``.xplane.pb``) to busy/idle, time per named
event, and the idle gaps by the host span that covers each.

One module, so that every PR computes the same numbers the same way.  It
reads the trace with ``jax.profiler.ProfileData`` and nothing else.
Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds the operations that ran on the device and ``XLA Modules`` the whole
programs.  Host spans are the ``pw.*`` ``TraceAnnotation`` events on the
host plane's threads; the two share the trace's clock.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_SPAN = re.compile(r"^pw\.")


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO text,
    ``%fusion.12 = bf16[...] fusion(...)``: the operation's own name is what
    stands before `` = `` (operands that mention a kernel do not count)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".textproto"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    """What the readers ask of one trace.  Times in seconds."""

    def __init__(self, profile, n_devices: int | None = None):
        self.device_lines: dict = {}   # plane -> line name -> [(name, s, e)]
        self.host_spans: list = []     # (name, s, e) of pw.* annotations
        for plane in profile.planes:
            if DEVICE_PLANE.match(plane.name):
                lines = self.device_lines.setdefault(plane.name, {})
                for line in plane.lines:
                    evs = [(ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                           for ev in line.events]
                    if evs:
                        lines.setdefault(line.name, []).extend(evs)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if HOST_SPAN.match(ev.name):
                            self.host_spans.append((
                                ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
        planes = sorted(self.device_lines)
        if n_devices is not None:
            planes = planes[:n_devices]
        self.planes = planes
        self.host_spans.sort(key=lambda x: x[1])

    def line_names(self) -> dict:
        return {p: {ln: len(ev) for ln, ev in lines.items()}
                for p, lines in self.device_lines.items()}

    def events(self, line: str, pattern: str) -> list:
        """Durations (s) of the events on ``line`` whose own name matches
        (see :func:`op_name`)."""
        rx = re.compile(pattern)
        return [e - s for p in self.planes
                for name, s, e in self.device_lines[p].get(line, ())
                if rx.search(op_name(name))]

    def busy(self) -> list:
        """Per device plane, the merged intervals in which an op ran."""
        return [_union([(s, e) for _n, s, e in
                        self.device_lines[p].get(OPS_LINE, ())])
                for p in self.planes]

    def busy_s(self) -> float:
        per = [sum(e - s for s, e in iv) for iv in self.busy()]
        return sum(per) / len(per) if per else 0.0

    def device_ops(self, top: int = 10) -> list:
        tot: dict = {}
        for p in self.planes:
            for name, s, e in self.device_lines[p].get(OPS_LINE, ()):
                tot[name] = tot.get(name, 0.0) + (e - s)
        n = max(len(self.planes), 1)
        return [[" ".join(k.replace("%", "").split())[:160], v / n]
                for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, t0: float, t1: float, top: int = 10) -> list:
        """Idle time of the first device between ``t0`` and ``t1``, summed
        by the ``pw.*`` host span that covers most of each gap."""
        busy = self.busy()[0] if self.planes else []
        edges = [t0] + [x for s, e in busy for x in (s, e)] + [t1]
        tot: dict = {}
        for gs, ge in zip(edges[0::2], edges[1::2]):
            gs, ge = max(gs, t0), min(ge, t1)
            if ge - gs <= 0:
                continue
            best, cover = "outside any pw span", 0.0
            for name, s, e in self.host_spans:
                if s >= ge:
                    break
                ov = min(e, ge) - max(s, gs)
                if ov > cover:
                    best, cover = name, ov
            tot[best] = tot.get(best, 0.0) + (ge - gs)
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

    def device_span(self) -> tuple:
        """First and last instant an operation ran on a device."""
        ts = [x for p in self.planes
              for _n, s, e in self.device_lines[p].get(OPS_LINE, ())
              for x in (s, e)]
        return (min(ts), max(ts)) if ts else (0.0, 0.0)

    def span(self) -> tuple:
        """First and last instant any device op or host span was seen."""
        ts = [x for p in self.planes
              for evs in self.device_lines[p].values()
              for _n, s, e in evs for x in (s, e)]
        ts += [x for _n, s, e in self.host_spans for x in (s, e)]
        return (min(ts), max(ts)) if ts else (0.0, 0.0)
