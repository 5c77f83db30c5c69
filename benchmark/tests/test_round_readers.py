"""The readers of the engine's own phases and spans (PR 25), on the CPU:
``span_stat`` on a synthetic recorder, ``host_span_ms`` and the clock
anchors on the hand-written ``trace_sample/round_phases_trace.textproto``.

    python3 -m pytest benchmark/tests/test_round_readers.py -q
"""

from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce as T  # noqa: E402
from benchmark.readers import host_span_ms, span_stat  # noqa: E402

SAMPLE = os.path.join(ROOT, "benchmark", "trace_sample",
                      "round_phases_trace.textproto")
WINDOW = (100.0, 151.0)


def span(name, trace, t0, t1, **attrs):
    return types.SimpleNamespace(name=name, trace_id=trace, t0=t0, t1=t1,
                                 attrs=attrs or None)


def request(trace, arrival, queue, pending, wait, prefill, **extra):
    """The spans one request leaves: queue, then the three parts of its
    TTFT, tiled from its arrival at the engine."""
    a, b, c = arrival + pending, arrival + pending + wait, \
        arrival + pending + wait + prefill
    return [span("serve.queue", trace, arrival - queue, arrival),
            span("engine.pending", trace, arrival, a),
            span("engine.prefill_wait", trace, a, b),
            span("engine.prefill", trace, b, c, chunks=3, **extra),
            span("engine.request", trace, arrival, c + 1.0,
                 ttft_s=c - arrival)]


def ring() -> list:
    out = []
    # five requests of the window: queue 1..5 ms, pending 0.1 s, wait
    # 1..5 s, prefill 0.5 s each
    for i in range(1, 6):
        out += request(f"r{i}", 100.0 + i, 1e-3 * i, 0.1, float(i), 0.5)
    # first token before the window and after it: not of the window
    out += request("early", 90.0, 0.5, 1.0, 2.0, 3.0)
    out += request("late", 149.0, 0.5, 1.0, 2.0, 3.0)
    # a re-admission's spans do not count into the request's own
    out += [span("engine.pending", "r1", 120.0, 129.0, readmit=True),
            span("engine.prefill", "r1", 129.0, 130.0, readmit=True)]
    # mixed rounds: 40 + 44 of 48 + 48 inside, one round outside, one chain
    out += [span("pw.round.build", "run", 110.0, 110.001, kind="mixed",
                 tokens=40, budget=48),
            span("pw.round.build", "run", 111.0, 111.001, kind="mixed",
                 tokens=44, budget=48),
            span("pw.round.build", "run", 99.0, 99.001, kind="mixed",
                 tokens=1, budget=48),
            span("pw.round.build", "run", 112.0, 112.001, kind="chain",
                 tokens=256, budget=256)]
    return sorted(out, key=lambda s: s.t1)


QUEUE = {"spans": ["serve.queue"], "p": 50, "scale": 1000.0}
WAIT = {"spans": ["engine.pending", "engine.prefill_wait"],
        "without": "readmit", "p": 50, "scale": 1000.0}
RUN = {"spans": ["engine.prefill"], "without": "readmit", "p": 100,
       "scale": 1000.0}
FILL = {"spans": ["pw.round.build"], "where": {"kind": "mixed"},
        "num": "tokens", "den": "budget", "scale": 100.0}


def test_span_stat_percentile_and_ratio():
    r = ring()
    n = len(r)
    assert span_stat.from_ring(QUEUE, r, n, WINDOW) == pytest.approx(3.0)
    assert span_stat.from_ring(WAIT, r, n, WINDOW) == pytest.approx(3100.0)
    assert span_stat.from_ring(RUN, r, n, WINDOW) == pytest.approx(500.0)
    assert span_stat.from_ring(FILL, r, n, WINDOW) \
        == pytest.approx(100.0 * 84 / 96)
    # with the re-admission counted, r1's wait would read 10.1 s
    loose = {k: v for k, v in WAIT.items() if k != "without"}
    assert span_stat.from_ring(dict(loose, p=100), r, n, WINDOW) \
        == pytest.approx(10100.0)


def test_span_stat_gives_no_reading_where_there_is_nothing_to_read():
    r = ring()
    n = len(r)
    # a program without the lifecycle (the parent commit): no ttft_s
    bare = [s for s in r if s.name != "engine.request"]
    assert span_stat.from_ring(WAIT, bare, len(bare), WINDOW) is None
    assert span_stat.from_ring(dict(FILL, spans=["pw.round.nothing"]),
                               r, n, WINDOW) is None
    assert span_stat.from_ring(dict(WAIT, min_count=6), r, n, WINDOW) is None
    # the ring evicted spans and the oldest one kept finished inside the
    # window: part of the window is gone
    kept = [s for s in r if s.t1 >= 101.0]
    assert span_stat.from_ring(WAIT, kept, n, WINDOW) is None
    assert span_stat.from_ring(FILL, kept, n, WINDOW) is None
    # evicted, but everything of the window is still there
    kept = [s for s in r if s.t1 >= 99.0]
    assert kept[0].t1 < WINDOW[0] and len(kept) < n
    assert span_stat.from_ring(WAIT, kept, n, WINDOW) \
        == pytest.approx(3100.0)


def test_span_stat_reads_the_programs_recorder():
    from pathway_tpu import obs

    rec = obs.recorder()
    rec.clear()
    ctx = (obs.new_trace_id(), 0)
    # whatever this process recorded before is gone from the ring; the
    # oldest span kept finished before the window, so nothing of it is
    obs.record_span("warm.up", 1.0, 2.0, ctx)
    obs.record_span("pw.round.build", 110.0, 110.1, ctx, kind="mixed",
                    tokens=30, budget=48)
    run = types.SimpleNamespace(window=WINDOW)
    assert span_stat.read(FILL, run) == pytest.approx(62.5)
    assert span_stat.read(WAIT, run) is None
    rec.clear()


def test_host_span_ms_on_the_recorded_sample():
    run = types.SimpleNamespace(
        trace=T.Trace(T.load(SAMPLE), n_devices=1))
    host = {"pattern": r"^pw\.(round\.(admit|build|h2d|deliver)|"
            r"(mixed_step|decode_step|chain_dispatch|prefill|verify_step)"
            r"(_sampled)?)$"}
    assert host_span_ms.read(host, run) \
        == pytest.approx((3.8 + 3.8 + 2.5) / 3)
    assert host_span_ms.read({"pattern": r"^pw\.round\.h2d$"}, run) \
        == pytest.approx((2.0 + 2.0 + 0.8) / 3)
    assert host_span_ms.read({"pattern": r"^pw\.round\.nothing$"},
                             run) is None
    # the sample as idle_gaps reads it: each gap whole to one span
    s0, s1 = run.trace.span()
    gaps = dict(run.trace.idle_gaps(s0, s1))
    assert gaps["pw.round.h2d"] == pytest.approx(0.0077)
    assert gaps["pw.chain_dispatch"] == pytest.approx(0.0027)
    assert gaps["pw.round.deliver"] == pytest.approx(0.0005)
    # a trace of a program without the phases (PR 24's sample): nothing
    old = types.SimpleNamespace(trace=T.Trace(T.load(os.path.join(
        ROOT, "benchmark", "trace_sample", "tiny_trace.textproto")), 1))
    assert host_span_ms.read(host, old) is None
    assert host_span_ms.read(host, types.SimpleNamespace(trace=None)) is None


def test_clock_anchors_map_perf_counter_to_the_traces_clock():
    anchors = host_span_ms.clock_anchors(T.load(SAMPLE))
    assert [round(t, 9) for _p, t in anchors] == [0.0045, 0.0138, 0.0217]
    offset, spread = host_span_ms.perf_to_trace(anchors)
    assert offset == pytest.approx(5000.0, abs=1e-9)
    assert spread == pytest.approx(30e-9, abs=1e-12)
    # a perf_counter reading lands on the trace's clock: the second sync
    assert 5000.0138 - offset == pytest.approx(0.0138, abs=1e-7)
    assert host_span_ms.clock_anchors(T.load(os.path.join(
        ROOT, "benchmark", "trace_sample", "tiny_trace.textproto"))) == []
