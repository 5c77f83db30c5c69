"""A round's device gap by durations alone (ISSUE 35): the benchmark's
readers ``module_gap_ms`` and ``sync_overhang_ms`` and the helper
``causal_clock_bounds`` on the hand-written
``benchmark/trace_sample/round_gap_trace.textproto`` (one chain and three
mixed rounds, a wave's boundary of 40 ms, the device plane's clock 1.5 ms
early), through the metric files the benchmark itself reads them by.
"""

import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SAMPLES = os.path.join(ROOT, "benchmark", "trace_sample")
NEW = ("mixed_round_gap_ms", "round_d2h_ms", "mixed_launch_ms",
       "mixed_round_unnamed_ms")
# what the sample's header says each reads, in ms
PLANTED = {"mixed_round_gap_ms": 3.75, "round_d2h_ms": 0.23,
           "mixed_launch_ms": 1.2, "mixed_round_unnamed_ms": 0.1}


def _sample_text(name: str = "round_gap_trace.textproto") -> str:
    with open(os.path.join(SAMPLES, name)) as f:
        return f.read()


def _run(text: str):
    from jax.profiler import ProfileData

    from benchmark import trace_reduce as T

    return types.SimpleNamespace(
        trace=T.Trace(ProfileData.from_text_proto(text), n_devices=1))


def _read(metric: str, run, **over):
    """The metric as ``benchmark/run.py`` reads it: the reader its file
    names, on the file's parameters (``over``: the sample holds three
    mixed rounds where a traced window holds dozens)."""
    import importlib

    with open(os.path.join(ROOT, "benchmark", "metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(dict(spec, **over), run)


def _aligned(text: str) -> str:
    """The sample with the device plane shifted back onto the host's
    clock."""
    assert text.count("timestamp_ns: 8500000") == 2
    return text.replace("timestamp_ns: 8500000", "timestamp_ns: 10000000")


def _without_d2h(text: str) -> str:
    """The trace a parent's program leaves: the pull is no phase of its
    own (here: an event the readers do not take for one)."""
    assert text.count('name: "pw.round.d2h"') == 1
    return text.replace('name: "pw.round.d2h"',
                        'name: "np.asarray(jax.Array)"')


@pytest.mark.parametrize("clock", ["early", "aligned"])
@pytest.mark.parametrize("metric", NEW)
def test_the_metrics_read_what_the_sample_plants(metric, clock):
    """Each of the four reads its planted value, and the planted 1.5 ms
    between the two planes' clocks moves none of them: they are made of
    durations."""
    text = _sample_text()
    run = _run(text if clock == "early" else _aligned(text))
    assert _read(metric, run, min_count=3) \
        == pytest.approx(PLANTED[metric], abs=1e-9)


def test_the_gap_is_a_median_so_a_waves_boundary_does_not_enter():
    from benchmark.readers import module_gap_ms as G

    run = _run(_sample_text())
    mods = G.modules(run.trace)
    assert [m[0] for m in mods] == ["jit__chained_fn(456)"] \
        + ["jit__mixed_fn(123)"] * 3
    found = G.gaps(mods, "mixed")
    assert [round(1e3 * g, 6) for g in found.values()] == [3.45, 3.75, 43.45]
    assert sum(found.values()) / 3 > 16e-3  # what a mean would read
    assert _read("mixed_round_gap_ms", run, min_count=3) \
        == pytest.approx(3.75)
    # the module that ENDS the gap chooses it: the chain is the first
    # module of the line and has no gap before it
    assert G.gaps(mods, "chained") == {}


@pytest.mark.parametrize("metric", NEW)
def test_no_reading_where_there_is_too_little_to_read(metric):
    """Never a zero: no trace, or fewer mixed rounds than the files ask
    for (eight; the sample holds three)."""
    assert _read(metric, types.SimpleNamespace(trace=None)) is None
    if metric != "round_d2h_ms":  # a sum over all dispatches: no count
        assert _read(metric, _run(_sample_text())) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_trace_without_d2h_reads_the_gap_alone(metric):
    """A parent's program laid under these files: its ``pw.round.sync``
    still holds the pull, so launch + tail under the new names would be
    another number.  The gap needs no span of the program's and reads."""
    run = _run(_without_d2h(_sample_text()))
    got = _read(metric, run, min_count=3)
    if metric == "mixed_round_gap_ms":
        assert got == pytest.approx(3.75)
    else:
        assert got is None
    # the same on PR 25's sample, whose rounds were recorded before the
    # split, and on PR 24's, which has no phases at all
    old = _run(_sample_text("round_phases_trace.textproto"))
    tiny = _run(_sample_text("tiny_trace.textproto"))
    if metric == "mixed_round_gap_ms":
        # one gap: module 3.6-8.8, then 12.9
        assert _read(metric, old, min_count=1) == pytest.approx(4.1)
    else:
        assert _read(metric, old, min_count=1) is None
        assert _read(metric, tiny, min_count=1) is None


def test_a_call_that_finds_no_module_gives_no_reading():
    """Round 2's module gone from the device plane (both lines): a call
    between two others pairs with nothing, and what was paired cannot be
    trusted.  A round cut at the trace's end is another matter."""
    from benchmark.readers import module_gap_ms as G

    text = _sample_text()
    event = "    events { metadata_id: 1 offset_ps: 38800000000 " \
        "duration_ps: 21000000000 }\n"
    assert text.count(event) == 1
    holed = _run(text.replace(event, ""))
    mods, ds = G.whole(holed.trace)
    assert [d["module"] for d in G.dispatches(holed.trace, mods)] \
        == [0, 1, None, 2]
    assert ds is None
    assert G.causal_clock_bounds(holed.trace) is None
    assert _read("mixed_launch_ms", holed, min_count=2) is None
    assert _read("mixed_round_unnamed_ms", holed, min_count=2) is None
    last = "    events { metadata_id: 1 offset_ps: 103250000000 " \
        "duration_ps: 21000000000 }\n"
    assert text.count(last) == 1
    cut = _run(text.replace(last, ""))
    assert len(G.whole(cut.trace)[1]) == 3
    assert _read("mixed_launch_ms", cut, min_count=2) \
        == pytest.approx(1.25)  # rounds 1 and 2: 1.2 and 1.3


def test_dispatches_pair_calls_with_syncs_pulls_and_modules():
    from benchmark.readers import module_gap_ms as G

    _mods, ds = G.whole(_run(_sample_text()).trace)
    assert [d["name"] for d in ds] == ["pw.chain_dispatch"] \
        + ["pw.mixed_step"] * 3
    assert [d["module"] for d in ds] == [0, 1, 2, 3]
    # the host's spans since the sync before: the chain's pull (0.02) is
    # the control, a mixed round's is 0.3
    assert [round(1e3 * d["host"], 6) for d in ds] == [0.7, 1.35, 1.55, 1.55]
    pulls = [1e3 * (d["d2h"][1] - d["d2h"][0]) for d in ds]
    assert pulls == pytest.approx([0.02, 0.3, 0.3, 0.3])
    assert pulls[0] < pulls[1] / 5


@pytest.mark.parametrize("clock,offset_ms", [("early", -1.5),
                                             ("aligned", 0.0)])
def test_causal_clock_bounds_hold_the_planted_offset(clock, offset_ms):
    """A module cannot start before its call starts nor end after its sync
    returns: the interval holds the planted offset and is as wide as the
    sample's wake-up (0.2) below it and the chain's call + launch (0.8 +
    0.4) above."""
    from benchmark.readers import module_gap_ms as G

    text = _sample_text()
    run = _run(text if clock == "early" else _aligned(text))
    low, high = G.causal_clock_bounds(run.trace)
    assert low <= offset_ms * 1e-3 <= high
    assert 1e3 * low == pytest.approx(offset_ms - 0.2)
    assert 1e3 * high == pytest.approx(offset_ms + 1.2)


def test_benchmark_json_names_the_four_in_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    tail = [by_name[name] for name in NEW]
    # later metrics follow them (PR 37: paged_query_tile_fill_pct)
    at = bench["per_layer"].index(tail[0])
    assert bench["per_layer"][at:at + 4] == tail
    for m in tail:
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "Step programs",
                     "moves": "out_tok_per_s", "workloads": cells}
    # the accepted host metrics do not see the new phase: their divisor
    # and their pattern stand
    import re

    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "round_host_ms.json")) as f:
        host = re.compile(json.load(f)["pattern"])
    assert not host.search("pw.round.d2h") and host.search("pw.round.h2d")
