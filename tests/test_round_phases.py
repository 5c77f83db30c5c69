"""Engine rounds and request lifecycles traced from inside (ISSUE 25).

- ``obs.phase``: one helper, two sinks (the profiler's host plane and the
  flight recorder), one name;
- the ``pw.round.*`` phases and the program-call phases of an engine run
  are contiguous, never overlap and cover the engine thread's time, on the
  mixed, the step and the chained path;
- a request's ``engine.pending`` + ``engine.prefill_wait`` +
  ``engine.prefill`` is its recorded TTFT, also across a preemption, with
  one span per transition and none per token;
- the hoist of the host-to-device transfers out of the call expressions
  changed no token (``h2d_hoist_tokens.json``: the parent commit's tokens
  for the runs of ``_hoist_runs``, taken on this CPU before the hoist).
"""

import json
import os

import jax
import numpy as np
import pytest

from pathway_tpu import obs
from pathway_tpu.kvcache import PagedDecodeEngine
from pathway_tpu.models.decoder import DecoderConfig, init_decoder_params
from pathway_tpu.obs import tracer

_CFG = DecoderConfig(
    vocab_size=64, d_model=64, n_layers=2, n_heads=8, d_ff=128, max_len=128
)
# wide enough that a round's device work (milliseconds on the CPU) stands
# well above the microseconds of interpreter time between two phases
_WIDE = DecoderConfig(
    vocab_size=64, d_model=512, n_layers=4, n_heads=8, d_ff=1024, max_len=128
)
_PHASES = ("admit", "build", "h2d", "sync", "d2h", "deliver")


@pytest.fixture(scope="module")
def params():
    return init_decoder_params(_CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _clean_recorder():
    rec = obs.recorder()
    rec.clear()
    rec.enabled = True
    yield
    rec.clear()
    rec.enabled = True


@pytest.fixture(scope="module")
def wide_params():
    return init_decoder_params(_WIDE, jax.random.PRNGKey(0))


def _engine(params, name, cfg=_CFG, **kw):
    kw.setdefault("num_blocks", 96)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("seq_buckets", (16, 32, 64))
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("chain_steps", 8)
    return PagedDecodeEngine(cfg, params, name=name, **kw)


def _prompts(sizes, seed=25):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, _CFG.vocab_size, size=n)]
            for n in sizes]


# -- obs.phase ------------------------------------------------------------


class _FakeAnnotation:
    seen: list = []

    def __init__(self, name, **attrs):
        self.seen.append(["new", name, attrs])

    def __enter__(self):
        self.seen.append(["enter"])

    def __exit__(self, *exc):
        self.seen.append(["exit"])


@pytest.mark.parametrize("recording", [True, False])
def test_phase_one_span_and_the_annotation(monkeypatch, recording):
    """A phase enters the profiler annotation under its own name and
    attributes, and lands ONE span of the same name in the recorder, with
    the attributes set in its body too; with recording disabled the
    annotation is still entered and no span is written."""
    monkeypatch.setattr(tracer, "_annotation_cls", _FakeAnnotation)
    _FakeAnnotation.seen = []
    ctx = (obs.new_trace_id(), 0)
    if recording:
        with obs.phase("pw.round.build", ctx, kind="mixed") as ph:
            ph.set(rows=3)
    else:
        with obs.disabled(), obs.phase("pw.round.build", ctx, kind="mixed"):
            pass
    assert _FakeAnnotation.seen == [
        ["new", "pw.round.build", {"kind": "mixed"}], ["enter"], ["exit"]]
    spans = obs.recorder().snapshot()
    if not recording:
        assert spans == []
        return
    assert [s.name for s in spans] == ["pw.round.build"]
    (s,) = spans
    assert s.trace_id == ctx[0] and s.attrs == {"kind": "mixed", "rows": 3}
    assert (s.t0, s.t1) == (ph.t0, ph.t1) and s.t1 >= s.t0


def test_phase_enters_the_real_annotation_class():
    """Without the fake: what a phase enters is jax's TraceAnnotation."""
    tracer._annotation_cls = None
    with obs.phase("pw.round.sync", perf_ns=1) as ph:
        assert isinstance(ph._ann, jax.profiler.TraceAnnotation)
    assert [s.name for s in obs.recorder().snapshot()] == ["pw.round.sync"]


# -- the phases of a run ----------------------------------------------------


def _every_sync_has_its_d2h(phases, kinds):
    """The sync is two sibling phases: every ``pw.round.sync`` (the wait
    until the result is ready on the device) is followed at once by
    exactly one ``pw.round.d2h`` (its pull into numpy) of the same
    ``kind``, the kind of the program call before them, and there is no
    ``d2h`` but these; every kind of ``kinds`` is seen.  No duration is
    asserted: a CPU array has no copy to wait for."""
    of_call = {"pw.mixed_step": "mixed", "pw.decode_step": "step",
               "pw.chain_dispatch": "chain", "pw.verify_step": "verify"}
    syncs = [i for i, s in enumerate(phases) if s.name == "pw.round.sync"]
    assert syncs and len(syncs) == sum(
        s.name == "pw.round.d2h" for s in phases)
    seen = set()
    for i in syncs:
        sync, d2h = phases[i], phases[i + 1]
        assert d2h.name == "pw.round.d2h", d2h.name
        assert d2h.attrs["kind"] == sync.attrs["kind"]
        assert d2h.attrs["bytes"] > 0
        call = next(s for s in reversed(phases[:i]) if s.name in of_call)
        assert of_call[call.name] == sync.attrs["kind"]
        seen.add(sync.attrs["kind"])
    assert kinds <= seen <= set(of_call.values()), seen


@pytest.mark.parametrize("path,kw,n_new", [
    # prompts of 3 chunks, one token each: mixed rounds and nothing else
    ("mixed", {"chain_steps": 8}, 1),
    # no chaining allowed: prefill, then one-token-per-row steps
    ("step", {"chain_steps": 1}, 10),
    # a quiet queue after prefill: double-buffered chains
    ("chain", {"chain_steps": 4}, 14),
])
def test_round_phases_tile_the_engine_thread(wide_params, path, kw, n_new):
    eng = _engine(wide_params, "t_phases_" + path, cfg=_WIDE, **kw)
    sizes = (20, 17, 23, 9, 21, 12)
    # compile every shape first, on other prompts (no prefix-cache hits)
    eng.generate_batch([(p, n_new) for p in _prompts(sizes, seed=1)])
    obs.recorder().clear()
    before = eng.pool.stats.snapshot()
    eng.generate_batch([(p, n_new) for p in _prompts(sizes)])
    spans = obs.recorder().snapshot()
    (run,) = [s for s in spans if s.name == "engine.run"]
    phases = sorted((s for s in spans if s.trace_id == run.trace_id
                     and s.name.startswith("pw.")), key=lambda s: s.t0)
    names = {s.name for s in phases}
    assert {"pw.round." + p for p in _PHASES} <= names
    calls = names - {"pw.round." + p for p in _PHASES}
    assert calls and calls <= {"pw.mixed_step", "pw.decode_step",
                               "pw.chain_dispatch"}
    kinds = {s.attrs["kind"] for s in phases if s.name == "pw.round.build"}
    assert path in kinds, kinds
    assert kinds <= {"mixed", "step", "chain", "none"}
    # in program order on one thread: contiguous, never overlapping
    assert {s.tid for s in phases} == {run.tid}
    assert phases[0].t0 >= run.t0 and phases[-1].t1 <= run.t1
    for a, b in zip(phases, phases[1:]):
        assert b.t0 >= a.t1, (a.name, b.name)
    covered = sum(s.t1 - s.t0 for s in phases)
    # what lies between two phases is a few lines of the loop.  The bound
    # is on the typical gaps, not on the wall clock: a worker descheduled
    # between two phases on a loaded machine stretches that one gap (the
    # suite runs under six workers), so the largest tenth of the gaps is
    # left out and the rest is held to 5% of the phases' own time
    gaps = sorted([phases[0].t0 - run.t0, run.t1 - phases[-1].t1] + [
        b.t0 - a.t1 for a, b in zip(phases, phases[1:])])
    typical = sum(gaps[: int(0.9 * len(gaps))])
    assert typical <= 0.05 * covered, (typical, covered, gaps[-3:])
    # the same time, by phase, in the pool's counters
    snap = eng.pool.stats.snapshot()
    assert set(snap["round_s"]) == set(_PHASES) | {"dispatch"}
    grown = sum(snap["round_s"].values()) - sum(before["round_s"].values())
    assert grown == pytest.approx(covered, rel=1e-6)
    # every build says what it packed; every sync carries the anchor: this
    # clock's reading taken on the way into the phase, so after the phase
    # before it ended and before its own start.  How long before is held
    # at the median, not in every round: a worker descheduled between the
    # reading and the phase's start (six workers share the machine)
    # stretches that one distance
    lead = []
    for prev, s in zip([run] + phases, phases):
        if s.name == "pw.round.sync":
            anchor = s.attrs["perf_ns"] * 1e-9
            assert (prev.t1 if prev is not run else run.t0) - 1e-6 \
                <= anchor <= s.t0 + 1e-6, (prev.name, anchor, s.t0)
            lead.append(s.t0 - anchor)
    assert sorted(lead)[len(lead) // 2] < 1e-3, sorted(lead)[-3:]
    _every_sync_has_its_d2h(phases, {path, "mixed"})
    for s in phases:
        if s.name == "pw.round.build":
            assert {"rows", "tokens", "budget", "waiting"} <= set(s.attrs)
            assert s.attrs["tokens"] <= s.attrs["budget"]
            if s.attrs["rows"]:  # the round calls a paged kernel
                assert 0 < s.attrs["kv_keys"] <= s.attrs["kv_key_lanes"]
        elif s.name == "pw.round.h2d":
            assert s.attrs["arrays"] in (5, 11) and s.attrs["bytes"] > 0
            assert s.attrs["transfers"] == 1
    if path == "chain":
        # chain N's callbacks run AFTER chain N+1 went out: a deliver
        # follows a chain dispatch before the next sync, truthfully
        order = [s.name for s in phases]
        i = order.index("pw.chain_dispatch", order.index(
            "pw.chain_dispatch") + 1)
        assert order[i + 1] == "pw.round.deliver"
        assert order[i + 2] == "pw.round.admit"
    mixed = [s for s in phases if s.name == "pw.round.build"
             and s.attrs["kind"] == "mixed"]
    assert len(mixed) == snap["mixed_steps"] - before["mixed_steps"]
    assert len(mixed) * (4 + 8) \
        == snap["mixed_tokens_budget"] - before["mixed_tokens_budget"]
    assert sum(s.attrs["tokens"] for s in mixed) \
        == snap["mixed_tokens_used"] - before["mixed_tokens_used"]


def test_sync_and_d2h_under_the_watchdog(params):
    """With a watchdog both waits run on its helper thread, and both
    phases are still entered on the engine thread, in the same order; the
    `engine.sync` fault point fires inside ``pw.round.sync``, so a hang
    there is the watchdog's to catch and ``pw.round.d2h`` is not
    reached."""
    from pathway_tpu import faults
    from pathway_tpu.serve.admission import EngineFailedError

    eng = _engine(params, "t_phases_watchdog", watchdog_timeout_s=30.0,
                  chain_steps=4)
    reqs = [(p, 9) for p in _prompts((20, 9, 13))]
    want = _engine(params, "t_phases_no_watchdog",
                   chain_steps=4).generate_batch(list(reqs))
    obs.recorder().clear()
    assert eng.generate_batch(list(reqs)) == want
    spans = obs.recorder().snapshot()
    (run,) = [s for s in spans if s.name == "engine.run"]
    phases = sorted((s for s in spans if s.trace_id == run.trace_id
                     and s.name.startswith("pw.")), key=lambda s: s.t0)
    assert {s.tid for s in phases} == {run.tid}
    _every_sync_has_its_d2h(phases, {"mixed", "chain"})
    for a, b in zip(phases, phases[1:]):
        assert b.t0 >= a.t1, (a.name, b.name)
    # a wedge at the fault point: the sync's phase closes with the
    # watchdog's error, and no readback follows it
    hung = _engine(params, "t_phases_hung", watchdog_timeout_s=0.3,
                   max_restarts=0, chain_steps=4)
    obs.recorder().clear()
    faults.install("engine.sync", "hang", nth=1, arg_ms=1500)
    try:
        with pytest.raises(EngineFailedError, match="watchdog deadline"):
            hung.generate_batch(list(reqs))
    finally:
        faults.clear()
    names = [s.name for s in sorted(obs.recorder().snapshot(),
                                    key=lambda s: s.t0)
             if s.name.startswith("pw.round.")]
    assert names.count("pw.round.sync") == 1 and names[-1] == "pw.round.sync"
    assert "pw.round.d2h" not in names


@pytest.mark.parametrize("wide", [False, True], ids=["toy", "whole_tiles"])
@pytest.mark.parametrize("chain_steps", [1, 4], ids=["step", "chain"])
def test_build_counts_the_keys_its_rows_attend(params, wide_params,
                                               chain_steps, wide):
    """``kv_keys`` / ``kv_key_lanes`` on ``pw.round.build``: one request
    alone, so every round has one live row and the sums are known - a
    prompt of 21 tokens in chunks of 8 attends 8 + 16 + 21 keys, then
    every decode step (alone or in a chain) the context it has reached.
    The lanes round each context up to whole spans: a block of 4 where the
    pool's lanes are no whole tiles (the toy's 64), 128 keys where they
    are (eight heads of 64)."""
    P, n_new = 21, 9
    cfg, par = (_WIDE, wide_params) if wide else (_CFG, params)
    eng = _engine(par, f"t_keys_{chain_steps}_{wide}", cfg=cfg,
                  chain_steps=chain_steps)
    span = 128 if wide else 4
    assert eng._span_keys == span
    eng.generate_batch([(p, n_new) for p in _prompts((P,))])
    builds = [s for s in obs.recorder().snapshot()
              if s.name == "pw.round.build" and s.attrs.get("rows")]
    contexts = [8, 16, P] + [P + i for i in range(1, n_new)]
    assert sum(s.attrs["kv_keys"] for s in builds) == sum(contexts)
    assert sum(s.attrs["kv_key_lanes"] for s in builds) \
        == sum(-(-c // span) * span for c in contexts)
    assert {s.attrs["kind"] for s in builds} \
        == {"mixed", "chain" if chain_steps > 1 else "step"}
    snap = eng.pool.stats.snapshot()
    assert snap["kv_keys"] == sum(contexts)
    assert snap["kv_key_lanes"] == sum(s.attrs["kv_key_lanes"]
                                       for s in builds)


@pytest.mark.parametrize("shared", [False, True], ids=["alone", "shared"])
def test_build_counts_the_blocks_its_tokens_land_in(params, shared):
    """``kv_write_blocks`` on the ``pw.round.build`` of mixed rounds: the
    distinct pool blocks the K/V writer moves a layer.  A prompt of 21
    tokens in chunks of 8 over blocks of 4 lands in 2 + 2 + 2 blocks
    (the last chunk's five tokens reach into a second one).  A second
    prompt that shares the first 20 tokens and comes a run later maps the
    five resident blocks and streams position 20 alone: one block."""
    eng = _engine(params, f"t_wblocks_{shared}")
    prompt = _prompts((21,))[0]
    eng.generate_batch([(prompt, 2)])
    want = 6
    if shared:
        eng.generate_batch([(prompt[:20] + [(prompt[20] + 1) % 64], 2)])
        want += 1
    builds = [s for s in obs.recorder().snapshot()
              if s.name == "pw.round.build"]
    mixed = [s for s in builds if s.attrs["kind"] == "mixed"]
    assert all("kv_write_blocks" not in s.attrs for s in builds
               if s.attrs["kind"] != "mixed")
    assert all(1 <= s.attrs["kv_write_blocks"] <= s.attrs["tokens"]
               for s in mixed)
    assert sum(s.attrs["kv_write_blocks"] for s in mixed) == want
    assert eng.pool.stats.snapshot()["kv_write_blocks"] == want


def test_round_counters_are_on_metrics(params):
    """The operator's view of the same seconds: /metrics carries the
    round's time by phase and the mixed steps' fill, beside the host gap."""
    from pathway_tpu.serve import metrics as M

    eng = _engine(params, "t_round_metrics")
    eng.generate_batch([(p, 6) for p in _prompts((20, 9, 13))])
    snap = eng.pool.stats.snapshot()
    lines = M.render_prometheus_lines()
    lbl = 'pool="t_round_metrics"'
    for phase in _PHASES + ("dispatch",):
        line = next(x for x in lines if x.startswith(
            f'pathway_kv_round_seconds_total{{{lbl},phase="{phase}"}} '))
        assert float(line.split()[-1]) == pytest.approx(
            snap["round_s"][phase], abs=1e-6)
    assert f"pathway_kv_mixed_tokens_used_total{{{lbl}}} " \
        f"{snap['mixed_tokens_used']}" in lines
    assert f"pathway_kv_mixed_tokens_budget_total{{{lbl}}} " \
        f"{snap['mixed_steps'] * eng.mixed_tokens}" in lines
    assert 0 < snap["mixed_tokens_used"] <= snap["mixed_tokens_budget"]
    assert f"pathway_kv_attended_keys_total{{{lbl}}} " \
        f"{snap['kv_keys']}" in lines
    assert f"pathway_kv_attended_key_lanes_total{{{lbl}}} " \
        f"{snap['kv_key_lanes']}" in lines
    assert 0 < snap["kv_keys"] <= snap["kv_key_lanes"]
    assert f"pathway_kv_write_blocks_total{{{lbl}}} " \
        f"{snap['kv_write_blocks']}" in lines
    assert 0 < snap["kv_write_blocks"] <= snap["mixed_tokens_used"]
    assert any(x.startswith(f"pathway_kv_host_gap_seconds_total{{{lbl}}}")
               for x in lines)
    # one transfer a dispatch, whatever it is made of (kvcache/packing.py)
    h2d = [s for s in obs.recorder().snapshot() if s.name == "pw.round.h2d"]
    assert snap["h2d_transfers"] == len(h2d) > 0
    assert snap["h2d_arrays"] == sum(s.attrs["arrays"] for s in h2d) \
        >= 5 * len(h2d)
    assert f"pathway_kv_h2d_arrays_total{{{lbl}}} " \
        f"{snap['h2d_arrays']}" in lines
    assert f"pathway_kv_h2d_transfers_total{{{lbl}}} " \
        f"{snap['h2d_transfers']}" in lines


# -- one transfer a dispatch: the packed operand ------------------------------

_B, _NB, _C, _K = 4, 16, 8, 4  # rows, table blocks, chunk, chain steps


def _round_arrays(kind, cache, sampled, rng):
    """Arrays of the shapes and dtypes the engine's builders make for one
    round of ``kind`` (``_build_decode``, ``_dispatch_chain``,
    ``_build_mixed``, ``_build_verify``), with the cache's row extras and
    the five sampling arrays, filled with whatever bits."""
    T = {"mixed": _B + _C, "verify": _B * _C}.get(kind)
    shapes = {
        "step": [(_B,), (_B,), (_B, _NB), (_B,), (_B,)],
        "chained": [(_B,), (_B,), (_B, _NB), (_B, _K), (_B, _K)],
    }.get(kind) or [(T,), (T,), (_B, _NB), (_B,), (_B,), (_B, _C), (T,),
                    (T,), (T,), (T,), (T if kind == "verify" else _B,)]
    shapes = shapes + {"decoder": [], "hybrid": [(_B,)],
                       "windowed": [(_B, _NB)]}[cache]
    out = [rng.integers(-2**31, 2**31, size=s, dtype=np.int64)
           .astype(np.int32) for s in shapes]
    if sampled:
        # temperature and top_p are float32 and ride as their bits: a
        # denormal, a negative zero and a NaN payload come back as sent
        temp = np.array([0.8, -0.0, 1e-45, 0.0], np.float32)
        top_p = rng.integers(0, 2**31, size=_B).astype(np.int32) \
            .view(np.float32)
        ints = [rng.integers(0, 2**31, size=_B).astype(np.int32)
                for _ in range(3)]
        out += [temp, ints[0], top_p, ints[1], ints[2]]
    return out


@pytest.mark.parametrize("cache", ["decoder", "hybrid", "windowed"])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", ["step", "mixed", "chained", "verify"])
def test_packed_operand_unpacks_bit_exact(kind, sampled, cache):
    """What the builders make crosses in one int32 buffer and the jitted
    program cuts back exactly the arrays its function takes: shapes,
    dtypes and every bit, the float sampling arrays included."""
    from pathway_tpu.kvcache.packing import RoundLayout

    rng = np.random.default_rng(32)
    host = _round_arrays(kind, cache, sampled, rng)
    n = {"step": 5, "chained": 5}.get(kind, 11) \
        + (cache != "decoder") + 5 * sampled
    assert len(host) == n
    layout = RoundLayout()
    packed = layout.pack(host)
    assert packed.dtype == np.int32 and packed.ndim == 1
    assert packed.nbytes == sum(a.nbytes for a in host) == 4 * layout.size

    def _echo_fn(params, pool, *arrays):
        return arrays, pool

    prog = layout.program(_echo_fn)
    assert prog.__name__ == "_echo_fn"
    got, _pool = jax.jit(prog, donate_argnums=(1,))(
        None, np.zeros(3, np.float32), packed)
    assert len(got) == len(host)
    for g, a in zip(got, host):
        g = np.asarray(g)
        assert g.shape == a.shape and g.dtype == a.dtype
        assert np.array_equal(g.view(np.int32), a.view(np.int32))
    # a second round of the same shapes goes through the same layout, in
    # a buffer of its own
    again = layout.pack(_round_arrays(kind, cache, sampled, rng))
    assert again.shape == packed.shape and again is not packed
    assert not np.shares_memory(again, packed)


@pytest.mark.parametrize("how", ["shape", "dtype", "count"])
def test_a_round_that_departs_from_the_layout_raises(how):
    """The compiled program's slices are the first round's: a later round
    whose arrays differ raises, the layout is not laid out anew."""
    from pathway_tpu.kvcache.packing import RoundLayout

    rng = np.random.default_rng(33)
    host = _round_arrays("mixed", "windowed", True, rng)
    layout = RoundLayout()
    layout.pack(host)
    fields = layout.fields
    if how == "shape":
        # the same words in all, another shape: the table transposed
        host[-6] = np.ascontiguousarray(host[-6].T)
    elif how == "dtype":
        host[-5] = host[-5].view(np.int32)  # temperature as integers
    else:
        host = host[:-1]
    with pytest.raises(ValueError, match="depart from the program's layout"):
        layout.pack(host)
    assert layout.fields is fields
    with pytest.raises(TypeError, match="word for word"):
        RoundLayout().pack([np.zeros(4, np.int64)])
    with pytest.raises(RuntimeError, match="no layout yet"):
        RoundLayout().unpack(np.zeros(4, np.int32))


def test_an_engine_round_of_another_shape_raises(params):
    """The same through the engine: its rounds pack by the layout of the
    program they call, fixed at the program's first round."""
    eng = _engine(params, "t_layout_departs")
    eng.generate_batch([(p, 3) for p in _prompts((9, 5))])
    layout = eng._layout("mixed")
    assert [f[0] for f in layout.fields][:3] == [(12,), (12,), (4, eng.max_blocks_per_seq)]
    assert eng._layout("mixed") is layout
    assert layout is not eng._layout("mixed", sampled=True)
    cached = eng.pool.blocks_in_use  # the prefix cache's, of the first run
    eng.mixed_tokens += 1  # a later round builds longer token arrays
    from pathway_tpu.serve.admission import EngineFailedError

    with pytest.raises(EngineFailedError,
                       match="depart from the program's layout"):
        eng.generate_batch([(p, 3) for p in _prompts((7,), seed=2)])
    assert eng._layout("mixed").fields is layout.fields
    assert eng.pool.blocks_in_use == cached


def test_the_benchmark_reads_arrays_per_transfer(params):
    """``h2d_arrays_per_transfer`` (benchmark/metrics) through its reader
    on this run's recorder: the counters' ratio, between a chain's five
    arrays and a mixed round's eleven; spans without ``transfers`` (the
    program before the packed operand) give no reading."""
    import time
    import types

    from benchmark.readers import span_stat

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "metrics", "h2d_arrays_per_transfer.json")) as f:
        spec = json.load(f)
    eng = _engine(params, "t_h2d_metric")
    t0 = time.perf_counter()
    eng.generate_batch([(p, 12) for p in _prompts((20, 9, 13))])
    window = (t0, time.perf_counter())
    ring = obs.recorder().snapshot()  # nothing evicted: all that was kept
    value = span_stat.from_ring(spec, ring, len(ring), window)
    snap = eng.pool.stats.snapshot()
    assert value == pytest.approx(snap["h2d_arrays"] / snap["h2d_transfers"])
    assert 5 < value < 11
    before = [types.SimpleNamespace(
        name=s.name, t0=s.t0, t1=s.t1, trace_id=s.trace_id,
        attrs={k: v for k, v in (s.attrs or {}).items() if k != "transfers"})
        for s in ring]
    assert span_stat.from_ring(spec, before, len(before), window) is None


def test_the_benchmark_reads_chunk_row_tokens(params):
    """``chunk_row_tokens`` (benchmark/metrics) through its reader on this
    run's recorder: prompt tokens a chunk row over the mixed rounds, no
    more than the engine's chunk; spans without ``chunk_rows`` (the
    program before it counted them) give no reading."""
    import time
    import types

    from benchmark.readers import span_stat

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "metrics", "chunk_row_tokens.json")) as f:
        spec = json.load(f)
    eng = _engine(params, "t_chunk_metric")
    lengths = (20, 9, 13)
    t0 = time.perf_counter()
    eng.generate_batch([(p, 4) for p in _prompts(lengths)])
    window = (t0, time.perf_counter())
    ring = obs.recorder().snapshot()
    value = span_stat.from_ring(spec, ring, len(ring), window)
    rows = sum(s.attrs["chunk_rows"] for s in ring
               if s.name == "pw.round.build"
               and s.attrs.get("kind") == "mixed")
    assert value == pytest.approx(sum(lengths) / rows)
    assert 1 <= value <= eng.prefill_chunk
    before = [types.SimpleNamespace(
        name=s.name, t0=s.t0, t1=s.t1, trace_id=s.trace_id,
        attrs={k: v for k, v in (s.attrs or {}).items()
               if k not in ("chunk_rows", "chunk_tokens")})
        for s in ring]
    assert span_stat.from_ring(spec, before, len(before), window) is None


def test_build_counts_the_query_columns_its_tiles_cover(wide_params):
    """``kv_query_cols`` / ``kv_query_tile_cols`` on the ``pw.round.build``
    of mixed rounds, against the kernel's own rule: eight heads of 64 at a
    chunk of 64 in f32 lie in a first tile of 8 columns and a wide one of 64
    (``_col_tiles``: 128 a wide tile where the chunk has them).  One request alone: a prompt of 70 tokens is a row of
    64 live columns, then one of 6, each beside three idle rows of one; a
    hand-built round of a decode row, an idle row, a chunk's 9 columns and
    a full chunk; and the benchmark's reader of the two
    (``paged_query_tile_fill_pct``)."""
    import importlib
    import time

    from benchmark.readers import span_stat

    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    eng = _engine(wide_params, "t_qcols", cfg=_WIDE, prefill_chunk=64,
                  seq_buckets=(64, 128))
    assert pa._col_tiles(2, 64, 1, eng._pool_kwargs["dtype"]) == (8, 64)
    t0 = time.perf_counter()
    eng.generate_batch([(p, 3) for p in _prompts((70,))])
    window = (t0, time.perf_counter())
    ring = obs.recorder().snapshot()
    builds = [s for s in ring if s.name == "pw.round.build"]
    mixed = [s.attrs for s in builds if s.attrs["kind"] == "mixed"]
    assert [(a["kv_query_cols"], a["kv_query_tile_cols"]) for a in mixed] \
        == [(64 + 3, 64 + 3 * 8), (6 + 3, 8 + 3 * 8)]
    assert all("kv_query_cols" not in s.attrs for s in builds
               if s.attrs["kind"] != "mixed")

    class _Ph:
        def set(self, **attrs):
            self.attrs = attrs

    ph, rows = _Ph(), np.array([1, 1, 9, 64], np.int32)
    eng._note_query_cols(ph, rows)
    assert eng._query_layout == (64, 4)  # rep 1: the rule keeps the rows
    assert ph.attrs == {
        "kv_query_cols": 75, "kv_query_slots": 4 * 64,
        "kv_query_tile_cols": int(pa.query_tile_columns(
            rows, 64, 8, 64, 512, eng._pool_kwargs["dtype"]).sum())}
    assert ph.attrs["kv_query_tile_cols"] == 8 + 8 + 64 + 64
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "metrics", "paged_query_tile_fill_pct.json")) as f:
        spec = json.load(f)
    assert span_stat.from_ring(spec, ring, len(ring), window) \
        == pytest.approx(100.0 * (67 + 9) / (88 + 32))


# -- a request's lifecycle ----------------------------------------------------


def _lifecycle(spans):
    """trace id -> name -> spans, for the engine.* spans of requests."""
    out: dict = {}
    for s in spans:
        if s.name.startswith("engine."):
            out.setdefault(s.trace_id, {}).setdefault(s.name, []).append(s)
    return {t: by for t, by in out.items() if "engine.request" in by}


@pytest.mark.parametrize("preempt", [False, True])
def test_request_lifecycle_sums_to_recorded_ttft(params, preempt):
    if preempt:
        # 12 usable blocks of 4: four requests of 10 + 10 tokens cannot
        # coexist, so decode MUST preempt (tests/test_kvcache.py)
        eng = PagedDecodeEngine(
            _CFG, params, num_blocks=13, block_size=4, max_batch_size=4,
            seq_buckets=(12, 20), prefix_sharing=False, name="t_life_oom")
        reqs = [(p, 10) for p in _prompts((10, 10, 10, 10), seed=3)]
    else:
        eng = _engine(params, "t_life")
        reqs = [(p, 12) for p in _prompts((20, 5, 23, 9, 17, 12))]
    before = eng.pool.stats.snapshot()
    out = eng.generate_batch(list(reqs))
    assert [len(o) for o in out] == [n for _p, n in reqs]
    after = eng.pool.stats.snapshot()
    assert (after["preemptions"] > before["preemptions"]) == preempt
    spans = obs.recorder().snapshot()
    assert not [s for s in spans if s.name == "engine.decode_step"]
    assert not [s for s in spans if s.name == "engine.sync"]
    life = _lifecycle(spans)
    assert len(life) == len(reqs)
    ttfts = []
    readmits = 0
    for by in life.values():
        (root,) = by["engine.request"]
        first = root.t0 + root.attrs["ttft_s"]
        ttfts.append(root.attrs["ttft_s"])
        for name in ("engine.pending", "engine.prefill_wait",
                     "engine.prefill", "engine.decode"):
            plain = [s for s in by.get(name, ())
                     if not (s.attrs or {}).get("readmit")]
            assert len(plain) == 1, (name, by.get(name))
            readmits += len(by.get(name, ())) - 1
            assert all(s.parent_id == root.span_id for s in by[name])
        # what came before the first token tiles [arrival, first token]
        parts = sorted((s for name in ("engine.pending",
                                       "engine.prefill_wait",
                                       "engine.prefill")
                        for s in by[name] if s.t1 <= first + 1e-9),
                       key=lambda s: s.t0)
        assert parts[0].t0 == root.t0
        for a, b in zip(parts, parts[1:]):
            assert b.t0 == a.t1
        assert sum(s.t1 - s.t0 for s in parts) == pytest.approx(
            root.attrs["ttft_s"], abs=1e-3)
        (dec,) = by["engine.decode"]
        assert dec.t0 == pytest.approx(first, abs=1e-9)
        assert dec.t1 <= root.t1
        assert dec.attrs["tokens"] == root.attrs["emitted"] - 1
        (pre,) = [s for s in by["engine.prefill"]
                  if not (s.attrs or {}).get("readmit")]
        assert pre.attrs["chunks"] >= 1
        assert pre.attrs["rounds"] \
            == pre.attrs["chunks"] + pre.attrs["rounds_skipped"]
    assert (readmits > 0) == preempt
    # the very numbers the pool recorded
    recent = list(eng.pool.stats.recent_ttfts)[-len(reqs):]
    assert sorted(recent) == pytest.approx(sorted(ttfts), abs=1e-9)


# -- the hoist changed no token ---------------------------------------------


def _hoist_runs(params, name, **kw):
    eng = _engine(params, "t_hoist_" + name, **kw)
    prompts = _prompts((5, 19, 11, 26, 7, 14))
    greedy = eng.generate_batch([(p, 12) for p in prompts])
    sampled = eng.generate_batch([
        (p, 12, {"sampling": (0.8, 8, 0.9, 1000 + i)})
        for i, p in enumerate(prompts)])
    return greedy, sampled


@pytest.mark.parametrize("name,kw", [
    ("chained", {"chain_steps": 8}),
    ("step", {"chain_steps": 1}),
])
def test_tokens_identical_before_and_after_the_h2d_hoist(params, name, kw):
    with open(os.path.join(os.path.dirname(__file__),
                           "h2d_hoist_tokens.json")) as f:
        before = json.load(f)
    greedy, sampled = _hoist_runs(params, name, **kw)
    assert greedy == before[name + "_greedy"]
    assert sampled == before[name + "_sampled"]
