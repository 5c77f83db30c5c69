"""Round-14 device cost observatory — ISSUE 15 acceptance.

Pins the tentpole guarantees of pathway_tpu/obs/{profiler,memory,costdb}:

- every jitted serving-path program registers at first lowering and
  shows up in ``/debug/profile`` with non-null FLOPs, bytes, measured
  dispatch ms and a roofline placement;
- a recompile records PROVENANCE: program name, the triggering arg
  shapes/dtypes, and a stack summary naming the calling test;
- the HBM ledger's KV term matches BlockPool's own ``per_shard_bytes``
  and an unfittable ``(num_blocks, chain_steps, max_batch)`` is
  rejected at CONSTRUCTION with the budget and the largest fitting
  alternative named (``hbm_fit="clamp"`` shrinks the pool instead);
- the cost store round-trips through its JSON file, keyed by backend
  fingerprint, and its writer thread shuts down cleanly;
- profiler-always-on cost stays <= 2% of the chained-decode window,
  measured in the same noise-immune per-event form as
  tests/test_obs.py's recorder guard;
- ``pathway_xla_*`` Prometheus lines render and ``cli.py profile``
  prints the ranked table.
"""

import json
import socket
import time
import urllib.request

import jax
import numpy as np
import pytest

from pathway_tpu.kvcache import PagedDecodeEngine
from pathway_tpu.models.decoder import DecoderConfig, init_decoder_params
from pathway_tpu.obs import costdb as costdb_mod
from pathway_tpu.obs import memory as obs_memory
from pathway_tpu.obs import profiler

_CFG = DecoderConfig(
    vocab_size=64, d_model=64, n_layers=2, n_heads=8, d_ff=128, max_len=128
)


@pytest.fixture(scope="module")
def params():
    return init_decoder_params(_CFG, jax.random.PRNGKey(0))


def _engine(params, name, **kw):
    kw.setdefault("num_blocks", 96)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("seq_buckets", (16, 32, 64))
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("chain_steps", 8)
    return PagedDecodeEngine(_CFG, params, name=name, **kw)


# -- program registry ------------------------------------------------------


def test_registry_records_serving_programs_with_cost_analysis(params):
    eng = _engine(params, "t_prof_reg")
    reqs = [([1, 2, 3, 4, 5], 10), ([7, 8, 9], 10)]
    n0 = profiler.registry().total_compiles()
    eng.generate_batch(list(reqs))
    eng.generate_batch(list(reqs))  # warm pass: dispatch reservoirs fill
    events = profiler.registry().compile_events(since=n0)
    assert events, "engine programs never registered"
    progs = {e.program for e in events}
    assert "pw.chained_decode" in progs
    for e in events:
        assert e.compile_s > 0
        assert e.stack, "compile event lost its stack summary"
    # cost introspection: FLOPs/bytes non-null for the engine programs.
    # Resolve THIS engine's records through its own compile events —
    # other tests' engines share program names under different buckets
    recs = {(r.program, r.bucket): r
            for r in profiler.registry().records()}
    by_prog = {e.program: recs[(e.program, e.bucket)] for e in events}
    for rec in by_prog.values():
        analysis = rec.try_analyze()
        assert analysis and analysis["flops"], rec.program
        assert analysis["bytes_accessed"], rec.program
    # the warm pass recorded real dispatch windows for the chained program
    assert by_prog["pw.chained_decode"].dispatches > 0
    assert by_prog["pw.chained_decode"].ms_percentile(0.5) > 0


def test_recompile_event_records_provenance():
    import jax.numpy as jnp

    f = profiler.profiled_jit("t_prof.toy", lambda x: x * 2 + 1)
    f(jnp.ones((4,), jnp.float32))
    n0 = profiler.registry().total_compiles()
    f(jnp.ones((8,), jnp.float32))  # new static shape -> new compile
    events = profiler.registry().compile_events(since=n0)
    assert len(events) == 1
    desc = events[0].describe()
    assert "t_prof.toy" in desc
    assert "f32[8]" in desc  # the triggering shapes
    assert "test_profiler.py" in desc  # the stack names this file


def test_window_fracs_decomposes_a_run(params):
    eng = _engine(params, "t_prof_frac")
    reqs = [([5, 6, 7, 8], 12), ([9, 10], 12)]
    eng.generate_batch(list(reqs))  # compile outside the window
    t0 = time.perf_counter()
    eng.generate_batch(list(reqs))
    t1 = time.perf_counter()
    fracs = profiler.registry().window_fracs(t0, t1)
    assert fracs, "no program dispatch landed in the window"
    assert "pw.chained_decode" in fracs
    assert all(0 < v <= 1.000001 for v in fracs.values())


# -- /debug/profile on every HTTP surface ----------------------------------


def test_debug_profile_endpoint_serves_full_rows(params):
    """ISSUE 15 acceptance: every jitted serving-path program appears in
    ``/debug/profile`` with non-null FLOPs, bytes, measured dispatch ms,
    and roofline placement."""
    eng = _engine(params, "t_prof_http")
    reqs = [([1, 2, 3], 8), ([4, 5, 6, 7], 8)]
    eng.generate_batch(list(reqs))
    eng.generate_batch(list(reqs))  # warm: measured dispatch ms exists

    from pathway_tpu.engine.telemetry import MetricsServer

    class _Sched:
        frontier = 0
        operators = ()

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    srv = MetricsServer(_Sched(), port=port)
    srv.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/profile", timeout=30
        ).read()
        data = json.loads(body)
        # many engines (across the test session) share program names
        # with different buckets: keep each program's most-dispatched row
        rows = {}
        for r in data["programs"]:
            cur = rows.get(r["program"])
            if cur is None or (r["dispatches"] or 0) > \
                    (cur["dispatches"] or 0):
                rows[r["program"]] = r
        # the serving-path programs this workload dispatched, with the
        # full acceptance tuple on each
        for prog in ("pw.chained_decode", "pw.mixed_step"):
            assert prog in rows, sorted(rows)
        checked = 0
        for prog, row in rows.items():
            if not prog.startswith("pw.") or not row["dispatches"]:
                continue
            assert row["flops"], prog
            assert row["bytes_accessed"], prog
            assert row["dispatch_ms_p50"], prog
            assert row.get("roofline", {}).get("bound") in (
                "memory", "compute",
            ), prog
            assert row.get("mfu") is not None, prog
            checked += 1
        assert checked >= 1
        assert data["n_device_programs"] >= 2
        assert data["compile_s_total"] > 0
        # the dashboard renders the device-programs table
        html = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/", timeout=10
        ).read().decode()
        assert "device programs" in html
        # pathway_xla_* rides the same /metrics scrape
        metrics = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        assert "pathway_xla_programs" in metrics
        assert "pathway_xla_compiles_total" in metrics
    finally:
        srv.stop()

    # the same table as the CLI's ranked text form
    from pathway_tpu.cli import format_profile_table

    table = format_profile_table(data)
    lines = table.splitlines()
    assert any("pw.chained_decode" in ln for ln in lines)
    assert "MFU" in lines[0] and "share" in lines[0]
    # ranked: first data row is the program with the largest dispatch share
    assert lines[2].split()[0] == data["programs"][0]["program"]


def test_counter_tracks_in_flight_recorder_dump(params):
    from pathway_tpu import obs

    eng = _engine(params, "t_prof_ctr")
    eng.generate_batch([([3, 1, 4], 8)])
    eng.generate_batch([([3, 1, 4], 8)])
    dump = json.loads(obs.recorder().chrome_trace_json())
    counters = [e for e in dump["traceEvents"] if e["ph"] == "C"]
    assert counters, "no counter tracks in the dump"
    assert any(e["name"].startswith("pw.xla.") for e in counters)
    assert all("dispatch_ms" in e["args"] for e in counters)


# -- HBM ledger + pre-flight fit -------------------------------------------


def _lfm2_published():
    """LFM2-8B-A1B's published widths, layers 1 to 13 (the benchmark's
    cut): one dense conv layer, then (attention, conv, conv, conv) x 3."""
    from pathway_tpu.models.lfm2 import ATTENTION as A, CONV as C, Lfm2Config

    return Lfm2Config(n_dense_layers=1, max_len=2048, dtype="bfloat16",
                      layer_types=(C, A, C, C, C, A, C, C, C, A, C, C, C))


@pytest.mark.parametrize("family", ["decoder", "lfm2"])
def test_hbm_plan_kv_term_matches_block_pool(params, family):
    from pathway_tpu.kvcache.backend import make_backend
    from pathway_tpu.kvcache.block_pool import BlockPool

    if family == "lfm2":
        # a hybrid family: K/V over the attention layers and K/V heads
        # only, the conv arena beside it, expert leaves at their own width
        import jax.numpy as jnp

        cfg = _lfm2_published()
        plan = obs_memory.hbm_plan(
            cfg, num_blocks=64, block_size=16, max_batch_size=16,
            chain_steps=16, dtype=jnp.bfloat16,
        )
        pool = make_backend(
            "hybrid", num_blocks=64, block_size=16, n_layers=3, n_heads=8,
            head_dim=64, dtype=jnp.bfloat16, name="t_prof_hybrid",
            conv_layers=10, conv_width=2048, conv_slots=16,
        )
        assert plan.per_block_bytes == 3 * 2 * 16 * 512 * 2 == 98304
        assert plan.conv_bytes == pool.conv_bytes == 10 * 17 * 2 * 2048 * 2
        assert plan.kv_bytes + plan.conv_bytes == pool.per_shard_bytes
        # 4,605M parameters in bf16, the experts 12 x 32 x 3 x 2048 x 1792
        experts = 12 * 32 * 3 * 2048 * 1792
        assert plan.params_bytes == cfg.param_count() * 2
        assert 0.9 < experts * 2 / plan.params_bytes < 0.93
        assert 9.1e9 < plan.params_bytes < 9.3e9
        assert plan.total_bytes == plan.params_bytes + plan.kv_bytes \
            + plan.conv_bytes + plan.temp_bytes
        return
    plan = obs_memory.hbm_plan(
        _CFG, num_blocks=64, block_size=8, max_batch_size=4,
        chain_steps=8, dtype=np.float32, params=params,
    )
    pool = BlockPool(
        num_blocks=64, block_size=8, n_layers=_CFG.n_layers,
        n_heads=_CFG.n_heads, head_dim=_CFG.d_model // _CFG.n_heads,
        name="t_prof_pool",
    )
    assert plan.kv_bytes == pool.per_shard_bytes
    assert plan.conv_bytes == 0
    # exact params term from the live pytree
    leaves = jax.tree_util.tree_leaves(params)
    assert plan.params_bytes == sum(
        l.size * l.dtype.itemsize for l in leaves
    )
    assert plan.fits  # no budget resolved on the CPU fallback
    assert plan.budget_bytes is None


def test_unfittable_config_rejected_at_construction(params):
    """ISSUE 15 satellite: an unfittable (num_blocks, chain_steps,
    max_batch) raises ValueError at CONSTRUCTION naming the HBM budget
    and the largest fitting alternative — never an OOM at dispatch."""
    budget = 4 << 20  # 4MB: the 4096-block pool alone needs ~256MB
    with pytest.raises(ValueError) as exc:
        PagedDecodeEngine(
            _CFG, params, num_blocks=4096, block_size=16,
            max_batch_size=8, chain_steps=8, name="t_prof_oom",
            hbm_budget_bytes=budget,
        )
    msg = str(exc.value)
    assert "4.0MB" in msg and "budget" in msg  # the budget, named
    assert "num_blocks=" in msg  # the largest fitting alternative
    assert "largest fitting alternative" in msg
    # the named alternative really fits: rebuild with it
    import re

    alt_blocks = int(re.search(r"num_blocks=(\d+)", msg).group(1))
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=alt_blocks, block_size=16,
        max_batch_size=8, chain_steps=8, name="t_prof_alt",
        hbm_budget_bytes=budget,
    )
    assert eng.hbm_plan.fits
    assert eng.hbm_plan.total_bytes <= budget


def test_clamp_mode_shrinks_the_pool_and_still_serves(params):
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=4096, block_size=16, max_batch_size=4,
        chain_steps=4, name="t_prof_clamp", hbm_budget_bytes=4 << 20,
        hbm_fit="clamp",
    )
    assert eng.pool.num_blocks < 4096
    assert eng.hbm_plan.fits
    out = eng.generate_batch([([1, 2, 3], 5)])
    assert len(out[0]) == 5


def test_fits_with_what_if(params):
    base = obs_memory.hbm_plan(
        _CFG, num_blocks=64, block_size=8, max_batch_size=4,
        chain_steps=8, dtype=np.float32, params=params,
    )
    plan_budget = base.total_bytes + 1024  # just fits
    plan = obs_memory.hbm_plan(
        _CFG, num_blocks=64, block_size=8, max_batch_size=4,
        chain_steps=8, dtype=np.float32, params=params,
        budget_bytes=plan_budget,
    )
    assert plan.fits
    # doubling the pool overflows the just-fitting budget; the what-if
    # says so without constructing anything
    assert not plan.fits_with(num_blocks=128)
    assert plan.fits_with(num_blocks=32)
    assert plan.budget_bytes == plan_budget


def test_engine_unaffected_without_budget(params):
    # no budget resolvable on CPU: huge configs construct exactly as
    # before (the ledger reports, nothing enforces)
    eng = _engine(params, "t_prof_nobudget", num_blocks=512)
    assert eng.hbm_plan.budget_bytes is None
    assert eng.pool.num_blocks == 512


# -- cost store -------------------------------------------------------------


def test_costdb_roundtrip_and_fingerprint(tmp_path):
    path = str(tmp_path / "costdb.json")
    db = costdb_mod.CostDB(path=path, flush_interval_s=60.0)
    db.observe("pw.chained_decode", "f32[4,8]", ms=3.25, flops=1e9,
               mfu=0.02)
    db.observe("pw.chained_decode", "f32[4,8]", ms=2.75)
    ent = db.get("pw.chained_decode", "f32[4,8]")
    assert ent["n"] == 2
    assert ent["ms_best"] == 2.75
    assert ent["flops"] == 1e9
    assert ent["fingerprint"] == costdb_mod.backend_fingerprint()
    db.shutdown()
    # a fresh instance reads the same file back
    db2 = costdb_mod.CostDB(path=path, flush_interval_s=60.0)
    ent2 = db2.get("pw.chained_decode", "f32[4,8]")
    assert ent2 and ent2["ms_best"] == 2.75
    # raw file is versioned JSON keyed program|bucket|fingerprint
    raw = json.load(open(path))
    assert raw["version"] == 1
    key = f"pw.chained_decode|f32[4,8]|{db.fingerprint}"
    assert key in raw["entries"]
    db2.shutdown()


def test_costdb_writer_thread_lifecycle(tmp_path):
    path = str(tmp_path / "costdb2.json")
    db = costdb_mod.CostDB(path=path, flush_interval_s=0.05)
    db.observe("p", "b", ms=1.0)
    assert db.writer_alive
    deadline = time.time() + 5.0
    while time.time() < deadline:
        try:
            if json.load(open(path))["entries"]:
                break
        except (OSError, ValueError):
            pass
        time.sleep(0.02)
    else:
        pytest.fail("writer thread never flushed")
    writer = db._writer  # capture BEFORE shutdown clears the slot
    db.shutdown()
    assert not db.writer_alive
    # the actual thread object really stopped (pytest hygiene)
    assert writer is not None and not writer.is_alive()


def test_costdb_flush_merges_concurrent_writers(tmp_path):
    """Two processes sharing the file must append to — not erase — each
    other's keys: flush() re-reads and merges the on-disk entries."""
    path = str(tmp_path / "shared.json")
    a = costdb_mod.CostDB(path=path, flush_interval_s=60.0)
    b = costdb_mod.CostDB(path=path, flush_interval_s=60.0)  # loaded empty
    a.observe("prog_a", "bkt", ms=1.0)
    a.flush()
    b.observe("prog_b", "bkt", ms=2.0)
    b.flush()  # a naive overwrite would drop prog_a here
    entries = json.load(open(path))["entries"]
    progs = {e["program"] for e in entries.values()}
    assert progs == {"prog_a", "prog_b"}
    a.shutdown()
    b.shutdown()


def test_hbm_fit_typo_fails_loudly(params):
    with pytest.raises(ValueError, match="hbm_fit"):
        PagedDecodeEngine(_CFG, params, num_blocks=16, block_size=4,
                          name="t_prof_fit_typo", hbm_fit="Clamp")


def test_publish_to_costdb_writes_measured_programs(params, tmp_path):
    eng = _engine(params, "t_prof_pub")
    eng.generate_batch([([1, 2, 3], 6)])
    eng.generate_batch([([1, 2, 3], 6)])  # warm dispatches
    db = costdb_mod.CostDB(path=str(tmp_path / "pub.json"),
                           flush_interval_s=60.0)
    n = profiler.publish_to_costdb(db, peak_flops=1e9)
    assert n >= 1
    rows = db.entries("pw.chained_decode")
    assert rows and rows[0]["ms_best"] > 0
    db.shutdown()


# -- overhead guard ---------------------------------------------------------


def test_profiler_overhead_guard_on_chained_microbench(params):
    """The <=2% budget in the noise-immune per-event form (same
    methodology as tests/test_obs.py's recorder guard): (profiled calls
    + dispatch records in a chained window) x (measured per-event
    bookkeeping cost) must stay under 2% of the window's wall."""
    eng = _engine(params, "t_prof_overhead")
    reqs = [([1 + i, 2, 3, 4], 12) for i in range(4)]
    eng.generate_batch(list(reqs))  # compile + warm every shape
    calls0 = eng._chained.calls + eng._mixed.calls + eng._step.calls
    rec0 = sum(r.dispatches for r in profiler.registry().records())
    t0 = time.perf_counter()
    eng.generate_batch(list(reqs))
    wall = time.perf_counter() - t0
    n_calls = (eng._chained.calls + eng._mixed.calls + eng._step.calls
               - calls0)
    n_disp = sum(
        r.dispatches for r in profiler.registry().records()
    ) - rec0
    assert n_calls > 0
    per_call = eng._chained.probe_overhead(20000)
    # dispatch-record cost: one deque append + dict lookup under a lock
    probe = profiler.ProfiledFunction("t_prof.ovh", lambda x: x)
    probe._key = None
    t0 = time.perf_counter()
    reps = 20000
    for _ in range(reps):
        probe.record_dispatch(1e-6, t_end=1.0, items=1)
    per_record = (time.perf_counter() - t0) / reps
    overhead_frac = (per_call * n_calls + per_record * n_disp) / wall
    assert overhead_frac <= 0.02, (
        f"profiler overhead {overhead_frac:.4f} > 2% ({n_calls} calls x "
        f"{per_call * 1e6:.2f}us + {n_disp} records x "
        f"{per_record * 1e6:.2f}us / {wall:.3f}s wall)"
    )
