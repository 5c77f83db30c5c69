"""Test helpers mirroring the reference's tests/utils.py:314-365.

PATHWAY_THREADS matrix (reference pattern: tests run under multiple worker
counts via env, python/pathway/tests/utils.py:44,111 + CI): when
PATHWAY_THREADS > 1 is set, `run_tables` here routes every test's pipeline
through the sharded ClusterRunner instead of the single-shard engine, so the
whole suite doubles as a multi-worker consistency matrix —
`PATHWAY_THREADS=4 pytest tests/` is the second CI leg (tests/test_matrix.py
runs a representative subset that way inside the default leg)."""

from __future__ import annotations

import os

import pathway_tpu as pw
from pathway_tpu.engine.runner import run_tables as _run_tables_single


def run_tables(*tables):
    n = int(os.environ.get("PATHWAY_THREADS", "1"))
    if n > 1:
        from pathway_tpu.parallel.cluster import run_tables_sharded

        return run_tables_sharded(*tables, n_shards=n)
    return _run_tables_single(*tables)


def _normalize(state: dict, colnames: list[str]):
    import numpy as np

    out = set()
    for key, row in state.items():
        norm = []
        for v in row:
            if isinstance(v, np.ndarray):
                v = ("#arr", v.shape, tuple(np.asarray(v).ravel().tolist()))
            if isinstance(v, float) and v == int(v) and abs(v) < 1e15:
                v = ("#num", float(v))
            if isinstance(v, (int,)) and not isinstance(v, bool):
                v = ("#num", float(v))
            norm.append(v)
        out.add((key, tuple(norm)))
    return out


def _normalize_wo_index(state: dict):
    import numpy as np
    from collections import Counter

    out = Counter()
    for _key, row in state.items():
        norm = []
        for v in row:
            if isinstance(v, np.ndarray):
                v = ("#arr", v.shape, tuple(np.asarray(v).ravel().tolist()))
            if isinstance(v, float) and v == int(v) and abs(v) < 1e15:
                v = ("#num", float(v))
            if isinstance(v, int) and not isinstance(v, bool):
                v = ("#num", float(v))
            try:
                hash(v)
            except TypeError:
                v = repr(v)
            norm.append(v)
        out[tuple(norm)] += 1
    return out


def assert_table_equality(actual: pw.Table, expected: pw.Table) -> None:
    caps = run_tables(actual, expected)
    a, e = caps[0].squash(), caps[1].squash()
    assert _normalize(a, caps[0].column_names) == _normalize(e, caps[1].column_names), (
        f"\nactual:   {sorted(a.items())}\nexpected: {sorted(e.items())}"
    )


def assert_table_equality_wo_index(actual: pw.Table, expected: pw.Table) -> None:
    caps = run_tables(actual, expected)
    a, e = caps[0].squash(), caps[1].squash()
    assert _normalize_wo_index(a) == _normalize_wo_index(e), (
        f"\nactual:   {sorted(map(repr, a.values()))}\nexpected: {sorted(map(repr, e.values()))}"
    )


assert_table_equality_wo_types = assert_table_equality
assert_table_equality_wo_index_types = assert_table_equality_wo_index


def run_and_squash(table: pw.Table) -> dict:
    [cap] = run_tables(table)
    return cap.squash()


def captured_stream(table: pw.Table):
    [cap] = run_tables(table)
    return cap.as_list()


# ---------------------------------------------------------------------------
# Update-stream assertions (reference: DiffEntry +
# assert_key_entries_in_stream_consistent / assert_stream_equality,
# python/pathway/tests/utils.py:183-310)
# ---------------------------------------------------------------------------

class DiffEntry:
    """One expected update: row values (by column), logical time, diff."""

    __slots__ = ("row", "time", "diff")

    def __init__(self, row: dict, time: int, diff: int):
        self.row = row
        self.time = time
        self.diff = diff

    def __repr__(self):  # pragma: no cover - diagnostics
        return f"DiffEntry({self.row}, t={self.time}, diff={self.diff})"


def captured_entries(table: pw.Table):
    """[(row_dict, time, diff)] in emission order."""
    [cap] = run_tables(table)
    cols = cap.column_names
    out = []
    from pathway_tpu.engine.types import unwrap_row

    for e in cap.entries:
        out.append((dict(zip(cols, unwrap_row(e.row))), e.time, e.diff))
    return out


def assert_stream_equal(table: pw.Table, expected: list[DiffEntry]) -> None:
    """The captured update stream must contain exactly the expected
    (row, time, diff) multiset — times included, so behaviors (buffers,
    forgetting) are observable, not just final state."""
    from collections import Counter

    got = Counter(
        (tuple(sorted(r.items())), t, d) for r, t, d in captured_entries(table)
    )
    want = Counter(
        (tuple(sorted(e.row.items())), e.time, e.diff) for e in expected
    )
    assert got == want, (
        f"\nunexpected: {sorted((got - want).items())}"
        f"\nmissing:    {sorted((want - got).items())}"
    )


def assert_key_entries_in_stream_consistent(table: pw.Table) -> None:
    """Every key's diffs must form a valid Z-set trajectory: multiplicity
    never negative and 0/1 at every prefix (single-row keys)."""
    [cap] = run_tables(table)
    state: dict = {}
    for e in sorted(cap.entries, key=lambda e: e.time):
        cur = state.get(e.key, 0) + e.diff
        assert cur in (0, 1), (
            f"key {e.key} multiplicity {cur} at time {e.time}"
        )
        state[e.key] = cur


# -- multi-process fabric test plumbing (round-12/13) ----------------------
# One shared implementation of the fixed-range port anchor, the
# mesh-formation retry predicate, the CLI-supervisor spawn idiom and the
# SIGALRM hard timeout: this container's loopback aborts connects
# intermittently, and ephemeral-range (bind-to-0) anchors race its own
# outbound connections.  Used by test_cluster, test_snapshots,
# test_overlap_fabric and test_chaos_cluster — keep the retryable-error
# set HERE only.

def fabric_port_block(n: int = 4) -> int:
    """Bindable anchor from the fixed 21000-28000 range; the fabric uses
    anchor..anchor+nprocs-1."""
    import random
    import socket

    rng = random.Random()
    for _ in range(64):
        base = 21000 + rng.randrange(0, 6800)
        try:
            for i in range(n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + i))
                s.close()
            return base
        except OSError:
            continue
    raise RuntimeError("no bindable port block in 21000-28000")


def fabric_mesh_flake(stderr: str) -> bool:
    """True when a failed spawn's stderr shows a mesh-formation flake
    (retry with a fresh port block) rather than a real failure."""
    return ("cannot reach peer" in stderr
            or "peers connected" in stderr
            or "cannot bind fabric port" in stderr)


def spawn_cluster(script, processes: int, threads: int = 1,
                  timeout: int = 150, extra_env: dict | None = None,
                  attempts: int = 4, restart: int = 0, check: bool = True):
    """The shared spawn-with-fixed-port-range + mesh-flake-retry idiom
    (previously duplicated across test_overlap_fabric / test_cluster /
    test_snapshots).  Runs the script under the CLI supervisor and
    returns the final CompletedProcess; a mesh-formation flake retries
    on a fresh port block, a real failure is surfaced (when ``check``)
    or returned for the caller to assert on (chaos cells that EXPECT a
    typed abort pass ``check=False``)."""
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(repo)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PW_FABRIC_CONNECT_TIMEOUT_S", "8")  # cheap mesh retries
    env.pop("PATHWAY_THREADS", None)
    env.pop("PATHWAY_PROCESSES", None)
    if extra_env:
        env.update(extra_env)
    res = None
    for _attempt in range(attempts):
        cmd = [
            sys.executable, "-m", "pathway_tpu", "spawn",
            "--threads", str(threads), "--processes", str(processes),
            "--first-port", str(fabric_port_block(processes)),
        ]
        if restart:
            cmd += ["--restart", str(restart)]
        cmd += ["--", sys.executable, str(script)]
        res = subprocess.run(cmd, env=env, capture_output=True, text=True,
                             timeout=timeout)
        if res.returncode == 0:
            return res
        if not fabric_mesh_flake(res.stderr):
            break  # real failure: surface it, never retry it away
    if check:
        raise AssertionError(
            f"spawn failed (rc={res.returncode}):\n"
            f"stdout={res.stdout[-1500:]}\nstderr={res.stderr[-3000:]}"
        )
    return res


class hard_alarm:
    """SIGALRM-based hard timeout (context manager): a wedged
    multi-process rendezvous fails the test, never the whole tier-1
    run.  Usable as the body of an autouse fixture or inline."""

    def __init__(self, seconds: int = 180):
        self.seconds = int(seconds)
        self._old = None

    def __enter__(self):
        import signal

        def boom(_sig, _frm):
            raise TimeoutError(
                f"test exceeded its {self.seconds}s hard timeout"
            )

        self._old = signal.signal(signal.SIGALRM, boom)
        signal.alarm(self.seconds)
        return self

    def __exit__(self, *exc):
        import signal

        signal.alarm(0)
        signal.signal(signal.SIGALRM, self._old)
        return False


def bare_fabric(pid: int = 0, peers=(1,)):
    """A Fabric with no sockets/threads — just the shared-state attrs the
    counted-mark/liveness wait paths read.  Unit tests for wait_marks
    and friends build on this instead of each re-listing the attrs."""
    import threading as _threading
    from collections import defaultdict as _dd

    from pathway_tpu import obs
    from pathway_tpu.parallel.comm import Fabric

    f = Fabric.__new__(Fabric)
    f.pid = pid
    f.peers = list(peers)
    f._cond = _threading.Condition()
    f._marks = _dd(dict)
    f._announced = {}
    f._recv_pos_counts = _dd(int)
    f._eot = set()
    f._done_peers = set()
    f._dead = None
    f._dead_peer = None
    f._poisoned = None
    f._closed = False
    # liveness defaults: heartbeats off (no threads here), generous wait
    f._hb_interval = 0.0
    f._peer_timeout_s = 0.0
    f._wait_timeout_s = 120.0
    f._last_seen = {p: 0.0 for p in peers}
    f.stats = {"wait_marks_s": 0.0, "wait_eot_s": 0.0}
    for p in peers:
        f.stats[f"wait_marks_s_p{p}"] = 0.0
    f._obs_ctx = (obs.new_trace_id(), 0)
    return f


class CompileWatch:
    """Round-14 zero-recompile idiom, replacing the jax_log_compiles
    log-string capture: compile events come from the device cost
    observatory's program registry (pathway_tpu.obs.profiler), so a
    guard failure prints each offender's RECORDED PROVENANCE — program
    name, the triggering arg shapes/dtypes, and a stack summary —
    instead of an opaque "Compiling ..." log line count.

        watch = CompileWatch()
        run_workload()          # cold pass
        assert watch.events()   # the capture mechanism really sees
        run_workload()          # warm pass
        watch.assert_no_compiles("second pass")

    Breadth note: besides the registry (wrapped programs, with
    provenance), the watch also tracks jax.monitoring's process-wide
    backend-compile counter, so a recompile of an UNWRAPPED jit — the
    coverage the old log capture had — still fails the guard (with a
    pointer to wrap it, instead of provenance).
    """

    def __init__(self):
        from pathway_tpu.obs import profiler

        self._profiler = profiler
        self._reg = profiler.registry()
        self._mark = self._reg.total_compiles()
        self._backend_mark = profiler.total_backend_compiles()

    def events(self):
        """Registry compile events since the last call (or construction);
        also re-marks the process-wide backend counter."""
        evs = self._reg.compile_events(since=self._mark)
        self._mark = self._reg.total_compiles()
        self._backend_mark = self._profiler.total_backend_compiles()
        return evs

    def assert_no_compiles(self, label: str = "warm pass"):
        backend_before = self._backend_mark
        evs = self.events()
        assert not evs, (
            f"{label} recompiled {len(evs)} program(s); recorded "
            "provenance:\n\n" + "\n\n".join(e.describe() for e in evs)
        )
        backend_grew = self._backend_mark - backend_before
        assert backend_grew == 0, (
            f"{label} triggered {backend_grew} XLA backend compile(s) "
            "from a jit NOT registered in the device cost observatory "
            "(no provenance available — wrap the entry point with "
            "obs.profiler.profiled_jit to name it)"
        )


def lfm2_feed(cfg, params, prompt, chunk, *, conv0=None, slot=1,
              block_size=8):
    """``prompt`` through ``models.lfm2.hybrid_mixed_step`` in runs of
    ``chunk`` tokens, one row, packed as the engine packs a chunk row (f32
    cache, gather path).  Returns ``[(tokens fed so far, the last fed
    position's logits)]`` a run, and the row's conv slot afterwards."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pathway_tpu.models import lfm2

    bs = block_size
    nb = -(-len(prompt) // bs)
    k = jnp.zeros((len(cfg.attn_layers), nb + 1, bs,
                   cfg.n_kv_heads * cfg.head_dim), jnp.float32)
    conv = jnp.zeros((len(cfg.conv_layers), slot + 1, 2, cfg.d_model),
                     jnp.float32) if conv0 is None else conv0
    state = (k, jnp.zeros_like(k), conv)
    table = np.arange(1, nb + 1, dtype=np.int32)[None, :]
    step = jax.jit(lambda *a: lfm2.hybrid_mixed_step(params, cfg, *a))

    def i32(x):
        return jnp.asarray(np.asarray(x, np.int32))

    out = []
    for s in range(0, len(prompt), chunk):
        run = list(prompt[s: s + chunk])
        nv, pad = len(run), chunk - len(run)
        pos = list(range(s, s + nv))
        logits, *state = step(
            *state, i32(run + [0] * pad), i32(pos + [0] * pad), i32(table),
            i32([s]), i32([nv]), i32([list(range(nv)) + [nv - 1] * pad]),
            i32([0] * chunk), i32(list(range(nv)) + [0] * pad),
            i32([table[0, p // bs] for p in pos] + [0] * pad),
            i32([p % bs for p in pos] + [0] * pad), i32([nv - 1]),
            i32([slot]))
        state = state[:3]
        out.append((s + nv, np.asarray(logits[0])))
    return out, np.asarray(state[2][:, slot])


def described_decode_plan(name: str):
    """``(cfg, decode plan as shapes, dtype, engine keywords)`` of a
    benchmark configuration, nothing allocated: ``gpt2_large_f32`` /
    ``gpt2_large_int8`` (the f32 plan as the chip keeps it, the
    int8-resident one), ``lfm2`` (LFM2-8B-A1B's published layers 1-13 in
    bf16, batch 16 as its cell asks), and ``mimo`` / ``phi4`` (the
    ``mimo-v2-flash-serve`` / ``phi-4-mini-flash-reasoning-serve`` files
    through their systems' own configuration, with the batch and the
    ``seq_buckets`` their files give the engine)."""
    import jax
    import jax.numpy as jnp

    if name in ("mimo", "phi4"):
        import importlib
        import json
        import pathlib

        from pathway_tpu.models import families

        file, system, module = {
            "mimo": ("mimo-v2-flash-serve", "serve_mimo_v2_flash",
                     "mimo_v2_flash"),
            "phi4": ("phi-4-mini-flash-reasoning-serve",
                     "serve_phi4_mini_flash", "phi4_mini_flash")}[name]
        root = pathlib.Path(__file__).resolve().parents[1]
        config = json.loads(
            (root / "benchmark" / "configs" / f"{file}.json").read_text())
        cfg = importlib.import_module(
            f"benchmark.systems.{system}").decoder_config(config)
        init = getattr(importlib.import_module(
            f"pathway_tpu.models.{module}"), f"init_{module}_params")
        fam = families.step_family(cfg)
        engine = config["engine"]
        return cfg, jax.eval_shape(lambda: fam.plan(
            cfg, init(cfg, jax.random.PRNGKey(0)), tp=1, quantize=None)), \
            jnp.bfloat16, {"max_batch_size": engine["max_batch_size"],
                           "seq_buckets": tuple(engine["seq_buckets"])}
    if name.startswith("gpt2_large"):
        from pathway_tpu.models import decoder

        cfg = decoder.DecoderConfig(
            vocab_size=50257, d_model=1280, n_layers=36, n_heads=20,
            d_ff=5120, max_len=1024, dtype="bfloat16")
        kw = {"quantize": "int8", "native": True} \
            if name.endswith("int8") else {"head_t": False}
        init, cast, extra = decoder.init_decoder_params, None, {}

        def plan(params):
            return decoder.plan_decode_params(cfg, params, **kw)
    else:
        from pathway_tpu.models import lfm2

        pattern = ("conv", "full_attention", "conv", "conv", "conv") \
            + ("full_attention", "conv", "conv", "conv") * 2
        cfg = lfm2.Lfm2Config(n_dense_layers=1, layer_types=pattern,
                              max_len=2048, dtype="bfloat16")
        init, cast, extra = lfm2.init_lfm2_params, jnp.bfloat16, \
            {"max_batch_size": 16}

        def plan(params):
            return lfm2.plan_params(cfg, params)

    def build():
        params = init(cfg, jax.random.PRNGKey(0))
        if cast is not None:
            params = jax.tree_util.tree_map(lambda w: w.astype(cast), params)
        return plan(params)

    return cfg, jax.eval_shape(build), jnp.bfloat16, extra
