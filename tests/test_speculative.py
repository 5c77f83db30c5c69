"""Round-18 speculative decoding on the chained scan — ISSUE 18.

Pins the tentpole guarantees:

- GREEDY TOKEN IDENTITY: draft + verify rounds (K proposals per row
  pushed through ONE ragged ``paged_mixed_step`` verify dispatch, longest
  matching prefix + free bonus token accepted) emit EXACTLY the tokens
  the non-speculative engine emits — for mixed lengths, shared prefixes,
  preemption-with-recompute, supervised engine restart and replica
  failover, on f32 AND int8 plans, tp=1 and tp=8;
- MULTI-TOKEN FLOOR: a drafter the target always agrees with (the target
  model drafting for itself) sustains > 1.5 accepted tokens per verify
  dispatch (the acceptance bar; the bench measures the realistic rate);
- ROLLBACK: rejected proposal slots are truncated out of the pool the
  same round (``BlockPool.truncate_slots``), so ``check_invariants``
  stays clean and no phantom KV outlives a verify round;
- DEGRADATION: a zero-accept drafter cools off via the controller's
  EWMA floor and the engine falls back to the plain chained scan —
  speculation can cost acceptance rate, never correctness or liveness;
- ADMISSION: arrivals discovered mid-decode are admitted at step
  boundaries exactly as before (the mixed dispatch), while rounds stay
  multi-token around them;
- COMPILE STABILITY: verify packing is static ``(B * (k+1),)`` — a
  second pass over the same workload compiles NOTHING new;
- OBSERVABILITY: pathway_kv_spec_* counters/accept-rate export through
  /metrics + OTLP + the dashboard kv table, and the ``pw.verify_step`` /
  ``pw.prefill_draft`` programs land in the observatory under their own
  names (the profile rollup folds ``_draft`` into the base family).
"""

import threading

import jax
import numpy as np
import pytest

from pathway_tpu import faults
from pathway_tpu.kvcache import (
    BlockPool, Drafter, DraftModelDrafter, NGramDrafter, PagedDecodeEngine,
    SpecController,
)
from pathway_tpu.models.decoder import (
    DecoderConfig, decode_step, init_decoder_params, prefill,
)

_CFG = DecoderConfig(
    vocab_size=64, d_model=64, n_layers=2, n_heads=8, d_ff=128, max_len=128
)


@pytest.fixture(scope="module")
def params():
    return init_decoder_params(_CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _engine(params, name, speculative, **kw):
    kw.setdefault("num_blocks", 96)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("seq_buckets", (16, 32, 64))
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("chain_steps", 4)
    return PagedDecodeEngine(
        _CFG, params, speculative=speculative, name=name, **kw
    )


def _prompts(lengths, seed=7):
    rng = np.random.default_rng(seed)
    return [
        [int(t) for t in rng.integers(0, _CFG.vocab_size, size=n)]
        for n in lengths
    ]


def _dense_greedy(params, prompt, n_new, bucket=64, cfg=_CFG):
    """Oracle: the dense batch-1 prefill + decode_step path."""
    import jax.numpy as jnp

    n = len(prompt)
    buf = np.zeros((1, bucket), np.int32)
    buf[0, :n] = prompt
    logits, cache = prefill(
        params, cfg, jnp.asarray(buf), jnp.asarray([n], jnp.int32)
    )
    out = [int(np.argmax(np.asarray(logits[0])))]
    pos = n
    for _ in range(n_new - 1):
        logits, cache = decode_step(
            params, cfg, cache, jnp.asarray([[out[-1]]], jnp.int32), pos
        )
        out.append(int(np.argmax(np.asarray(logits[0]))))
        pos += 1
    return out


def _spec_stats(eng):
    s = eng.pool.stats.snapshot()
    return {k: s[k] for k in s if k.startswith("spec")}


# -- token identity ----------------------------------------------------------


def test_spec_identity_mixed_lengths(params):
    prompts = _prompts((3, 5, 8, 11, 16, 17, 27, 31))
    off = _engine(params, "t_sp_off", "off")
    on = _engine(params, "t_sp_on", "ngram")
    got_off = off.generate_batch([(p, 11) for p in prompts])
    got_on = on.generate_batch([(p, 11) for p in prompts])
    assert got_on == got_off
    assert got_on == [_dense_greedy(params, p, 11) for p in prompts]
    sp = _spec_stats(on)
    assert sp["spec_rounds"] > 0, "the drafter never produced a round"
    assert sp["spec_proposed"] > 0
    # rejected slots were rolled back the same round: the pool holds no
    # phantom KV and every refcount balances
    on.pool.check_invariants(external_refs=on.prefix.external_refs())


def test_spec_identity_through_the_interpreted_kernels():
    """A verify round is the mixed program at ``k + 1`` = 5 query columns:
    through the programs the chip runs (two heads of 64, the Pallas kernels
    interpreted) it emits what the chained engine emits.  Five columns are
    no whole sublane tile: the ragged kernel takes such a row as one tile
    (``paged_attention._col_tiles``)."""
    cfg = DecoderConfig(vocab_size=64, d_model=128, n_layers=1, n_heads=2,
                        d_ff=128, max_len=128)
    params = init_decoder_params(cfg, jax.random.PRNGKey(1))
    prompts = [p + p[:6] for p in _prompts((9, 14, 21))]  # n-grams to draft

    def engine(name, speculative):
        return PagedDecodeEngine(
            cfg, params, speculative=speculative, name=name, num_blocks=64,
            block_size=8, max_batch_size=4, seq_buckets=(32, 64),
            prefill_chunk=8, chain_steps=4, attn="pallas")

    on = engine("t_sp_pallas_on", "ngram")
    assert on._spec.k + 1 == 5
    got = on.generate_batch([(p, 9) for p in prompts])
    assert got == engine("t_sp_pallas_off", "off").generate_batch(
        [(p, 9) for p in prompts])
    assert got == [_dense_greedy(params, p, 9, cfg=cfg) for p in prompts]
    assert _spec_stats(on)["spec_rounds"] > 0
    on.pool.check_invariants(external_refs=on.prefix.external_refs())


def test_spec_identity_shared_prefixes(params):
    # rows sharing long prefixes: spec rounds run over prefix-cache-shared
    # block tables (COW on the write slots), and a SECOND pass drafts
    # from both the prefix cache AND the drafter's learned table
    base = _prompts((24,), seed=19)[0]
    prompts = [base[:20] + p for p in _prompts((4, 7, 9, 11), seed=23)]
    off = _engine(params, "t_sp_pfx_off", "off")
    on = _engine(params, "t_sp_pfx_on", "ngram")
    reqs = [(list(p), 10) for p in prompts]
    got_off = off.generate_batch(list(reqs))
    assert on.generate_batch(list(reqs)) == got_off
    assert on.generate_batch(list(reqs)) == got_off  # trained-table pass
    assert _spec_stats(on)["spec_rounds"] > 0
    on.pool.check_invariants(external_refs=on.prefix.external_refs())


def test_spec_identity_under_preemption(params):
    # pool too small for 4 growing rows: verify pre-extension (k+1 slots
    # per row) must trigger preemption-with-recompute and stay identical
    prompts = _prompts((3, 5, 8, 11))
    outs, preempts = {}, {}
    for mode in ("off", "ngram"):
        eng = _engine(params, f"t_sp_pre_{mode}", mode, num_blocks=14)
        outs[mode] = eng.generate_batch([(p, 12) for p in prompts])
        preempts[mode] = eng.pool.stats.snapshot()["preemptions"]
        eng.pool.check_invariants(
            external_refs=eng.prefix.external_refs()
        )
    assert outs["ngram"] == outs["off"]
    assert preempts["ngram"] > 0, "pool pressure never forced a preemption"


def test_spec_identity_int8(params):
    prompts = _prompts((3, 8, 17, 27), seed=31)
    off = _engine(params, "t_sp_i8_off", "off", quantize="int8")
    on = _engine(params, "t_sp_i8_on", "ngram", quantize="int8")
    got_off = off.generate_batch([(p, 10) for p in prompts])
    assert on.generate_batch([(p, 10) for p in prompts]) == got_off
    assert _spec_stats(on)["spec_rounds"] > 0


def test_spec_identity_tp8(params):
    prompts = _prompts((3, 8, 17, 27))
    out = {}
    for tp in (1, 8):
        eng = _engine(params, f"t_sp_tp{tp}", "ngram", tp=tp)
        out[tp] = eng.generate_batch([(p, 9) for p in prompts])
        assert _spec_stats(eng)["spec_rounds"] > 0
    assert out[8] == out[1]
    assert out[1] == [_dense_greedy(params, p, 9) for p in prompts]


# -- multi-token floor --------------------------------------------------------


def test_model_drafter_sustains_multi_token_dispatches(params):
    """The target model drafting for itself is the accept-rate ceiling:
    every proposal matches the verify argmax, so each dispatch must
    advance k (accepted) + 1 (bonus) tokens per row — far above the
    > 1.5 accepted-tokens-per-dispatch acceptance bar."""
    prompts = _prompts((3, 5, 9, 14), seed=37)
    off = _engine(params, "t_sp_md_off", "off")
    ctrl = SpecController(DraftModelDrafter(_CFG, params, k=4))
    on = _engine(params, "t_sp_md_on", ctrl)
    got_off = off.generate_batch([(p, 12) for p in prompts])
    assert on.generate_batch([(p, 12) for p in prompts]) == got_off
    sp = _spec_stats(on)
    assert sp["spec_rounds"] > 0
    assert sp["spec_accept_rate"] == 1.0, sp
    assert sp["spec_emitted_per_round"] > 1.5, sp
    on.pool.check_invariants(external_refs=on.prefix.external_refs())


def test_draft_model_hbm_gate_falls_back_to_ngram(params):
    """A draft model that does not fit the HBM ledger raises
    SpecResourceError at bind, and the engine falls back to the n-gram
    drafter instead of failing or OOMing at first dispatch."""
    from pathway_tpu.kvcache.speculative import (
        SpecResourceError, resolve_speculative,
    )

    eng = _engine(params, "t_sp_gate", "off")

    class _NoRoom:
        budget_bytes = 1
        per_block_bytes = 1
        num_blocks = 1

        def fits_with(self, **kw):
            return False

    eng.hbm_plan = _NoRoom()
    dd = DraftModelDrafter(_CFG, params, k=3)
    with pytest.raises(SpecResourceError):
        dd.bind(eng)
    ctrl = resolve_speculative(dd, eng)
    assert isinstance(ctrl.drafter, NGramDrafter)
    assert ctrl.drafter.k == 3  # the requested K survives the fallback


# -- zero-accept degradation --------------------------------------------------


class _AlwaysWrongDrafter(Drafter):
    """Proposes the one token GUARANTEED to be refuted: the target's own
    next argmax (via the dense oracle) plus one, mod vocab."""

    name = "always_wrong"
    k = 2

    def __init__(self, params):
        self._params = params

    def propose(self, ctx_tokens, k: int) -> list[int]:
        nxt = _dense_greedy(self._params, list(ctx_tokens), 1)[0]
        return [(nxt + 1) % _CFG.vocab_size]


def test_zero_accept_degrades_to_chained(params):
    """Worst case: every proposal refuted.  The EWMA floor must cool the
    drafter off and the engine must fall back to the CHAINED scan (not
    1-token verify rounds forever), still token-identical."""
    prompts = _prompts((5, 9, 14), seed=41)
    off = _engine(params, "t_sp_zero_off", "off")
    ctrl = SpecController(
        _AlwaysWrongDrafter(params), accept_floor=0.6, cooloff_rounds=8
    )
    on = _engine(params, "t_sp_zero_on", ctrl)
    got_off = off.generate_batch([(p, 14) for p in prompts])
    assert on.generate_batch([(p, 14) for p in prompts]) == got_off
    sp = _spec_stats(on)
    assert sp["spec_rounds"] > 0
    assert sp["spec_accepted"] == 0
    assert sp["spec_rejected"] == sp["spec_proposed"] > 0
    # every verify round still made progress (the bonus token)
    assert sp["spec_emitted"] >= sp["spec_rounds"]
    # ... and the cooloff handed the quiet queue back to the chain
    snap = on.pool.stats.snapshot()
    assert snap["chain_steps_sum"] > snap["chain_count"], \
        "cooloff never fell back to a multi-step chain"
    on.pool.check_invariants(external_refs=on.prefix.external_refs())


# -- rollback / pool contract -------------------------------------------------


def test_truncate_slots_inverts_extend():
    pool = BlockPool(num_blocks=8, block_size=4, n_layers=1, n_heads=2,
                     head_dim=4, name="t_trunc")
    pool.allocate(1, 6)  # 2 blocks, offset 2
    free0 = list(pool._free)
    blocks0 = list(pool.sequence(1).block_ids)
    pool.extend_slots(1, 5)  # -> 11 tokens, 3 blocks
    pool.truncate_slots(1, 5)  # full rollback
    assert pool.sequence(1).n_tokens == 6
    assert pool.sequence(1).block_ids == blocks0
    assert list(pool._free) == free0
    pool.check_invariants()
    # partial rollback: keep 2 of 5 speculative slots (8 tokens, the
    # third block stays because token 7..8 live in it)
    pool.extend_slots(1, 5)
    pool.truncate_slots(1, 3)
    assert pool.sequence(1).n_tokens == 8
    assert len(pool.sequence(1).block_ids) == 2
    pool.check_invariants()
    # guard rails: k > n_tokens is a caller bug, k <= 0 a no-op
    with pytest.raises(ValueError):
        pool.truncate_slots(1, 9)
    pool.truncate_slots(1, 0)
    assert pool.sequence(1).n_tokens == 8
    pool.check_invariants()


# -- restart / failover -------------------------------------------------------


def _mixed_requests():
    rng = np.random.default_rng(11)
    lengths = [3, 5, 7, 9, 12, 15, 21, 27]
    return [
        (list(rng.integers(1, _CFG.vocab_size, size=n)), 6 + (i % 5))
        for i, n in enumerate(lengths)
    ]


def test_spec_restart_token_identical(params):
    """A verify dispatch that fails mid-run feeds the supervised restart
    path; recomputed sessions must replay byte-equal (the drafter is a
    pure function of the tokens it is shown, so proposals replay too)."""
    reqs = _mixed_requests()
    clean = _engine(
        params, "t_sp_rs_clean", "off", max_batch_size=8
    ).generate_batch([(list(p), n) for p, n in reqs])
    eng = _engine(
        params, "t_sp_rs_faulty",
        SpecController(DraftModelDrafter(_CFG, params, k=4)),
        max_batch_size=8, max_restarts=1,
    )
    faults.install("engine.dispatch.verify", "raise", nth=2)
    got = eng.generate_batch([(list(p), n) for p, n in reqs])
    assert got == clean, "restart changed emitted tokens"
    assert eng.pool.stats.engine_restarts >= 1
    assert eng.pool.sequences() == []
    assert _spec_stats(eng)["spec_rounds"] > 0


def test_spec_fleet_failover_token_identical(params):
    """Kill one replica of a SPECULATIVE fleet mid-decode: every
    in-flight request completes on the peer, byte-equal to the
    non-speculative reference."""
    from pathway_tpu.serve import ReplicaFleet

    ekw = dict(num_blocks=96, block_size=4, max_batch_size=8,
               seq_buckets=(16, 32, 64), prefill_chunk=8, chain_steps=4)
    prompts = [[i + 1, i + 2, i + 3, 5] for i in range(6)]
    ref = PagedDecodeEngine(
        _CFG, params, speculative="off", name="t_sp_fl_ref", **ekw
    ).generate_batch([(p, 12) for p in prompts])
    fleet = ReplicaFleet(_CFG, params, replicas=2, name="t_sp_fleet",
                         max_restarts=0, speculative="ngram", **ekw)
    try:
        results: list = [None] * len(prompts)
        errors: list = []

        def run(i, p):
            try:
                results[i] = fleet.submit(p, 12, timeout_s=120.0)
            except Exception as exc:  # noqa: BLE001 - asserted below
                errors.append((i, exc))

        faults.install("engine.dispatch.verify", "raise", nth=2)
        threads = [
            threading.Thread(target=run, args=(i, p))
            for i, p in enumerate(prompts)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180.0)
        assert not errors, errors
        assert results == ref
        st = fleet.stats()
        assert st["live"] == 1  # exactly one replica died
        assert st["recovery_s"], "no failover was recorded"
    finally:
        fleet.shutdown(drain=False, timeout_s=5.0)


# -- admission stays step-boundary --------------------------------------------


def test_spec_arrival_admitted_at_step_boundary(params):
    """An arrival discovered mid-decode is admitted through the mixed
    dispatch at the next step boundary — speculative rounds continue
    around it and output matches the non-speculative run exactly."""
    prompts = _prompts((6, 9, 13, 30), seed=17)
    results = {}
    events_spec = []
    for mode in ("off", "ngram"):
        eng = _engine(params, f"t_sp_arr_{mode}", mode)
        events = events_spec if mode == "ngram" else []

        def spy(fn, kind, _ev=events):
            def run(*a):
                _ev.append(kind)
                return fn(*a)
            return run

        eng._mixed = spy(eng._mixed, "mixed")
        orig_vp = eng._verify_program

        def vp(_o=orig_vp, _ev=events):
            return spy(_o(), "verify")
        eng._verify_program = vp
        got = []
        state = {"rounds": 0}

        def poll(n, _s=state, _ev=events):
            _s["rounds"] += 1
            if _s["rounds"] == 3:
                _ev.append("arrival")
                return [((prompts[3], 6), 1, got.append,
                         lambda e: got.append(e))]
            return []

        base = eng.generate_batch([(p, 14) for p in prompts[:3]], poll=poll)
        results[mode] = (base, got)
    assert results["ngram"] == results["off"]
    ev = events_spec
    assert "verify" in ev, "the drafter never produced a verify round"
    i_arr = ev.index("arrival")
    assert "mixed" in ev[i_arr:], "arrival was never admitted"


# -- compile stability --------------------------------------------------------


def test_spec_second_pass_zero_recompiles(params):
    """Verify packing is static (B*(k+1) tokens, padded): the same
    workload twice compiles pw.verify_step and pw.prefill_draft exactly
    once, and NOTHING on the second pass."""
    from .utils import CompileWatch

    ctrl = SpecController(DraftModelDrafter(_CFG, params, k=4))
    eng = _engine(params, "t_sp_compile", ctrl)
    prompts = _prompts((3, 9, 15, 21), seed=23)
    reqs = [(p, 11) for p in prompts]
    watch = CompileWatch()
    eng.generate_batch(list(reqs))
    first = watch.events()
    progs = {e.program for e in first}
    assert "pw.verify_step" in progs, progs
    assert "pw.prefill_draft" in progs, progs
    assert _spec_stats(eng)["spec_rounds"] > 0
    eng.generate_batch(list(reqs))
    watch.assert_no_compiles("second speculative pass")


# -- n-gram drafter unit ------------------------------------------------------


def test_ngram_self_match_prefers_most_recent():
    d = NGramDrafter(k=3, max_n=3)
    # suffix [7, 8] occurred twice; the LATER occurrence's continuation
    # ([5, 5, 9]) must win over the earlier one's ([1, 2, 3])
    ctx = [7, 8, 1, 2, 3, 7, 8, 5, 5, 9, 7, 8]
    assert d.propose(ctx, 3) == [5, 5, 9]
    assert d.propose(ctx, 2) == [5, 5]
    assert d.propose([1, 2, 3], 3) == []  # no repetition, no table
    assert d.propose(ctx, 0) == []


def test_ngram_chain_hash_table_cross_request():
    # all-distinct tokens so the self-matcher stays silent and the
    # chain-hash table is the only proposal source
    d = NGramDrafter(k=4, max_n=2)
    d._block_size = 4
    stream = [3, 1, 4, 2, 5, 9, 7, 6, 10, 11, 12, 13]
    d.note_release(stream)
    # a NEW request reaching the first full block drafts the released
    # stream's continuation...
    assert d.propose([3, 1, 4, 2], 4) == [5, 9, 7, 6]
    # ...mid-block: the partial tail must MATCH the learned continuation
    assert d.propose([3, 1, 4, 2, 5, 9], 4) == [7, 6, 10, 11]
    # ...and a diverged tail must not draft from it
    assert d.propose([3, 1, 4, 2, 8, 9], 4) == []
    # two full blocks: the deeper chain hash keys the later continuation
    assert d.propose([3, 1, 4, 2, 5, 9, 7, 6], 4) == [10, 11, 12, 13]


def test_spec_controller_cooloff_and_reprobe():
    class _Fixed(Drafter):
        name, k = "fixed", 2

        def propose(self, ctx, k):
            return [1, 2][:k]

    ctrl = SpecController(_Fixed(), accept_floor=0.5, cooloff_rounds=3,
                          ewma_alpha=1.0)  # judge on the last round alone
    assert ctrl.propose_batch([[0]], [2]) == [[1, 2]]
    ctrl.note_round(proposed=2, accepted=0, emitted=1, ms=1.0)
    # EWMA 0 < floor: the next 3 rounds are cooloff (empty proposals)
    for _ in range(3):
        assert ctrl.propose_batch([[0]], [2]) == [[]]
    # re-probe: optimistic slate restored
    assert ctrl.propose_batch([[0]], [2]) == [[1, 2]]


# -- observability ------------------------------------------------------------


def test_spec_metrics_export(params):
    from pathway_tpu.serve import metrics as M

    eng = _engine(params, "t_sp_metrics",
                  SpecController(DraftModelDrafter(_CFG, params, k=4)))
    prompts = _prompts((5, 9, 14), seed=29)
    eng.generate_batch([(p, 11) for p in prompts])
    snap = eng.pool.stats.snapshot()
    assert snap["spec_rounds"] > 0
    assert snap["spec_proposed"] >= snap["spec_accepted"] > 0
    assert snap["spec_emitted"] >= snap["spec_accepted"]
    assert 0.0 < snap["spec_accept_rate"] <= 1.0
    lines = "\n".join(M.render_prometheus_lines())
    lbl = f'pool="{eng.pool.name}"'
    for metric in ("spec_proposed_total", "spec_accepted_total",
                   "spec_rejected_total", "spec_emitted_total",
                   "spec_rounds_total"):
        assert f"pathway_kv_{metric}{{{lbl}}}" in lines, metric
    assert f"pathway_kv_spec_accept_rate{{{lbl}}}" in lines
    points = M.otlp_points("0")
    counters = {
        a["value"]["stringValue"]
        for p in points for a in p["attributes"]
        if a["key"] == "counter"
    }
    assert {"spec_proposed", "spec_accepted", "spec_rejected",
            "spec_emitted", "spec_rounds", "spec_accept_rate"} <= counters
    # dashboard renders the spec column without an engine scheduler
    from pathway_tpu.engine import telemetry as T

    class _FakeOp:
        name, id, rows_in, rows_out = "op", 0, 1, 1

    class _FakeSched:
        operators = [_FakeOp()]
        frontier = 0

    ms = T.MetricsServer.__new__(T.MetricsServer)
    ms.scheduler = _FakeSched()
    ms.started_at = 0.0
    html = ms.render_dashboard()
    assert "spec acc/prop (rate)" in html


def test_spec_tier_rows_flow_to_costdb(params, tmp_path, monkeypatch):
    """generate_batch flushes the controller's aggregates as a
    pw.spec_tier row, and speculative="auto" reads the recorded pick."""
    from pathway_tpu.obs import costdb

    db = costdb.CostDB(str(tmp_path / "costdb.json"))
    monkeypatch.setattr(costdb, "_default", db)
    try:
        eng = _engine(params, "t_sp_costdb", "ngram")
        eng.generate_batch(
            [(p, 12) for p in _prompts((5, 9, 14), seed=43)]
        )
        entry = db.get("pw.spec_tier", "ngram|k4")
        assert entry is not None, "no spec_tier row was flushed"
        extra = entry.get("extra") or {}
        assert extra.get("drafter") == "ngram"
        assert extra.get("k") == 4
        assert 0.0 <= extra.get("accept_rate", -1.0) <= 1.0
        # the bench-recorded pick drives "auto"
        db.observe("pw.spec_tier", "pick",
                   extra={"drafter": "ngram", "k": 2})
        auto = _engine(params, "t_sp_auto", "auto")
        assert isinstance(auto._spec.drafter, NGramDrafter)
        assert auto._spec.k == 2
    finally:
        db.shutdown(5.0)


def test_profile_rollup_folds_draft_programs():
    from pathway_tpu.cli import _program_family, format_profile_diff

    assert _program_family("pw.prefill_draft") == _program_family(
        "pw.prefill"
    )
    assert _program_family("pw.prefill_draft_i8") == _program_family(
        "pw.prefill_i8"
    )
    assert _program_family("pw.verify_step") == _program_family(
        "pw.verify_step"
    )

    def snap(rows):
        return {"programs": rows, "total_dispatch_s":
                sum(r.get("dispatch_s_total", 0) for r in rows)}

    before = snap([
        {"program": "pw.chained_decode", "bucket": "b8",
         "dispatch_ms_p50": 40.0, "mfu": 0.02, "dispatch_s_total": 3.0},
        {"program": "pw.prefill_draft", "bucket": "b8",
         "dispatch_ms_p50": 2.0, "mfu": 0.01, "dispatch_s_total": 0.2},
    ])
    after = snap([
        {"program": "pw.chained_decode", "bucket": "b8",
         "dispatch_ms_p50": 40.0, "mfu": 0.02, "dispatch_s_total": 3.0},
        {"program": "pw.prefill_draft_i8", "bucket": "b8",
         "dispatch_ms_p50": 1.0, "mfu": 0.02, "dispatch_s_total": 0.1},
    ])
    text = format_profile_diff(before, after)
    # drafter programs appearing/disappearing get their own callout
    assert "pw.prefill_draft_i8 (+drafter)" in text
    assert "pw.prefill_draft (-drafter)" in text
