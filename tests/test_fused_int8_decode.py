"""Fused decode plan + device int8 matmuls (Round-17) — ISSUE 18
acceptance.

Pins the tentpole guarantees:

- the Round-17 decode plan (fused [D,3D] QKV matmul, pre-transposed
  [D,V] head) that every PagedDecodeEngine now dispatches with is
  TOKEN-IDENTICAL to the raw round-7/8 dense path — greedy and
  fixed-seed sampled, across mixed lengths, shared prefixes,
  preemption-with-recompute, and the tp=8 virtual mesh;
- ``quantize="int8"`` (per-output-channel scales, f32 accumulation) is
  DETERMINISTIC: byte-equal tokens across engine rebuilds (restart) and
  across a fault-injected engine restart mid-batch (failover), greedy
  and fixed-seed sampled;
- every fused/int8 program variant (``pw.*_i8``) compiles once — a
  second pass over the same workload triggers zero new XLA compiles;
- engine default shapes come from the HBM ledger's what-if walk
  (obs.memory.choose_engine_config): documented defaults when no budget
  resolves, budget-fitted shapes (asserted re-constructible) under
  ``PW_HBM_BUDGET_BYTES``;
- the ledger bills int8 plan leaves at their true one-byte width;
- ``cli profile --diff`` renders the per-program before→after delta
  table from two saved ``/debug/profile`` snapshots;
- the fused ``paged_append_attend`` op's reference path is bit-identical
  to scatter-then-reference-attend, and the Pallas kernel (interpret
  mode) matches to fp tolerance with the slot K/V really written.
"""

import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu import faults
from pathway_tpu.kvcache import PagedDecodeEngine
from pathway_tpu.models.decoder import (
    DecoderConfig, decode_step, init_decoder_params, plan_decode_params,
    prefill, quantize_weight_int8,
)
from pathway_tpu.obs import memory as obs_memory

# 8 KV heads / 64 vocab: tp=8 divides both on the virtual 8-device mesh
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


def _engine(params, name, **kw):
    kw.setdefault("num_blocks", 96)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_batch_size", 8)
    kw.setdefault("seq_buckets", (16, 32, 64))
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("chain_steps", 8)
    return PagedDecodeEngine(_CFG, params, name=name, **kw)


def _prompts(lengths, seed=7):
    rng = np.random.default_rng(seed)
    return [
        [int(t) for t in rng.integers(1, _CFG.vocab_size, size=n)]
        for n in lengths
    ]


def _dense_greedy(params, prompt, n_new, bucket=64):
    """Oracle: the raw-pytree dense prefill + decode_step path."""
    n = len(prompt)
    buf = np.zeros((1, bucket), np.int32)
    buf[0, :n] = prompt
    logits, cache = prefill(
        params, _CFG, jnp.asarray(buf), jnp.asarray([n], jnp.int32)
    )
    out = [int(np.argmax(np.asarray(logits[0])))]
    pos = n
    for _ in range(n_new - 1):
        logits, cache = decode_step(
            params, _CFG, cache, jnp.asarray([[out[-1]]], jnp.int32), pos
        )
        out.append(int(np.argmax(np.asarray(logits[0]))))
        pos += 1
    return out


# -- token identity: fused plan vs raw dense path ----------------------------


def test_plan_greedy_identity_mixed_lengths_shared_prefixes(params):
    """The engine's Round-17 plan (fused wqkv + embed_t head) must emit
    exactly the raw dense path's tokens — mixed lengths, and two
    prompts sharing a 5-token prefix (prefix-cache block sharing)."""
    prompts = _prompts((3, 5, 9, 16, 27))
    prompts.append(list(prompts[3][:5]) + [7, 9, 2])  # shared prefix
    eng = _engine(params, "t_r17_plan_id")
    got = eng.generate_batch([(list(p), 9) for p in prompts])
    assert got == [_dense_greedy(params, p, 9) for p in prompts]


def test_plan_sampled_fixed_seed_identity(params):
    """Fixed-seed sampled decoding through the plan is deterministic
    across two independently built engines (compile + plan rebuild),
    f32 AND int8 — the device sampling head reads the same plan
    logits."""
    prompts = _prompts((4, 7, 12), seed=13)
    opts = {"sampling": (0.8, 8, 0.95, 42)}
    for quant in (None, "int8"):
        runs = []
        for i in range(2):
            eng = _engine(params, f"t_r17_samp_{quant}_{i}", quantize=quant)
            runs.append(eng.generate_batch(
                [(list(p), 8, opts) for p in prompts]
            ))
        assert runs[0] == runs[1], f"sampled quantize={quant} nondeterministic"
        assert all(len(toks) == 8 for toks in runs[0])


def test_plan_preemption_recompute_identity(params):
    """Pool pressure forcing preemption-with-recompute must not change
    tokens vs the unpressured plan engine — f32 and int8."""
    prompts = _prompts((3, 5, 8, 11), seed=5)
    for quant in (None, "int8"):
        calm = _engine(params, f"t_r17_pre_calm_{quant}", quantize=quant)
        want = calm.generate_batch([(list(p), 12) for p in prompts])
        tight = _engine(params, f"t_r17_pre_tight_{quant}",
                        num_blocks=14, quantize=quant)
        got = tight.generate_batch([(list(p), 12) for p in prompts])
        assert got == want
        assert tight.pool.stats.snapshot()["preemptions"] > 0, \
            "pool pressure never forced a preemption"
        if quant is None:
            # the f32 plan additionally matches the raw dense oracle
            # (int8's oracle is its own calm run — quantization may
            # legitimately flip near-tied argmaxes vs f32)
            assert want == [_dense_greedy(params, p, 12) for p in prompts]


def test_plan_tp8_identity(params):
    """tp=8 on the virtual mesh is token-identical to tp=1 — with the
    fused plan sharded per the Round-17 mesh rules, f32 and int8."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device virtual mesh")
    prompts = _prompts((3, 9, 15, 21), seed=11)
    for quant in (None, "int8"):
        out = {}
        for tp in (1, 8):
            eng = _engine(params, f"t_r17_tp{tp}_{quant}",
                          tp=tp, quantize=quant)
            out[tp] = eng.generate_batch([(list(p), 9) for p in prompts])
        assert out[8] == out[1], f"tp=8 diverged (quantize={quant})"
    # and the f32 plan run (last `quant` loop overwrote out — redo f32)
    eng = _engine(params, "t_r17_tp1_f32b", tp=1)
    got = eng.generate_batch([(list(p), 9) for p in prompts])
    assert got == [_dense_greedy(params, p, 9) for p in prompts]


# -- int8 determinism: restart + failover ------------------------------------


def test_int8_deterministic_across_restart(params):
    """Two engine builds from the same raw pytree re-quantize to the
    SAME plan: byte-equal tokens (the restart/process-rebuild case)."""
    prompts = _prompts((3, 7, 13, 20), seed=19)
    reqs = [(list(p), 10) for p in prompts]
    a = _engine(params, "t_r17_i8_r1", quantize="int8").generate_batch(
        [(list(p), n) for p, n in reqs])
    b = _engine(params, "t_r17_i8_r2", quantize="int8").generate_batch(
        [(list(p), n) for p, n in reqs])
    assert a == b


def test_int8_deterministic_across_failover(params):
    """A fault-injected engine restart mid-batch (the failover path:
    dispatch raises, supervisor rebuilds pool + recomputes) emits
    byte-equal int8 tokens."""
    reqs = [(list(p), 6 + (i % 5))
            for i, p in enumerate(_prompts((3, 5, 9, 14, 21), seed=23))]
    clean = _engine(params, "t_r17_i8_clean", quantize="int8",
                    chain_steps=4).generate_batch(
        [(list(p), n) for p, n in reqs])
    eng = _engine(params, "t_r17_i8_faulty", quantize="int8",
                  chain_steps=4, max_restarts=1)
    faults.install("engine.dispatch.chain", "raise", nth=2)
    got = eng.generate_batch([(list(p), n) for p, n in reqs])
    assert got == clean, "failover changed int8 tokens"
    assert eng.pool.stats.engine_restarts >= 1


# -- zero-recompile: every fused/int8 variant --------------------------------


def test_int8_second_pass_zero_recompiles(params):
    """The ``_i8`` program family (prefill/mixed/chained, greedy and
    sampled) is shape-static like its f32 twins: a second pass over the
    same mixed workload compiles NOTHING."""
    from .utils import CompileWatch

    prompts = _prompts((3, 9, 15, 21), seed=29)
    reqs = [(list(p), 11) for p in prompts]
    sreqs = [(list(p), 7, {"sampling": (0.7, 6, 0.9, 3)})
             for p in prompts]
    eng = _engine(params, "t_r17_i8_compile", quantize="int8")
    watch = CompileWatch()
    eng.generate_batch([tuple(r) for r in reqs])
    eng.generate_batch([tuple(r) for r in sreqs])
    first = watch.events()
    assert first, "registry saw no compiles on the cold pass"
    names = {e.program for e in first}
    assert any(n.startswith("pw.chained_decode_i8") for n in names), names
    assert any("_sampled_i8" in n for n in names), names
    eng.generate_batch([tuple(r) for r in reqs])
    eng.generate_batch([tuple(r) for r in sreqs])
    watch.assert_no_compiles("second pass (int8 variants)")


# -- ledger-chosen engine shapes ---------------------------------------------


def test_autoconfig_defaults_without_budget(params, monkeypatch):
    """No shapes given + no HBM budget resolvable → the documented
    ENGINE_DEFAULTS, reported as such in auto_config."""
    monkeypatch.delenv("PW_HBM_BUDGET_BYTES", raising=False)
    eng = PagedDecodeEngine(_CFG, params, seq_buckets=(16, 32, 64),
                            name="t_r17_auto_def")
    ac = eng.auto_config
    assert set(ac["chosen"]) == {"num_blocks", "block_size",
                                 "max_batch_size", "chain_steps",
                                 "prefill_chunk"}
    assert "defaults" in ac["source"]
    # no budget, no roof: two blocks, reported as a default
    assert ac["prefill_chunk"] == eng.prefill_chunk == 2 * ac["block_size"]
    assert ac["chunk_source"].startswith("default")
    for k, v in obs_memory.ENGINE_DEFAULTS.items():
        assert ac[k] == v, (k, ac)


def test_autoconfig_budget_ladder_and_reconstruct(params, monkeypatch):
    """Under ``PW_HBM_BUDGET_BYTES`` the shapes come off the what-if
    ladder, fit the ledger, and are RE-CONSTRUCTIBLE: a second engine
    built with the chosen shapes made explicit also fits."""
    monkeypatch.setenv("PW_HBM_BUDGET_BYTES", str(8 * 2 ** 20))
    eng = PagedDecodeEngine(_CFG, params, seq_buckets=(16, 32, 64),
                            name="t_r17_auto_fit")
    ac = eng.auto_config
    assert ac["chosen"], "budget resolved but nothing was auto-chosen"
    assert "what-if" in ac["source"]
    assert eng.hbm_plan.fits, eng.hbm_plan.reject_message()
    redo = PagedDecodeEngine(
        _CFG, params, seq_buckets=(16, 32, 64),
        num_blocks=ac["num_blocks"], block_size=ac["block_size"],
        max_batch_size=ac["max_batch_size"],
        chain_steps=ac["chain_steps"], prefill_chunk=ac["prefill_chunk"],
        name="t_r17_auto_redo",
    )
    assert redo.auto_config["chosen"] == []
    assert redo.hbm_plan.fits, redo.hbm_plan.reject_message()
    assert (redo.pool.num_blocks, redo.pool.block_size) == \
        (ac["num_blocks"], ac["block_size"])
    # explicit values are honored verbatim even when they differ from
    # what the ladder would pick
    tiny = PagedDecodeEngine(_CFG, params, seq_buckets=(16, 32, 64),
                             num_blocks=24, block_size=4,
                             max_batch_size=2, chain_steps=4,
                             prefill_chunk=12,
                             name="t_r17_auto_explicit")
    assert tiny.auto_config["chosen"] == []
    assert tiny.pool.num_blocks == 24
    assert tiny.prefill_chunk == tiny.hbm_plan.prefill_chunk == 12
    # a budget but no device roof (the CPU): the chunk stays two blocks,
    # chosen and reported as a default, and the ledger carries it
    assert "prefill_chunk" in ac["chosen"]
    assert eng.prefill_chunk == eng.hbm_plan.prefill_chunk == 32
    assert ac["chunk_source"].startswith("default: two blocks (no device")


# -- the fifth chosen shape: prefill_chunk ------------------------------------

# a described v5e: the published roof (obs/profiler.TPU_PEAKS) and the
# budget its memory_stats report
_V5E = {"peak": 197e12, "membw": 819e9}
_V5E_HBM = int(15.02 * 2 ** 30)


def _choose(name, **kw):
    from .utils import described_decode_plan

    cfg, plan, dtype, extra = described_decode_plan(name)
    kw = {"budget_bytes": _V5E_HBM, "roof": _V5E,
          "seq_buckets": (64, 256, 1024), **extra, **kw}
    return cfg, plan, dtype, obs_memory.choose_engine_config(
        cfg, params=plan, dtype=dtype, reference_attn=False, **kw)


@pytest.mark.parametrize("name", ["gpt2_large_f32", "gpt2_large_int8",
                                  "lfm2"])
def test_chunk_is_chosen_from_ledger_cap_and_ridge(name):
    """Under a described v5e roof and budget the chunk is a rung of the
    ladder, listed as chosen with its reckoning; it never lowers the three
    shapes chosen before it; and the ledger re-constructed from the chosen
    numbers carries the chunk and fits."""
    cfg, plan, dtype, res = _choose(name)
    assert res["prefill_chunk"] in obs_memory._CHUNK_LADDER
    assert "prefill_chunk" in res["chosen"]
    assert res["chunk_source"].startswith("ridge"), res["chunk_source"]
    _c, _p, _d, narrow = _choose(name, prefill_chunk=32)
    assert "prefill_chunk" not in narrow["chosen"]
    for shape in ("num_blocks", "max_batch_size", "chain_steps"):
        assert res[shape] == narrow[shape], (shape, res, narrow)
    assert res["num_blocks"] == res["max_batch_size"] \
        * (cfg.max_len // 16) + 1  # full coverage
    redo = obs_memory.hbm_plan(
        cfg, num_blocks=res["num_blocks"], block_size=res["block_size"],
        max_batch_size=res["max_batch_size"],
        chain_steps=res["chain_steps"],
        prefill_chunk=res["prefill_chunk"], dtype=dtype, params=plan,
        budget_bytes=_V5E_HBM, reference_attn=False)
    assert redo.prefill_chunk == res["prefill_chunk"]
    assert redo.as_dict()["prefill_chunk"] == res["prefill_chunk"]
    assert redo.fits and redo.total_bytes == res["plan"].total_bytes
    # a wider step keeps more temporaries: the ledger bills them
    assert redo.temp_bytes >= narrow["plan"].temp_bytes


def test_chunk_follows_the_bytes_a_step_streams():
    """Same shapes, fewer bytes, a narrower-or-equal chunk (no test of
    ``quantize``: the int8 plan's leaves are a byte wide); LFM2's 9 GB of
    experts against ~1 GFLOP a token allow at least GPT-2's rung."""
    f32 = _choose("gpt2_large_f32")[3]
    i8 = _choose("gpt2_large_int8")[3]
    moe = _choose("lfm2")[3]
    assert f32["plan"].params_bytes > 2 * i8["plan"].params_bytes
    assert f32["prefill_chunk"] >= i8["prefill_chunk"] > 0
    assert moe["prefill_chunk"] >= f32["prefill_chunk"]
    # the routed experts count at top_k / n_experts of their FLOPs
    cfg, plan, _d, _r = _choose("lfm2")
    dense = 2 * sum(w.size for w in jax.tree_util.tree_leaves(plan)
                    if w.ndim >= 2)
    assert obs_memory.step_flops_per_token(cfg, plan) < 0.3 * dense


@pytest.mark.parametrize("case", ["explicit", "no_budget", "no_roof",
                                  "prompt_cap", "tight_ledger",
                                  "short_pool"])
def test_chunk_rule_edges(case, monkeypatch):
    monkeypatch.delenv("PW_HBM_BUDGET_BYTES", raising=False)
    from .utils import described_decode_plan

    cfg, plan, dtype, _x = described_decode_plan("gpt2_large_f32")
    kw = dict(params=plan, dtype=dtype, reference_attn=False,
              budget_bytes=_V5E_HBM, roof=_V5E, seq_buckets=(64, 256, 1024))
    if case == "explicit":  # honoured verbatim, not listed, not a rung
        res = obs_memory.choose_engine_config(cfg, prefill_chunk=48, **kw)
        assert res["prefill_chunk"] == res["plan"].prefill_chunk == 48
        assert "prefill_chunk" not in res["chosen"]
        assert res["chunk_source"] == "explicit"
        return
    if case == "no_budget":
        kw["budget_bytes"] = None
    elif case == "no_roof":  # this backend resolves none
        kw["roof"] = None
    elif case == "prompt_cap":
        kw["seq_buckets"] = (64,)
    elif case == "tight_ledger":
        # room for the two-block configuration at full coverage and 40 MB
        base = obs_memory.choose_engine_config(cfg, prefill_chunk=32, **kw)
        kw["budget_bytes"] = base["plan"].total_bytes + 40 * 2 ** 20
    elif case == "short_pool":
        base = obs_memory.choose_engine_config(cfg, prefill_chunk=32, **kw)
        kw["budget_bytes"] = base["plan"].total_bytes - 2 ** 30
    res = obs_memory.choose_engine_config(cfg, **kw)
    assert "prefill_chunk" in res["chosen"]
    assert res["plan"].prefill_chunk == res["prefill_chunk"]
    if case in ("no_budget", "no_roof", "short_pool"):
        assert res["prefill_chunk"] == 32
        assert res["chunk_source"].startswith("default"), res["chunk_source"]
    elif case == "prompt_cap":
        assert res["prefill_chunk"] == 64
    else:
        wide = obs_memory.choose_engine_config(
            cfg, **dict(kw, budget_bytes=_V5E_HBM))
        assert 32 <= res["prefill_chunk"] < wide["prefill_chunk"]
        assert res["num_blocks"] == wide["num_blocks"]
        assert res["plan"].fits
    if case == "short_pool":  # the pool was clamped: nothing to spare
        assert res["num_blocks"] < 16 * 64 + 1


def test_hbm_plan_bills_int8_at_true_byte_width(params):
    """The ledger's weights term reads each plan leaf's OWN dtype:
    the int8-resident plan (native=True forces device-resident
    ``{w}_q``/``{w}_s`` leaves on CPU too) must bill well under half
    the f32 plan's bytes."""
    f32_plan = plan_decode_params(_CFG, params, head_t=True)
    i8_plan = plan_decode_params(_CFG, params, quantize="int8",
                                 native=True)
    kw = dict(num_blocks=64, block_size=4, tp=1)
    f32_b = obs_memory.hbm_plan(_CFG, params=f32_plan, **kw).params_bytes
    i8_b = obs_memory.hbm_plan(_CFG, params=i8_plan, **kw).params_bytes
    assert 0 < i8_b < 0.6 * f32_b, (i8_b, f32_b)
    # the quantized leaves really are int8 + per-output-channel f32
    lyr = i8_plan["layers"][0]
    assert lyr["wqkv_q"].dtype == jnp.int8
    assert lyr["wqkv_s"].dtype == jnp.float32
    assert lyr["wqkv_s"].shape == (lyr["wqkv_q"].shape[-1],)


def test_quantize_weight_int8_contract():
    """q = clip(round(w/s), ±127) with s = amax(|w|, axis=0)/127 —
    dequant error bounded by s/2 per element, zero columns safe."""
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    w = w.at[:, 3].set(0.0)
    q, s = quantize_weight_int8(w)
    assert q.dtype == jnp.int8 and s.shape == (8,)
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= 127
    err = np.abs(np.asarray(q, np.float32) * np.asarray(s) - np.asarray(w))
    assert (err <= np.asarray(s)[None, :] * 0.5 + 1e-8).all()
    assert not np.isnan(np.asarray(s)).any()


# -- profile --diff ----------------------------------------------------------


def _snap(rows):
    return {"programs": rows, "total_dispatch_s":
            sum(r.get("dispatch_s_total", 0) for r in rows)}


def test_profile_diff_rows_and_cli(tmp_path):
    before = _snap([
        {"program": "pw.chained_decode", "bucket": "b8",
         "dispatch_ms_p50": 40.0, "mfu": 0.02, "dispatch_s_total": 3.0},
        {"program": "pw.retired", "bucket": "b1",
         "dispatch_ms_p50": 5.0, "mfu": 0.01, "dispatch_s_total": 1.0},
    ])
    after = _snap([
        {"program": "pw.chained_decode", "bucket": "b8",
         "dispatch_ms_p50": 10.0, "mfu": 0.08, "dispatch_s_total": 1.0},
        {"program": "pw.chained_decode_i8", "bucket": "b8",
         "dispatch_ms_p50": 8.0, "mfu": 0.1, "dispatch_s_total": 0.5},
    ])
    from pathway_tpu.obs.profiler import profile_diff

    rows = {(r["program"], r["status"]): r
            for r in profile_diff(before, after)}
    assert ("pw.chained_decode_i8", "new") in rows
    assert ("pw.retired", "gone") in rows
    both = rows[("pw.chained_decode", "both")]
    assert both["ms_p50_delta"] == -30.0
    assert both["mfu_delta"] == pytest.approx(0.06)
    assert both["share_before"] == 0.75 and both["share_after"] \
        == pytest.approx(1.0 / 1.5, abs=1e-3)

    from pathway_tpu.cli import profile_command

    bpath, apath = tmp_path / "b.json", tmp_path / "a.json"
    bpath.write_text(json.dumps(before))
    apath.write_text(json.dumps(after))
    buf = io.StringIO()
    assert profile_command(str(apath), diff=str(bpath), out=buf) == 0
    txt = buf.getvalue()
    assert "pw.chained_decode_i8 (new)" in txt
    assert "pw.retired (gone)" in txt
    assert "40.00→10.00" in txt
    jbuf = io.StringIO()
    assert profile_command(str(apath), diff=str(bpath), as_json=True,
                           out=jbuf) == 0
    assert json.loads(jbuf.getvalue())[0]["program"]


# -- fused append+attend op --------------------------------------------------


def _append_case(seed=0, B=3, H=2, hd=128, NB=4, BS=4, cl=None):
    """A decode-step-shaped case: slot at the context tail; pools in
    BlockPool's per-layer shape, heads fused on the minor axis."""
    rng = np.random.default_rng(seed)
    B = B if cl is None else len(cl)
    nb_total = 1 + B * NB  # block 0 is the null block
    q = rng.normal(size=(B, 1, H, hd)).astype(np.float32)
    k_new = rng.normal(size=(B, H, hd)).astype(np.float32)
    v_new = rng.normal(size=(B, H, hd)).astype(np.float32)
    k_pool = rng.normal(size=(nb_total, BS, H * hd)).astype(np.float32)
    v_pool = rng.normal(size=(nb_total, BS, H * hd)).astype(np.float32)
    bt = np.zeros((B, NB), np.int32)
    cl = np.array([3, BS + 1, 2 * BS] if cl is None else cl, np.int32)[:B]
    for b in range(B):
        used = -(-int(cl[b]) // BS)
        bt[b, :used] = 1 + b * NB + np.arange(used)
    sb = bt[np.arange(B), (cl - 1) // BS]
    so = ((cl - 1) % BS).astype(np.int32)
    return tuple(jnp.asarray(x) for x in
                 (q, k_new, v_new, k_pool, v_pool, bt, cl, sb, so))


def test_paged_append_attend_reference_bit_identity():
    """The op's reference path IS scatter-then-reference-attend."""
    from pathway_tpu.kvcache.paged_attention import (
        paged_append_attend, paged_attention_reference,
    )

    q, k1, v1, kp, vp, bt, cl, sb, so = _append_case()
    a, ko, vo = paged_append_attend(q, k1, v1, kp, vp, bt, cl, sb, so,
                                    use_pallas=False)
    kp2 = kp.at[sb, so].set(k1.reshape(k1.shape[0], -1))
    vp2 = vp.at[sb, so].set(v1.reshape(v1.shape[0], -1))
    want = paged_attention_reference(q, kp2, vp2, bt, cl)
    assert (np.asarray(a) == np.asarray(want)).all()
    assert (np.asarray(ko) == np.asarray(kp2)).all()
    assert (np.asarray(vo) == np.asarray(vp2)).all()


# "spans*": blocks of 16 in tables of 20, so a grid step attends a span of
# 128 keys (eight blocks) and the table is 2.5 spans: the appended token is
# a row's first, a block's last, inside a span, a span's last key, the
# first key of the next span, and the table's last.
@pytest.mark.parametrize("H,hd,geometry", [
    (2, 128, {}),
    (20, 64, {}),
    (2, 128, dict(NB=20, BS=16, cl=[1, 16, 200, 128, 129, 320])),
    (20, 64, dict(NB=20, BS=16, cl=[16, 320, 129])),
], ids=["toy", "gpt2_large_heads", "spans", "spans_gpt2_large_heads"])
def test_paged_append_attend_kernel_interpret(H, hd, geometry):
    """The Pallas kernel (interpret mode on CPU) matches the reference
    to fp tolerance, with the new token's K/V landed in the slot block
    through the in-place pool alias: the pool differs from its input in
    exactly one block a row, the tail."""
    from pathway_tpu.kvcache.paged_attention import (
        paged_append_attend, paged_attention_reference,
    )

    q, k1, v1, kp, vp, bt, cl, sb, so = _append_case(H=H, hd=hd, **geometry)
    kp_np, vp_np = np.asarray(kp), np.asarray(vp)
    k1, v1 = k1.reshape(k1.shape[0], -1), v1.reshape(v1.shape[0], -1)
    want = paged_attention_reference(
        q, kp.at[sb, so].set(k1), vp.at[sb, so].set(v1), bt, cl
    )
    a, ko, vo = paged_append_attend(
        q, k1.reshape(-1, H, hd), v1.reshape(-1, H, hd), kp, vp, bt, cl, sb,
        so, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    sb_np, so_np = np.asarray(sb), np.asarray(so)
    ko_np, vo_np = np.asarray(ko), np.asarray(vo)
    np.testing.assert_array_equal(ko_np[sb_np, so_np], np.asarray(k1))
    np.testing.assert_array_equal(vo_np[sb_np, so_np], np.asarray(v1))
    # untouched blocks pass through unchanged, and so do the tail blocks'
    # other rows
    for new, old in ((ko_np, kp_np), (vo_np, vp_np)):
        changed = np.argwhere(new != old)
        assert sorted(set(map(tuple, changed[:, :2]))) \
            == sorted(zip(sb_np.tolist(), so_np.tolist()))


# -- generate(fused="auto") reads the measured tier prior --------------------


def test_generate_auto_consults_costdb_tier(monkeypatch):
    """A bench-recorded single-stream race verdict routes fused="auto"
    CPU generation through the paged engine at the winning tier."""
    from pathway_tpu.models.decoder import (
        JaxDecoderLM, measured_tier_prior,
    )
    from pathway_tpu.obs import costdb

    class _FakeDB:
        def __init__(self, tier):
            self._e = {"extra": {"tier": tier}}

        def get(self, program, bucket):
            if (program, bucket) == ("pw.decode_tier",
                                     "single_stream_pick"):
                return self._e
            return None

    monkeypatch.setattr(costdb, "default_db",
                        lambda: _FakeDB("int8_device"))
    assert measured_tier_prior() == "int8_device"
    cfg = DecoderConfig(vocab_size=64, d_model=64, n_layers=2,
                        n_heads=8, d_ff=128, max_len=128)
    lm = JaxDecoderLM(cfg)
    txt = lm.generate("<5> <6> <7>", max_new_tokens=6)
    assert txt and lm._paged_engine_inst[1] is not None
    assert lm._paged_engine_inst[1].quantize == "int8"
    monkeypatch.setattr(costdb, "default_db", lambda: _FakeDB(None))
    assert measured_tier_prior() is None
