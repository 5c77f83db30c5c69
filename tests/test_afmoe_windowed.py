"""The ``afmoe`` block family (arcee-ai Trinity) on the paged engine, at toy
widths on the CPU: 64 wide, 8 query heads over 2 K/V heads of 16, 16
experts top-4 beside a shared one, a sliding window of 24 positions, the
pattern sliding dense, sliding, full, sliding; seeded weights.

The reference is ``benchmark/reference/afmoe_f32.py`` (plain f32, no cache,
no kernels, no batching, imports nothing of the program; the window is a
mask over the full score matrix).  Tolerance, f32: 1e-4 of the logits'
standard deviation - program and reference do the same f32 arithmetic and
differ in reduction order only (readings: 2e-6 to 6e-6).  Contexts run to
over four times the window, so prefill chunks, decode steps and chains all
cross it and window blocks are freed under every one of them.  The same
comparison fails by three orders of magnitude when the program computes in
bf16, ignores the window or frees a window block one block early.
"""

import importlib
import types

import numpy as np
import pytest

S, F = "sliding_attention", "full_attention"
PATTERN = (S, S, F, S)
VOCAB, WINDOW = 257, 24


def _cfg(dtype="float32", **over):
    import jax.numpy as jnp

    from pathway_tpu.models.afmoe import AfmoeConfig

    kw = dict(vocab_size=VOCAB, d_model=64, n_heads=8, n_kv_heads=2,
              head_dim=16, d_ff=96, d_ff_expert=32, n_experts=16, top_k=4,
              n_dense_layers=1, layer_types=PATTERN, sliding_window=WINDOW,
              max_len=256, dtype=getattr(jnp, dtype))
    kw.update(over)
    return AfmoeConfig(**kw)


def _shape(cfg):
    from benchmark.systems.serve_afmoe import decoder_shape

    return decoder_shape(cfg, 0)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    import jax

    from pathway_tpu.models.afmoe import init_afmoe_params

    return init_afmoe_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, name, **kw):
    from pathway_tpu.kvcache.engine import PagedDecodeEngine

    geom = dict(num_blocks=64, block_size=8, max_batch_size=4,
                chain_steps=4, prefill_chunk=16, seq_buckets=(64, 256),
                attn="reference")
    geom.update(kw)
    return PagedDecodeEngine(cfg, params, name=name, **geom)


def _prompts(lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(4, VOCAB, n).tolist() for n in lengths]


REQS = [(70, 9), (7, 12), (101, 5), (3, 6), (55, 8), (33, 7)]


def _requests(seed=0):
    return [(p, n) for p, (_l, n) in zip(
        _prompts([l for l, _n in REQS], seed), REQS)]


@pytest.fixture(scope="module")
def clean_tokens(cfg, params):
    """What an engine that is never disturbed emits (gather path)."""
    eng = _engine(cfg, params, "t_afmoe_clean")
    out = eng.generate_batch(_requests())
    eng.pool.check_invariants()
    assert eng.pool.sequences() == [] and eng.pool.window_blocks_in_use == 0
    return out


# -- logits against the reference ---------------------------------------------


def _logits_through_engine(cfg, params, prompt, n_new, name, monkeypatch,
                           **kw):
    """One request alone through the engine's own programs (chunked prefill
    over the mixed step, chained decode, the single step at the tail),
    every program's logits caught where it turns them into ids.  Row 0 is
    the request: the last mixed step's logits are the prompt's last
    position's, every decode step's the next position's."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import afmoe

    caught = []

    def spy(logits):
        jax.debug.callback(lambda x: caught.append(np.asarray(x[0])), logits,
                           ordered=True)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(afmoe, "greedy_ids", spy)
    eng = _engine(cfg, params, name, **kw)
    tokens = eng.generate(prompt, n_new)
    jax.effects_barrier()
    snap = eng.pool.stats.snapshot()
    assert snap["prefill_chunks"] >= 3 and snap["mixed_steps"] >= 3
    assert snap["chain_steps_sum"] > snap["chain_count"]  # really chained
    assert snap["kv_window_blocks_freed"] > 0
    n_mixed = int(snap["mixed_steps"])
    rows = [caught[n_mixed - 1]] + caught[n_mixed:]
    assert len(rows) >= n_new
    return tokens, np.stack(rows[:n_new]), eng


def _reference(params, cfg, prompt, tokens):
    ref = importlib.import_module("benchmark.reference.afmoe_f32")
    cols = np.arange(len(prompt) - 1, len(prompt) + len(tokens) - 1)
    logits, margin = ref.logits_at(params, _shape(cfg), prompt + tokens, cols)
    return np.asarray(logits), np.asarray(margin)


def _error(cfg, params, name, monkeypatch, run_params=None, n_new=26, **kw):
    """The largest difference between the engine's logits and the
    reference's over a request whose prompt (70) is nearly three windows
    long and whose reply crosses a block boundary, in logit deviations."""
    prompt = _prompts([70], seed=5)[0]
    tokens, got, eng = _logits_through_engine(
        cfg, run_params if run_params is not None else params, prompt,
        n_new, name, monkeypatch, **kw)
    want, _margin = _reference(params, _cfg(), prompt, tokens)
    return np.abs(got - want).max(axis=-1) / want.std(axis=-1), tokens, want


@pytest.mark.parametrize("attn", ["reference", "pallas"])
def test_f32_logits_match_the_reference(cfg, params, attn, monkeypatch):
    if attn == "pallas":  # the kernels take heads in whole lane tiles
        cfg = _cfg(head_dim=128)
        import jax

        from pathway_tpu.models.afmoe import init_afmoe_params

        params = init_afmoe_params(cfg, jax.random.PRNGKey(0))
    prompt = _prompts([70], seed=5)[0]
    tokens, got, eng = _logits_through_engine(
        cfg, params, prompt, 26 if attn == "reference" else 7,
        f"t_afmoe_logits_{attn}", monkeypatch, attn=attn)
    ref = importlib.import_module("benchmark.reference.afmoe_f32")
    cols = np.arange(len(prompt) - 1, len(prompt) + len(tokens) - 1)
    want = np.asarray(ref.logits_at(params, _shape(cfg), prompt + tokens,
                                    cols)[0])
    err = np.abs(got - want).max(axis=-1) / want.std(axis=-1)
    assert err.max() < 1e-4, err
    assert tokens == want.argmax(-1).tolist()
    eng.pool.check_invariants()
    # the window pool never held the whole context
    peak = eng.pool.stats.kv_window_blocks_allocated \
        - eng.pool.stats.kv_window_blocks_freed
    assert peak == 0 and eng.pool.window_blocks_in_use == 0


def test_bf16_fails_the_f32_tolerance(cfg, params, monkeypatch):
    import jax
    import jax.numpy as jnp

    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
        if a.ndim >= 2 else a, params)
    err, _t, _w = _error(cfg, params, "t_afmoe_bf16", monkeypatch,
                         run_params=low)
    assert err.max() > 1e-2, err


def test_ignoring_the_window_fails_it(cfg, params, monkeypatch):
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")

    ragged, append = pa.paged_attention, pa.paged_append_attend
    monkeypatch.setattr(pa, "paged_attention",
                        lambda *a, window=None, **kw: ragged(*a, **kw))
    monkeypatch.setattr(pa, "paged_append_attend",
                        lambda *a, window=None, **kw: append(*a, **kw))
    err, _t, _w = _error(cfg, params, "t_afmoe_nowindow", monkeypatch)
    assert err.max() > 1e-1, err


def test_freeing_a_block_early_fails_it(cfg, params, monkeypatch):
    from pathway_tpu.kvcache.windowed import WindowedCache

    dead = WindowedCache.dead_blocks
    monkeypatch.setattr(WindowedCache, "dead_blocks",
                        lambda self, nxt: dead(self, nxt + self.block_size))
    err, _t, _w = _error(cfg, params, "t_afmoe_early", monkeypatch)
    assert err.max() > 1e-2, err


# -- the engine over the windowed cache ------------------------------------------


def test_kernels_and_gather_path_emit_the_same_tokens():
    import jax

    from pathway_tpu.models.afmoe import init_afmoe_params

    cfg = _cfg(head_dim=128, layer_types=(S, F))
    params = init_afmoe_params(cfg, jax.random.PRNGKey(1))
    reqs = _requests(seed=3)[:3]
    a = _engine(cfg, params, "t_afmoe_gather").generate_batch(reqs)
    b = _engine(cfg, params, "t_afmoe_kernels",
                attn="pallas").generate_batch(reqs)
    assert a == b


def test_preemption_recomputes_both_tables(cfg, params, clean_tokens):
    """A full pool too small for the batch: a victim gives back its blocks
    of both pools and is rebuilt by recompute, token for token."""
    eng = _engine(cfg, params, "t_afmoe_preempt", num_blocks=24)
    out = eng.generate_batch(_requests())
    assert out == clean_tokens
    assert eng.pool.stats.snapshot()["preemptions"] > 0
    eng.pool.check_invariants()
    assert eng.pool.sequences() == [] and eng.pool.window_blocks_in_use == 0
    assert eng.pool.num_free == eng.pool.num_blocks - 1


def test_tokens_are_the_references_best(cfg, params, clean_tokens):
    for (prompt, _n), tokens in zip(_requests(), clean_tokens):
        want, _m = _reference(params, cfg, prompt, tokens)
        assert tokens == want.argmax(-1).tolist()


def test_a_common_prefix_shares_no_block(cfg, params):
    eng = _engine(cfg, params, "t_afmoe_prefix", prefix_sharing=True)
    assert eng.prefix is None and eng.pool.supports_prefix is False
    base = _prompts([40], seed=9)[0]
    out = eng.generate_batch([(base + [7, 8], 4), (base + [9], 4)])
    assert len(out[0]) == len(out[1]) == 4
    assert eng.pool.stats.snapshot()["prefix_hits"] == 0


def test_second_pass_compiles_nothing(cfg, params):
    from pathway_tpu.obs import profiler

    eng = _engine(cfg, params, "t_afmoe_compiles")
    eng.generate_batch(_requests())
    before = profiler.registry().total_compiles()
    eng.generate_batch(_requests(seed=4))
    assert profiler.registry().total_compiles() == before


def test_round_spans_and_counters_carry_the_window(cfg, params):
    from pathway_tpu import obs
    from pathway_tpu.serve import metrics as serve_metrics

    eng = _engine(cfg, params, "t_afmoe_spans")
    eng.generate_batch(_requests())
    builds = [s for s in obs.recorder().snapshot()
              if s.name == "pw.round.build" and s.attrs
              and "kv_window_keys" in s.attrs]
    assert builds, "no round noted the window layers' keys"
    assert all(0 < s.attrs["kv_window_keys"] <= s.attrs["kv_window_ctx_keys"]
               == s.attrs["kv_keys"] for s in builds)
    assert any(s.attrs["kv_window_keys"] < s.attrs["kv_window_ctx_keys"]
               for s in builds)
    snap = eng.pool.stats.snapshot()
    assert 0 < snap["kv_window_keys"] < snap["kv_window_ctx_keys"]
    assert snap["kv_window_blocks_allocated"] \
        == snap["kv_window_blocks_freed"] > 0
    assert len(snap["moe_tokens_per_expert"]) == cfg.n_experts
    assert sum(snap["moe_tokens_per_expert"]) == snap["moe_routed_pairs"] > 0
    lines = serve_metrics.render_prometheus_lines()
    for metric in ("window_blocks_in_use", "window_blocks_total",
                   "window_blocks_freed_total", "window_keys_total",
                   "moe_routed_pairs_total"):
        assert any(ln.startswith(f'pathway_kv_{metric}{{pool="t_afmoe_spans"')
                   for ln in lines), metric


def test_hbm_plan_equals_the_live_bytes(cfg, params):
    eng = _engine(cfg, params, "t_afmoe_hbm")
    plan = eng.hbm_plan
    assert plan.kv_bytes + plan.window_bytes == eng.pool.per_shard_bytes
    assert plan.window_bytes == eng.pool.window_bytes > 0
    assert plan.total_bytes == plan.params_bytes + plan.kv_bytes \
        + plan.window_bytes + plan.temp_bytes
    from pathway_tpu.kvcache.windowed import window_seq_blocks

    # sized exactly: rows x (ceil((window + round) / block) + 2), + null
    assert eng.pool.window_blocks == 4 * window_seq_blocks(WINDOW, 16, 8) + 1
    assert window_seq_blocks(2048, 256, 16) == 146


# -- what the family refuses -----------------------------------------------------


@pytest.mark.parametrize("kwargs,names", [
    (dict(tp=2), ["tensor parallelism"]),
    (dict(quantize="int8"), ["quantize='int8'"]),
    (dict(speculative="ngram"), ["speculative drafting"]),
    (dict(tp=2, quantize="int8"), ["tensor parallelism", "quantize='int8'"]),
])
def test_unsupported_engine_options_are_refused_by_name(cfg, params, kwargs,
                                                        names):
    with pytest.raises(ValueError, match="afmoe block family") as err:
        _engine(cfg, params, "t_afmoe_refused", **kwargs)
    assert all(n in str(err.value) for n in names)


def test_a_session_store_is_refused_by_name(cfg, params):
    from pathway_tpu.kvcache import SessionStore

    with pytest.raises(ValueError, match="afmoe .* host tiering"):
        _engine(cfg, params, "t_afmoe_store", session_store=SessionStore())


def test_a_sampled_request_fails_alone(cfg, params, clean_tokens):
    eng = _engine(cfg, params, "t_afmoe_sampled")
    reqs = _requests()
    out = eng.generate_batch(
        reqs[:2] + [reqs[2] + ({"sampling": (0.8, 0, 1.0, 7)},)],
        return_exceptions=True)
    assert out[:2] == clean_tokens[:2]
    assert isinstance(out[2], ValueError) and "greedily" in str(out[2])


# -- the published configuration -------------------------------------------------


def test_hf_import_reads_the_published_config():
    import json
    import os

    from pathway_tpu.models import hf_import

    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "trinity-mini-serve.json")
    with open(path) as f:
        file = json.load(f)
    published = dict(file, **file["published"])
    cfg = hf_import.config_from_afmoe(types.SimpleNamespace(**published))
    assert (cfg.family, cfg.n_layers, cfg.n_dense_layers) == ("afmoe", 32, 2)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) \
        == (2048, 32, 4, 128)
    assert (cfg.d_ff, cfg.d_ff_expert, cfg.n_experts, cfg.top_k,
            cfg.n_shared_experts) == (6144, 1024, 128, 8, 1)
    assert (cfg.sliding_window, cfg.vocab_size, cfg.max_len) \
        == (2048, 200192, 131072)
    assert len(cfg.full_layers) == 8 and len(cfg.window_layers) == 24
    assert cfg.full_layers == tuple(range(3, 32, 4))
    assert (cfg.route_scale, cfg.route_norm, cfg.mup_enabled,
            cfg.rope_theta, cfg.norm_eps) == (2.826, True, True, 1e4, 1e-5)
    assert cfg.param_count() == pytest.approx(26.1e9, rel=0.01)
    # the cell's cut: layers 1-8, 13.52 GB in bf16
    cut = hf_import.config_from_afmoe(types.SimpleNamespace(**file),
                                      max_len=file["serve"]["max_len"])
    assert cut.layer_types == tuple(published["layer_types"][1:9])
    assert cut.max_len == 8192 and cut.n_dense_layers == 1
    assert 2 * cut.param_count() == pytest.approx(13.52e9, rel=0.002)
    for key, bad in (("score_func", "softmax"), ("n_group", 2),
                     ("tie_word_embeddings", True),
                     ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match="not written down"):
            hf_import.config_from_afmoe(
                types.SimpleNamespace(**dict(published, **{key: bad})))
    with pytest.raises(ValueError, match="expected an afmoe config"):
        hf_import.config_from_afmoe(types.SimpleNamespace(
            **dict(published, model_type="lfm2_moe")))


# -- the windowed backend alone ----------------------------------------------------


def _cache(name, **over):
    from pathway_tpu.kvcache import make_backend

    kw = dict(num_blocks=12, block_size=4, n_layers=1, n_heads=2, head_dim=8,
              window=10, window_layers=2, round_tokens=8, max_seqs=3,
              name=name)
    kw.update(over)
    return make_backend("windowed", **kw)


def test_blocks_are_freed_behind_the_window_after_the_sync():
    pool = _cache("t_win_free")
    assert pool.window == 10 and pool.window_blocks == 3 * 7 + 1
    pool.allocate(1, 30)
    assert pool.window_table(1) == [] and len(pool.sequence(1).block_ids) == 8
    pool.reserve_chunk(1, 8)
    held = list(pool.window_table(1))
    assert len(held) == 2 and 0 not in held
    pool.after_sync()  # next = 8: every position <= -2 is dead: none
    assert pool.window_table(1) == held
    pool.reserve_chunk(1, 16)
    # not before the sync: the round in flight still reads them
    assert pool.window_table(1)[:2] == held
    pool.after_sync()  # next = 16: positions <= 6 are dead: block 0 only
    assert pool.window_table(1)[0] == 0 and pool.window_table(1)[1] == held[1]
    assert [pool.dead_blocks(n) for n in (9, 12, 13, 16, 17, 21)] \
        == [0, 0, 1, 1, 2, 3]
    pool.check_invariants()
    pool.reserve_chunk(1, 30)
    pool.after_sync()
    slots = pool.extend_slots(1, 5)  # a chain: both tables grow
    assert len(slots) == 5 and len(pool.window_table(1)) == 9
    assert pool.sequence(1).n_tokens == 35
    pool.after_sync()  # next = 35: positions <= 25 dead: blocks 0-5
    assert pool.window_table(1)[:6] == [0] * 6 and all(
        pool.window_table(1)[6:])
    pool.check_invariants()
    stats = pool.stats.snapshot()
    assert stats["kv_window_blocks_allocated"] == 9
    assert stats["kv_window_blocks_freed"] == 6
    assert stats["window_blocks_in_use"] == 3
    # the tables a program takes: by position, the null block behind
    (tables,) = pool.row_extras([1], 2, 10)
    assert tables.shape == (2, 10) and tables.dtype == np.int32
    assert tables[0, :6].tolist() == [0] * 6 and tables[0, 6:9].all()
    assert tables[0, 9] == 0 and not tables[1].any()
    pool.free_sequence(1)
    pool.check_invariants()
    assert pool.window_blocks_in_use == 0
    assert pool.stats.snapshot()["kv_window_blocks_freed"] == 9


def test_exhaustion_of_the_full_pool_leaves_neither_table_changed():
    from pathway_tpu.kvcache import PoolExhausted, UnsupportedCacheOp

    pool = _cache("t_win_exhaust", num_blocks=8)
    pool.allocate(1, 12)
    pool.reserve_chunk(1, 12)
    pool.allocate(2, 12)
    before = (pool.num_free, len(pool._wfree), list(pool.window_table(1)))
    with pytest.raises(PoolExhausted):
        pool.extend_slots(1, 9)  # needs 3 full blocks, 1 is free
    assert (pool.num_free, len(pool._wfree), pool.window_table(1)) == before
    assert pool.sequence(1).n_tokens == 12
    with pytest.raises(PoolExhausted):
        pool.allocate(3, 8)
    assert 3 not in [s.seq_id for s in pool.sequences()]
    victim = pool.preempt(exclude={2})
    assert victim.seq_id == 1 and len(pool._wfree) == pool.window_blocks - 1
    pool.allocate(3, 8)
    pool.allocate(4, 1)
    with pytest.raises(PoolExhausted, match="sized for 3"):
        pool.allocate(5, 1)
    pool.check_invariants()
    with pytest.raises(UnsupportedCacheOp, match="roll slots back"):
        pool.truncate_slots(2, 1)
    with pytest.raises(ValueError, match="at least one full and one window"):
        _cache("t_win_nolayer", window_layers=0)


# -- the kernels with a window ---------------------------------------------------


@pytest.mark.parametrize("window", [24, 128], ids=["W24", "W128"])
@pytest.mark.parametrize("C", [1, 4, 40, 64],
                         ids=["decode", "chunk4", "chunk40", "chunk64_tiled"])
def test_paged_kernels_mask_and_skip_behind_the_window(C, window):
    """hd 128, eight query heads folded on a K/V head, blocks of 16: the
    interpreted kernels against the gather reference, with a window that is
    a whole span of 128 keys and one that is not even whole blocks; rows
    that start inside the first span, behind one dead span and behind two.
    64 query columns x 8 folded heads take two column tiles."""
    import jax.numpy as jnp

    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")

    rng = np.random.default_rng(C * 1000 + window)
    B, KV, rep, hd, BS, NB, NBLK = 3, 2, 8, 128, 16, 28, 96
    kp = jnp.asarray(rng.standard_normal((NBLK, BS, KV * hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NBLK, BS, KV * hd)), jnp.float32)
    perm = rng.permutation(np.arange(1, NBLK))
    bt = np.stack([perm[b * NB:(b + 1) * NB] for b in range(B)]).astype(
        np.int32)
    q = jnp.asarray(rng.standard_normal((B, C, KV * rep, hd)), jnp.float32)
    start = np.array([0, 150, 300], np.int32)
    nv = np.array([C, max(C // 2, 1), 1], np.int32)
    # one K/V head a group: a first tile of one query column's eight folded
    # heads, wide tiles of 256 rows where they divide the folded columns
    assert pa._col_tiles(1, C * rep, rep, jnp.float32) \
        == {1: None, 4: (8, 32), 40: None, 64: (8, 256)}[C]
    want = pa.paged_attention_reference(q, kp, vp, bt, start_pos=start,
                                        n_valid=nv, window=window)
    got = pa.paged_attention(q, kp, vp, bt, start_pos=start, n_valid=nv,
                             window=window, use_pallas=True, interpret=True)
    full = pa.paged_attention_reference(q, kp, vp, bt, start_pos=start,
                                        n_valid=nv)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(got)[b, :nv[b]],
                                   np.asarray(want)[b, :nv[b]], atol=2e-5)
    assert np.abs(np.asarray(full)[2, 0] - np.asarray(want)[2, 0]).max() > 0.05
    if C == 1:
        k1 = jnp.asarray(rng.standard_normal((B, KV, hd)), jnp.float32)
        v1 = jnp.asarray(rng.standard_normal((B, KV, hd)), jnp.float32)
        cl = start + 1
        sb = np.array([bt[b, (cl[b] - 1) // BS] for b in range(B)], np.int32)
        so = ((cl - 1) % BS).astype(np.int32)
        a0, k0, v0 = pa.paged_append_attend(
            q, k1, v1, kp, vp, bt, cl, sb, so, window=window,
            use_pallas=False)
        a1, k2, v2 = pa.paged_append_attend(
            q, k1, v1, kp, vp, bt, cl, sb, so, window=window,
            use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(a1), np.asarray(a0), atol=2e-5)
        np.testing.assert_array_equal(np.asarray(k2), np.asarray(k0))
        np.testing.assert_array_equal(np.asarray(v2), np.asarray(v0))


@pytest.mark.parametrize("C", [1, 8], ids=["decode", "chunk"])
def test_a_block_a_step_path_takes_the_window_too(C):
    """Lanes that are no whole tiles (two K/V heads of 16): a grid step is
    one block, brought by its block spec, whose index is clamped to the
    row's first live block; a window that is no whole number of blocks."""
    import jax.numpy as jnp

    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    rng = np.random.default_rng(C)
    B, KV, rep, hd, BS, NB, NBLK = 3, 2, 4, 16, 16, 12, 64
    assert pa.span_blocks(BS, NB, KV * hd) == 1
    kp = jnp.asarray(rng.standard_normal((NBLK, BS, KV * hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NBLK, BS, KV * hd)), jnp.float32)
    perm = rng.permutation(np.arange(1, NBLK))
    bt = np.stack([perm[b * NB:(b + 1) * NB] for b in range(B)]).astype(
        np.int32)
    q = jnp.asarray(rng.standard_normal((B, C, KV * rep, hd)), jnp.float32)
    start = np.array([0, 70, 150], np.int32)
    nv = np.array([C, max(C // 2, 1), 1], np.int32)
    want = pa.paged_attention_reference(q, kp, vp, bt, start_pos=start,
                                        n_valid=nv, window=24)
    got = pa.paged_attention(q, kp, vp, bt, start_pos=start, n_valid=nv,
                             window=24, use_pallas=True, interpret=True)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(got)[b, :nv[b]],
                                   np.asarray(want)[b, :nv[b]], atol=2e-5)


def test_no_window_lowers_to_the_kernels_of_before():
    """``window=None`` adds nothing to a kernel's call: no window keyword
    reaches the kernel body and no compiler parameters the call."""
    import jax
    import jax.numpy as jnp

    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")

    q = jnp.zeros((2, 4, 4, 64), jnp.float32)
    pool = jnp.zeros((1, 9, 16, 256), jnp.float32)
    bt = jnp.zeros((2, 4), jnp.int32)
    c = jnp.ones((2,), jnp.int32)
    layer = jnp.zeros((1,), jnp.int32)

    def text(**kw):
        return jax.jit(lambda *a: pa._paged_ragged_fn(
            *a, d_true=64, interpret=True, **kw)).lower(
            q, pool, pool, layer, bt, c, c + 3).as_text()

    assert text() == text(window=None)
    assert text() != text(window=24)
    assert pa._vmem_limit(8, 16, 1280, jnp.bfloat16, 20, 32, 64, 2,
                          jnp.bfloat16) == {}
    assert pa._vmem_limit(8, 16, 512, jnp.bfloat16, 4, 2048, 128, 1,
                          jnp.bfloat16) != {}


# -- the expert layer at the published counts ---------------------------------------


def test_expert_ffn_at_128_experts_top_8_with_scale_and_shared_expert():
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.lfm2 import _swiglu
    from pathway_tpu.ops import moe

    E, k, D, Fe, T, scale = 128, 8, 32, 16, 37, 2.826
    ks = iter(jax.random.split(jax.random.PRNGKey(2), 12))

    def n(*shape, s=1.0):
        return jax.random.normal(next(ks), shape, jnp.float32) * s

    lay = {"wg": n(D, E, s=D ** -0.5), "expert_bias": n(E, s=0.02),
           "w1": n(E, D, Fe, s=D ** -0.5), "w3": n(E, D, Fe, s=D ** -0.5),
           "w2": n(E, Fe, D, s=Fe ** -0.5)}
    shared = {"w1": n(D, Fe, s=D ** -0.5), "w3": n(D, Fe, s=D ** -0.5),
              "w2": n(Fe, D, s=Fe ** -0.5)}
    h = n(T, D)
    valid = jnp.arange(T) % 9 != 8
    with jax.default_matmul_precision("highest"):
        y, counts = moe.expert_ffn(h, lay, valid, top_k=k, scale=scale,
                                   renorm_eps=1e-20, use_pallas=False)
        y = y + _swiglu(shared, h)
        s = jax.nn.sigmoid(h @ lay["wg"])
        _top, idx = jax.lax.top_k(s + lay["expert_bias"], k)
        w = jnp.take_along_axis(s, idx, axis=1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
        want = _swiglu(shared, h)
        for e in range(E):
            we = jnp.where(idx == e, w, 0.0).sum(-1, keepdims=True)
            want = want + we * _swiglu(
                {n_: lay[n_][e] for n_ in ("w1", "w3", "w2")}, h)
    ok = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(y)[ok], np.asarray(want)[ok],
                               atol=2e-5)
    counts = counts[:E]  # then ops.moe.COUNTER_TAIL
    assert int(counts.sum()) == int(ok.sum()) * k
    np.testing.assert_allclose(np.asarray(w.sum(-1)), scale, rtol=1e-6)
    # the kernel's layout holds 128 groups: every tile one expert's
    g = moe.group_rows(idx.astype(jnp.int32), valid, E)
    assert g["tile_expert"].shape == (moe.n_tiles(T * k, E),)
    assert int(g["n_live"][0]) == int(np.ceil(
        np.asarray(g["counts"]) / moe.TM).sum())
