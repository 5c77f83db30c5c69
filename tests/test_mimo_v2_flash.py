"""The ``mimo_v2_flash`` block family (XiaomiMiMo MiMo-V2-Flash) on the paged
engine, at toy widths on the CPU: 64 wide, 8 query heads of 24 over 1 K/V
head on the full layers and 2 on the sliding ones, values of 16, rotary on
the leading 8, a sliding window of 24 positions with a sink a query head, a
router of 16 of which experts 4-7 are held, the pattern full dense, sliding,
sliding, full, sliding; seeded weights.

The reference is ``benchmark/reference/mimo_v2_flash_f32.py`` (plain f32, no
cache, no kernels, no batching, imports nothing of the program; the window
is a mask over the full score matrix, the sink a concatenated logit).
Tolerance, f32: 1e-4 of the logits' standard deviation - program and
reference do the same f32 arithmetic and differ in reduction order only
(readings: 2e-6 to 8e-6).  Contexts run to over four times the window, the
prefill chunk is narrower (16) AND wider (32, 64) than it, so chunks, decode
steps and chains all cross it and window blocks are freed and reused inside
one long prompt.
"""

import importlib

import numpy as np
import pytest

S, F = "sliding_attention", "full_attention"
PATTERN = (F, S, S, F, S)
VOCAB, WINDOW = 257, 24


def _pa():
    return importlib.import_module("pathway_tpu.kvcache.paged_attention")


def _cfg(dtype="float32", **over):
    import jax.numpy as jnp

    from pathway_tpu.models.mimo_v2_flash import MimoV2FlashConfig

    kw = dict(vocab_size=VOCAB, d_model=64, n_heads=8, n_kv_heads=1,
              window_kv_heads=2, head_dim=24, v_head_dim=16, rotary_dim=8,
              d_ff=96, d_ff_expert=32, n_experts=16, n_held_experts=4,
              first_expert=4, top_k=4, n_dense_layers=1,
              layer_types=PATTERN, sliding_window=WINDOW, max_len=256,
              dtype=getattr(jnp, dtype))
    kw.update(over)
    return MimoV2FlashConfig(**kw)


def _shape(cfg):
    from benchmark.systems.serve_lfm2 import decoder_shape

    return decoder_shape(cfg, 0)


def _init(cfg, seed=0):
    import jax

    from pathway_tpu.models.mimo_v2_flash import init_mimo_v2_flash_params

    return init_mimo_v2_flash_params(cfg, jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return _init(cfg)


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
    eng = _engine(cfg, params, "t_mimo_clean")
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
    the request."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import blocks

    caught = []

    def spy(logits):
        jax.debug.callback(lambda x: caught.append(np.asarray(x[0])), logits,
                           ordered=True)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(blocks, "greedy_ids", spy)
    eng = _engine(cfg, params, name, **kw)
    tokens = eng.generate(prompt, n_new)
    jax.effects_barrier()
    snap = eng.pool.stats.snapshot()
    assert snap["mixed_steps"] >= 2
    assert snap["chain_steps_sum"] > snap["chain_count"]  # really chained
    assert snap["kv_window_blocks_freed"] > 0
    n_mixed = int(snap["mixed_steps"])
    rows = [caught[n_mixed - 1]] + caught[n_mixed:]
    assert len(rows) >= n_new
    return tokens, np.stack(rows[:n_new]), eng


def _reference(params, cfg, prompt, tokens):
    ref = importlib.import_module("benchmark.reference.mimo_v2_flash_f32")
    cols = np.arange(len(prompt) - 1, len(prompt) + len(tokens) - 1)
    logits, margin = ref.logits_at(params, _shape(cfg), prompt + tokens, cols)
    return np.asarray(logits), np.asarray(margin)


@pytest.mark.parametrize("chunk", [16, 32, 64],
                         ids=["narrower", "wider", "wider_still"])
def test_f32_logits_match_the_reference(cfg, params, chunk, monkeypatch):
    """Prefill in chunks narrower and wider than the window of 24, decode
    steps and a chain through both pools: the engine's logits are the
    reference's full forward's within 1e-4 of their deviation."""
    prompt = _prompts([70], seed=5)[0]
    tokens, got, eng = _logits_through_engine(
        cfg, params, prompt, 26, f"t_mimo_logits_{chunk}", monkeypatch,
        prefill_chunk=chunk)
    want, _m = _reference(params, cfg, prompt, tokens)
    err = np.abs(got - want).max(axis=-1) / want.std(axis=-1)
    assert err.max() < 1e-4, err
    assert tokens == want.argmax(-1).tolist()
    eng.pool.check_invariants()
    assert eng.pool.window_blocks_in_use == 0
    assert eng.pool.window < 32 or chunk == 16


def test_interpreted_kernels_match_the_reference(monkeypatch):
    """The three kernels in interpret mode under the family at heads of
    whole lane tiles (keys of 256 beside values of 128): the reference's
    logits within the same tolerance."""
    cfg = _cfg(head_dim=256, v_head_dim=128, rotary_dim=64,
               layer_types=(F, S, S))
    params = _init(cfg, 2)
    prompt = _prompts([45], seed=6)[0]
    tokens, got, eng = _logits_through_engine(
        cfg, params, prompt, 7, "t_mimo_logits_pallas", monkeypatch,
        attn="pallas")
    want, _m = _reference(params, cfg, prompt, tokens)
    err = np.abs(got - want).max(axis=-1) / want.std(axis=-1)
    assert err.max() < 1e-4, err
    assert eng.pool.k.shape[-1] == 256 and eng.pool.v.shape[-1] == 128
    assert eng.pool.kw.shape[-1] == 512 and eng.pool.vw.shape[-1] == 256


@pytest.mark.parametrize("fault", ["sink_left_out", "window_25",
                                   "value_scale_left_out", "bf16"])
def test_a_fault_fails_the_f32_tolerance(cfg, params, fault, monkeypatch):
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import mimo_v2_flash as m

    pa, run = _pa(), params
    ragged, append = pa.paged_attention, pa.paged_append_attend
    if fault == "sink_left_out":
        monkeypatch.setattr(pa, "paged_attention",
                            lambda *a, sinks=None, **kw: ragged(*a, **kw))
        monkeypatch.setattr(pa, "paged_append_attend",
                            lambda *a, sinks=None, **kw: append(*a, **kw))
    elif fault == "window_25":
        def wider(fn):
            return lambda *a, window=None, **kw: fn(
                *a, window=None if window is None else window + 1, **kw)
        monkeypatch.setattr(pa, "paged_attention", wider(ragged))
        monkeypatch.setattr(pa, "paged_append_attend", wider(append))
    elif fault == "value_scale_left_out":
        monkeypatch.setattr(m, "_values", lambda v, scale: v)
    else:
        run = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
            if a.ndim >= 2 else a, params)
    prompt = _prompts([70], seed=5)[0]
    tokens, got, _e = _logits_through_engine(
        cfg, run, prompt, 26, f"t_mimo_{fault}", monkeypatch)
    want, _m = _reference(params, cfg, prompt, tokens)
    err = np.abs(got - want).max(axis=-1) / want.std(axis=-1)
    assert err.max() > 1e-2, err


def test_the_shares_routed_parts_add_up_to_the_uncut_layer():
    """The guide's share test: a model that holds every expert against the
    four shares of four experts each, one expert layer deep, same weights:
    the shares' routed parts (each a layer's output less the stream it
    entered with) add up to the uncut reference's expert layer."""
    import jax.numpy as jnp

    ref = importlib.import_module("benchmark.reference.mimo_v2_flash_f32")
    whole = _cfg(n_held_experts=None, first_expert=0)
    params = _init(whole, 3)
    lay = {n: params["layers"][1][n] for n in ref._EXPERT_LEAVES}
    x = jnp.asarray(np.random.default_rng(0).standard_normal((37, 64)),
                    jnp.float32)
    want, _m, weights = ref._experts(x, lay, _shape(whole))
    assert float(jnp.abs(weights.sum(-1) - 1).max()) < 1e-5
    total = jnp.zeros_like(want)
    for first in range(0, 16, 4):
        share = _cfg(first_expert=first)
        held = dict(lay, **{n: lay[n][first:first + 4]
                            for n in ("w1", "w3", "w2")})
        part, _m, mine = ref._experts(x, held, _shape(share))
        assert jnp.array_equal(mine, weights[:, first:first + 4])
        total = total + part
        # the program's expert layer computes the same share
        from pathway_tpu.ops.moe import expert_ffn

        got, _counts = expert_ffn(
            x, held, jnp.ones((37,), bool), h_route=x, top_k=4,
            norm_topk=True, renorm_eps=1e-20, use_pallas=False,
            first_expert=first)
        assert float(jnp.abs(got - part).max()) < 1e-5
    assert float(jnp.abs(total - want).max()) < 1e-5


# -- the engine over the windowed cache ------------------------------------------


def test_kernels_and_gather_path_emit_the_same_tokens():
    cfg = _cfg(head_dim=256, v_head_dim=128, rotary_dim=64,
               layer_types=(S, F))
    params = _init(cfg, 1)
    reqs = _requests(seed=3)[:3]
    a = _engine(cfg, params, "t_mimo_gather").generate_batch(reqs)
    b = _engine(cfg, params, "t_mimo_kernels",
                attn="pallas").generate_batch(reqs)
    assert a == b


@pytest.mark.parametrize("P", [16, 8])
def test_kernels_in_pieces_emit_the_gather_paths_tokens(monkeypatch, P):
    """The family's mixed step with its ragged calls' rows cut from the
    packed stream in pieces of ``P`` query columns (``query_layout`` steered
    here, for both layer kinds): the gather path's tokens, to the last."""
    pa = _pa()
    cfg = _cfg(head_dim=256, v_head_dim=128, rotary_dim=64,
               layer_types=(S, F))
    params = _init(cfg, 1)
    reqs = _requests(seed=3)[:3]
    a = _engine(cfg, params, f"t_mimo_gather_{P}").generate_batch(reqs)
    calls = []

    def pieces(T, B, C, *_a, **_k):
        calls.append((T, B, C))
        return P, T // P + B

    monkeypatch.setattr(pa, "query_layout", pieces)
    b = _engine(cfg, params, f"t_mimo_pieces_{P}",
                attn="pallas").generate_batch(reqs)
    assert a == b
    assert (20, 4, 16) in calls  # the mixed step's: T = B + C


def test_tokens_are_the_references_best(cfg, params, clean_tokens):
    for (prompt, _n), tokens in zip(_requests(), clean_tokens):
        want, _m = _reference(params, cfg, prompt, tokens)
        assert tokens == want.argmax(-1).tolist()


def test_it_serves_through_the_scheduler_on_the_windowed_kind(cfg, params,
                                                               clean_tokens):
    from pathway_tpu.models import families
    from pathway_tpu.serve.scheduler import RequestScheduler

    assert len(families._FAMILIES) == 7  # PR 45: phi4_mini_flash
    assert families.step_family(cfg).cache_kind == "windowed"
    eng = _engine(cfg, params, "t_mimo_sched")
    assert eng.pool.cache_kind == "windowed" and eng.prefix is None
    holder = {}
    sched = RequestScheduler(
        lambda reqs: eng.serve_batch(reqs, scheduler=holder["s"]),
        name="t_mimo_sched", max_batch_size=4, max_queue=16)
    holder["s"] = sched
    from concurrent.futures import ThreadPoolExecutor

    try:
        with ThreadPoolExecutor(3) as callers:
            out = list(callers.map(sched.submit, _requests()[:3]))
    finally:
        sched.shutdown(drain=True)
    assert out == clean_tokens[:3]
    eng.pool.check_invariants()


def test_preemption_recomputes_both_tables(cfg, params, clean_tokens):
    eng = _engine(cfg, params, "t_mimo_preempt", num_blocks=24)
    out = eng.generate_batch(_requests())
    assert out == clean_tokens
    assert eng.pool.stats.snapshot()["preemptions"] > 0
    eng.pool.check_invariants()
    assert eng.pool.sequences() == [] and eng.pool.window_blocks_in_use == 0


def test_second_pass_compiles_nothing(cfg, params):
    from pathway_tpu.obs import profiler

    eng = _engine(cfg, params, "t_mimo_compiles")
    eng.generate_batch(_requests())
    before = profiler.registry().total_compiles()
    eng.generate_batch(_requests(seed=4))
    assert profiler.registry().total_compiles() == before


@pytest.mark.parametrize("kwargs,names", [
    (dict(tp=2), ["tensor parallelism"]),
    (dict(quantize="int8"), ["quantize='int8'"]),
    (dict(speculative="ngram"), ["speculative drafting"]),
])
def test_unsupported_engine_options_are_refused_by_name(cfg, params, kwargs,
                                                        names):
    with pytest.raises(ValueError, match="mimo_v2_flash block family") as err:
        _engine(cfg, params, "t_mimo_refused", **kwargs)
    assert all(n in str(err.value) for n in names)


def test_a_session_store_and_a_sampled_row_are_refused(cfg, params,
                                                       clean_tokens):
    from pathway_tpu.kvcache import SessionStore

    with pytest.raises(ValueError, match="mimo_v2_flash .* host tiering"):
        _engine(cfg, params, "t_mimo_store", session_store=SessionStore())
    eng = _engine(cfg, params, "t_mimo_sampled")
    reqs = _requests()
    out = eng.generate_batch(
        reqs[:1] + [reqs[1] + ({"sampling": (0.8, 0, 1.0, 7)},)],
        return_exceptions=True)
    assert out[0] == clean_tokens[0]
    assert isinstance(out[1], ValueError) and "greedily" in str(out[1])


# -- the four pools, their bytes and the round's attributes ------------------------


def test_hbm_plan_bills_four_pools_at_four_widths(cfg, params):
    from pathway_tpu.serve import metrics as serve_metrics

    eng = _engine(cfg, params, "t_mimo_hbm", prefill_chunk=32)
    pool, plan = eng.pool, eng.hbm_plan
    item = pool.k.dtype.itemsize
    parts = pool.pool_part_bytes
    # full: 2 layers x 64 blocks x 8 x (1 x 24 | 1 x 16); window: 3 layers
    # x (4 x (ceil((24 + 32) / 8) + 2) + 1 = 37) x 8 x (2 x 24 | 2 x 16)
    assert parts == {"full_k": 2 * 64 * 8 * 24 * item,
                     "full_v": 2 * 64 * 8 * 16 * item,
                     "window_k": 3 * 37 * 8 * 48 * item,
                     "window_v": 3 * 37 * 8 * 32 * item}
    assert plan.kv_bytes == parts["full_k"] + parts["full_v"]
    assert plan.window_bytes == parts["window_k"] + parts["window_v"] \
        == pool.window_bytes
    assert plan.kv_bytes + plan.window_bytes == pool.per_shard_bytes
    lines = serve_metrics.render_prometheus_lines()
    for part, n in parts.items():
        assert (f'pathway_kv_pool_bytes{{pool="t_mimo_hbm",part="{part}"}} '
                f'{n}') in lines


def test_the_existing_families_pools_are_built_as_before():
    """``v_head_dim`` / ``window_heads`` at their defaults: a pool pair of
    one width, the window pool of the full pool's geometry."""
    from pathway_tpu.kvcache.backend import make_backend
    from pathway_tpu.kvcache.block_pool import BlockPool

    pool = BlockPool(num_blocks=9, block_size=8, n_layers=2, n_heads=2,
                     head_dim=16, name="t_mimo_plain")
    assert pool.k.shape == pool.v.shape == (2, 9, 8, 32)
    assert pool.per_shard_bytes == 2 * pool.k.size * 4
    cache = make_backend("windowed", num_blocks=9, block_size=8, n_layers=1,
                         n_heads=2, head_dim=16, window=24, window_layers=3,
                         round_tokens=16, max_seqs=2, name="t_mimo_afmoe")
    assert cache.kw.shape == cache.vw.shape == (3, 2 * 7 + 1, 8, 32)
    assert cache.window_bytes == 2 * cache.kw.size * 4


def test_round_build_counts_the_window_pairs_by_hand(cfg, params):
    """``kv_window_band_pairs`` / ``kv_window_span_pairs`` of a mixed round
    against a count by hand: one prompt of 70 tokens alone, a chunk of 32
    over a window of 24, blocks of 8 (a span is a block here: pools of 48
    lanes are no whole tiles).  The rule lays both pools out in pieces of 8
    columns (heads of 24 lanes split tiles); a piece of 8 columns is 32
    folded ones (rep 4), a first tile of 8 (2 columns) or one wide tile of 32
    (8 columns), over every span from the one that holds its first column's
    oldest key to the one that holds its last key."""
    from pathway_tpu import obs

    eng = _engine(cfg, params, "t_mimo_pairs", prefill_chunk=32)
    assert eng._query_layout == (8, 8) and eng._window_pairs["cols"] == 8
    eng.generate(_prompts([70], seed=8)[0], 2)
    builds = [s.attrs for s in obs.recorder().snapshot()
              if s.name == "pw.round.build" and s.attrs
              and s.attrs.get("kind") == "mixed"
              and "kv_window_band_pairs" in s.attrs][-3:]
    hand = []
    for start, n in ((0, 32), (32, 32), (64, 6)):
        band = sum(min(p + 1, WINDOW) for p in range(start, start + n))
        run = 0
        for k in range(-(-n // 8)):
            cols = min(8, n - 8 * k)
            c0 = start + 1 + 8 * k
            first = max(c0 - WINDOW, 0) // 8
            last = (c0 + cols - 2) // 8
            run += (2 if cols <= 2 else 8) * (last - first + 1) * 8
        hand.append((band, run))
    got = [(a["kv_window_band_pairs"], a["kv_window_span_pairs"])
           for a in builds[:3]]
    assert got == hand, (got, hand)
    assert all(b <= r for b, r in got)
    snap = eng.pool.stats.snapshot()
    assert snap["kv_window_band_pairs"] >= sum(b for b, _r in hand)
    # a window as wide as the chunk, or a cache without one: no attribute
    wide = _engine(_cfg(sliding_window=64), _init(_cfg(sliding_window=64)),
                   "t_mimo_pairs_wide", prefill_chunk=32)
    wide.generate(_prompts([40], seed=8)[0], 2)
    assert wide.pool.stats.snapshot()["kv_window_span_pairs"] == 0


def test_round_build_counts_the_query_slots_of_the_pieces(cfg, params):
    """``kv_query_cols`` / ``kv_query_slots`` / ``kv_query_tile_cols`` of a
    mixed round in pieces, by hand: the toy's full pool (8 query heads of 24
    on one K/V head: rep 8, heads that split tiles) at a chunk of 32 and
    four rows is N = 36 // 8 + 4 = 8 kernel rows of P = 8 columns, 64 slots
    whatever the round holds; a piece of 8 columns is 64 folded ones, a
    first tile of 8 (one column) or a wide one of 64 (eight); a kernel row
    past the rows' pieces runs one column's tile.  The slots reach the
    pool's counter and ``/metrics``."""
    from pathway_tpu.serve import metrics as serve_metrics

    class _Ph:
        def set(self, **attrs):
            self.attrs = attrs

    eng = _engine(cfg, params, "t_mimo_slots", prefill_chunk=32)
    assert eng._query_layout == (8, 8)
    for rows, cols, tiles in (([1, 1, 9, 32], 43, 1 + 1 + 9 + 32),
                              ([1, 1, 1, 1], 4, 4 + 4)):
        ph = _Ph()
        eng._note_query_cols(ph, np.asarray(rows, np.int32))
        assert ph.attrs == {"kv_query_cols": cols, "kv_query_slots": 64,
                            "kv_query_tile_cols": tiles}
    assert eng.pool.stats.snapshot()["kv_query_slots"] == 128
    assert 'pathway_kv_query_slots_total{pool="t_mimo_slots"} 128' \
        in serve_metrics.render_prometheus_lines()


def test_window_pairs_walks_the_kernels_pieces_and_tiles():
    """At the cell's geometry (64 query heads of 192 on 8 K/V heads, a chunk
    of 512 the rule lays out in pieces of 64 columns, tiles of 8 / 128
    folded columns, spans of 128 keys, a window of 128): a whole chunk from
    position 1,024 sees 128 keys a column and computes two spans a piece of
    64 columns; a decode row at context 3,000 sees 128 and computes one
    column's tile over two spans (no dead piece of its own: the rows'
    pieces are their live columns')."""
    import jax.numpy as jnp

    pa = _pa()
    bf = jnp.bfloat16
    assert pa.query_layout(528, 16, 512, 64, 192, 8 * 192, bf, Dv=8 * 128,
                           keys=8192) == (64, 24)
    args = (512, 64, 192, 8 * 192, bf, 128, 128)
    band, run = pa.window_pairs([1024], [512], *args, hd_v=128, cols=64)
    assert band == 512 * 128
    assert run == 8 * 64 * 2 * 128
    band, run = pa.window_pairs([2999], [1], *args, hd_v=128, cols=64)
    first = pa._col_tiles(2, 64 * 8, 8, bf)[0] // 8
    assert band == 128 and first == 1
    assert run == first * 2 * 128


# -- the cache with a window pool of its own geometry ------------------------------


def _cache(name, **over):
    from pathway_tpu.kvcache.backend import make_backend

    kw = dict(num_blocks=40, block_size=8, n_layers=2, n_heads=1,
              head_dim=24, v_head_dim=16, window=24, window_layers=3,
              window_heads=2, round_tokens=64, max_seqs=2, name=name)
    kw.update(over)
    return make_backend("windowed", **kw)


def test_window_blocks_are_freed_and_reused_inside_one_long_prompt():
    """A window narrower than ``round_tokens`` (24 under chunks of 64): the
    pool is sized by the round, a prompt of 300 positions claims blocks
    chunk by chunk, gives back those behind its window after every sync,
    and claims them again: more blocks pass through its table than the pool
    holds."""
    from pathway_tpu.kvcache.windowed import window_seq_blocks

    cache = _cache("t_mimo_cache")
    assert cache.k.shape == (2, 40, 8, 24) and cache.v.shape == (2, 40, 8, 16)
    assert cache.kw.shape == (3, 2 * window_seq_blocks(24, 64, 8) + 1, 8, 48)
    assert cache.vw.shape == cache.kw.shape[:3] + (32,)
    cache.allocate(1, 300)
    seen, peak = set(), 0
    for end in range(64, 301, 64):
        cache.reserve_chunk(1, end)
        table = cache.window_table(1)
        seen.update(b for b in table if b)
        peak = max(peak, cache.window_blocks_in_use)
        cache.check_invariants()
        cache.after_sync()
        cache.check_invariants()
        # what a query from ``end`` on can see is still held
        live = table[(end - 24 + 1) // 8:]
        assert all(live)
    st = cache.stats
    assert st.kv_window_blocks_allocated == -(-256 // 8)
    assert st.kv_window_blocks_allocated > cache.window_blocks - 1 >= peak
    assert len(seen) < st.kv_window_blocks_allocated  # blocks came back
    cache.free_sequence(1)
    assert cache.window_blocks_in_use == 0
    assert st.kv_window_blocks_freed == st.kv_window_blocks_allocated
    cache.check_invariants()


# -- the kernels: K and V lanes apart, and the sink --------------------------------


def _dense(q, k, v, ctx, window, sinks):
    """Dense softmax a row: q (C, H, hd), k (L, KV, hd), v (L, KV, hd_v),
    ctx (C,) keys each column sees."""
    C, H, hd = q.shape
    rep = H // k.shape[1]
    out = np.zeros((C, H, v.shape[2]), np.float64)
    for c in range(C):
        lo = 0 if window is None else max(ctx[c] - window, 0)
        for h in range(H):
            s = k[lo:ctx[c], h // rep].astype(np.float64) @ q[c, h] \
                / np.sqrt(hd)
            m = max(s.max(), sinks[h]) if sinks is not None else s.max()
            p = np.exp(s - m)
            den = p.sum() + (np.exp(sinks[h] - m) if sinks is not None else 0)
            out[c, h] = (p / den) @ v[lo:ctx[c], h // rep]
    return out


@pytest.mark.parametrize("sinks", [False, True], ids=["no_sink", "sinks"])
@pytest.mark.parametrize("hd,hd_v", [(128, 128), (192, 128)],
                         ids=["hd_v_eq", "k192_v128"])
@pytest.mark.parametrize("rep", [8, 16])
def test_paged_kernels_against_a_dense_softmax(rep, hd, hd_v, sinks):
    """Both paged kernels in interpret mode and the gather path against a
    dense softmax: V's heads of another width than K's, sinks, both, at 8
    and 16 query heads a K/V head; rows of a chunk (tiled), a short tail
    and one decode column, a window of 40 and none; the fused append writes
    both pools at their own widths."""
    import jax.numpy as jnp

    pa = _pa()
    rng = np.random.default_rng(rep + hd)
    KV, BS, NB, B = 2, 16, 6, 3
    H = KV * rep
    kp = rng.standard_normal((B * NB + 1, BS, KV * hd)).astype(np.float32)
    vp = rng.standard_normal((B * NB + 1, BS, KV * hd_v)).astype(np.float32)
    bt = 1 + np.arange(B * NB, dtype=np.int32).reshape(B, NB)
    sk = (rng.standard_normal(H) + 1).astype(np.float32) if sinks else None
    kw = {} if sk is None else {"sinks": jnp.asarray(sk)}

    def rows(b, n):
        blocks = bt[b]
        k = kp[blocks].reshape(NB * BS, KV, hd)[:n]
        v = vp[blocks].reshape(NB * BS, KV, hd_v)[:n]
        return k, v

    C, start, nvalid = 32, np.array([0, 37, 50]), np.array([32, 5, 17])
    q = rng.standard_normal((B, C, H, hd)).astype(np.float32)
    for window in (None, 40):
        got = {name: np.asarray(fn(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), start_pos=jnp.asarray(start, jnp.int32),
            n_valid=jnp.asarray(nvalid, jnp.int32), window=window, **kw,
            **extra)) for name, fn, extra in (
            ("gather", pa.paged_attention_reference, {}),
            ("ragged", pa.paged_attention,
             {"use_pallas": True, "interpret": True}))}
        for b in range(B):
            n = int(nvalid[b])
            k, v = rows(b, start[b] + n)
            want = _dense(q[b, :n], k, v, start[b] + 1 + np.arange(n),
                          window, sk)
            for name, out in got.items():
                assert out.shape == (B, C, H, hd_v)
                assert np.abs(out[b, :n] - want).max() < 2e-5, (name, window)
        # one decode column through the fused append: the new row lands in
        # both pools at its own width and is attended
        ctx = np.array([9, 33, 64])
        q1 = rng.standard_normal((B, 1, H, hd)).astype(np.float32)
        k1 = rng.standard_normal((B, KV, hd)).astype(np.float32)
        v1 = rng.standard_normal((B, KV, hd_v)).astype(np.float32)
        sb = bt[np.arange(B), (ctx - 1) // BS]
        so = (ctx - 1) % BS
        outs = []
        for fused in (False, True):
            a, k2, v2 = pa.paged_append_attend(
                jnp.asarray(q1), jnp.asarray(k1), jnp.asarray(v1),
                jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
                jnp.asarray(ctx, jnp.int32), jnp.asarray(sb),
                jnp.asarray(so, jnp.int32), use_pallas=fused,
                interpret=True, window=window, **kw)
            outs.append((np.asarray(a), np.asarray(k2), np.asarray(v2)))
        (a0, k20, v20), (a1, k21, v21) = outs
        assert np.array_equal(k20, k21) and np.array_equal(v20, v21)
        assert np.array_equal(v21[sb, so], v1.reshape(B, -1))
        for b in range(B):
            kk = k21[bt[b]].reshape(NB * BS, KV, hd)[:ctx[b]]
            vv = v21[bt[b]].reshape(NB * BS, KV, hd_v)[:ctx[b]]
            want = _dense(q1[b], kk, vv, ctx[b:b + 1], window, sk)
            for a in (a0, a1):
                assert np.abs(a[b] - want).max() < 2e-5


def test_the_writer_takes_two_row_widths():
    import jax.numpy as jnp

    pa = _pa()
    rng = np.random.default_rng(4)
    kp = jnp.zeros((2, 9, 16, 384), jnp.float32)
    vp = jnp.zeros((2, 9, 16, 256), jnp.float32)
    k = jnp.asarray(rng.standard_normal((20, 2, 192)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((20, 2, 128)), jnp.float32)
    sb = jnp.asarray([3] * 16 + [5] * 4, jnp.int32)
    so = jnp.asarray(list(range(16)) + list(range(4)), jnp.int32)
    want = pa.paged_write_rows(kp, vp, sb, so, k, v, layer=1,
                               use_pallas=False)
    got = pa.paged_write_rows(kp, vp, sb, so, k, v, layer=1, use_pallas=True,
                              interpret=True)
    for a, b in zip(want, got):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(got[1][1, 5, :4]).sum()) > 0
    assert float(jnp.abs(got[1][0]).sum()) == 0


def test_a_long_row_is_attended_in_pieces(monkeypatch):
    """Rows handed to ``paged_attention`` whole (B, C, H, hd): the rule keeps
    them while a row's scratch and blocks and the room over them fit the
    most a call asks of VMEM (every older cell's geometry at its chunk:
    sixteen heads of 256 at 1,024 ask 96.5 of the 100 MiB), and cuts them
    from a stream of B x C tokens past it (64 heads of 192 beside 128 at
    512); a row cut in pieces reads what the whole row reads."""
    import jax.numpy as jnp

    pa = _pa()
    bf = jnp.bfloat16
    for C, H, kv, hd in ((256, 20, 20, 64), (512, 32, 8, 64),
                         (256, 32, 4, 128), (512, 16, 2, 256),
                         (1024, 16, 2, 256)):
        assert pa.query_pieces(C, H, hd, kv * hd, bf) == 1
    assert pa._vmem_need(1, 128, 512, bf, 2, 8192, 256, 1, bf) \
        + pa._VMEM_ROOM == 101187584 <= pa._VMEM_CAP
    for kv in (4, 8):  # the full layers' K/V heads, the sliding layers'
        assert pa.query_pieces(256, 64, 192, kv * 192, bf, 128) == 1
        assert pa.query_pieces(512, 64, 192, kv * 192, bf, 128) > 1
    rng = np.random.default_rng(5)
    B, C, H, KV, hd, BS, NB = 2, 64, 4, 2, 128, 16, 8
    kp = jnp.asarray(rng.standard_normal((B * NB + 1, BS, KV * hd)),
                     jnp.float32)
    bt = jnp.asarray(1 + np.arange(B * NB).reshape(B, NB), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, C, H, hd)), jnp.float32)
    sp, nv = jnp.asarray([30, 0], jnp.int32), jnp.asarray([64, 20], jnp.int32)
    want = np.asarray(pa.paged_attention(
        q, kp, kp, bt, start_pos=sp, n_valid=nv, use_pallas=True,
        interpret=True, window=24))
    # a cap that holds a quarter of the row and no more
    monkeypatch.setattr(pa, "_VMEM_ROOM", 0)
    monkeypatch.setattr(pa, "_VMEM_CAP", pa._vmem_need(
        1, 128, KV * hd, jnp.float32, KV, C // 4 * (H // KV), hd, 1,
        jnp.float32))
    P, N = pa.query_layout(B * C, B, C, H, hd, KV * hd, jnp.float32,
                           keys=NB * BS)
    assert P <= C // 4 and N == B * C // P + B
    got = np.asarray(pa.paged_attention(
        q, kp, kp, bt, start_pos=sp, n_valid=nv, use_pallas=True,
        interpret=True, window=24))
    for b, n in enumerate((64, 20)):
        assert np.abs(got[b, :n] - want[b, :n]).max() < 1e-5
