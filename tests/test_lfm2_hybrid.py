"""The ``lfm2_moe`` block family on the paged engine, at toy widths on the
CPU: 64 wide, 4 query heads over 2 K/V heads, 8 experts top-2, the pattern
dense conv + (attention, conv, conv, conv) twice; seeded weights.

The reference is ``benchmark/reference/lfm2_moe_f32.py`` (plain f32, no
cache, no kernels, imports nothing of the program).  Tolerances:

- f32: 1e-4 of the logits' standard deviation - program and reference do
  the same f32 arithmetic and differ in reduction order only;
- bf16: over sixteen positions, every one within 0.5 of the logits'
  standard deviation and the median of those that are no router near-tie
  within 0.05 (readings over three draws of the weights: 0.01-0.04 where
  the program's router agrees with the reference's, median 0.02; 0.1-0.35
  at a position where it chose the other expert, decaying over the next
  few through K/V and conv state).  Nine layers of bf16 weights,
  activations and cache against f32, at a width of 64 where a logit's
  standard deviation is 0.16: where the reference's router has its
  ``top_k``-th and next selection scores within ``ROUTER_MARGIN`` the bf16
  program may choose the other expert, and the logits then differ by a
  share of an expert's contribution.  Such positions are counted and
  printed.  (The weights' scales keep a rounding error from growing with
  the depth: ``init_lfm2_params``.)
"""

import importlib
import types

import numpy as np
import pytest

A, C = "full_attention", "conv"
PATTERN = (C, A, C, C, C, A, C, C, C)
ROUTER_MARGIN = 0.002  # bf16 at toy width: a selection score is good to ~1e-3
VOCAB = 257


def _cfg(dtype, **over):
    import jax.numpy as jnp

    from pathway_tpu.models.lfm2 import Lfm2Config

    kw = dict(vocab_size=VOCAB, d_model=64, n_heads=4, n_kv_heads=2,
              d_ff=128, d_ff_expert=32, n_experts=8, top_k=2,
              n_dense_layers=1, layer_types=PATTERN, max_len=256,
              dtype=getattr(jnp, dtype))
    kw.update(over)
    return Lfm2Config(**kw)


def _shape(cfg):
    from benchmark.systems.serve_lfm2 import decoder_shape

    return decoder_shape(cfg, 0)


@pytest.fixture(scope="module")
def cfg():
    return _cfg("float32")


@pytest.fixture(scope="module")
def params(cfg):
    import jax

    from pathway_tpu.models.lfm2 import init_lfm2_params

    return init_lfm2_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, name, **kw):
    from pathway_tpu.kvcache.engine import PagedDecodeEngine

    geom = dict(num_blocks=96, block_size=8, max_batch_size=4,
                chain_steps=4, prefill_chunk=16)
    geom.update(kw)
    return PagedDecodeEngine(cfg, params, name=name, **geom)


def _prompts(lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(4, VOCAB, n).tolist() for n in lengths]


REQS = [(40, 9), (7, 12), (21, 5), (3, 6), (60, 8), (33, 7)]


def _requests(seed=0):
    return [(p, n) for p, (_l, n) in zip(
        _prompts([l for l, _n in REQS], seed), REQS)]


@pytest.fixture(scope="module")
def clean_tokens(cfg, params):
    """What an engine that is never disturbed emits (gather path)."""
    eng = _engine(cfg, params, "t_lfm2_clean", attn="reference")
    out = eng.generate_batch(_requests())
    eng.pool.check_invariants()
    assert eng.pool.sequences() == [] and eng.pool.slots_in_use == 0
    return out


# -- logits against the reference ---------------------------------------------


def _logits_through_engine(cfg, params, prompt, n_new, name, attn,
                           monkeypatch):
    """One request alone through the engine's own programs (chunked
    prefill over three mixed steps, chained decode, the single step at the
    tail), every program's logits caught where it turns them into ids.
    Row 0 is the request: the last mixed step's logits are the prompt's
    last position's, every decode step's the next position's."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import lfm2

    caught = []

    def spy(logits):
        jax.debug.callback(lambda x: caught.append(np.asarray(x[0])), logits,
                           ordered=True)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(lfm2, "greedy_ids", spy)
    eng = _engine(cfg, params, name, attn=attn)
    tokens = eng.generate(prompt, n_new)
    jax.effects_barrier()
    snap = eng.pool.stats.snapshot()
    assert snap["prefill_chunks"] >= 3 and snap["mixed_steps"] >= 3
    assert snap["chain_steps_sum"] > snap["chain_count"]  # really chained
    n_mixed = int(snap["mixed_steps"])
    rows = [caught[n_mixed - 1]] + caught[n_mixed:]
    assert len(rows) >= n_new
    return tokens, np.stack(rows[:n_new])


def _reference(params, cfg, prompt, tokens):
    ref = importlib.import_module("benchmark.reference.lfm2_moe_f32")
    seq = np.asarray([prompt + tokens], np.int32)
    cols = np.arange(len(prompt) - 1, len(prompt) + len(tokens) - 1)
    logits, margin = ref.logits_at(params, _shape(cfg), seq,
                                   np.zeros(len(cols), np.int32), cols)
    return np.asarray(logits), np.asarray(margin)


@pytest.mark.parametrize("attn", ["reference", "pallas"])
def test_f32_logits_match_the_reference(cfg, params, attn, monkeypatch):
    prompt = _prompts([40], seed=5)[0]
    tokens, got = _logits_through_engine(
        cfg, params, prompt, 11, f"t_lfm2_logits_{attn}", attn, monkeypatch)
    want, _margin = _reference(params, cfg, prompt, tokens)
    err = np.abs(got - want).max(axis=-1) / want.std(axis=-1)
    assert err.max() < 1e-4, err
    assert tokens == want.argmax(-1).tolist()


def test_bf16_logits_stay_near_the_reference(monkeypatch):
    import jax

    from pathway_tpu.models.lfm2 import init_lfm2_params

    cfg = _cfg("bfloat16")
    params = init_lfm2_params(cfg, jax.random.PRNGKey(0))
    assert all(l.dtype == jax.numpy.bfloat16
               for l in jax.tree_util.tree_leaves(params)
               if l.ndim >= 2)
    prompt = _prompts([40], seed=5)[0]
    tokens, got = _logits_through_engine(
        cfg, params, prompt, 16, "t_lfm2_logits_bf16", "reference",
        monkeypatch)
    want, margin = _reference(params, cfg, prompt, tokens)
    tie = margin < ROUTER_MARGIN
    err = np.abs(got - want).max(axis=-1) / want.std(axis=-1)
    print(f"router near-ties (margin < {ROUTER_MARGIN}): {int(tie.sum())} "
          f"of {len(tie)} positions, error there {err[tie].round(3)}; "
          f"elsewhere at most {err[~tie].max():.4f}")
    assert (~tie).sum() >= 8, "too few positions left to compare"
    assert err.max() < 0.5, err
    assert np.median(err[~tie]) < 0.05, err


# -- the conv state -----------------------------------------------------------


def _feed(cfg, params, prompt, chunk, conv0=None):
    """The last position's logits and the row's conv slot after the prompt
    went through the mixed step in runs of ``chunk`` tokens."""
    from .utils import lfm2_feed

    runs, state = lfm2_feed(cfg, params, prompt, chunk, conv0=conv0, slot=3)
    return runs[-1][1], state


def test_conv_state_is_the_same_however_the_prompt_is_chunked(cfg, params):
    import jax.numpy as jnp

    prompt = _prompts([40], seed=9)[0]
    whole_logits, whole_state = _feed(cfg, params, prompt, len(prompt))
    assert np.abs(whole_state).max() > 0
    for chunk in (1, 3, 32):
        logits, state = _feed(cfg, params, prompt, chunk)
        np.testing.assert_allclose(state, whole_state, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(logits, whole_logits, atol=1e-4)
    # a slot another sequence left full reads as zero for a new one
    dirty = jnp.full((len(cfg.conv_layers), 5, 2, cfg.d_model), 1e3)
    logits, state = _feed(cfg, params, prompt, 3, conv0=dirty)
    np.testing.assert_allclose(state, whole_state, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(logits, whole_logits, atol=1e-4)


# -- router and grouped matmul ------------------------------------------------


def test_router_chooses_by_score_plus_bias_and_weighs_by_score():
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    h = jax.random.normal(ks[0], (12, 64))
    wg = jax.random.normal(ks[1], (64, 8)) / 8.0
    e0, w0, s = moe.route(h, wg, None, top_k=3)
    assert np.allclose(np.asarray(w0).sum(-1), 1.0, atol=1e-4)
    want = np.argsort(-np.asarray(s), axis=-1)[:, :3]
    assert (np.sort(np.asarray(e0)) == np.sort(want)).all()
    # a bias that lifts expert 5 into every choice: chosen by s + b ...
    bias = jnp.zeros(8).at[5].set(10.0)
    e1, w1, _ = moe.route(h, wg, bias, top_k=3, norm_topk=False)
    assert (np.asarray(e1)[:, 0] == 5).all()
    # ... weighed by s alone, and the experts still chosen keep the weight
    # they had (unnormalised: the raw score, bias or none)
    _, w_raw, _ = moe.route(h, wg, None, top_k=3, norm_topk=False)
    s_np, e0_np, e1_np = np.asarray(s), np.asarray(e0), np.asarray(e1)
    np.testing.assert_allclose(np.asarray(w1)[:, 0], s_np[:, 5], rtol=1e-6)
    for t in range(12):
        for j, e in enumerate(e1_np[t]):
            if e in e0_np[t]:
                k = list(e0_np[t]).index(e)
                assert np.asarray(w1)[t, j] == np.asarray(w_raw)[t, k]


def _expert_layer(T, E, bias, D=128, F=256, seed=3):
    """(h (T, D), an expert layer of E experts with the given bias)."""
    import jax

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    h = jax.random.normal(ks[0], (T, D))
    layer = {"wg": jax.random.normal(ks[1], (D, E)) / np.sqrt(D),
             "expert_bias": bias,
             "w1": jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D),
             "w3": jax.random.normal(ks[3], (E, D, F)) / np.sqrt(D),
             "w2": jax.random.normal(ks[4], (E, F, D)) / np.sqrt(F)}
    return h, layer


def _dense_sum(h, layer, experts, weights):
    """Every token through its own experts, one at a time."""
    import jax
    import jax.numpy as jnp

    dense = jnp.zeros_like(h)
    for j in range(experts.shape[1]):
        e = experts[:, j]
        a = jnp.einsum("td,tdf->tf", h, layer["w1"][e])
        b = jnp.einsum("td,tdf->tf", h, layer["w3"][e])
        dense += weights[:, j:j + 1] * jnp.einsum(
            "tf,tfd->td", jax.nn.silu(a) * b, layer["w2"][e])
    return np.asarray(dense)


def _kernel_against_reference(h, layer, g, tm):
    """The interpreted kernels against the gather reference over the live
    rows of the layout ``g`` (tiles of ``tm`` rows)."""
    from pathway_tpu.ops import moe

    args = (h[g["row_token"]], layer["w1"], layer["w3"], layer["w2"],
            g["tile_expert"], g["n_live"])
    want = np.asarray(moe.moe_gmm_reference(*args, tm=tm))
    got = np.asarray(moe._moe_gmm(*args, tm=tm, interpret=True))
    live = int(g["n_live"][0]) * tm
    assert got.shape[0] == g["tile_expert"].shape[0] * tm >= live
    np.testing.assert_allclose(got[:live], want[:live], atol=2e-5)


@pytest.mark.parametrize("tm", [16, 32, 64, 128])
@pytest.mark.parametrize("case", ["one_idle_expert", "one_takes_all"])
def test_grouped_matmul_kernel_matches_its_reference(case, tm):
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    T, E, k = 24, 8, 2
    bias = jnp.zeros(E).at[3].set(-10.0) if case == "one_idle_expert" \
        else jnp.zeros(E).at[5].set(10.0)
    h, layer = _expert_layer(T, E, bias)
    valid = jnp.arange(T) < 20
    experts, weights, _s = moe.route(h, layer["wg"], bias, top_k=k)
    g = moe.group_rows(experts, valid, E, tm)
    counts = np.asarray(g["counts"])
    assert counts.sum() == 20 * k
    if case == "one_idle_expert":
        assert counts[3] == 0 and 3 not in np.asarray(g["tile_expert"])
    else:
        assert counts[5] == 20
    n_live = int(g["n_live"][0])
    assert n_live == sum(-(-int(c) // tm) for c in counts)
    _kernel_against_reference(h, layer, g, tm)
    # and the layer as a whole against every token through its own experts
    out, n_tok = moe.expert_ffn(h, layer, valid, top_k=k, use_pallas=True,
                                tm=tm)
    dense = _dense_sum(h, layer, experts, weights)
    np.testing.assert_allclose(np.asarray(out)[:20], dense[:20], atol=2e-5)
    assert (np.asarray(out)[20:] == 0).all()
    # tokens per expert, then ops.moe.COUNTER_TAIL: the live rows in units
    # of 16 whatever the tile, the kernel's own tiles last
    assert (np.asarray(n_tok)[:len(counts)] == counts).all()
    assert np.asarray(n_tok)[len(counts):].tolist() == [
        0, n_live * (tm // 16), int((counts > 0).sum()), 1, n_live]


@pytest.mark.parametrize("tm", [32, 64])
def test_an_experts_rows_span_two_tall_tiles_beside_an_idle_expert(tm):
    """Every valid token chooses expert 5 (2 x tm + 8 rows: three tiles, the
    last one nearly empty), none chooses expert 3; the second choices
    spread over the other six."""
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    E, k, n = 8, 2, 2 * tm + 8
    T = n + 6
    bias = jnp.zeros(E).at[5].set(10.0).at[3].set(-10.0)
    h, layer = _expert_layer(T, E, bias, seed=5)
    valid = jnp.arange(T) < n
    experts, weights, _s = moe.route(h, layer["wg"], bias, top_k=k)
    g = moe.group_rows(experts, valid, E, tm)
    counts, tiles = np.asarray(g["counts"]), np.asarray(g["tile_expert"])
    n_live = int(g["n_live"][0])
    assert counts[5] == n and counts[3] == 0 and counts.sum() == n * k
    assert (tiles[:n_live] == 5).sum() == 3 and 3 not in tiles
    # the group's rows are consecutive over its three tiles
    rows5 = np.sort(np.asarray(g["pair_row"])[np.asarray(
        (experts == 5) & valid[:, None])])
    assert (np.diff(rows5) == 1).all() and rows5[0] % tm == 0
    _kernel_against_reference(h, layer, g, tm)
    out, n_tok = moe.expert_ffn(h, layer, valid, top_k=k, use_pallas=True,
                                tm=tm)
    dense = _dense_sum(h, layer, experts, weights)
    np.testing.assert_allclose(np.asarray(out)[:n], dense[:n], atol=2e-5)
    assert (np.asarray(out)[n:] == 0).all()
    assert np.asarray(n_tok)[E:].tolist() == [
        0, n_live * (tm // 16), 7, 1, n_live]


def test_expert_ffn_at_the_rules_tall_tile_equals_the_dense_sum_and_the_tile_of_16():
    """128 tokens x 2 on 8 experts: 32 rows an expert, the rule's tile is
    64.  A pair's row is the same dot products under any tile height: the
    layer's output is its output with the tile forced to 16, bit for bit."""
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    T, E, k = 128, 8, 2
    assert moe.row_tile(T, k, E) == 64
    h, layer = _expert_layer(T, E, jnp.zeros(E), seed=7)
    valid = jnp.arange(T) < 120
    experts, weights, _s = moe.route(h, layer["wg"], None, top_k=k)
    out, n_tok = moe.expert_ffn(h, layer, valid, top_k=k, use_pallas=True)
    dense = _dense_sum(h, layer, experts, weights)
    np.testing.assert_allclose(np.asarray(out)[:120], dense[:120], atol=2e-5)
    assert (np.asarray(out)[120:] == 0).all()
    out16, n_tok16 = moe.expert_ffn(h, layer, valid, top_k=k,
                                    use_pallas=True, tm=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out16))
    # the same pairs in fewer, taller tiles: 16 x live_tiles / row_tiles is
    # the height that ran
    tall, short = np.asarray(n_tok), np.asarray(n_tok16)
    assert (tall[:E] == short[:E]).all() and tall[:E].sum() == 240
    assert tall[E + 1] == 4 * tall[E + 4] and short[E + 1] == short[E + 4]
    assert tall[E + 4] < short[E + 4] <= tall[E + 1]


# the benchmark's configurations: router experts, top_k and the tokens of
# their mixed step (sixteen rows and the engine's prefill chunk)
_CELL_STEPS = {"lfm2": (32, 4, 528), "trinity": (128, 8, 272),
               "kimi_linear": (256, 8, 528), "qwen3_next": (512, 10, 1040)}


@pytest.mark.parametrize("step", ["mixed", "decode"])
@pytest.mark.parametrize("name", sorted(_CELL_STEPS))
def test_row_tile_at_the_benchmarks_shapes(name, step):
    """The rule rounds up from half a rung (PERF.md section 6, PR 39: all
    five rows of the sweep's table decided it): 128 where a mixed step
    brings an expert 66 rows, 32 where it brings 16.5 to 20.3, 16 in every
    decode and chain step (0.3 to 2 rows)."""
    from pathway_tpu.ops import moe

    E, k, T = _CELL_STEPS[name]
    got = moe.row_tile(T if step == "mixed" else 16, k, E)
    assert got == (16 if step == "decode" else 128 if name == "lfm2" else 32)
    assert got in moe.ROW_TILES and got % moe.TM == 0


def test_row_tile_is_the_tallest_rung_the_mean_rows_half_fill():
    from pathway_tpu.ops import moe

    for rung in (32, 64, 128):  # 8 experts: rung / 2 rows each, one fewer
        assert moe.row_tile(rung * 4, 1, 8) == rung
        assert moe.row_tile(rung * 4 - 1, 1, 8) == rung // 2
    assert moe.row_tile(1, 1, 8) == moe.row_tile(127, 1, 8) == 16
    assert moe.row_tile(10 ** 6, 8, 8) == 128


def test_a_tile_of_16_traces_to_the_same_jaxpr_given_or_not():
    """A shape whose tile the rule leaves at 16 is the program it was:
    passing ``tm=16`` changes nothing in the trace."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    T, E, k = 24, 8, 2
    assert moe.row_tile(T, k, E) == 16
    h, layer = _expert_layer(T, E, jnp.zeros(E))
    valid = jnp.arange(T) < 20
    texts = [str(jax.make_jaxpr(lambda h, layer, valid: moe.expert_ffn(
        h, layer, valid, top_k=k, use_pallas=True, **kw))(h, layer, valid))
        for kw in ({}, {"tm": 16})]
    assert texts[0] == texts[1] and "pallas_call" in texts[0]


def test_hbm_plan_bills_the_rows_of_the_row_tile(monkeypatch):
    """The ledger's temporaries for the published lfm2 widths at a chunk of
    512 bill the layout the kernel runs: 528 x 4 pairs and a tile of 128
    rows an expert, 112 rows an expert more than tiles of 16 would."""
    import jax.numpy as jnp

    from pathway_tpu.obs import memory
    from pathway_tpu.ops import moe

    from .utils import described_decode_plan

    cfg, plan, dtype, kw = described_decode_plan("lfm2")
    args = dict(num_blocks=2049, block_size=16, chain_steps=16,
                prefill_chunk=512, dtype=dtype, params=plan,
                budget_bytes=int(15.02 * 2 ** 30), reference_attn=False, **kw)
    assert moe.row_tile(16 + 512, cfg.top_k, cfg.n_experts) == 128
    tall = memory.hbm_plan(cfg, **args)
    monkeypatch.setattr(moe, "row_tile", lambda *a: 16)
    short = memory.hbm_plan(cfg, **args)
    assert tall.temp_source == short.temp_source == "analytic"
    wide = 2 * cfg.d_model + cfg.d_ff_expert
    assert tall.temp_bytes - short.temp_bytes == \
        (128 - 16) * cfg.n_experts * wide * jnp.dtype(dtype).itemsize \
        == 42_205_184
    assert tall.fits


# "spans": K/V heads whose lanes are a whole tile, so a grid step of the
# kernels attends 128 keys (eight blocks of 16) of a table of 20 blocks (2.5
# spans): contexts of one block, ending inside a span, at a span's edge (the
# chunk's last column / the appended token is the span's last key) and at
# the table's end.
@pytest.mark.parametrize("KV,HD,BS,NB,last", [
    (2, 16, 8, 4, [18, 10, 26]),
    (2, 64, 16, 20, [16, 200, 128, 320, 257]),
], ids=["toy", "spans"])
@pytest.mark.parametrize("C", [1, 4], ids=["decode", "chunk"])
def test_paged_kernels_group_query_heads(C, KV, HD, BS, NB, last):
    """Four query heads over two K/V heads in both paged kernels (the
    interpreted kernels against the gather reference); the fused append
    changes exactly one block a row, the tail."""
    import jax
    import jax.numpy as jnp

    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    L, REP, B = 2, 2, len(last)
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    kp = jax.random.normal(ks[0], (L, 1 + B * NB, BS, KV * HD))
    vp = jax.random.normal(ks[1], (L, 1 + B * NB, BS, KV * HD))
    bt = np.zeros((B, NB), np.int32)
    for b, n in enumerate(last):  # the row's own blocks, the null behind
        bt[b, :-(-n // BS)] = 1 + b * NB + np.arange(-(-n // BS))
    bt = jnp.asarray(bt)
    q = jax.random.normal(ks[2], (B, C, KV * REP, HD))
    last = jnp.asarray(last, jnp.int32)  # the last column's context
    if C > 1:
        start = last - C
        nv = jnp.full((B,), C, jnp.int32).at[1].set(C - 1)
        want = pa.paged_attention_reference(q, kp[1], vp[1], bt,
                                            start_pos=start, n_valid=nv)
        got = pa.paged_attention(q, kp, vp, bt, start_pos=start, n_valid=nv,
                                 layer=1, use_pallas=True, interpret=True)
        real = (jnp.arange(C)[None, :] < nv[:, None])[:, :, None, None]
        assert float(jnp.abs(jnp.where(real, got - want, 0)).max()) < 1e-5
        return
    k1 = jax.random.normal(ks[3], (B, KV, HD))
    v1 = jax.random.normal(ks[4], (B, KV, HD))
    sb, so = bt[jnp.arange(B), (last - 1) // BS], (last - 1) % BS
    a0, k0, v0 = pa.paged_append_attend(q, k1, v1, kp, vp, bt, last, sb, so,
                                        layer=0, use_pallas=False)
    before = np.asarray(kp), np.asarray(vp)  # the kernel's call donates them
    a1, k_, v_ = pa.paged_append_attend(q, k1, v1, kp, vp, bt, last, sb, so,
                                        layer=0, use_pallas=True,
                                        interpret=True)
    assert float(jnp.abs(a0 - a1).max()) < 1e-5
    assert bool((k0 == k_).all()) and bool((v0 == v_).all())
    for new, old in zip((k_, v_), before):
        changed = np.argwhere((np.asarray(new) != old).any(axis=(2, 3)))
        assert changed.tolist() == [[0, int(b)] for b in sorted(sb)]


# -- the engine: batching, preemption, restart, exhaustion --------------------


def test_kernels_and_gather_path_emit_the_same_tokens(cfg, params,
                                                      clean_tokens):
    eng = _engine(cfg, params, "t_lfm2_pallas", attn="pallas")
    assert eng.generate_batch(_requests()) == clean_tokens
    snap = eng.pool.stats.snapshot()
    assert snap["moe_routed_pairs"] > 0
    assert sum(snap["moe_tokens_per_expert"]) == snap["moe_routed_pairs"]
    assert snap["conv_slots_total"] == 4 and snap["conv_slots_in_use"] == 0
    from pathway_tpu.serve.metrics import render_prometheus_lines

    lines = "\n".join(render_prometheus_lines())
    assert 'pathway_kv_moe_routed_pairs_total{pool="t_lfm2_pallas"}' in lines
    assert 'pathway_kv_conv_slots_total{pool="t_lfm2_pallas"} 4' in lines


def test_preemption_recomputes_blocks_and_conv_state(cfg, params,
                                                     clean_tokens):
    """A pool too small for the batch: sequences are preempted, lose
    blocks and slot together, and are rebuilt by recompute over prompt +
    emitted - the same tokens as never having been preempted."""
    eng = _engine(cfg, params, "t_lfm2_preempt", attn="reference",
                  num_blocks=14)
    assert eng.generate_batch(_requests()) == clean_tokens
    assert eng.pool.stats.preemptions > 0
    eng.pool.check_invariants()
    assert eng.pool.sequences() == [] and eng.pool.slots_in_use == 0


def test_restart_readmits_through_the_hybrid_cache(cfg, params,
                                                   clean_tokens):
    from pathway_tpu import faults

    eng = _engine(cfg, params, "t_lfm2_restart", attn="reference",
                  max_restarts=1)
    faults.install("engine.dispatch.chain", "raise", nth=2)
    try:
        assert eng.generate_batch(_requests()) == clean_tokens
    finally:
        faults.clear()
    assert eng.pool.stats.engine_restarts >= 1
    assert eng.pool.cache_kind == "hybrid"
    eng.pool.check_invariants()
    assert eng.pool.sequences() == [] and eng.pool.slots_in_use == 0


def test_a_common_prefix_shares_no_block(cfg, params):
    eng = _engine(cfg, params, "t_lfm2_prefix", attn="reference")
    assert eng.prefix is None and not eng.pool.supports_prefix
    common = _prompts([32], seed=4)[0]
    reqs = [(common + [5, 6, 7], 4), (common + [9, 8], 4)]
    seen: list = []
    build = eng._build_mixed

    def spy(reserved, chunks, ph):
        seen.append({a.seq_id: tuple(eng.pool.sequence(a.seq_id).block_ids)
                     for a in chunks})
        return build(reserved, chunks, ph)

    eng._build_mixed = spy
    both = eng.generate_batch(reqs)
    tables = {}
    for round_ in seen:
        tables.update(round_)
    assert len(tables) == 2
    a, b = tables.values()
    assert not set(a) & set(b)
    assert eng.pool.stats.prefix_hits == 0
    # and sharing changes nothing: each alone emits the same
    assert both == [eng.generate(p, n) for p, n in reqs]


def test_exhaustion_leaves_neither_slot_nor_block_behind():
    import jax.numpy as jnp

    from pathway_tpu.kvcache import PoolExhausted, UnsupportedCacheOp
    from pathway_tpu.kvcache.backend import make_backend

    pool = make_backend(
        "hybrid", num_blocks=6, block_size=8, n_layers=2, n_heads=2,
        head_dim=16, dtype=jnp.float32, name="t_lfm2_pool", conv_layers=7,
        conv_width=64, conv_slots=2)
    assert pool.k.shape == (2, 6, 8, 32) and pool.conv.shape == (7, 3, 2, 64)
    pool.allocate(1, 20)                      # three blocks, one slot
    with pytest.raises(PoolExhausted):
        pool.allocate(2, 40)                  # five blocks: two are free
    assert pool.slots_in_use == 1 and pool.num_free == 2
    pool.allocate(2, 8)
    with pytest.raises(PoolExhausted):
        pool.allocate(3, 8)                   # a block is free, no slot is
    assert pool.num_free == 1 and sorted(pool._slot_of) == [1, 2]
    pool.check_invariants()
    with pytest.raises(UnsupportedCacheOp):
        pool.allocate(3, 16, shared_blocks=[1])
    with pytest.raises(UnsupportedCacheOp):
        pool.fork(1, 4)
    victim = pool.preempt()
    assert victim.seq_id == 2 and pool.slots_in_use == 1
    pool.free_sequence(1)
    pool.check_invariants()
    assert pool.slots_in_use == 0 and pool.num_free == 5
    slots = pool.row_extras([], 4)[0]
    assert slots.tolist() == [0, 0, 0, 0]


def test_second_pass_compiles_nothing(cfg, params):
    from .utils import CompileWatch

    eng = _engine(cfg, params, "t_lfm2_compile", attn="reference")
    watch = CompileWatch()
    eng.generate_batch(_requests())
    first = {e.program for e in watch.events()}
    assert {"pw.mixed_step", "pw.decode_step", "pw.chained_decode"} <= first
    eng.generate_batch(_requests(seed=1))
    watch.assert_no_compiles("second pass")


def test_hbm_plan_equals_the_live_bytes(cfg, params):
    import jax

    eng = _engine(cfg, params, "t_lfm2_hbm", attn="reference")
    plan = eng.hbm_plan
    live = sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(eng.params))
    assert plan.params_bytes == live
    assert plan.kv_bytes == (eng.pool.k.size + eng.pool.v.size) * 4
    assert plan.conv_bytes == eng.pool.conv_bytes > 0
    assert plan.kv_bytes + plan.conv_bytes == eng.pool.per_shard_bytes
    # a K/V block spans the attention layers and the K/V heads only
    assert plan.per_block_bytes == 2 * 2 * 8 * (2 * 16) * 4
    assert plan.total_bytes == plan.params_bytes + plan.kv_bytes \
        + plan.conv_bytes + plan.temp_bytes


# -- what this family cannot do yet fails typed, at once ----------------------


@pytest.mark.parametrize("kwargs,names", [
    ({"tp": 2}, "tensor parallelism"),
    ({"quantize": "int8"}, "quantize='int8'"),
    ({"speculative": "ngram"}, "speculative drafting"),
    ({"session_store": object()}, "host tiering"),
])
def test_unsupported_engine_options_are_refused_by_name(cfg, params, kwargs,
                                                        names):
    with pytest.raises(ValueError, match="lfm2 block family") as err:
        _engine(cfg, params, "t_lfm2_refused", **kwargs)
    assert names in str(err.value)


def test_a_sampled_request_fails_alone(cfg, params, clean_tokens):
    eng = _engine(cfg, params, "t_lfm2_sampled", attn="reference")
    reqs = _requests()
    out = eng.generate_batch(
        reqs[:2] + [reqs[2] + ({"sampling": (0.8, 0, 1.0, 7)},)],
        return_exceptions=True)
    assert out[:2] == clean_tokens[:2]
    assert isinstance(out[2], ValueError) and "greedily" in str(out[2])


def test_hf_import_reads_the_published_config():
    """The catalog's copy of LiquidAI/LFM2-8B-A1B's config.json."""
    from pathway_tpu.models import hf_import
    from pathway_tpu.models.families import step_family

    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "conv", "full_attention", "conv", "conv", "conv",
                        "full_attention", "conv", "conv", "conv",
                        "full_attention", "conv", "conv", "conv",
                        "full_attention", "conv", "conv", "full_attention",
                        "conv", "conv"],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    cfg = hf_import.config_from_lfm2_moe(types.SimpleNamespace(**published),
                                         max_len=2048)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) \
        == (2048, 32, 8, 64)
    assert (cfg.d_ff, cfg.d_ff_expert, cfg.n_experts, cfg.top_k) \
        == (7168, 1792, 32, 4)
    assert cfg.n_layers == 24 and len(cfg.attn_layers) == 6
    assert cfg.attn_layers == (2, 6, 10, 14, 18, 21)
    assert cfg.max_len == 2048 and cfg.tie_embedding
    assert step_family(cfg).name == "lfm2"
    # 8.3B parameters as published (tied head)
    assert 8.2e9 < cfg.param_count() < 8.5e9
