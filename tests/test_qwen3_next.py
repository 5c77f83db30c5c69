"""The ``qwen3_next`` block family (Qwen3-Next) on the paged engine, at toy
widths on the CPU: 64 wide, eight layers ``L L L F`` x 2 (gated DeltaNet: 2
key heads feeding 4 value heads of 16; full attention: 4 query heads over 2
K/V heads of 16, rotary on 4 of the 16), 16 experts top-3 of which 4 are
held, beside a sigmoid-gated shared one; seeded weights, f32.

The reference is ``benchmark/reference/qwen3_next_f32.py`` (plain f32, no
cache, no kernels, no batching, imports nothing of the program: the delta
rule token by token, attention over the whole sequence, every held expert
applied to every token).  Tolerance: 1e-4 of the logits' standard deviation
- program and reference do the same f32 arithmetic and differ in reduction
order only (readings: 2e-6 to 6e-6).
"""

import importlib

import numpy as np
import pytest

L, F = "linear_attention", "full_attention"
PATTERN = (L, L, L, F) * 2
VOCAB = 257


def _cfg(dtype="float32", **over):
    import jax.numpy as jnp

    from pathway_tpu.models.qwen3_next import Qwen3NextConfig

    kw = dict(vocab_size=VOCAB, d_model=64, n_heads=4, n_kv_heads=2,
              head_dim=16, rotary_dim=4, gdn_key_heads=2, gdn_value_heads=4,
              gdn_key_dim=16, gdn_value_dim=16, d_ff_expert=32,
              d_ff_shared=32, n_experts=16, n_held_experts=4, first_expert=8,
              top_k=3, layer_types=PATTERN, max_len=256,
              dtype=getattr(jnp, dtype), gdn_chunk=16)
    kw.update(over)
    return Qwen3NextConfig(**kw)


def _shape(cfg):
    from benchmark.systems.serve_lfm2 import decoder_shape

    return decoder_shape(cfg, 0)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    import jax

    from pathway_tpu.models.qwen3_next import init_qwen3_next_params

    return init_qwen3_next_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, name, **kw):
    from pathway_tpu.kvcache.engine import PagedDecodeEngine

    geom = dict(num_blocks=64, block_size=8, max_batch_size=4,
                chain_steps=4, prefill_chunk=32, seq_buckets=(64, 256),
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
    eng = _engine(cfg, params, "t_q3n_clean")
    out = eng.generate_batch(_requests())
    eng.pool.check_invariants()
    assert eng.pool.sequences() == [] and eng.pool.slots_in_use == 0
    return out


# -- logits against the reference ---------------------------------------------


def _spy(monkeypatch, rows=slice(0, 1)):
    """Catch every program's logits where it turns them into ids."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import blocks

    caught = []

    def spy(logits):
        jax.debug.callback(lambda x: caught.append(np.asarray(x[rows])),
                           logits, ordered=True)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(blocks, "greedy_ids", spy)
    return caught


def _logits_through_engine(cfg, params, prompt, n_new, name, monkeypatch,
                           **kw):
    """One request alone through the engine's own programs (chunked prefill
    over the mixed step, chained decode, the single step at the tail)."""
    import jax

    caught = _spy(monkeypatch)
    eng = _engine(cfg, params, name, **kw)
    tokens = eng.generate(prompt, n_new)
    jax.effects_barrier()
    snap = eng.pool.stats.snapshot()
    assert snap["prefill_chunks"] >= 3 and snap["mixed_steps"] >= 3
    assert snap["chain_steps_sum"] > snap["chain_count"]  # really chained
    assert snap["kda_state_resets"] == 1
    n_mixed = int(snap["mixed_steps"])
    rows = [caught[n_mixed - 1][0]] + [c[0] for c in caught[n_mixed:]]
    assert len(rows) >= n_new
    return tokens, np.stack(rows[:n_new]), eng


def _reference(params, cfg, prompt, tokens):
    ref = importlib.import_module("benchmark.reference.qwen3_next_f32")
    cols = np.arange(len(prompt) - 1, len(prompt) + len(tokens) - 1)
    logits, margin = ref.logits_at(params, _shape(cfg), prompt + tokens, cols)
    return np.asarray(logits), np.asarray(margin)


@pytest.mark.parametrize("attn,chunk", [
    ("reference", 32), ("pallas", 32), ("reference", 16), ("pallas", 24)])
def test_engine_logits_equal_the_reference(cfg, params, monkeypatch, attn,
                                           chunk):
    """Chunked prefill (70 tokens in chunks of 16, 24 or 32: the state, the
    conv inputs and the K/V blocks cross the chunk boundaries, the last
    chunk is a few tokens), then chains and single steps: every emitted
    position's logits, with 4 of the 16 experts held."""
    prompt = _prompts([70], seed=5)[0]
    tokens, got, eng = _logits_through_engine(
        cfg, params, prompt, 22, f"t_q3n_logits_{attn}_{chunk}", monkeypatch,
        attn=attn, prefill_chunk=chunk)
    want, _margin = _reference(params, cfg, prompt, tokens)
    assert np.abs(got - want).max() / want.std() < 1e-4
    assert tokens == np.argmax(want, -1).tolist()
    pool = eng.pool
    assert pool.cache_kind == "kv_state" and eng.prefix is None
    assert pool.k.shape == pool.v.shape == (2, 64, 8, 2 * 16)
    assert pool.conv.shape == (6, 5, 3, 2 * 32 + 64)
    assert pool.state.shape == (6, 5, 4, 16, 16)
    assert len(pool.device_state()) == 4
    pool.check_invariants()
    assert pool.slots_in_use == 0
    snap = pool.stats.snapshot()
    assert len(snap["moe_tokens_per_expert"]) == 4
    # every routed pair is counted once, here or elsewhere, in 8 layers
    n_tokens = len(prompt) + len(tokens) - 1
    assert snap["moe_pairs_elsewhere"] + snap["moe_routed_pairs"] \
        == n_tokens * cfg.top_k * 8
    # a live tile holds 1 to 16 pairs; a touched expert has a tile or more
    assert snap["moe_routed_pairs"] <= 16 * snap["moe_live_tiles"]
    assert 0 < snap["moe_experts_touched"] <= snap["moe_live_tiles"] \
        <= snap["moe_routed_pairs"]
    # the passes are counted where they happen, one an expert layer and
    # forward pass: every chunk of the prompt and every fed-back token is
    # one forward pass or more (a chain may run a step past a row's last)
    forwards, rest = divmod(snap["moe_expert_passes"], 8)
    assert rest == 0 and forwards >= -(-len(prompt) // chunk) + 21
    assert snap["moe_experts_touched"] <= 4 * snap["moe_expert_passes"]


def test_every_expert_held_equals_the_reference(monkeypatch):
    """No share: all 16 experts here, nothing routed elsewhere."""
    import jax

    from pathway_tpu.models.qwen3_next import init_qwen3_next_params

    cfg = _cfg(n_held_experts=None, first_expert=0, layer_types=(L, F, L))
    params = init_qwen3_next_params(cfg, jax.random.PRNGKey(1))
    assert params["layers"][0]["w1"].shape[0] == 16
    prompt = _prompts([70], seed=6)[0]
    tokens, got, eng = _logits_through_engine(
        cfg, params, prompt, 10, "t_q3n_whole", monkeypatch)
    want, _margin = _reference(params, cfg, prompt, tokens)
    assert np.abs(got - want).max() / want.std() < 1e-4
    snap = eng.pool.stats.snapshot()
    assert snap["moe_pairs_elsewhere"] == 0
    assert snap["moe_routed_pairs"] \
        == (len(prompt) + len(tokens) - 1) * cfg.top_k * 3


def test_a_mixed_step_of_a_chunk_row_a_decode_row_and_an_idle_row(
        cfg, params, monkeypatch):
    """Two requests on four rows: while the long one is still in its
    chunks the short one decodes beside it and two rows are idle; both
    requests' logits at every emitted position are the reference's."""
    import jax

    from pathway_tpu import obs

    caught = _spy(monkeypatch, rows=slice(0, 4))
    short, long_ = _prompts([5, 90], seed=9)
    eng = _engine(cfg, params, "t_q3n_mixed_rows")
    out = eng.generate_batch([(short, 12), (long_, 6)])
    jax.effects_barrier()
    builds = [s.attrs for s in obs.recorder().snapshot()
              if s.name == "pw.round.build"
              and (s.attrs or {}).get("kind") == "mixed"]
    # a mixed step with a chunk row (two tokens or more) AND a decode row
    assert any(a["kda_chunk_tokens"] >= 2 and a["kda_step_rows"] >= 1
               and a["rows"] == 2 for a in builds[-8:])
    for prompt, tokens in zip((short, long_), out):
        want, _m = _reference(params, cfg, prompt, tokens)
        assert tokens == np.argmax(want, -1).tolist()
        # the served token's logit row was caught somewhere: the nearest
        # caught row to each reference row is within the tolerance
        rows = np.concatenate(caught)
        for w in want:
            err = np.abs(rows - w[None]).max(-1).min()
            assert err / want.std() < 1e-4


# -- the four shares add up ---------------------------------------------------


def test_four_shares_and_the_gated_shared_expert_once_add_up_to_the_layer():
    """At toy widths: what the four shares of 4 experts give for one expert
    layer, with the gated shared expert (which every chip computes alike)
    counted once, is what the uncut reference gives for the whole layer."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops.moe import COUNTER_TAIL, expert_ffn

    ref = importlib.import_module("benchmark.reference.qwen3_next_f32")
    rng = jax.random.split(jax.random.PRNGKey(3), 9)
    D, Fe, E, k, T = 64, 32, 16, 3, 50

    def mat(key, *dims):
        return jax.random.normal(key, dims, jnp.float32) / np.sqrt(dims[-2])

    lay = {"wg": mat(rng[0], D, E), "w1": mat(rng[1], E, D, Fe),
           "w3": mat(rng[2], E, D, Fe), "w2": mat(rng[3], E, Fe, D),
           "w_sg": mat(rng[4], D, 1),
           "shared": {"w1": mat(rng[5], D, Fe), "w3": mat(rng[6], D, Fe),
                      "w2": mat(rng[7], Fe, D)}}
    h = jax.random.normal(rng[8], (T, D), jnp.float32)
    valid = jnp.ones((T,), bool)
    shape = {"top_k": k, "n_held_experts": None, "first_expert": 0}
    with jax.default_matmul_precision("highest"):
        whole, _margin = ref._experts(h, lay, shape)
        shared = jax.nn.sigmoid(h @ lay["w_sg"]) * ref._swiglu(
            h, *(lay["shared"][n] for n in ("w1", "w3", "w2")))
        total = shared
        pairs = elsewhere = 0
        for first in range(0, E, 4):
            part = {**lay, **{n: lay[n][first:first + 4]
                              for n in ("w1", "w3", "w2")}}
            y, vec = expert_ffn(
                h, part, valid, top_k=k, norm_topk=True, renorm_eps=0.0,
                use_pallas=False, first_expert=first, score="softmax")
            assert vec.shape == (4 + len(COUNTER_TAIL),)
            total = total + y
            pairs += int(vec[:4].sum())
            elsewhere += int(vec[4])
            assert 1 <= int(vec[6]) <= 4 and int(vec[5]) >= int(vec[6])
            # 50 x 3 on 16 experts: tiles of 16, so both counts of tiles
            assert int(vec[8]) == int(vec[5])
            tall = expert_ffn(
                h, part, valid, top_k=k, norm_topk=True, renorm_eps=0.0,
                use_pallas=False, first_expert=first, score="softmax",
                tm=64)
            # (the gather path's einsum sums in another order: rounding)
            np.testing.assert_allclose(tall[0], y, atol=2e-6)
            # a live tile of 64 rows counts 4 in units of 16, 1 as a tile
            assert int(tall[1][5]) == 4 * int(tall[1][8]) \
                and int(tall[1][8]) == int(vec[6])
            assert (np.asarray(tall[1])[[0, 1, 2, 3, 4, 6, 7]]
                    == np.asarray(vec)[[0, 1, 2, 3, 4, 6, 7]]).all()
            # the reference, given the same share, leaves out the same
            alone, _m = ref._experts(h, part, {**shape, "n_held_experts": 4,
                                               "first_expert": first})
            np.testing.assert_allclose(alone - shared, y, atol=2e-5)
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert pairs == T * k and elsewhere == 3 * T * k


# -- the router ---------------------------------------------------------------


def test_softmax_route_is_a_softmax_over_the_chosen_logits():
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    h = jax.random.normal(jax.random.PRNGKey(0), (40, 64), jnp.float32)
    wg = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32) / 8
    idx, w, p = moe.route(h, wg, None, top_k=5, renorm_eps=0.0,
                          score="softmax")
    logits = np.asarray(jnp.dot(h, wg, precision=jax.lax.Precision.HIGHEST))
    want_idx = np.argsort(-logits, -1)[:, :5]
    assert (np.sort(np.asarray(idx), -1) == np.sort(want_idx, -1)).all()
    chosen = np.take_along_axis(logits, np.asarray(idx), -1)
    want = np.exp(chosen - chosen.max(-1, keepdims=True))
    want /= want.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), want, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(p).sum(-1), 1.0, rtol=1e-6)
    # without the renormalisation: the softmax over all the logits
    _i, raw, _p = moe.route(h, wg, None, top_k=5, norm_topk=False,
                            score="softmax")
    np.testing.assert_allclose(
        np.asarray(raw), np.take_along_axis(np.asarray(p), np.asarray(idx),
                                            -1))


def test_sigmoid_route_is_bit_for_bit_the_parents():
    """The sigmoid path of ``route`` against the lines it was at the
    parent, on the same inputs: the same bits, and the same jaxpr whether
    ``score`` is left out or given as ``"sigmoid"``."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    def parent(h, wg, bias, *, top_k, norm_topk=True, scale=1.0,
               renorm_eps=1e-6):
        s = jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.float32), wg.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        sel = s if bias is None else s + bias.astype(jnp.float32)
        _, idx = jax.lax.top_k(sel, top_k)
        w = jnp.take_along_axis(s, idx, axis=1)
        if norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + renorm_eps)
        return idx.astype(jnp.int32), w * scale, s

    h = jax.random.normal(jax.random.PRNGKey(2), (33, 64), jnp.bfloat16)
    wg = jax.random.normal(jax.random.PRNGKey(3), (64, 16), jnp.bfloat16)
    bias = jax.random.normal(jax.random.PRNGKey(4), (16,)) * 0.02
    kw = dict(top_k=4, scale=2.446, renorm_eps=1e-20)
    for got, want in zip(moe.route(h, wg, bias, **kw),
                         parent(h, wg, bias, **kw)):
        assert (np.asarray(got) == np.asarray(want)).all()
    texts = {str(jax.make_jaxpr(lambda a, b, c: f(a, b, c, **k2))(h, wg, bias))
             for f, k2 in ((moe.route, kw), (parent, kw),
                           (moe.route, {**kw, "score": "sigmoid"}))}
    assert len(texts) == 1


# -- the engine: batching, preemption, slot reuse -----------------------------


def test_kernels_and_gather_path_emit_the_same_tokens(cfg, params,
                                                      clean_tokens):
    """Rows of unequal length in one batch (prompts of 3 to 101 tokens,
    replies of 5 to 12), interpreted kernels against the gather path; both
    gauges of the one cache and the new counters on /metrics."""
    eng = _engine(cfg, params, "t_q3n_pallas", attn="pallas")
    assert eng.generate_batch(_requests()) == clean_tokens
    snap = eng.pool.stats.snapshot()
    assert snap["moe_routed_pairs"] > 0 and snap["moe_pairs_elsewhere"] > 0
    assert sum(snap["moe_tokens_per_expert"]) == snap["moe_routed_pairs"]
    assert snap["state_slots_total"] == 4 and snap["state_slots_in_use"] == 0
    assert snap["blocks_total"] == 63 and snap["blocks_in_use"] == 0
    assert snap["kda_state_resets"] >= len(REQS) - 1  # 3 tokens: no chunk
    from pathway_tpu.serve.metrics import render_prometheus_lines

    lines = "\n".join(render_prometheus_lines())
    for name in ("state_slots_total", "conv_slots_total",
                 "kda_state_resets_total", "moe_pairs_elsewhere_total",
                 "moe_live_tiles_total", "moe_row_tiles_total",
                 "moe_experts_touched_total", "moe_routed_pairs_total"):
        assert f'pathway_kv_{name}{{pool="t_q3n_pallas"}}' in lines, name
    # the benchmark's reading of the tiles' rows (its counters() multiplies
    # the live tiles by ops.moe.TM) stands: rows in units of 16, and at this
    # engine's 36 x 3 pairs on 16 experts every tile is 16 rows tall
    import types

    from benchmark.systems.serve_qwen3_next import ServeQwen3Next
    from pathway_tpu.ops.moe import TM

    none = types.SimpleNamespace(completed=0, batches=0, batched_requests=0)
    read = ServeQwen3Next.counters(types.SimpleNamespace(
        engine=eng, cfg=cfg, sched=types.SimpleNamespace(stats=none)))
    assert TM == 16 and snap["moe_row_tiles"] == snap["moe_live_tiles"] > 0
    assert read["engine.moe_tile_rows"] == 16.0 * snap["moe_row_tiles"]
    assert read["engine.moe_routed_pairs"] <= read["engine.moe_tile_rows"]


def test_a_batch_emits_what_each_request_emits_alone(cfg, params,
                                                     clean_tokens):
    eng = _engine(cfg, params, "t_q3n_alone")
    assert [eng.generate(p, n) for p, n in _requests()] == clean_tokens


def test_preemption_recomputes_blocks_state_and_conv_inputs(cfg, params,
                                                            clean_tokens):
    """A pool too small for the batch: sequences are preempted, lose their
    blocks and their slot together, and are rebuilt by recompute over
    prompt + emitted - the same tokens as never having been preempted, and
    those are the argmax of the reference's logits."""
    eng = _engine(cfg, params, "t_q3n_preempt", num_blocks=24)
    out = eng.generate_batch(_requests())
    assert out == clean_tokens
    assert eng.pool.stats.preemptions > 0
    eng.pool.check_invariants()
    assert eng.pool.sequences() == [] and eng.pool.slots_in_use == 0
    for (prompt, _n), tokens in zip(_requests(), out):
        want, _m = _reference(params, cfg, prompt, tokens)
        assert tokens == np.argmax(want, -1).tolist()


def test_a_preempted_sequence_is_rebuilt_to_the_same_logits(cfg, params,
                                                            monkeypatch):
    """The logits, not the tokens: with a pool that forces preemption, every
    reference row of every request is still among the caught rows."""
    import jax

    caught = _spy(monkeypatch, rows=slice(0, 4))
    eng = _engine(cfg, params, "t_q3n_preempt_logits", num_blocks=24)
    reqs = _requests()
    out = eng.generate_batch(reqs)
    jax.effects_barrier()
    assert eng.pool.stats.preemptions > 0
    rows = np.concatenate(caught)
    for (prompt, _n), tokens in zip(reqs, out):
        want, _m = _reference(params, cfg, prompt, tokens)
        for w in want:
            assert np.abs(rows - w[None]).max(-1).min() / want.std() < 1e-4


@pytest.mark.parametrize("attn", ["reference", "pallas"])
def test_a_slot_reused_after_a_longer_sequence_starts_from_zero(cfg, params,
                                                                attn):
    long_, short = _prompts([120, 21], seed=8)
    eng = _engine(cfg, params, f"t_q3n_reuse_{attn}", max_batch_size=1,
                  attn=attn)
    assert eng.pool.conv_slots == 1
    eng.generate(long_, 6)
    assert float(np.abs(np.asarray(eng.pool.state[:, 1])).max()) > 0
    got = eng.generate(short, 9)
    fresh = _engine(cfg, params, f"t_q3n_fresh_{attn}", max_batch_size=1,
                    attn=attn)
    assert got == fresh.generate(short, 9)


def test_round_spans_name_the_state_rows_and_the_keys(cfg, params):
    from pathway_tpu import obs

    eng = _engine(cfg, params, "t_q3n_spans")
    eng.generate_batch(_requests()[:3])
    builds = [s.attrs for s in obs.recorder().snapshot()
              if s.name == "pw.round.build"
              and "state_rows" in (s.attrs or {})]
    assert {"mixed", "chain"} <= {a["kind"] for a in builds}
    mixed = [a for a in builds if a["kind"] == "mixed"]
    assert all(a["kda_chunk_tokens"] + a["kda_step_rows"] == a["tokens"]
               for a in mixed)
    assert all(a["state_rows"] == a["rows"] and a["conv_rows"] == a["rows"]
               for a in mixed)
    assert all("kv_keys" in a and "kv_write_blocks" in a
               and "kv_query_cols" in a for a in mixed)


def test_second_pass_compiles_nothing(cfg, params):
    from .utils import CompileWatch

    eng = _engine(cfg, params, "t_q3n_compile")
    watch = CompileWatch()
    eng.generate_batch(_requests())
    first = {e.program for e in watch.events()}
    assert {"pw.mixed_step", "pw.decode_step", "pw.chained_decode"} <= first
    eng.generate_batch(_requests(seed=1))
    watch.assert_no_compiles("second pass")


def test_hbm_plan_bills_the_pool_and_both_arenas_to_the_byte(cfg, params):
    import jax

    eng = _engine(cfg, params, "t_q3n_hbm")
    plan = eng.hbm_plan
    live = sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(eng.params))
    assert plan.params_bytes == live == cfg.param_count() * 4
    pool = eng.pool
    assert plan.kv_bytes == 2 * pool.k.size * 4 == 2 * 2 * 64 * 8 * 32 * 4
    assert plan.conv_bytes == pool.conv_bytes == 6 * 5 * 3 * 128 * 4
    assert plan.state_bytes == pool.state_bytes == 6 * 5 * 4 * 16 * 16 * 4
    assert plan.kv_bytes + plan.conv_bytes + plan.state_bytes \
        == pool.per_shard_bytes
    assert plan.total_bytes == plan.params_bytes + pool.per_shard_bytes \
        + plan.temp_bytes


def test_step_flops_count_the_held_experts_at_the_routers_share(cfg, params):
    """``step_flops`` (the chunk rule's): a held expert's leaves
    at ``top_k / n_experts``, the router's width, whatever share is held."""
    from pathway_tpu.obs import memory

    plan = _engine(cfg, params, "t_q3n_flops").params
    got = memory.step_flops(cfg, plan, rows=0, tokens=1)
    import jax

    want = 0.0
    for leaf in jax.tree_util.tree_leaves(plan):
        if leaf.ndim >= 2:
            routed = leaf.ndim == 3 and leaf.shape[0] == 4
            want += 2.0 * leaf.size * (3 / 16 if routed else 1.0)
    assert got == int(want)


# -- the family's refusals ----------------------------------------------------


@pytest.mark.parametrize("asked,named", [
    ({"tp": 2}, "tensor parallelism"),
    ({"quantize": "int8"}, "quantize='int8'"),
    ({"speculative": "ngram"}, "speculative drafting"),
    ({"session_store": object()}, "host tiering"),
])
def test_the_family_refuses_by_name(cfg, params, asked, named):
    with pytest.raises(ValueError, match="qwen3_next block family") as e:
        _engine(cfg, params, "t_q3n_refused", **asked)
    assert named in str(e.value)


def test_a_sampled_request_fails_alone_and_five_families_are_known(cfg):
    from pathway_tpu.models import families

    assert families.step_family(cfg) is families.Qwen3NextFamily
    assert families.Qwen3NextFamily.greedy_only
    assert len(families._FAMILIES) == 7  # the sixth: PR 40; the seventh: PR 45
    with pytest.raises(ValueError, match="decodes greedily"):
        families.Qwen3NextFamily.programs(cfg, "reference", None,
                                          sampled=True)


def test_prefix_sharing_is_off_whatever_was_asked(cfg, params):
    eng = _engine(cfg, params, "t_q3n_prefix", prefix_sharing=True)
    assert eng.prefix is None and not eng.pool.supports_prefix
