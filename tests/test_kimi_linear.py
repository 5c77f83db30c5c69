"""The ``kimi_linear`` block family (moonshotai Kimi Linear) on the paged
engine, at toy widths on the CPU: 64 wide, 4 heads of 16 (KDA) and of 16 +
8 over a latent of 32 (MLA), 16 experts top-4 beside a shared one, the
pattern kda dense, kda, mla, kda; seeded weights, f32.

The reference is ``benchmark/reference/kimi_linear_f32.py`` (plain f32, no
cache, no kernels, no batching, imports nothing of the program: KDA token by
token, latent attention expanded a head over the whole sequence).
Tolerance: 1e-4 of the logits' standard deviation - program and reference do
the same f32 arithmetic and differ in reduction order only (readings: 4e-6
to 5e-6).
"""

import importlib
import json

import numpy as np
import pytest

K, M = "kda", "mla"
PATTERN = (K, K, M, K)
VOCAB = 257


def _cfg(dtype="float32", **over):
    import jax.numpy as jnp

    from pathway_tpu.models.kimi_linear import KimiLinearConfig

    kw = dict(vocab_size=VOCAB, d_model=64, n_heads=4, kda_head_dim=16,
              kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=16, d_ff=96, d_ff_expert=32, n_experts=16, top_k=4,
              n_dense_layers=1, layer_types=PATTERN, max_len=256,
              dtype=getattr(jnp, dtype), kda_chunk=16)
    kw.update(over)
    return KimiLinearConfig(**kw)


def _shape(cfg):
    from benchmark.systems.serve_lfm2 import decoder_shape

    return decoder_shape(cfg, 0)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    import jax

    from pathway_tpu.models.kimi_linear import init_kimi_linear_params

    return init_kimi_linear_params(cfg, jax.random.PRNGKey(0))


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
    eng = _engine(cfg, params, "t_kimi_clean")
    out = eng.generate_batch(_requests())
    eng.pool.check_invariants()
    assert eng.pool.sequences() == [] and eng.pool.slots_in_use == 0
    return out


# -- logits against the reference ---------------------------------------------


def _logits_through_engine(cfg, params, prompt, n_new, name, monkeypatch,
                           **kw):
    """One request alone through the engine's own programs (chunked prefill
    over the mixed step, chained decode, the single step at the tail),
    every program's logits caught where it turns them into ids."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import kimi_linear

    caught = []

    def spy(logits):
        jax.debug.callback(lambda x: caught.append(np.asarray(x[0])), logits,
                           ordered=True)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(kimi_linear, "greedy_ids", spy)
    eng = _engine(cfg, params, name, **kw)
    tokens = eng.generate(prompt, n_new)
    jax.effects_barrier()
    snap = eng.pool.stats.snapshot()
    assert snap["prefill_chunks"] >= 3 and snap["mixed_steps"] >= 3
    assert snap["chain_steps_sum"] > snap["chain_count"]  # really chained
    assert snap["kda_state_resets"] == 1
    n_mixed = int(snap["mixed_steps"])
    rows = [caught[n_mixed - 1]] + caught[n_mixed:]
    assert len(rows) >= n_new
    return tokens, np.stack(rows[:n_new]), eng


def _reference(params, cfg, prompt, tokens):
    ref = importlib.import_module("benchmark.reference.kimi_linear_f32")
    cols = np.arange(len(prompt) - 1, len(prompt) + len(tokens) - 1)
    logits, margin = ref.logits_at(params, _shape(cfg), prompt + tokens, cols)
    return np.asarray(logits), np.asarray(margin)


@pytest.mark.parametrize("attn", ["reference", "pallas"])
def test_engine_logits_equal_the_reference(cfg, params, monkeypatch, attn):
    """Chunked prefill (70 tokens in chunks of 32: the state and the conv
    inputs cross two chunk boundaries, the last chunk is 6 tokens), then
    chains and single steps: every emitted position's logits."""
    prompt = _prompts([70], seed=5)[0]
    tokens, got, eng = _logits_through_engine(
        cfg, params, prompt, 22, f"t_kimi_logits_{attn}", monkeypatch,
        attn=attn)
    want, _margin = _reference(params, cfg, prompt, tokens)
    assert np.abs(got - want).max() / want.std() < 1e-4
    assert tokens == np.argmax(want, -1).tolist()
    assert eng.pool.cache_kind == "latent_state" and eng.prefix is None
    assert eng.pool.v is None   # never per-head K or V: one stored row
    assert eng.pool.k.shape[-1] == cfg.latent_lanes == 128
    eng.pool.check_invariants()
    assert eng.pool.slots_in_use == 0


def test_a_held_share_of_the_experts_equals_the_reference(monkeypatch):
    """The chip's share: 4 of 16 experts held (8 .. 12), the router 16
    wide; program and reference leave out what the other twelve would
    add."""
    import jax

    from pathway_tpu.models.kimi_linear import init_kimi_linear_params

    cfg = _cfg(n_held_experts=4, first_expert=8)
    params = init_kimi_linear_params(cfg, jax.random.PRNGKey(1))
    assert params["layers"][1]["w1"].shape[0] == 4
    assert params["layers"][1]["wg"].shape[1] == 16
    prompt = _prompts([70], seed=6)[0]
    tokens, got, eng = _logits_through_engine(
        cfg, params, prompt, 10, "t_kimi_share", monkeypatch)
    want, _margin = _reference(params, cfg, prompt, tokens)
    assert np.abs(got - want).max() / want.std() < 1e-4
    snap = eng.pool.stats.snapshot()
    assert len(snap["moe_tokens_per_expert"]) == 4
    assert snap["moe_pairs_elsewhere"] > snap["moe_routed_pairs"] > 0
    # every routed pair is counted once, here or elsewhere
    n_tokens = len(prompt) + len(tokens) - 1
    assert snap["moe_pairs_elsewhere"] + snap["moe_routed_pairs"] \
        == n_tokens * cfg.top_k * 3


# -- the four shares add up ---------------------------------------------------


def test_four_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """At toy widths: what the four shares of 4 experts give for one expert
    layer, with the shared expert (which every chip computes alike) counted
    once, is what the uncut reference gives for the whole layer."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops.moe import expert_ffn

    ref = importlib.import_module("benchmark.reference.kimi_linear_f32")
    rng = jax.random.split(jax.random.PRNGKey(3), 8)
    D, F, E, k, T = 64, 32, 16, 4, 50

    def mat(key, *dims):
        return jax.random.normal(key, dims, jnp.float32) / np.sqrt(dims[-2])

    lay = {"wg": mat(rng[0], D, E), "w1": mat(rng[1], E, D, F),
           "w3": mat(rng[2], E, D, F), "w2": mat(rng[3], E, F, D),
           "expert_bias": jax.random.normal(rng[4], (E,)) * 0.02,
           "shared": {"w1": mat(rng[5], D, F), "w3": mat(rng[6], D, F),
                      "w2": mat(rng[7], F, D)}}
    h = jax.random.normal(jax.random.PRNGKey(4), (T, D), jnp.float32)
    valid = jnp.ones((T,), bool)
    shape = {"top_k": k, "route_norm": True, "route_scale": 2.446,
             "n_held_experts": None, "first_expert": 0}
    with jax.default_matmul_precision("highest"):
        whole, _margin = ref._experts(h, lay, shape)
        shared = ref._swiglu(h, *(lay["shared"][n] for n in
                                  ("w1", "w3", "w2")))
        total = shared
        pairs = elsewhere = 0
        for first in range(0, E, 4):
            part = {**lay, **{n: lay[n][first:first + 4]
                              for n in ("w1", "w3", "w2")}}
            y, vec = expert_ffn(
                h, part, valid, top_k=k, norm_topk=True, scale=2.446,
                renorm_eps=1e-20, use_pallas=False, first_expert=first)
            total = total + y
            pairs += int(vec[:4].sum())     # tokens per held expert, then
            elsewhere += int(vec[4])        # ops.moe.COUNTER_TAIL
            # the reference, given the same share, leaves out the same
            alone, _m = ref._experts(h, part, {**shape, "n_held_experts": 4,
                                               "first_expert": first})
            np.testing.assert_allclose(alone - shared, y, atol=2e-5)
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert pairs == T * k and elsewhere == 3 * T * k


# -- absorbed against expanded latent attention -------------------------------


def test_absorbed_latent_attention_equals_the_expanded_form(cfg, params):
    """The program's latent layer (absorbed: every head on one stored row
    of the pool, W_kv_b's halves on the query and on the mix) against the
    reference's (expanded: k and v a head over the whole sequence), one
    sequence prefilled in one chunk."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.kvcache.paged_attention import (latent_attention,
                                                     latent_write_rows)
    from pathway_tpu.models import kimi_linear as m

    ref = importlib.import_module("benchmark.reference.kimi_linear_f32")
    lay = params["layers"][2]
    plan = m.plan_params(cfg, params)["layers"][2]
    T, BS = 40, 8
    h = jax.random.normal(jax.random.PRNGKey(7), (T, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = ref._mla(h, lay, _shape(cfg))
        r, nope, rope = cfg.kv_lora_rank, cfg.qk_nope_head_dim, \
            cfg.qk_rope_head_dim
        qh = (h @ plan["wq"]).reshape(T, cfg.n_heads, nope + rope)
        kv = h @ plan["wkv_a"]
        row = jnp.concatenate([
            m._rms(kv[:, :r], plan["kv_norm"], cfg.norm_eps), kv[:, r:],
            jnp.zeros((T, cfg.latent_lanes - r - rope))], -1)
        pool = jnp.zeros((8, BS, cfg.latent_lanes))
        pos = jnp.arange(T)
        pool = latent_write_rows(pool, 1 + pos // BS, pos % BS, row)
        q = m._absorbed_query(qh, plan["w_kb"], nope,
                              cfg.latent_lanes - r - rope)
        a = latent_attention(
            q[None], pool, jnp.arange(1, 6)[None],
            start_pos=jnp.zeros((1,), jnp.int32),
            n_valid=jnp.full((1,), T, jnp.int32),
            scale=(nope + rope) ** -0.5)[0]
        got = jnp.einsum("thc,hcv->thv", a[..., :r], plan["w_vb"]
                         ).reshape(T, -1) @ plan["wo"]
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- the engine: batching, preemption, slot reuse -----------------------------


def test_kernels_and_gather_path_emit_the_same_tokens(cfg, params,
                                                      clean_tokens):
    """Rows of unequal length in one batch (prompts of 3 to 101 tokens,
    replies of 5 to 12), interpreted kernels against the gather path."""
    eng = _engine(cfg, params, "t_kimi_pallas", attn="pallas")
    assert eng.generate_batch(_requests()) == clean_tokens
    snap = eng.pool.stats.snapshot()
    assert snap["moe_routed_pairs"] > 0 and snap["moe_pairs_elsewhere"] == 0
    assert sum(snap["moe_tokens_per_expert"]) == snap["moe_routed_pairs"]
    assert snap["state_slots_total"] == 4 and snap["state_slots_in_use"] == 0
    assert snap["kda_state_resets"] >= len(REQS) - 1  # 3 tokens: no chunk
    from pathway_tpu.serve.metrics import render_prometheus_lines

    lines = "\n".join(render_prometheus_lines())
    assert 'pathway_kv_state_slots_total{pool="t_kimi_pallas"} 4' in lines
    assert 'pathway_kv_kda_state_resets_total{pool="t_kimi_pallas"}' in lines
    assert 'pathway_kv_moe_pairs_elsewhere_total{pool="t_kimi_pallas"} 0' \
        in lines


def test_a_batch_emits_what_each_request_emits_alone(cfg, params,
                                                     clean_tokens):
    eng = _engine(cfg, params, "t_kimi_alone")
    assert [eng.generate(p, n) for p, n in _requests()] == clean_tokens


def test_preemption_recomputes_blocks_state_and_conv_inputs(cfg, params,
                                                            clean_tokens):
    """A pool too small for the batch: sequences are preempted, lose their
    blocks and their slot together, and are rebuilt by recompute over
    prompt + emitted - the same tokens as never having been preempted."""
    eng = _engine(cfg, params, "t_kimi_preempt", num_blocks=24)
    assert eng.generate_batch(_requests()) == clean_tokens
    assert eng.pool.stats.preemptions > 0
    eng.pool.check_invariants()
    assert eng.pool.sequences() == [] and eng.pool.slots_in_use == 0


@pytest.mark.parametrize("attn", ["reference", "pallas"])
def test_a_slot_reused_after_a_longer_sequence_starts_from_zero(cfg, params,
                                                                attn):
    """One slot: a short sequence after a long one rides the slot the long
    one summed its state into, and emits what it emits on a fresh engine
    (its first chunk starts from zero inside the program)."""
    long_, short = _prompts([120, 21], seed=8)
    eng = _engine(cfg, params, f"t_kimi_reuse_{attn}", max_batch_size=1,
                  attn=attn)
    assert eng.pool.conv_slots == 1
    eng.generate(long_, 6)
    assert float(np.abs(np.asarray(eng.pool.state[:, 1])).max()) > 0
    got = eng.generate(short, 9)
    fresh = _engine(cfg, params, f"t_kimi_fresh_{attn}", max_batch_size=1,
                    attn=attn)
    assert got == fresh.generate(short, 9)


def test_round_spans_name_the_state_rows(cfg, params):
    from pathway_tpu import obs

    eng = _engine(cfg, params, "t_kimi_spans")
    eng.generate_batch(_requests()[:3])
    builds = [s.attrs for s in obs.recorder().snapshot()
              if s.name == "pw.round.build"
              and "state_rows" in (s.attrs or {})]
    kinds = {a["kind"] for a in builds}
    assert {"mixed", "chain"} <= kinds
    mixed = [a for a in builds if a["kind"] == "mixed"]
    assert all(a["kda_chunk_tokens"] + a["kda_step_rows"] == a["tokens"]
               for a in mixed)
    assert all(a["state_rows"] == a["rows"] for a in mixed)
    assert any(a["kda_chunk_tokens"] >= 32 for a in mixed)
    chains = [a for a in builds if a["kind"] == "chain"]
    assert all(a["kda_step_rows"] == a["tokens"] and "kv_keys" in a
               for a in chains)


def test_second_pass_compiles_nothing(cfg, params):
    from .utils import CompileWatch

    eng = _engine(cfg, params, "t_kimi_compile")
    watch = CompileWatch()
    eng.generate_batch(_requests())
    first = {e.program for e in watch.events()}
    assert {"pw.mixed_step", "pw.decode_step", "pw.chained_decode"} <= first
    eng.generate_batch(_requests(seed=1))
    watch.assert_no_compiles("second pass")


# -- the cache ----------------------------------------------------------------


def test_state_cache_gives_blocks_and_slot_together_or_not_at_all():
    import jax.numpy as jnp

    from pathway_tpu.kvcache import PoolExhausted, UnsupportedCacheOp
    from pathway_tpu.kvcache.backend import make_backend

    pool = make_backend(
        "latent_state", num_blocks=6, block_size=8, n_layers=2, n_heads=1,
        head_dim=128, dtype=jnp.float32, name="t_kimi_pool", conv_layers=3,
        conv_width=96, conv_taps=3, conv_slots=2, state_heads=2, state_dk=16,
        state_dv=16)
    assert pool.k.shape == (2, 6, 8, 128) and pool.v is None
    assert pool.conv.shape == (3, 3, 3, 96)
    assert pool.state.shape == (3, 3, 2, 16, 16)
    assert pool.state.dtype == jnp.float32
    assert pool.per_shard_bytes == 4 * (2 * 6 * 8 * 128 + 3 * 3 * 3 * 96
                                        + 3 * 3 * 2 * 16 * 16)
    assert len(pool.device_state()) == 3
    pool.allocate(1, 20)                      # three blocks, one slot
    with pytest.raises(PoolExhausted):
        pool.allocate(2, 40)                  # five blocks: two are free
    assert pool.slots_in_use == 1 and pool.num_free == 2
    pool.allocate(2, 8)
    with pytest.raises(PoolExhausted):
        pool.allocate(3, 8)                   # a block is free, no slot is
    assert pool.num_free == 1 and sorted(pool._slot_of) == [1, 2]
    pool.check_invariants()
    assert pool.stats.state_slots_in_use == 2
    with pytest.raises(UnsupportedCacheOp):
        pool.allocate(3, 16, shared_blocks=[1])
    with pytest.raises(UnsupportedCacheOp):
        pool.fork(1, 4)
    with pytest.raises(UnsupportedCacheOp):
        pool.suspend_host(1, [])
    victim = pool.preempt()
    assert victim.seq_id == 2 and pool.slots_in_use == 1
    pool._slot_of[9] = 2                      # a slot without its sequence
    with pytest.raises(AssertionError):
        pool.check_invariants()
    del pool._slot_of[9]
    pool.free_sequence(1)
    pool.check_invariants()
    assert pool.slots_in_use == 0 and pool.num_free == 5
    assert pool.row_extras([], 4)[0].tolist() == [0, 0, 0, 0]


def test_hbm_plan_bills_the_latent_pool_and_the_state_arena(cfg, params):
    import jax

    eng = _engine(cfg, params, "t_kimi_hbm")
    plan = eng.hbm_plan
    live = sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(eng.params))
    assert plan.params_bytes == live
    pool = eng.pool
    assert plan.kv_bytes == pool.k.size * 4       # one array, no V
    assert plan.conv_bytes == pool.conv_bytes
    assert plan.state_bytes == pool.state_bytes == 3 * 5 * 4 * 16 * 16 * 4
    assert plan.kv_bytes + plan.conv_bytes + plan.state_bytes \
        == pool.per_shard_bytes
    assert plan.total_bytes == plan.params_bytes + pool.per_shard_bytes \
        + plan.temp_bytes
    assert plan.as_dict()["state_bytes"] == plan.state_bytes


# -- the family's refusals, and the import ------------------------------------


@pytest.mark.parametrize("asked,named", [
    ({"tp": 2}, "tensor parallelism"),
    ({"quantize": "int8"}, "quantize='int8'"),
    ({"speculative": "ngram"}, "speculative drafting"),
    ({"session_store": object()}, "host tiering"),
])
def test_the_family_refuses_by_name(cfg, params, asked, named):
    with pytest.raises(ValueError, match="kimi_linear block family") as e:
        _engine(cfg, params, "t_kimi_refused", **asked)
    assert named in str(e.value)


def test_a_sampled_request_fails_alone(cfg, params):
    from pathway_tpu.models.families import KimiLinearFamily, step_family

    assert step_family(cfg) is KimiLinearFamily
    assert KimiLinearFamily.greedy_only
    with pytest.raises(ValueError, match="decodes greedily"):
        KimiLinearFamily.programs(cfg, "reference", None, sampled=True)


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(path) as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("the catalog is not here")
    return next(r for r in rows
                if r["name"] == "Kimi-Linear-48B-A3B-Instruct")


def test_config_from_kimi_linear_on_the_catalog_row():
    import types

    from pathway_tpu.models import hf_import

    row = _catalog_row()
    cfg = hf_import.config_from_kimi_linear(
        types.SimpleNamespace(**row["config"]), dtype="bfloat16")
    assert cfg.family == "kimi_linear" and cfg.n_layers == 27
    assert len(cfg.kda_layers) == 20 and len(cfg.mla_layers) == 7
    assert cfg.mla_layers == (3, 7, 11, 15, 19, 23, 26)
    assert (cfg.d_model, cfg.n_heads, cfg.kda_head_dim) == (2304, 32, 128)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert cfg.latent_width == 576 and cfg.latent_lanes == 640
    assert (cfg.n_experts, cfg.held_experts, cfg.share, cfg.top_k) \
        == (256, 256, None, 8)
    assert cfg.n_dense_layers == 1 and cfg.route_scale == 2.446
    assert cfg.max_len == 1048576 and cfg.vocab_size == 163840
    assert round(cfg.param_count() / 1e9, 2) == 49.12


def test_config_from_kimi_linear_takes_a_share_and_a_cut():
    import types

    from pathway_tpu.models import hf_import

    pub = dict(_catalog_row()["config"])
    pub.update(num_hidden_layers=13, num_experts=64)
    cfg = hf_import.config_from_kimi_linear(
        types.SimpleNamespace(**pub), max_len=8192, router_experts=256,
        first_expert=64)
    assert cfg.layer_types == (K, K, K, M, K, K, K, M, K, K, K, M, K)
    assert (cfg.n_experts, cfg.held_experts, cfg.share) == (256, 64, 64)
    assert cfg.max_len == 8192
    assert round(cfg.param_count() / 1e6) == 6829


@pytest.mark.parametrize("key,value,named", [
    ("moe_router_activation_func", "softmax", "sigmoid"),
    ("topk_group", 2, "group limit"),
    ("q_lora_rank", 1536, "q_lora_rank"),
    ("mla_use_nope", False, "rotary on the latent layers"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("num_nextn_predict_layers", 1, "num_nextn_predict_layers"),
])
def test_config_from_kimi_linear_refuses_what_is_not_written_down(
        key, value, named):
    import types

    from pathway_tpu.models import hf_import

    pub = dict(_catalog_row()["config"], **{key: value})
    with pytest.raises(ValueError, match="not written down") as e:
        hf_import.config_from_kimi_linear(types.SimpleNamespace(**pub))
    assert named in str(e.value)


def test_decays_lie_where_the_configuration_says():
    """``decay_parameters`` with the decay projection at a fifth of its
    fan-in scale: every decay a token between 0.9 and 0.9999, four
    deviations of the projection out."""
    import jax

    from pathway_tpu.models.kimi_linear import decay_parameters

    a_log, dt_bias = decay_parameters(jax.random.PRNGKey(0), 32, 4096)
    for f in (-0.8, 0.0, 0.8):
        alpha = np.exp(-np.exp(np.asarray(a_log))[:, None] * np.log1p(
            np.exp(np.asarray(dt_bias).reshape(32, 128) + f)))
        assert 0.9 < alpha.min() and alpha.max() < 0.99992
