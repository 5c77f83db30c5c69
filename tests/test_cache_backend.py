"""The engine<->cache contract (kvcache/backend.py) and the family seam
(models/families.py), over the five kinds of cache that serve cells:

- ``paged``: BlockPool, K/V blocks for every layer;
- ``hybrid``: HybridCache, K/V blocks for the attention layers and a conv
  slot a sequence beside them;
- ``windowed``: WindowedCache, K/V blocks for the full-attention layers
  and a second pool and table for the sliding-window layers, whose blocks
  are freed behind the window;
- ``latent_state``: StateCache, a latent pool of one array (a stored row is
  key and value both) and, under one slot a sequence, the conv inputs and
  the f32 matrix states of the delta-rule layers;
- ``kv_state``: KVStateCache, that state cache with a plain K/V pool (keys
  AND values) in the latent pool's place.

Each contract test runs over every kind through ``make_backend``, the seam
the engine builds (and, on a supervised restart, rebuilds) its cache
through.  The family tests hold what the engine and the trace readers take
from ``programs()``: the three kinds of step program, which arguments are
donated, and the function names the device trace carries
(``jit__mixed_fn`` on ``XLA Modules``: a rename would silence a metric).
"""

import ast
import json
import os
import random
import re
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.kvcache import (
    BlockPool, CacheBackend, HybridCache, PagedDecodeEngine, PoolExhausted,
    SessionStore, UnsupportedCacheOp, make_backend,
)
from pathway_tpu.models.decoder import DecoderConfig, init_decoder_params
from pathway_tpu.models.families import step_family
from pathway_tpu.serve import metrics as serve_metrics

KINDS = ("paged", "hybrid", "windowed", "latent_state", "kv_state")
# the kinds whose kernels are _paged_*_fn
PAGED_KERNEL_KINDS = ("paged", "hybrid", "windowed", "kv_state")
SLOTTED = ("hybrid", "latent_state", "kv_state")
STATEFUL = ("latent_state", "kv_state")  # a matrix state beside the conv slot
_GEOM = dict(num_blocks=24, block_size=4, n_layers=2, n_heads=2, head_dim=8)
_CONV = dict(conv_layers=3, conv_width=16, conv_slots=5)
_WINDOW = dict(window=10, window_layers=3, round_tokens=6, max_seqs=5)
_STATE = dict(_CONV, conv_taps=3, state_heads=2, state_dk=8, state_dv=8)
_EXTRA = {"paged": {}, "hybrid": _CONV, "windowed": _WINDOW,
          "latent_state": _STATE, "kv_state": _STATE}

_CFG = DecoderConfig(
    vocab_size=64, d_model=64, n_layers=2, n_heads=8, d_ff=128, max_len=128
)
_HD = _CFG.d_model // _CFG.n_heads


def _make(kind, name, **over):
    kw = dict(_GEOM, name=name, **_EXTRA[kind])
    kw.update(over)
    return make_backend(kind, **kw)


@pytest.fixture(scope="module")
def params():
    return init_decoder_params(_CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def lfm2_only():
    from pathway_tpu.models.lfm2 import Lfm2Config, init_lfm2_params

    a, c = "full_attention", "conv"
    cfg = Lfm2Config(vocab_size=257, d_model=64, n_heads=4, n_kv_heads=2,
                     d_ff=128, d_ff_expert=32, n_experts=8, top_k=2,
                     n_dense_layers=1, layer_types=(c, a, c, c, a),
                     max_len=256, dtype=jnp.float32)
    return cfg, init_lfm2_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def afmoe():
    from pathway_tpu.models.afmoe import AfmoeConfig, init_afmoe_params

    s, f = "sliding_attention", "full_attention"
    cfg = AfmoeConfig(vocab_size=257, d_model=64, n_heads=8, n_kv_heads=2,
                      head_dim=128, d_ff=96, d_ff_expert=32, n_experts=8,
                      top_k=2, n_dense_layers=1, layer_types=(s, f, s),
                      sliding_window=8, max_len=256, dtype=jnp.float32)
    return cfg, init_afmoe_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def kimi():
    from pathway_tpu.models.kimi_linear import (KimiLinearConfig,
                                                init_kimi_linear_params)

    k, m = "kda", "mla"
    cfg = KimiLinearConfig(vocab_size=257, d_model=64, n_heads=4,
                           kda_head_dim=16, kv_lora_rank=32,
                           qk_nope_head_dim=16, qk_rope_head_dim=8,
                           v_head_dim=16, d_ff=96, d_ff_expert=32,
                           n_experts=8, top_k=2, n_dense_layers=1,
                           layer_types=(k, m, k), max_len=256,
                           dtype=jnp.float32, kda_chunk=8)
    return cfg, init_kimi_linear_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def qwen3_next():
    from pathway_tpu.models.qwen3_next import (Qwen3NextConfig,
                                               init_qwen3_next_params)

    g, f = "linear_attention", "full_attention"
    cfg = Qwen3NextConfig(vocab_size=257, d_model=64, n_heads=4,
                          n_kv_heads=2, head_dim=16, rotary_dim=4,
                          gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
                          gdn_value_dim=16, d_ff_expert=32, d_ff_shared=32,
                          n_experts=8, top_k=2, layer_types=(g, f, g),
                          max_len=256, dtype=jnp.float32, gdn_chunk=8)
    return cfg, init_qwen3_next_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def lfm2(lfm2_only, afmoe, kimi, qwen3_next):
    """The four families that bring a cache of their own, by its kind."""
    return {"hybrid": lfm2_only, "windowed": afmoe, "latent_state": kimi,
            "kv_state": qwen3_next}


def _engine(kind, params, lfm2, name, **kw):
    geom = dict(num_blocks=64, block_size=4, max_batch_size=4,
                prefill_chunk=8, chain_steps=4)
    geom.update(kw)
    if kind == "paged":
        return PagedDecodeEngine(_CFG, params, name=name, **geom)
    cfg, fparams = lfm2[kind]
    geom.setdefault("attn", "reference")
    return PagedDecodeEngine(cfg, fparams, name=name, **geom)


def _nbytes(arrays) -> int:
    return sum(int(a.size) * a.dtype.itemsize for a in arrays)


def _cache_arrays(kind) -> int:
    """K and V pools, and the hybrid kind's conv arena or the windowed
    kind's second pool pair; the latent pool (one array) and the state
    kind's two arenas."""
    return {"paged": 2, "hybrid": 3, "windowed": 4, "latent_state": 3,
            "kv_state": 4}[kind]


# -- the contract, over both kinds ---------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_lifecycle_fuzz_holds_the_invariants_after_every_operation(kind):
    pool = _make(kind, f"t_cb_fuzz_{kind}")
    assert isinstance(pool, CacheBackend) and pool.cache_kind == kind
    rng = random.Random(0xB10C + len(kind))
    live: list[int] = []
    next_id = 1
    counts = {"allocate": 0, "extend": 0, "free": 0, "preempt": 0,
              "exhausted": 0}
    for _step in range(400):
        op = rng.random()
        before = (pool.num_free, len(pool.sequences()))
        try:
            if op < 0.35 or not live:
                pool.allocate(next_id, rng.randint(0, 18),
                              priority=rng.randint(0, 2))
                live.append(next_id)
                next_id += 1
                counts["allocate"] += 1
            elif op < 0.70:
                sid = rng.choice(live)
                k = rng.randint(1, 6)
                n0 = pool.sequence(sid).n_tokens
                slots = pool.extend_slots(sid, k)
                assert len(slots) == k
                assert pool.sequence(sid).n_tokens == n0 + k
                # the slots are the sequence's own blocks, never the null one
                assert {b for b, _o in slots} <= set(
                    pool.sequence(sid).block_ids) - {0}
                counts["extend"] += 1
            elif op < 0.90:
                sid = live.pop(rng.randrange(len(live)))
                pool.free_sequence(sid)
                counts["free"] += 1
            else:
                victim = pool.preempt()
                assert victim is not None
                live.remove(victim.seq_id)
                counts["preempt"] += 1
        except PoolExhausted:
            # no partial side effect: neither a block nor a sequence moved
            assert (pool.num_free, len(pool.sequences())) == before
            counts["exhausted"] += 1
            victim = pool.preempt()
            if victim is not None:
                live.remove(victim.seq_id)
        pool.after_sync()  # a windowed cache frees behind the window here
        pool.check_invariants()
        assert sorted(s.seq_id for s in pool.sequences()) == sorted(live)
    assert all(counts.values()), counts
    for sid in live:
        pool.free_sequence(sid)
    pool.check_invariants()
    assert pool.num_free == pool.num_blocks - 1
    if kind in SLOTTED:
        assert pool.slots_in_use == 0
    if kind == "windowed":
        assert pool.window_blocks_in_use == 0
        snap = pool.stats.snapshot()
        assert snap["kv_window_blocks_allocated"] \
            == snap["kv_window_blocks_freed"] > 0


@pytest.mark.parametrize("kind", KINDS)
def test_optional_operations_work_or_refuse_as_the_flags_say(kind):
    pool = _make(kind, f"t_cb_flags_{kind}")
    parent = pool.allocate(1, 9)
    assert pool.supports_preemption
    assert pool.supports_fork == pool.supports_prefix == (kind == "paged")
    if pool.supports_fork:
        child = pool.fork(1, 2)
        assert child.block_ids == parent.block_ids
    else:
        with pytest.raises(UnsupportedCacheOp, match="fork"):
            pool.fork(1, 2)
    if pool.supports_prefix:
        # the two full blocks of the parent, shared
        st = pool.allocate(3, 9, shared_blocks=parent.block_ids[:2])
        assert st.block_ids[:2] == parent.block_ids[:2]
    else:
        with pytest.raises(UnsupportedCacheOp, match="shared blocks"):
            pool.allocate(3, 9, shared_blocks=parent.block_ids[:2])
    n_live = len(pool.sequences())
    victim = pool.preempt()
    assert victim is not None and len(pool.sequences()) == n_live - 1
    pool.check_invariants()
    with pytest.raises(ValueError, match="unknown cache backend"):
        make_backend("state")


@pytest.mark.parametrize("kind", KINDS)
def test_per_shard_bytes_are_the_bytes_of_the_device_state(kind):
    pool = _make(kind, f"t_cb_bytes_{kind}", dtype=jnp.bfloat16)
    state = pool.device_state()
    assert len(state) == _cache_arrays(kind)
    # the matrix states are f32 whatever the cache's dtype
    assert [a.dtype for a in state] == [jnp.bfloat16] * (len(state) - 1) + [
        jnp.float32 if kind in STATEFUL else jnp.bfloat16]
    assert pool.per_shard_bytes == _nbytes(state)
    # what /metrics reports a shard to hold
    assert pool.stats.shard_hbm_bytes == pool.per_shard_bytes


@pytest.mark.parametrize("kind", KINDS)
def test_restart_rebuilds_the_cache_through_the_seam(kind, params, lfm2):
    eng = _engine(kind, params, lfm2, f"t_cb_restart_{kind}")
    eng.generate_batch([([5, 9, 20, 3, 7, 11, 2], 6), ([41, 2, 8], 6)])
    old = eng.pool
    shapes = [(a.shape, a.dtype) for a in old.device_state()]
    assert all(np.asarray(a).any() for a in old.device_state())
    eng._restart([], deque(), "Boom", "a test's restart", 1)
    new = eng.pool
    assert new is not old and type(new) is type(old)
    assert new.cache_kind == kind == eng.family.cache_kind
    # the parent's geometry, and nothing of its contents
    assert [(a.shape, a.dtype) for a in new.device_state()] == shapes
    assert not any(np.asarray(a).any() for a in new.device_state())
    assert (new.num_blocks, new.block_size) \
        == (old.num_blocks, old.block_size)
    assert new.sequences() == [] and new.num_free == new.num_blocks - 1
    new.check_invariants()
    # the counters stay with the name, across the rebuild
    assert new.name == old.name and new.stats is old.stats
    assert new.stats.snapshot()["engine_restarts"] == 1
    # and the seam gives the same cache to anyone holding the same kwargs
    twin = make_backend(kind, **{**eng._pool_kwargs,
                                 "name": f"t_cb_restart_twin_{kind}"})
    assert [(a.shape, a.dtype) for a in twin.device_state()] == shapes
    assert twin.per_shard_bytes == new.per_shard_bytes
    # the rebuilt engine serves
    out = eng.generate_batch([([5, 9, 20, 3, 7, 11, 2], 6)])
    assert len(out[0]) == 6


def _gauge(lines, metric: str, pool_name: str):
    want = f'{metric}{{pool="{pool_name}"}} '
    got = [ln for ln in lines if ln.startswith(want)]
    return [float(ln[len(want):]) for ln in got]


@pytest.mark.parametrize("kind", KINDS)
def test_retire_hands_the_name_and_its_gauges_to_the_next_cache(kind):
    # a retired cache gives up its /metrics name at once: the cache built
    # after it under the same name owns the gauges (no "#1" twin), and the
    # counters stay monotonic across the two
    name = f"t_cb_retire_{kind}"
    first = _make(kind, name)
    first.allocate(1, 9)
    first.preempt()
    first.allocate(2, 9)
    lines = serve_metrics.render_prometheus_lines()
    assert _gauge(lines, "pathway_kv_blocks_in_use", name) == [3.0]
    if kind in SLOTTED:
        assert _gauge(lines, "pathway_kv_conv_slots_in_use", name) == [1.0]
    if kind in STATEFUL:
        assert _gauge(lines, "pathway_kv_state_slots_in_use", name) == [1.0]
    if kind == "windowed":
        first.reserve_chunk(2, 9)
        lines = serve_metrics.render_prometheus_lines()
        assert _gauge(lines, "pathway_kv_window_blocks_in_use", name) == [3.0]
    unretired = _make(kind, name)
    assert unretired.name == name + "#1"
    unretired.retire()
    first.retire()
    second = _make(kind, name)
    assert second.name == name and second.stats is first.stats
    lines = serve_metrics.render_prometheus_lines()
    assert _gauge(lines, "pathway_kv_blocks_in_use", name) == [0.0]
    assert _gauge(lines, "pathway_kv_preemptions_total", name) == [1.0]
    if kind in SLOTTED:
        assert _gauge(lines, "pathway_kv_conv_slots_in_use", name) == [0.0]
        assert _gauge(lines, "pathway_kv_conv_slots_total", name) \
            == [float(_CONV["conv_slots"])]
    if kind in STATEFUL:
        assert _gauge(lines, "pathway_kv_state_slots_total", name) \
            == [float(_CONV["conv_slots"])]
    if kind == "windowed":
        assert _gauge(lines, "pathway_kv_window_blocks_in_use", name) == [0.0]
        assert _gauge(lines, "pathway_kv_window_blocks_total", name) \
            == [float(second.window_blocks - 1)]
    second.retire()


@pytest.mark.parametrize("kind", KINDS)
def test_host_tiering_round_trips_or_refuses_by_name(kind):
    pool = _make(kind, f"t_cb_tier_{kind}")
    st = pool.allocate(1, 11)
    if kind != "paged":
        with pytest.raises(UnsupportedCacheOp, match="host tiering"):
            pool.suspend_host(1, list(range(11)))
        with pytest.raises(UnsupportedCacheOp, match="host tiering"):
            pool.resume_host({}, st.block_ids)
        # a refusal takes nothing from the sequence
        assert pool.sequence(1).block_ids == st.block_ids
        pool.check_invariants()
        return
    rng = np.random.default_rng(5)
    k = rng.standard_normal(pool.k.shape).astype(np.float32)
    v = rng.standard_normal(pool.v.shape).astype(np.float32)
    pool.set_device_state(jnp.asarray(k), jnp.asarray(v))
    blocks = list(st.block_ids)
    payload, nbytes = pool.suspend_host(1, list(range(11)))
    assert pool.sequences() == [] and pool.num_free == pool.num_blocks - 1
    assert nbytes == payload["k"].nbytes + payload["v"].nbytes
    # other sequences take the freed blocks first: the resumed one lands
    # elsewhere and must read the same bytes
    pool.allocate(2, 7)
    back = pool.allocate(3, 11)
    assert back.block_ids != blocks
    pool.resume_host(payload, back.block_ids)
    for arr, src in ((pool.k, k), (pool.v, v)):
        np.testing.assert_array_equal(
            np.asarray(arr)[:, back.block_ids], src[:, blocks])
    pool.check_invariants()


# -- kept from the suite of the engine that went --------------------------------


def test_paged_engine_builds_pool_through_make_backend(params):
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=64, block_size=4, max_batch_size=4,
        seq_buckets=(16, 32, 64), prefill_chunk=8, name="t_seam_engine",
    )
    assert isinstance(eng.pool, CacheBackend)
    assert isinstance(eng.pool, BlockPool)
    assert eng.pool.cache_kind == "paged"


def test_blockpool_parity_through_backend_interface():
    # the SAME behavior whether BlockPool is constructed directly or
    # through the make_backend seam: allocation layout, suspend payload
    # bytes, invariants
    kw = dict(num_blocks=32, block_size=4, n_layers=_CFG.n_layers,
              n_heads=_CFG.n_heads, head_dim=_HD)
    direct = BlockPool(name="t_seam_direct", **kw)
    seamed = make_backend("paged", name="t_seam_made", **kw)
    assert type(seamed) is BlockPool
    for pool in (direct, seamed):
        st = pool.allocate(0, 11)
        assert len(st.block_ids) == pool.blocks_for(11)
    assert (direct.sequence(0).block_ids
            == seamed.sequence(0).block_ids)
    p_direct, b_direct = direct.suspend_host(0, list(range(11)))
    p_seamed, b_seamed = seamed.suspend_host(0, list(range(11)))
    assert b_direct == b_seamed
    np.testing.assert_array_equal(p_direct["k"], p_seamed["k"])
    direct.check_invariants()
    seamed.check_invariants()
    with pytest.raises(ValueError, match="unknown cache backend"):
        make_backend("bogus")


def test_session_store_charges_real_buffer_bytes():
    store = SessionStore()
    # 11 tokens -> 3 blocks, padded gather width 4 — the charge is the
    # PADDED buffer (k + v), not the logical 3-block span
    pool = BlockPool(num_blocks=32, block_size=4, n_layers=2, n_heads=4,
                     head_dim=8, name="t_charge_paged")
    pool.allocate(0, 11)
    per_block = 2 * 4 * 4 * 8 * 4  # L * bs * H * hd * itemsize
    store.suspend("pg", pool, 0, list(range(11)))
    ent = store.match("pg", list(range(11)))
    assert ent is not None
    assert ent.nbytes == 2 * 4 * per_block  # k+v, padded 3 -> 4 blocks
    assert ent.payload["k"].nbytes == 4 * per_block
    assert store.host_bytes >= ent.nbytes


# -- a conv slot is not cleared between sequences -------------------------------


def test_a_reused_conv_slot_gives_what_a_fresh_engine_gives(params, lfm2):
    # one row, one slot: every request takes the slot the one before it
    # left, with that sequence's last two conv inputs still in it
    reqs = [([9, 4, 250, 17, 33, 8, 101, 64, 5], 7), ([77, 12], 9),
            ([201, 5, 5, 90, 13, 44, 2, 150, 31, 6, 18], 5)]
    eng = _engine("hybrid", params, lfm2, "t_cb_slot_reuse",
                  max_batch_size=1)
    assert isinstance(eng.pool, HybridCache) and eng.pool.conv_slots == 1
    got = []
    for prompt, n in reqs:
        got.append(eng.generate(prompt, n))
        # the sequence is gone, what it wrote into the slot is not
        assert eng.pool.slots_in_use == 0
        assert np.asarray(eng.pool.conv[:, 1]).any()
    for i, (prompt, n) in enumerate(reqs):
        fresh = _engine("hybrid", params, lfm2, f"t_cb_slot_fresh{i}",
                        max_batch_size=1)
        assert fresh.generate(prompt, n) == got[i]


# -- the family's table of step programs ----------------------------------------


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_family_names_its_three_programs_for_the_trace(kind, sampled,
                                                         params, lfm2):
    cfg = _CFG if kind == "paged" else lfm2[kind][0]
    family = step_family(cfg)
    assert family.cache_kind == kind
    if family.greedy_only and sampled:
        # never asked for by the engine (a sampled request fails alone at
        # admission); asked anyway, the family refuses by name
        with pytest.raises(ValueError,
                           match=family.name + " .* decodes greedily"):
            family.programs(cfg, "reference", None, sampled=True)
        return
    table = family.programs(cfg, "reference", None, sampled=sampled)
    assert sorted(table) == ["chained", "mixed", "step"]
    # exactly the cache's arrays are donated: they follow the params
    n = _cache_arrays(kind)
    for fn, donated in table.values():
        assert tuple(donated) == tuple(range(1, n + 1))
    # the device trace names a program by its function: jit__mixed_fn and
    # jit__chained_fn are what mixed_step_ms / decode_step_ms search for
    assert {k: fn.__name__ for k, (fn, _d) in table.items()} == {
        "step": "_step_fn", "mixed": "_mixed_fn", "chained": "_chained_fn"}
    # and the engine registers them under the names the round spans carry
    eng = _engine(kind, params, lfm2,
                  f"t_cb_table_{kind}_{'s' if sampled else 'g'}")
    progs = eng._sampled_programs() if sampled else {
        "step": eng._step, "mixed": eng._mixed, "chained": eng._chained}
    sfx = "_sampled" if sampled else ""
    assert {k: p.program for k, p in progs.items()} == {
        "step": "pw.decode_step" + sfx, "mixed": "pw.mixed_step" + sfx,
        "chained": "pw.chained_decode" + sfx}
    assert len(eng.pool.device_state()) == n


def _lowered_program(eng, attr: str, n_new: int) -> str:
    """The StableHLO of the step program ``eng.<attr>`` as the engine
    calls it: on ``(params, *cache arrays, packed)``."""
    prog, texts = getattr(eng, attr), []

    def lowering(*args):
        if not texts:
            assert len(args) == 2 + len(eng.pool.device_state())
            assert args[-1].dtype == np.int32 and args[-1].ndim == 1
            texts.append(prog.lower(*args).as_text())
        return prog(*args)

    setattr(eng, attr, lowering)
    eng.generate(list(range(1, 12)), n_new)
    return texts[0]


@pytest.mark.parametrize("kind", PAGED_KERNEL_KINDS)
def test_a_chained_program_names_itself_and_its_kernel(kind, params, lfm2):
    """The chained program behind its packed operand: the module is still
    ``jit__chained_fn`` (what ``decode_step_ms`` and the MFUs' decode
    events search ``XLA Modules`` for) and its one kernel a pool pair is
    ``_paged_append_fn``, which the attention rooflines read."""
    eng = _engine(kind, params, lfm2, f"t_cb_chain_{kind}", attn="pallas")
    text = _lowered_program(eng, "_chained", 8)
    assert re.search(r"module @(\w+)", text).group(1) == "jit__chained_fn"
    funcs = re.findall(r"func\.func \w+ @(\w+)\(", text)
    found = [f for f in funcs if f.startswith("_paged_")]
    assert len(found) == (2 if kind == "windowed" else 1)
    assert {re.sub(r"_\d+$", "", f) for f in found} == {"_paged_append_fn"}


@pytest.mark.parametrize("kind", PAGED_KERNEL_KINDS)
def test_a_mixed_program_names_its_paged_kernels(kind, params, lfm2):
    """The device trace names a kernel's events by the jitted function
    that holds the call: ``paged_attn_roofline`` /
    ``paged_attn_gqa_roofline`` read ``^_paged_(append|ragged)_fn`` and
    ``kv_write_ms`` reads the mixed step's K/V writer, which the
    rooflines must not count.  Each is one function of the lowered
    program however many layers call it.  The program itself, behind its
    packed operand, is still ``jit__mixed_fn``: ``mixed_step_ms`` and the
    MFUs find it on ``XLA Modules`` by that name."""
    eng = _engine(kind, params, lfm2, f"t_cb_kernels_{kind}", attn="pallas")
    text = _lowered_program(eng, "_mixed", 2)
    assert re.search(r"module @(\w+)", text).group(1) == "jit__mixed_fn"
    funcs = re.findall(r"func\.func \w+ @(\w+)\(", text)
    found = [f for f in funcs if f.startswith("_paged_")]
    # one function a pool pair: the windowed kind's second pair has shapes
    # (and a window) of its own, and its functions a numbered name that
    # the readers' patterns match as they match the first
    assert len(found) == (4 if kind == "windowed" else 2)
    kernels = sorted({re.sub(r"_\d+$", "", f) for f in found})
    assert kernels == ["_paged_ragged_fn", "_paged_write_fn"]
    assert all(re.match(r"^_paged_(ragged|write)_fn", f) for f in found)
    metrics = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "metrics")
    with open(os.path.join(metrics, "mixed_step_ms.json")) as f:
        assert re.search(json.load(f)["pattern"], "jit__mixed_fn(123)")
    reads = {}
    for metric in ("kv_write_ms", "paged_attn_roofline",
                   "paged_attn_gqa_roofline", "paged_attn_swa_roofline"):
        with open(os.path.join(metrics, metric + ".json")) as f:
            pattern = json.load(f)["pattern"]
        reads[metric] = [k for k in kernels + ["_paged_append_fn"]
                         if re.match(pattern, k + ".7")]
    assert reads == {
        "kv_write_ms": ["_paged_write_fn"],
        "paged_attn_roofline": ["_paged_ragged_fn", "_paged_append_fn"],
        "paged_attn_gqa_roofline": ["_paged_ragged_fn", "_paged_append_fn"],
        "paged_attn_swa_roofline": ["_paged_ragged_fn", "_paged_append_fn"]}


def test_the_verify_program_is_the_familys_mixed_program(params, lfm2):
    eng = _engine("paged", params, lfm2, "t_cb_verify", speculative="ngram")
    assert eng._verify is None
    verify = eng._verify_program()
    assert verify.program == "pw.verify_step"
    assert verify is eng._verify_program()
    assert verify is not eng._mixed
    assert verify._jit.__wrapped__.__name__ == "_mixed_fn"


def _imported_modules(path: str, package: str) -> set[str]:
    """Absolute names of everything ``path`` imports, at any depth of the
    file (module level, functions, methods)."""
    with open(path) as f:
        tree = ast.parse(f.read())
    parts = package.split(".")
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = parts[:len(parts) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            found.add(mod)
            found.update(f"{mod}.{a.name}" for a in node.names)
    return found


def test_the_engine_imports_no_models_math():
    # kvcache/engine.py -> models/families.py -> models/decoder.py |
    # models/lfm2.py | models/afmoe.py: the engine reaches a model's
    # programs through its family and no other way
    import pathway_tpu.kvcache.engine as engine_mod

    mods = _imported_modules(engine_mod.__file__, "pathway_tpu.kvcache")
    assert "pathway_tpu.models.families" in mods
    models = {m for m in mods if m.startswith("pathway_tpu.models")}
    assert not {m for m in models
                if m.split(".")[2] in ("decoder", "lfm2", "afmoe")}, models
    with open(engine_mod.__file__) as f:
        src = f.read()
    assert not any("models." + m in src for m in ("decoder", "lfm2", "afmoe"))
    assert "afmoe" not in src  # no keyword, no branch names the family


# -- the fifth kind: a K/V pool beside the state arena (PR 38) ------------------


def test_kv_state_cache_gives_blocks_and_slot_together_or_not_at_all():
    """``KVStateCache`` is ``StateCache`` with a value pool: four device
    arrays, the bytes of all four counted, and the slot logic it inherits
    (blocks and slot together or neither, both freed, the invariants)."""
    from pathway_tpu.kvcache.hybrid import KVStateCache, StateCache

    assert issubclass(KVStateCache, StateCache)
    assert issubclass(KVStateCache, HybridCache)
    # nothing of the slot logic is written again
    for name in ("allocate", "free_sequence", "preempt", "row_extras",
                 "after_sync", "check_invariants", "device_state",
                 "set_device_state", "fork", "suspend_host"):
        assert name not in vars(KVStateCache), name
    pool = make_backend(
        "kv_state", num_blocks=6, block_size=8, n_layers=3, n_heads=2,
        head_dim=256, dtype=jnp.bfloat16, name="t_cb_kv_state",
        conv_layers=9, conv_width=8192, conv_taps=3, conv_slots=2,
        state_heads=32, state_dk=128, state_dv=128)
    assert type(pool) is KVStateCache and pool.cache_kind == "kv_state"
    assert pool.k.shape == pool.v.shape == (3, 6, 8, 512)
    assert pool.conv.shape == (9, 3, 3, 8192)
    assert pool.state.shape == (9, 3, 32, 128, 128)
    assert pool.state.dtype == jnp.float32
    assert pool.per_shard_bytes == 2 * (2 * 3 * 6 * 8 * 512) \
        + 2 * (9 * 3 * 3 * 8192) + 4 * (9 * 3 * 32 * 128 * 128)
    k, v, conv, state = pool.device_state()
    assert k is pool.k and v is pool.v and state is pool.state
    pool.allocate(1, 20)                      # three blocks, one slot
    with pytest.raises(PoolExhausted):
        pool.allocate(2, 40)                  # five blocks: two are free
    assert pool.slots_in_use == 1 and pool.num_free == 2
    pool.allocate(2, 8)
    with pytest.raises(PoolExhausted):
        pool.allocate(3, 8)                   # a block is free, no slot is
    assert pool.num_free == 1 and sorted(pool._slot_of) == [1, 2]
    pool.check_invariants()
    snap = pool.stats.snapshot()
    assert (snap["blocks_in_use"], snap["state_slots_in_use"],
            snap["conv_slots_in_use"]) == (4, 2, 2)
    with pytest.raises(UnsupportedCacheOp):
        pool.fork(1, 4)
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
    # what a program gives back: four arrays and the counter vector
    pool.set_device_state(k, v, conv, state,
                          jnp.asarray([3, 0, 5, 7, 8, 1, 4, 2], jnp.int32))
    pool.after_sync()
    snap = pool.stats.snapshot()
    assert snap["moe_tokens_per_expert"] == [3, 0, 5]
    # two live row tiles of 64 rows: eight in units of 16
    assert (snap["moe_routed_pairs"], snap["moe_pairs_elsewhere"],
            snap["moe_live_tiles"], snap["moe_experts_touched"],
            snap["moe_expert_passes"], snap["moe_row_tiles"]) \
        == (8, 7, 8, 1, 4, 2)
    pool.retire()


@pytest.mark.parametrize("kind", ("hybrid", "windowed", "latent_state",
                                  "kv_state"))
def test_the_counter_vector_has_one_reader(kind, params, lfm2):
    """Every family that routes hands its cache ONE vector a program:
    tokens per held expert, then ``ops.moe.COUNTER_TAIL``; the caches fold
    it through ``ExpertCounts.fold_expert_counts`` and nowhere else."""
    from pathway_tpu.kvcache.backend import ExpertCounts
    from pathway_tpu.ops.moe import COUNTER_TAIL

    assert COUNTER_TAIL == ("moe_pairs_elsewhere", "moe_live_tiles",
                            "moe_experts_touched", "moe_expert_passes",
                            "moe_row_tiles")
    eng = _engine(kind, params, lfm2, f"t_cb_counters_{kind}")
    assert isinstance(eng.pool, ExpertCounts)
    assert type(eng.pool).after_sync is not ExpertCounts  # the cache's own
    eng.generate_batch([([5, 9, 20, 3, 7, 11, 2, 8, 1, 30], 6), ([41, 2], 4)])
    snap = eng.pool.stats.snapshot()
    cfg = lfm2[kind][0]
    assert len(snap["moe_tokens_per_expert"]) == cfg.n_experts
    assert snap["moe_pairs_elsewhere"] == 0     # every expert is held here
    assert 0 < snap["moe_experts_touched"] <= snap["moe_live_tiles"] \
        <= snap["moe_routed_pairs"] <= 16 * snap["moe_live_tiles"]
    lines = serve_metrics.render_prometheus_lines()
    name = f"t_cb_counters_{kind}"
    assert _gauge(lines, "pathway_kv_moe_live_tiles_total", name) \
        == [float(snap["moe_live_tiles"])]
    # 12 tokens x 2 on 8 experts: every tile here is 16 rows tall
    assert snap["moe_row_tiles"] == snap["moe_live_tiles"]
    assert _gauge(lines, "pathway_kv_moe_row_tiles_total", name) \
        == [float(snap["moe_row_tiles"])]
    assert _gauge(lines, "pathway_kv_moe_experts_touched_total", name) \
        == [float(snap["moe_experts_touched"])]
    # one pass an expert layer and forward pass: no held expert is touched
    # more often than that
    assert snap["moe_experts_touched"] \
        <= cfg.n_experts * snap["moe_expert_passes"]
    assert _gauge(lines, "pathway_kv_moe_expert_passes_total", name) \
        == [float(snap["moe_expert_passes"])]


def test_a_tall_row_tile_counts_its_rows_in_units_of_16(params, lfm2):
    """A chunk of 124 beside four rows: 128 tokens x 2 on 8 experts are 32
    rows an expert, so the mixed steps run row tiles of 64 (each counts 4
    in ``moe_live_tiles``, 1 in ``moe_row_tiles``) and the chains tiles of
    16; the tokens are those of the engine whose tiles are all 16."""
    from pathway_tpu.ops.moe import row_tile

    cfg = lfm2["hybrid"][0]
    assert row_tile(4 + 124, cfg.top_k, cfg.n_experts) == 64
    assert row_tile(4 + 8, cfg.top_k, cfg.n_experts) == 16
    reqs = [(list(range(1, 41)), 6), ([41, 2], 4)]
    tall = _engine("hybrid", params, lfm2, "t_cb_tall_tile",
                   prefill_chunk=124)
    short = _engine("hybrid", params, lfm2, "t_cb_short_tile")
    assert tall.generate_batch(reqs) == short.generate_batch(reqs)
    a, b = tall.pool.stats.snapshot(), short.pool.stats.snapshot()
    assert b["moe_live_tiles"] == b["moe_row_tiles"]
    assert a["moe_row_tiles"] < a["moe_live_tiles"] < 4 * a["moe_row_tiles"]
    assert a["moe_routed_pairs"] <= 16 * a["moe_live_tiles"]
    lines = serve_metrics.render_prometheus_lines()
    assert _gauge(lines, "pathway_kv_moe_row_tiles_total", "t_cb_tall_tile") \
        == [float(a["moe_row_tiles"])]


def test_the_head_256_roofline_reads_the_attention_kernels_alone():
    metrics = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "metrics")
    reads = {}
    for metric in ("paged_attn_hd256_roofline", "gdn_scan_roofline",
                   "qwen3next_expert_roofline"):
        with open(os.path.join(metrics, metric + ".json")) as f:
            pattern = json.load(f)["pattern"]
        reads[metric] = [k for k in (
            "_paged_ragged_fn", "_paged_append_fn", "_paged_write_fn",
            "_kda_chunk_fn", "_kda_step_fn", "_moe_gmm_fn_w13",
            "_moe_gmm_fn_w2", "_paged_latent_fn") if re.match(pattern,
                                                              k + ".7")]
    assert reads == {
        "paged_attn_hd256_roofline": ["_paged_ragged_fn", "_paged_append_fn"],
        "gdn_scan_roofline": ["_kda_chunk_fn", "_kda_step_fn"],
        "qwen3next_expert_roofline": ["_moe_gmm_fn_w13", "_moe_gmm_fn_w2"]}
