"""Paged KV-cache subsystem (kvcache/) — Round-7 acceptance.

Pins the three guarantees ISSUE 2 names:

- token identity: greedy decode through the paged path equals the dense
  batch-1 path for a mixed-length batch of >= 8 sequences (CPU reference
  kernel), including across preemption-with-recompute;
- prefix sharing: a shared-prefix workload records prefix hits and holds
  fewer physical blocks than the sum of per-sequence block needs;
- liveness: pool exhaustion triggers preemption + re-admission and every
  request still completes.

Plus allocator invariants (no double-free, refcounts return to 0, COW
fork preserves parent bytes) and a randomized fuzz of
alloc/extend/fork/free/preempt against BlockPool.check_invariants.
"""

import random
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.kvcache import (
    BlockPool, PagedDecodeEngine, PoolExhausted, PrefixCache,
)
from pathway_tpu.models.decoder import (
    DecoderConfig, decode_step, init_decoder_params, prefill,
)

_CFG = DecoderConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_len=128
)


@pytest.fixture(scope="module")
def params():
    return init_decoder_params(_CFG, jax.random.PRNGKey(0))


def _pool(num_blocks=16, block_size=4, name="test_pool"):
    return BlockPool(
        num_blocks=num_blocks, block_size=block_size, n_layers=2,
        n_heads=2, head_dim=4, name=name,
    )


def _dense_greedy(params, prompt, n_new, bucket=64, cfg=_CFG):
    """Oracle: the dense batch-1 prefill + decode_step path."""
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


# -- allocator invariants ---------------------------------------------------


def test_double_free_raises():
    pool = _pool(name="t_dfree")
    pool.allocate(1, 6)
    pool.free_sequence(1)
    with pytest.raises(KeyError):
        pool.free_sequence(1)
    # manual decref past zero on a returned block is also rejected
    b = pool.allocate(2, 2).block_ids[0]
    pool.free_sequence(2)
    with pytest.raises(ValueError, match="double free"):
        pool.decref(b)


def test_refcounts_return_to_zero_on_release():
    pool = _pool(name="t_refzero")
    a = pool.allocate(1, 10)
    pool.fork(1, 2)
    for b in a.block_ids:
        assert pool.refcount(b) == 2
    pool.free_sequence(2)
    for b in a.block_ids:
        assert pool.refcount(b) == 1
    pool.free_sequence(1)
    for b in a.block_ids:
        assert pool.refcount(b) == 0
    assert pool.blocks_in_use == 0
    assert pool.num_free == pool.num_blocks - 1
    pool.check_invariants()


def test_cow_fork_preserves_parent_bytes():
    pool = _pool(name="t_cow")
    seq = pool.allocate(1, 6)  # blocks 0-1, tail half full
    tail = seq.block_ids[-1]
    marker = jnp.full_like(pool.k[:, tail], 7.5)
    pool.k = pool.k.at[:, tail].set(marker)
    pool.v = pool.v.at[:, tail].set(marker)
    pool.fork(1, 2)
    # child's first append must COW the shared tail, not write into it
    blk, off = pool.append_slot(2)
    assert blk != tail
    assert off == 6 % pool.block_size
    pool.k = pool.k.at[:, blk, off].set(-1.0)
    pool.v = pool.v.at[:, blk, off].set(-1.0)
    assert np.array_equal(np.asarray(pool.k[:, tail]), np.asarray(marker))
    assert np.array_equal(np.asarray(pool.v[:, tail]), np.asarray(marker))
    # COW copied the parent's prefix of the tail block
    assert np.array_equal(
        np.asarray(pool.k[:, blk, :2]), np.asarray(marker[:, :2])
    )
    assert pool.refcount(tail) == 1 and pool.refcount(blk) == 1
    pool.check_invariants()


def test_allocate_rolls_back_on_exhaustion():
    pool = _pool(num_blocks=5, name="t_exhaust")  # 4 usable
    pool.allocate(1, 12)  # 3 blocks
    with pytest.raises(PoolExhausted):
        pool.allocate(2, 12)
    pool.check_invariants()  # no partial allocation leaked
    assert pool.num_free == 1


def test_preempt_order_priority_then_arrival():
    pool = _pool(num_blocks=32, name="t_preempt")
    pool.allocate(1, 4, priority=0)
    pool.allocate(2, 4, priority=2)
    pool.allocate(3, 4, priority=2)
    pool.allocate(4, 4, priority=1)
    # lowest priority class first (highest value), newest arrival within it
    assert pool.preempt().seq_id == 3
    assert pool.preempt().seq_id == 2
    assert pool.preempt(exclude={1}).seq_id == 4
    assert pool.preempt(exclude={1}) is None
    pool.check_invariants()


def test_fuzz_allocator_invariants():
    rng = random.Random(0xC0FFEE)
    pool = _pool(num_blocks=24, block_size=4, name="t_fuzz")
    cache = PrefixCache(pool)
    live: list[int] = []
    next_id = 1
    for step in range(600):
        op = rng.random()
        try:
            if op < 0.35 or not live:
                n = rng.randint(1, 20)
                tokens = [rng.randint(0, 31) for _ in range(n)]
                shared, keys = cache.match(tokens)
                state = pool.allocate(
                    next_id, n, shared_blocks=shared,
                    priority=rng.randint(0, 2),
                )
                if rng.random() < 0.5:
                    cache.insert(keys, state.block_ids)
                live.append(next_id)
                next_id += 1
            elif op < 0.60:
                pool.append_slot(rng.choice(live))
            elif op < 0.72:
                pool.fork(rng.choice(live), next_id)
                live.append(next_id)
                next_id += 1
            elif op < 0.88:
                sid = rng.choice(live)
                live.remove(sid)
                pool.free_sequence(sid)
            elif op < 0.95:
                victim = pool.preempt()
                if victim is not None:
                    live.remove(victim.seq_id)
            else:
                cache.evict(rng.randint(1, 3))
        except PoolExhausted:
            # resolve the way the engine does: evict cached prefix blocks
            # first, preempt a victim second
            if cache.evict(2) == 0:
                victim = pool.preempt()
                if victim is not None:
                    live.remove(victim.seq_id)
        if step % 20 == 0:
            pool.check_invariants(external_refs=cache.external_refs())
    pool.check_invariants(external_refs=cache.external_refs())
    for sid in list(live):
        pool.free_sequence(sid)
    cache.clear()
    pool.check_invariants()
    assert pool.blocks_in_use == 0


# -- prefix cache -----------------------------------------------------------


def test_prefix_chain_position_sensitivity():
    from pathway_tpu.kvcache.prefix_cache import chain_hashes

    a = chain_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
    b = chain_hashes([5, 6, 7, 8, 1, 2, 3, 4], 4)
    assert len(a) == 2 and len(b) == 2
    # same 4-token block at a different depth hashes differently
    assert a[0] != b[1] and a[1] != b[0]
    # partial tail block gets no key
    assert len(chain_hashes([1, 2, 3, 4, 5], 4)) == 1


def test_prefix_sharing_uses_fewer_blocks_than_sum():
    pool = _pool(num_blocks=32, block_size=4, name="t_share")
    cache = PrefixCache(pool)
    header = [9, 9, 9, 9, 8, 8, 8, 8]  # two full blocks of shared prefix
    needs = []
    for i in range(4):
        tokens = header + [i, i + 1, i + 2]
        shared, keys = cache.match(tokens)
        state = pool.allocate(100 + i, len(tokens), shared_blocks=shared)
        cache.insert(keys, state.block_ids)
        needs.append(pool.blocks_for(len(tokens)))
    assert pool.blocks_in_use < sum(needs)  # 6 physical vs 12 naive
    snap = pool.stats.snapshot()
    assert snap["prefix_hits"] > 0
    # all four tables alias the same two physical header blocks
    tables = [pool.sequence(100 + i).block_ids[:2] for i in range(4)]
    assert all(t == tables[0] for t in tables)
    pool.check_invariants(external_refs=cache.external_refs())
    for i in range(4):
        pool.free_sequence(100 + i)
    # cached header blocks survive their sequences until evicted
    assert pool.blocks_in_use == 2
    assert cache.evict(8) == 2
    assert pool.blocks_in_use == 0


def test_prefix_lru_eviction_skips_live_blocks():
    pool = _pool(num_blocks=16, block_size=4, name="t_lru")
    cache = PrefixCache(pool)
    s1 = pool.allocate(1, 4)
    _, keys = cache.match([1, 2, 3, 4])
    cache.insert(keys, s1.block_ids)
    # seq 1 still references its block: only the cache's hold exists after
    # free, and eviction must not fire while the sequence is live
    assert cache.evict(1) == 0
    pool.free_sequence(1)
    assert cache.evict(1) == 1
    assert pool.blocks_in_use == 0


# -- engine: the ISSUE acceptance criteria ----------------------------------


def test_paged_greedy_token_identical_to_dense_mixed_batch(params):
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=96, block_size=8, max_batch_size=4,
        seq_buckets=(16, 32, 64), name="t_identity",
    )
    rng = np.random.default_rng(7)
    lengths = [3, 5, 9, 12, 17, 22, 27, 31]  # mixed, straddling buckets
    prompts = [
        [int(t) for t in rng.integers(0, _CFG.vocab_size, size=n)]
        for n in lengths
    ]
    got = eng.generate_batch([(p, 8) for p in prompts])
    want = [_dense_greedy(params, p, 8) for p in prompts]
    assert got == want


def test_shared_prefix_workload_hits_and_saves_blocks(params):
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=96, block_size=8, max_batch_size=8,
        seq_buckets=(32, 64), name="t_prefixwl",
    )
    header = [11] * 8 + [13] * 8  # two full blocks shared by every prompt
    prompts = [header + [20 + i, 30 + i] for i in range(6)]
    before = eng.pool.stats.snapshot()
    peak = {"blocks": 0}
    orig = eng.pool.allocate

    def tracking_allocate(*a, **kw):
        state = orig(*a, **kw)
        peak["blocks"] = max(peak["blocks"], eng.pool.blocks_in_use)
        return state

    eng.pool.allocate = tracking_allocate
    got = eng.generate_batch([(p, 6) for p in prompts])
    after = eng.pool.stats.snapshot()
    assert after["prefix_hits"] - before["prefix_hits"] > 0
    # fewer physical blocks than sum(seq_blocks): 6 seqs x 3 blocks naive
    naive = sum(eng.pool.blocks_for(len(p) + 6) for p in prompts)
    assert peak["blocks"] < naive
    # sharing must not perturb the tokens
    want = [_dense_greedy(params, p, 6) for p in prompts]
    assert got == want


def test_pool_exhaustion_preempts_and_completes_all(params):
    # 12 usable blocks of 4 = 48 token slots; four 10-token prompts + 10
    # new tokens each (80 slots) cannot coexist -> decode MUST preempt
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=13, block_size=4, max_batch_size=4,
        seq_buckets=(12, 20), prefix_sharing=False, name="t_oom",
    )
    rng = np.random.default_rng(3)
    prompts = [
        [int(t) for t in rng.integers(0, _CFG.vocab_size, size=10)]
        for _ in range(4)
    ]
    before = eng.pool.stats.snapshot()["preemptions"]
    got = eng.generate_batch([(p, 10) for p in prompts])
    assert eng.pool.stats.snapshot()["preemptions"] > before
    assert all(len(o) == 10 for o in got)
    # preemption-with-recompute is token-identical to never being preempted
    want = [_dense_greedy(params, p, 10) for p in prompts]
    assert got == want
    assert eng.pool.blocks_in_use == 0


def test_allocate_zero_tokens_owns_no_blocks():
    pool = _pool(name="t_zero")
    seq = pool.allocate(1, 0)
    assert seq.block_ids == [] and pool.blocks_in_use == 0
    blk, off = pool.append_slot(1)  # first append opens the first block
    assert off == 0 and pool.sequence(1).block_ids == [blk]
    pool.check_invariants()
    pool.free_sequence(1)
    assert pool.blocks_in_use == 0


def test_generate_zero_new_tokens(params):
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=16, block_size=8, max_batch_size=2,
        seq_buckets=(16,), name="t_zeronew",
    )
    # the dense path returns nothing for max_new=0 — so must the engine
    assert eng.generate_batch([([1, 2, 3], 0), ([4, 5], 2)])[0] == []
    assert eng.pool.blocks_in_use == 0


def test_serve_batch_priority_passthrough(params):
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=32, block_size=8, max_batch_size=4,
        seq_buckets=(16,), name="t_prio",
    )
    # a third payload element (submit-time priority class) must survive
    # into the engine, not be silently dropped to NORMAL — including the
    # string form submit() accepts
    out = eng.serve_batch([([1, 2, 3], 3, 2), ([4, 5], 3, "high")])
    assert out == [
        _dense_greedy(params, [1, 2, 3], 3),
        _dense_greedy(params, [4, 5], 3),
    ]


def test_one_bad_request_does_not_poison_batch(params):
    # table allows 5 blocks but the pool only backs 3: a 16-token prompt
    # can never fit, yet the other request's decode must still complete
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=4, block_size=4, max_batch_size=2,
        max_blocks_per_seq=5, seq_buckets=(16,), prefix_sharing=False,
        name="t_poison",
    )
    out = eng.serve_batch([(list(range(16)), 2), ([1, 2, 3], 2)])
    assert isinstance(out[0], RuntimeError) and "cannot hold" in str(out[0])
    assert out[1] == _dense_greedy(params, [1, 2, 3], 2)
    # and the scheduler maps a per-item exception to just that caller
    from pathway_tpu.serve.scheduler import RequestScheduler

    sched = RequestScheduler(
        lambda reqs: eng.serve_batch(reqs), name="t_poison_sched",
        max_batch_size=2, batch_linger_ms=20.0,
    )
    try:
        results = {}

        def submit(key, payload):
            try:
                results[key] = sched.submit(payload)
            except BaseException as exc:  # noqa: BLE001
                results[key] = exc

        ts = [
            threading.Thread(
                target=submit, args=("bad", (list(range(16)), 2))
            ),
            threading.Thread(target=submit, args=("good", ([1, 2, 3], 2))),
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert isinstance(results["bad"], RuntimeError)
        assert results["good"] == _dense_greedy(params, [1, 2, 3], 2)
    finally:
        sched.shutdown()


def test_engine_failure_releases_inflight_waiters(params):
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=32, block_size=8, max_batch_size=2,
        seq_buckets=(16,), name="t_fail",
    )

    def boom(*_a, **_k):
        raise RuntimeError("device exploded")

    eng._step = boom
    eng._chained = boom  # round-10: a quiet queue decodes via the chain
    got = {}
    polled = [(
        ([1, 2, 3], 4), 1,
        lambda r: got.setdefault("done", r),
        lambda e: got.setdefault("err", e),
    )]

    def poll(n):
        items, polled[:] = list(polled), []
        return items

    # the batch-origin caller gets the real error...
    with pytest.raises(RuntimeError, match="device exploded"):
        eng.generate_batch([([4, 5, 6], 4)], poll=poll)
    # ...and so does the poll_inflight-admitted one (instead of hanging
    # its waiter until the scheduler's deadline)
    assert isinstance(got.get("err"), RuntimeError)
    assert eng.pool.blocks_in_use == 0


def test_nonaligned_max_len_buckets(params):
    # cfg.max_len=60 is NOT a multiple of block_size=8: buckets must
    # round DOWN to 56, and a long prompt trims to the bucket
    cfg2 = DecoderConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_len=60,
    )
    params2 = init_decoder_params(cfg2, jax.random.PRNGKey(1))
    eng = PagedDecodeEngine(
        cfg2, params2, num_blocks=32, block_size=8, max_batch_size=2,
        seq_buckets=(64,), prefix_sharing=False, name="t_unaligned",
    )
    assert eng.seq_buckets == [56]
    prompt = [int(t) for t in
              np.random.default_rng(2).integers(0, 64, size=50)]
    got = eng.generate_batch([(prompt, 4)])
    assert got == [_dense_greedy(params2, prompt, 4, bucket=56, cfg=cfg2)]


def test_prompt_longer_than_largest_bucket_is_trimmed(params):
    # table capacity (max_seq_tokens=48) exceeds the largest prefill
    # bucket (16): the prompt must trim to the bucket, not crash admission
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=16, block_size=4, max_batch_size=2,
        seq_buckets=(16,), prefix_sharing=False, name="t_bucketcap",
    )
    prompt = list(np.random.default_rng(9).integers(0, _CFG.vocab_size, 40))
    got = eng.generate_batch([([int(t) for t in prompt], 4)])
    want = [_dense_greedy(params, [int(t) for t in prompt[-16:]], 4)]
    assert got == want
    assert eng.pool.blocks_in_use == 0


def test_single_oversized_request_fails_cleanly(params):
    # max_blocks_per_seq exceeds the pool, so a request the TABLE permits
    # can still never fit physically -> delivered as an error, not a hang
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=4, block_size=4, max_batch_size=2,
        max_blocks_per_seq=5, seq_buckets=(16,), prefix_sharing=False,
        name="t_toobig",
    )
    with pytest.raises(RuntimeError, match="cannot hold"):
        eng.generate_batch([(list(range(16)), 2)])
    assert eng.pool.blocks_in_use == 0


def _decode_case(H, hd, BS, NB, lens):
    """Decode rows (C=1) over a pool in BlockPool's shape, heads fused on
    the minor axis: every row its own blocks, the null block behind."""
    rng = np.random.default_rng(5)
    B = len(lens)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    pool = (1 + B * NB, BS, H * hd)
    k_pool = jnp.asarray(rng.standard_normal(pool), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal(pool), jnp.float32)
    tables = np.zeros((B, NB), np.int32)
    for b, n in enumerate(lens):
        used = -(-n // BS)
        tables[b, :used] = 1 + b * NB + rng.permutation(NB)[:used]
    return q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens, jnp.int32)


# A grid step of the kernels attends a span of 128 keys (eight blocks of
# 16) where a block's lanes are whole tiles.  Tables of 20 blocks are 2.5
# spans: contexts of one token, one block, ending inside a span, at a
# span's edge, and at the table's end; a one-block row beside a full one.
@pytest.mark.parametrize("H,hd,BS,NB,lens", [
    (2, 16, 8, 3, [20, 9, 24]),
    (20, 64, 8, 3, [20, 9, 24]),
    (2, 64, 16, 20, [1, 16, 200, 128, 256, 320]),
    (20, 64, 16, 20, [16, 320, 129]),
    (2, 64, 16, 8, [128, 5]),
    (2, 64, 16, 5, [80, 17, 64]),
], ids=["toy", "gpt2_large_heads", "spans", "spans_gpt2_large_heads",
        "one_span_table", "table_shorter_than_a_span"])
def test_pallas_kernel_matches_reference_interpreted(H, hd, BS, NB, lens):
    """The TPU kernel path (interpret mode on CPU) must agree with the
    gather reference to f32 tolerance: decode rows (C=1) over a pool in
    BlockPool's shape, heads fused on the minor axis."""
    from pathway_tpu.kvcache.paged_attention import (
        paged_attention, paged_attention_reference,
    )

    q, k_pool, v_pool, tables, lens = _decode_case(H, hd, BS, NB, lens)
    want = paged_attention_reference(q, k_pool, v_pool, tables, lens)
    got = paged_attention(
        q, k_pool, v_pool, tables, lens, use_pallas=True, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# -- continuous batching through the serve scheduler ------------------------


def test_continuous_batching_admits_mid_flight(params):
    from pathway_tpu.serve.scheduler import RequestScheduler

    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=96, block_size=8, max_batch_size=4,
        seq_buckets=(16, 32), name="t_cbatch",
    )
    calls = {"n": 0}
    box = {}

    def batch_fn(reqs):
        calls["n"] += 1
        return eng.serve_batch(reqs, scheduler=box["sched"])

    box["sched"] = sched = RequestScheduler(
        batch_fn, name="t_cbatch_sched", max_batch_size=4,
        batch_linger_ms=20.0, max_queue=32,
    )
    try:
        rng = np.random.default_rng(11)
        prompts = [
            [int(t) for t in rng.integers(0, _CFG.vocab_size, size=4 + i)]
            for i in range(8)
        ]
        results = [None] * 8

        def submit(i):
            results[i] = sched.submit((prompts[i], 12))

        threads = [
            threading.Thread(target=submit, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        want = [_dense_greedy(params, p, 12) for p in prompts]
        assert results == want
        # 8 requests, batch cap 4: step-boundary admission folds late
        # arrivals into the in-flight batch instead of a per-request call
        assert calls["n"] <= 4
    finally:
        sched.shutdown()


# -- metrics surface --------------------------------------------------------


def test_kv_metrics_render_prometheus_and_dashboard():
    from pathway_tpu.serve import metrics as M

    pool = _pool(name="t_metrics")
    pool.allocate(1, 8)
    pool.stats.record_prefix_hit(3)
    pool.stats.record_preemption()
    lines = "\n".join(M.render_prometheus_lines())
    assert 'pathway_kv_blocks_in_use{pool="t_metrics"} 2' in lines
    assert 'pathway_kv_prefix_hit_total{pool="t_metrics"} 3' in lines
    assert 'pathway_kv_preemptions_total{pool="t_metrics"} 1' in lines
    points = M.otlp_points("0")
    assert any(
        a == {"key": "pool", "value": {"stringValue": "t_metrics"}}
        for p in points for a in p["attributes"]
    )


def test_concurrent_pools_get_distinct_stats():
    # two live pools under one requested name must not share (and corrupt)
    # a stats block — the second gets a suffixed name
    p1 = _pool(name="t_dup")
    p2 = _pool(name="t_dup")
    assert p1.name != p2.name
    p1.allocate(1, 8)  # 2 blocks
    p2.allocate(1, 4)  # 1 block
    assert p1.stats.blocks_in_use == 2
    assert p2.stats.blocks_in_use == 1


# -- satellites -------------------------------------------------------------


def test_llm_scheduler_sizes_from_paged_engine():
    from pathway_tpu.xpacks.llm.llms import JaxChat
    from pathway_tpu.xpacks.llm import question_answering as qa

    chat = JaxChat(_CFG, max_new_tokens=4)
    rag = qa.BaseRAGQuestionAnswerer.__new__(qa.BaseRAGQuestionAnswerer)
    qa.BaseRAGQuestionAnswerer.__init__(
        rag, chat, indexer=None, llm_scheduler=True
    )
    try:
        # paged batch entry point present -> true batched decode tier
        assert rag._llm_scheduler.max_batch_size > 1
        out = rag._llm_scheduler.submit([{"role": "user", "content": "hi"}])
        assert isinstance(out, str)
    finally:
        rag._llm_scheduler.shutdown()

    class SerialLLM:
        def __call__(self, messages):
            return "ok"

    qa._warned_serial.clear()
    rag2 = qa.BaseRAGQuestionAnswerer.__new__(qa.BaseRAGQuestionAnswerer)
    qa.BaseRAGQuestionAnswerer.__init__(
        rag2, SerialLLM(), indexer=None, llm_scheduler=True
    )
    try:
        assert rag2._llm_scheduler.max_batch_size == 1
        assert "SerialLLM" in qa._warned_serial  # warned, not silent
    finally:
        rag2._llm_scheduler.shutdown()


def test_release_auto_key_cache():
    from pathway_tpu.internals import value as V

    keys = V.auto_row_keys(32)
    assert len(keys) == 32
    released = V.release_auto_key_cache()
    assert released >= 32
    # existing keys stay valid; the next build recomputes identically
    assert V.auto_row_keys(32) == keys
    assert V.release_auto_key_cache() >= 32
