"""Ragged fused-step paged decode (Round-8) — ISSUE 3 acceptance.

Pins the four tentpole guarantees:

- chunked-prefill token identity: greedy output through block-aligned
  chunk streaming is identical to the dense batch-1 path AND to the
  Round-7 whole-bucket prefill path — for mixed-length batches, prompts
  that are not chunk-aligned (partial tail chunk), shared prefixes
  (including same-round lockstep sharing), and across
  preemption-with-recompute;
- fused mixed step: same-round arrivals ride ONE dispatch (their first
  tokens all come from that dispatch's device-side argmax);
- device-side sampling: the jitted step returns [B] int32 ids, not
  [B, vocab] logits;
- recompile guard: a bucket-ladder workload compiles the step programs
  once — the second pass triggers ZERO new XLA compilations
  (jax_log_compiles capture), catching accidental shape polymorphism.

Plus the paged-attention ``context >= 1`` contract (fail loudly instead
of NaNs) and the Round-8 metrics surface (prefill chunks, mixed-step
occupancy, TTFT histogram).
"""

import importlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.kvcache import BlockPool, PagedDecodeEngine
from pathway_tpu.models.decoder import (
    DecoderConfig, decode_step, init_decoder_params, paged_mixed_step,
    prefill,
)

_CFG = DecoderConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_len=128
)


@pytest.fixture(scope="module")
def params():
    return init_decoder_params(_CFG, jax.random.PRNGKey(0))


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


# -- chunked-prefill token identity -----------------------------------------


def test_chunked_identity_mixed_lengths_and_partial_tail(params):
    # chunk=8 over block_size 4: lengths 3..31 cover prompts shorter than
    # one chunk, exact multiples, and partial tail chunks (11, 17, 27)
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=96, block_size=4, max_batch_size=4,
        seq_buckets=(16, 32, 64), prefill_chunk=8, name="t_r8_identity",
    )
    assert eng.prefill_chunk == 8
    rng = np.random.default_rng(7)
    lengths = [3, 5, 8, 11, 16, 17, 27, 31]
    prompts = [
        [int(t) for t in rng.integers(0, _CFG.vocab_size, size=n)]
        for n in lengths
    ]
    got = eng.generate_batch([(p, 8) for p in prompts])
    want = [_dense_greedy(params, p, 8) for p in prompts]
    assert got == want
    # only the prefix cache's own holds survive the batch
    eng.prefix.clear()
    assert eng.pool.blocks_in_use == 0
    # the prompts really were streamed chunkwise, not whole-bucket
    assert eng.pool.stats.snapshot()["prefill_chunks"] >= sum(
        -(-n // 8) for n in lengths if n > 8
    )


def test_chunked_matches_dense_at_the_default_chunk(params):
    # the engine's own chunk width (two blocks of 8)
    rng = np.random.default_rng(13)
    prompts = [
        [int(t) for t in rng.integers(0, _CFG.vocab_size, size=n)]
        for n in (6, 13, 21, 30)
    ]
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=96, block_size=8, max_batch_size=4,
        seq_buckets=(16, 32, 64), name="t_r8_cmp",
    )
    assert eng.prefill_chunk == 16
    out = eng.generate_batch([(p, 6) for p in prompts])
    assert out == [_dense_greedy(params, p, 6) for p in prompts]


@pytest.mark.parametrize("chunk", [None, 32, 64])
def test_chunk_width_regroups_positions_and_is_counted(params, chunk):
    """A chunk only regroups a prompt's positions: served at the engine's
    own two blocks (no budget, no roof on the CPU), at 32 and at the
    widest rung of the chooser's ladder that the prompt cap (64) allows,
    the greedy tokens are the dense path's; and the mixed rounds'
    ``pw.round.build`` spans count the rows that carried a chunk and
    their tokens, which sum to the prompts' tokens."""
    from pathway_tpu import obs
    from pathway_tpu.obs import memory as obs_memory

    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=96, block_size=8, max_batch_size=4,
        seq_buckets=(16, 32, 64), prefix_sharing=False,
        prefill_chunk=chunk, name=f"t_r34_chunk_{chunk}",
    )
    if chunk is None:
        assert eng.prefill_chunk == 16
        assert "prefill_chunk" in eng.auto_config["chosen"]
        assert eng.auto_config["chunk_source"].startswith("default")
    else:
        assert chunk in obs_memory._CHUNK_LADDER
        assert eng.prefill_chunk == chunk <= eng.seq_buckets[-1]
        assert "prefill_chunk" not in eng.auto_config["chosen"]
    assert eng.auto_config["prefill_chunk"] == eng.prefill_chunk
    assert eng.mixed_tokens == eng.max_batch_size + eng.prefill_chunk
    rng = np.random.default_rng(34)
    lengths = [5, 17, 33, 40, 61, 64]
    prompts = [[int(t) for t in rng.integers(0, _CFG.vocab_size, size=n)]
               for n in lengths]
    rec = obs.recorder()
    n0 = rec.n_recorded
    got = eng.generate_batch([(p, 5) for p in prompts])
    assert got == [_dense_greedy(params, p, 5) for p in prompts]
    assert rec.n_recorded - n0 < rec.capacity  # nothing of it evicted
    builds = [s.attrs for s in rec.snapshot()
              if s.name == "pw.round.build" and s.trace_id == eng._run_ctx[0]
              and s.attrs.get("kind") == "mixed"]
    assert builds and all(
        0 < a["chunk_rows"] <= a["rows"]
        and a["tokens"] == a["chunk_tokens"] + a["rows"] - a["chunk_rows"]
        and a["chunk_tokens"] <= a["chunk_rows"] * eng.prefill_chunk
        for a in builds)
    assert sum(a["chunk_tokens"] for a in builds) == sum(lengths)
    assert sum(a["chunk_rows"] for a in builds) >= sum(
        -(-n // eng.prefill_chunk) for n in lengths)


@pytest.mark.parametrize("attn", ["reference", "pallas"])
def test_chunked_identity_under_shared_prefixes_same_round(params, attn):
    # every prompt shares a two-block header and ALL are admitted in the
    # same round: later arrivals must map the first writer's IN-FLIGHT
    # blocks (lockstep gate) — physical sharing from round one, token
    # output untouched.  One prompt equals the header exactly (the
    # fully-shared case recomputes only its final token).  ``pallas``: the
    # programs the chip runs, interpreted - a reader attends what the
    # writer kernel put into the pool in the SAME dispatch
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=96, block_size=8, max_batch_size=8,
        seq_buckets=(32, 64), prefill_chunk=16, attn=attn,
        name=f"t_r8_prefix_{attn}",
    )
    header = [11] * 8 + [13] * 8
    prompts = [header + [20 + i, 30 + i] for i in range(5)] + [list(header)]
    peak = {"blocks": 0}
    orig_mixed = eng._mixed

    def tracking_mixed(*a, **k):
        peak["blocks"] = max(peak["blocks"], eng.pool.blocks_in_use)
        return orig_mixed(*a, **k)

    eng._mixed = tracking_mixed
    got = eng.generate_batch([(p, 6) for p in prompts])
    want = [_dense_greedy(params, p, 6) for p in prompts]
    assert got == want
    snap = eng.pool.stats.snapshot()
    assert snap["prefix_hits"] > 0
    naive = sum(eng.pool.blocks_for(len(p) + 6) for p in prompts)
    assert peak["blocks"] < naive


def test_chunked_identity_across_preemption(params):
    # 12 usable blocks of 4 = 48 slots; four 10-token prompts + 10 new
    # tokens each (80 slots) cannot coexist -> decode MUST preempt, and
    # recompute re-streams the victim's chunks
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=13, block_size=4, max_batch_size=4,
        seq_buckets=(12, 20), prefix_sharing=False, prefill_chunk=8,
        name="t_r8_oom",
    )
    rng = np.random.default_rng(3)
    prompts = [
        [int(t) for t in rng.integers(0, _CFG.vocab_size, size=10)]
        for _ in range(4)
    ]
    before = eng.pool.stats.snapshot()["preemptions"]
    got = eng.generate_batch([(p, 10) for p in prompts])
    assert eng.pool.stats.snapshot()["preemptions"] > before
    assert got == [_dense_greedy(params, p, 10) for p in prompts]
    assert eng.pool.blocks_in_use == 0


def test_mid_prefill_failure_fails_cleanly(params):
    # the chunked analog of the legacy prefill-failure test: a mixed-step
    # device failure mid-prefill must fail the batch loudly AND free the
    # admitted sequence's blocks (it IS in `running`, unlike the legacy
    # admission-prefill case)
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=16, block_size=8, max_batch_size=2,
        seq_buckets=(16,), name="t_r8_fail",
    )

    def boom(*_a, **_k):
        raise RuntimeError("mixed step exploded")

    eng._mixed = boom
    with pytest.raises(RuntimeError, match="mixed step exploded"):
        eng.generate_batch([([1, 2, 3], 4)])
    assert eng.pool.blocks_in_use == 0
    assert not eng._inflight_prefix


def test_cascade_preempt_judges_by_writer_progress(params):
    """A sharer starts with n_filled == n_diverted (chunking begins after
    the shared region) yet has READ nothing until its first chunk runs —
    safety on writer preemption must be judged by the WRITER's progress:
    requeue the sharer when the writer had not written past the shared
    region, keep it when it had."""
    from collections import deque

    from pathway_tpu.kvcache.engine import _Active, _Request

    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=32, block_size=8, max_batch_size=4,
        seq_buckets=(64,), name="t_r8_cascade",
    )
    pool = eng.pool

    def make_pair(w_seq, s_seq, writer_filled):
        wreq = _Request([1] * 40, 4)
        w = _Active(w_seq, wreq)
        pool.allocate(w_seq, 40)
        w.tokens = list(wreq.prompt)
        w.n_filled = writer_filled
        sreq = _Request([1] * 32 + [2, 3], 4)
        s = _Active(s_seq, sreq)
        pool.allocate(
            s_seq, 34,
            shared_blocks=pool.sequence(w_seq).block_ids[:4],
        )
        s.tokens = list(sreq.prompt)
        s.n_filled = s.n_diverted = 32  # admission state: nothing read yet
        s.wait_writer = w
        return w, s, sreq

    # writer preempted having written only 16 of the 32 shared tokens:
    # the sharer MUST be requeued (its future chunks would attend
    # through never-written K/V)
    w, s, sreq = make_pair(1, 2, writer_filled=16)
    running, pending = [s], deque()
    pool.free_sequence(1)  # what pool.preempt() does to the victim
    eng._cascade_preempt([w], running, pending)
    assert running == [] and list(pending) == [sreq]
    assert pool.blocks_in_use == 0

    # writer preempted AFTER writing past the shared region: the sharer
    # keeps running (its refs keep the fully-written blocks alive)
    w2, s2, _ = make_pair(3, 4, writer_filled=40)
    running2, pending2 = [s2], deque()
    pool.free_sequence(3)
    eng._cascade_preempt([w2], running2, pending2)
    assert running2 == [s2] and not pending2
    assert s2.wait_writer is None
    pool.free_sequence(4)
    assert pool.blocks_in_use == 0


# -- fused mixed step / device-side sampling --------------------------------


def test_same_round_arrivals_share_one_dispatch(params):
    # N same-round admissions with prompts <= one chunk finish their
    # prefill in ONE mixed dispatch; first tokens come from that
    # dispatch's device-side argmax — 1 dispatch, not N (the Round-7
    # path ran one whole-bucket prefill per admission)
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=64, block_size=8, max_batch_size=4,
        seq_buckets=(16, 32), prefix_sharing=False, prefill_chunk=16,
        name="t_r8_oneshot",
    )
    rng = np.random.default_rng(5)
    # 4+6+8 = 18 tokens fits one mixed_tokens budget (B=4 + chunk 16)
    prompts = [
        [int(t) for t in rng.integers(0, _CFG.vocab_size, size=n)]
        for n in (4, 6, 8)
    ]
    assert sum(len(p) for p in prompts) <= eng.mixed_tokens
    before = eng.pool.stats.snapshot()
    got = eng.generate_batch([(p, 1) for p in prompts])
    after = eng.pool.stats.snapshot()
    assert after["mixed_steps"] - before["mixed_steps"] == 1
    assert after["prefill_chunks"] - before["prefill_chunks"] == 3
    assert got == [_dense_greedy(params, p, 1) for p in prompts]


def test_device_side_sampling_returns_ids_not_logits(params):
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=32, block_size=8, max_batch_size=2,
        seq_buckets=(16,), name="t_r8_ids",
    )
    seen = []
    for attr in ("_step", "_mixed"):
        orig = getattr(eng, attr)

        def spy(*a, _orig=orig, _attr=attr):
            out = _orig(*a)
            seen.append((_attr, out[0].shape, out[0].dtype))
            return out

        setattr(eng, attr, spy)
    eng.generate_batch([([1, 2, 3], 3)])
    assert seen, "no step dispatched"
    for _attr, shape, dtype in seen:
        # [B] int32 ids cross the boundary — not [B, vocab] f32 logits
        assert shape == (eng.max_batch_size,)
        assert dtype == jnp.int32


def test_mixed_step_chunk_stream_matches_dense_prefill(params):
    """Unit-level: streaming one prompt through packed paged_mixed_step
    runs reproduces dense prefill's next-token logits (allclose — the
    engine tests pin argmax identity)."""
    pool = BlockPool(
        num_blocks=16, block_size=4, n_layers=_CFG.n_layers,
        n_heads=_CFG.n_heads, head_dim=_CFG.d_model // _CFG.n_heads,
        name="t_r8_unit",
    )
    prompt = [5, 9, 20, 3, 7, 41, 2, 8, 30, 12, 1]  # 11 tokens: tail run
    n = len(prompt)
    seq = pool.allocate(1, n)
    C = 4  # packed stream width: padding tokens ride the null block
    logits = None
    for s in range(0, n, C):
        e = min(s + C, n)
        nv = e - s
        tokens = np.zeros(C, np.int32)
        tokens[:nv] = prompt[s:e]
        positions = np.zeros(C, np.int32)
        pos = np.arange(s, e)
        positions[:nv] = pos
        sb = np.zeros(C, np.int32)
        so = np.zeros(C, np.int32)
        sb[:nv] = np.asarray(seq.block_ids, np.int32)[pos // 4]
        so[:nv] = pos % 4
        row_tables = np.zeros((1, 8), np.int32)
        row_tables[0, : len(seq.block_ids)] = seq.block_ids
        row_token_idx = np.full((1, C), nv - 1, np.int32)
        row_token_idx[0, :nv] = np.arange(nv)
        tok_col = np.zeros(C, np.int32)
        tok_col[:nv] = np.arange(nv)
        logits, pool.k, pool.v = paged_mixed_step(
            params, _CFG, pool.k, pool.v, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(row_tables),
            jnp.asarray([s], jnp.int32), jnp.asarray([nv], jnp.int32),
            jnp.asarray(row_token_idx),
            jnp.zeros(C, jnp.int32), jnp.asarray(tok_col),
            jnp.asarray(sb), jnp.asarray(so),
            jnp.asarray([nv - 1], jnp.int32),
        )
    buf = np.zeros((1, 12), np.int32)
    buf[0, :n] = prompt
    want, _cache = prefill(
        params, _CFG, jnp.asarray(buf), jnp.asarray([n], jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(logits[0]), np.asarray(want[0]), rtol=2e-4, atol=2e-4
    )


# -- recompile guard ---------------------------------------------------------


def test_second_pass_triggers_zero_recompiles(params):
    """Run a full bucket-ladder workload twice; the second pass must not
    compile ANYTHING — the ragged step's static (B, chunk) shape is the
    whole point, and an accidental shape-polymorphic input would show up
    here as a per-length compile.  Round-14: the guard reads the device
    cost observatory's program registry instead of capturing
    jax_log_compiles log strings, so a failure names the offending
    program with its triggering shapes and stack (CompileWatch)."""
    from .utils import CompileWatch

    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=96, block_size=8, max_batch_size=4,
        seq_buckets=(16, 32, 64), name="t_r8_compile",
    )
    rng = np.random.default_rng(23)
    # straddle every bucket, mix chunk-aligned and partial-tail lengths
    reqs = [
        ([int(t) for t in rng.integers(0, _CFG.vocab_size, size=n)], 5)
        for n in (3, 9, 15, 16, 21, 33, 40, 60)
    ]
    watch = CompileWatch()
    eng.generate_batch(list(reqs))
    first = watch.events()
    assert first, "registry saw no compiles on the cold pass"
    # chunked mode's contract: the whole bucket ladder compiles only the
    # engine's static step programs — never a per-length prefill
    progs = {e.program for e in first}
    assert "pw.mixed_step" in progs, progs
    assert progs <= {"pw.mixed_step", "pw.decode_step",
                     "pw.chained_decode"}, progs
    eng.generate_batch(list(reqs))
    watch.assert_no_compiles("second pass")


# -- paged-attention context contract ----------------------------------------


def test_zero_length_context_fails_loudly():
    from pathway_tpu.kvcache.paged_attention import (
        paged_attention, paged_attention_reference,
    )

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 1, 2, 4)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((4, 4, 2 * 4)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((4, 4, 2 * 4)), jnp.float32)
    bt = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    with pytest.raises(ValueError, match="context_lens >= n_queries"):
        paged_attention_reference(q, kp, vp, bt, jnp.asarray([0, 3]))
    with pytest.raises(ValueError, match="n_valid >= 1"):
        paged_attention_reference(
            q, kp, vp, bt, start_pos=jnp.asarray([0, 0]),
            n_valid=jnp.asarray([1, 0]),
        )
    with pytest.raises(ValueError, match="start_pos >= 0"):
        paged_attention(
            q, kp, vp, bt, start_pos=jnp.asarray([-1, 0]),
            n_valid=jnp.asarray([1, 1]), use_pallas=False,
        )
    # the satisfied contract passes and yields finite output
    out = paged_attention_reference(q, kp, vp, bt, jnp.asarray([1, 3]))
    assert np.isfinite(np.asarray(out)).all()


# The last three: spans of 128 keys (eight blocks of 16) over tables of 20
# blocks (2.5 spans) - chunks that end inside a span, exactly at a span's
# edge and at the table's end, a one-token row beside them, rows whose
# valid queries stop short of the chunk (``n_valid`` < C).
@pytest.mark.parametrize(
    "C,H,hd,BS,NB,sp,nv",
    [(4, 2, 16, 8, 4, [17, 4, 0], [4, 2, 1]),
     (32, 20, 64, 8, 4, [0, 5, 31], [32, 11, 1]),
     (4, 2, 64, 16, 20, [126, 0, 250, 316, 124], [4, 1, 3, 4, 4]),
     (32, 2, 64, 16, 20, [96, 288, 0, 130], [32, 32, 7, 1]),
     (32, 20, 64, 16, 20, [96, 300], [32, 11])],
    ids=["toy", "gpt2_large_chunk", "spans", "spans_chunk",
         "spans_gpt2_large_chunk"],
)
def test_ragged_kernel_matches_reference_interpreted(C, H, hd, BS, NB, sp,
                                                     nv):
    """The length-aware multi-query kernel (interpret mode on CPU) must
    agree with the gather reference on every VALID query column, over a
    pool in BlockPool's shape (heads fused on the minor axis)."""
    from pathway_tpu.kvcache.paged_attention import (
        paged_attention, paged_attention_reference,
    )

    rng = np.random.default_rng(5)
    B = len(sp)
    q = jnp.asarray(rng.standard_normal((B, C, H, hd)), jnp.float32)
    pool = (1 + B * NB, BS, H * hd)
    k_pool = jnp.asarray(rng.standard_normal(pool), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal(pool), jnp.float32)
    # every row its own blocks as far as its context reaches, the null
    # block behind; ragged: a full chunk deep in its sequence or at its
    # start, a partial tail chunk, and a 1-token decode-style row
    tables = np.zeros((B, NB), np.int32)
    for b in range(B):
        used = -(-(sp[b] + nv[b]) // BS)
        tables[b, :used] = 1 + b * NB + rng.permutation(NB)[:used]
    tables = jnp.asarray(tables)
    sp = jnp.asarray(sp, jnp.int32)
    nv = jnp.asarray(nv, jnp.int32)
    want = paged_attention_reference(
        q, k_pool, v_pool, tables, start_pos=sp, n_valid=nv
    )
    got = paged_attention(
        q, k_pool, v_pool, tables, start_pos=sp, n_valid=nv,
        use_pallas=True, interpret=True,
    )
    for b in range(B):
        for c in range(int(nv[b])):
            np.testing.assert_allclose(
                np.asarray(got)[b, c], np.asarray(want)[b, c],
                rtol=2e-5, atol=2e-5,
            )


# -- the query-column tiles (PR 37) -------------------------------------------
# A row of the ragged kernels pays for its live query columns: its rows lie
# in column tiles, of which the live ones run (``_col_tiles``,
# ``_live_tiles``).  The four cells' geometries scaled down, ONE batch a
# case: an idle row (context 1 on the null block) at the head and in the
# middle, a decode row deep in its sequence, a chunk's short tail inside
# the first tile, a row whose live columns end inside a wide tile, one a
# column past a wide tile's edge, and one that fills the chunk.
_IDLE = (0, 1)  # start, valid columns; its table is the null block alone

_TILE_CASES = {
    # C, query heads, head_dim, rep, window, dtype, (first, tile) folded
    "gpt2_g2_rep1": (256, 4, 64, 1, None, "float32", (8, 128)),
    "gpt2_g2_rep1_bf16": (256, 2, 64, 1, None, "bfloat16", (8, 128)),
    "lfm2_g2_rep4": (64, 16, 64, 4, None, "float32", (8, 128)),
    "trinity_g1_rep8_window": (64, 8, 128, 8, 24, "float32", (8, 256)),
    "trinity_g1_rep8_bf16": (64, 8, 128, 8, None, "bfloat16", (16, 256)),
}


def _tile_rows(C, rep, first, tile):
    """(start, valid columns) a row, in query columns."""
    first, tile = max(first // rep, 1), tile // rep
    return [_IDLE, (200, 1), (40, first), (96, tile - 3), _IDLE,
            (130, tile + 1), (30, C)]


def _paged_case(rng, rows, C, H, hd, lanes, dtype, BS=16, NB=20):
    B = len(rows)
    q = jnp.asarray(rng.standard_normal((B, C, H, hd)), dtype)
    tables = np.zeros((B, NB), np.int32)
    for b, (start, n) in enumerate(rows):
        if (start, n) != _IDLE:
            used = -(-(start + n) // BS)
            tables[b, :used] = 1 + b * NB + rng.permutation(NB)[:used]
    pool = jnp.asarray(rng.standard_normal((1 + B * NB, BS, lanes)), dtype)
    return q, pool, jnp.asarray(tables), \
        jnp.asarray([r[0] for r in rows], jnp.int32), \
        jnp.asarray([r[1] for r in rows], jnp.int32)


def _assert_live_columns(got, want, rows, tile_cols, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()  # the padding columns too
    for b, (_start, n) in enumerate(rows):
        np.testing.assert_allclose(got[b, :n], want[b, :n], rtol=tol,
                                   atol=tol)
        # past the row's live tiles nothing was computed: zeros
        assert not got[b, int(tile_cols[b]):].any()


@pytest.mark.parametrize("case", list(_TILE_CASES))
def test_tiled_ragged_kernel_matches_reference_interpreted(case):
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    C, H, hd, rep, window, dtype, tiles = _TILE_CASES[case]
    dtype = jnp.dtype(dtype)
    lanes = H // rep * hd
    G = pa._heads_per_group(H // rep, hd, C * rep)
    assert pa._col_tiles(G, C * rep, rep, dtype) == tiles
    rows = _tile_rows(C, rep, *tiles)
    rng = np.random.default_rng(11)
    q, k_pool, tables, sp, nv = _paged_case(rng, rows, C, H, hd, lanes, dtype)
    v_pool = jnp.asarray(rng.standard_normal(k_pool.shape), dtype)
    kw = {} if window is None else {"window": window}
    want = pa.paged_attention_reference(
        q, k_pool, v_pool, tables, start_pos=sp, n_valid=nv, **kw)
    got = pa.paged_attention(
        q, k_pool, v_pool, tables, start_pos=sp, n_valid=nv,
        use_pallas=True, interpret=True, **kw)
    cols = pa.query_tile_columns(np.asarray(nv), C, H, hd, lanes, dtype)
    assert [int(c) for c in cols] == [
        tiles[0] // rep if n * rep <= tiles[0]
        else -(-n * rep // tiles[1]) * tiles[1] // rep for _s, n in rows]
    _assert_live_columns(got, want, rows, cols,
                         3e-2 if dtype == jnp.bfloat16 else 2e-5)


# A verify round of speculative decoding is the mixed program at ``k + 1``
# query columns (5 at the default k): folded widths of 40 (Trinity), 20
# (LFM2), 5 / 12 / 24 (GPT-2), which are no whole sublane tiles of bf16 and
# seldom of f32.  Such a row is ONE tile (the interpreter does not refuse a
# read past the block; the chip's compiler does, tests/test_chip_compile.py).
_VERIFY_CASES = {
    # query columns, query heads, head_dim, rep, dtype, (first, tile) | None
    "trinity_k4_bf16": (5, 8, 128, 8, "bfloat16", None),
    "trinity_k4_f32": (5, 8, 128, 8, "float32", (8, 40)),
    "lfm2_k4_bf16": (5, 8, 64, 4, "bfloat16", None),
    "lfm2_k4_f32": (5, 8, 64, 4, "float32", None),
    "gpt2_k4_bf16": (5, 4, 64, 1, "bfloat16", None),
    "gpt2_k11_bf16": (12, 4, 64, 1, "bfloat16", None),
    "gpt2_k23_bf16": (24, 4, 64, 1, "bfloat16", None),
    "gpt2_k23_f32": (24, 4, 64, 1, "float32", (8, 24)),
}


@pytest.mark.parametrize("case", list(_VERIFY_CASES))
def test_ragged_kernel_matches_reference_at_verify_widths(case):
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    C, H, hd, rep, dtype, tiles = _VERIFY_CASES[case]
    dtype = jnp.dtype(dtype)
    lanes = H // rep * hd
    G = pa._heads_per_group(H // rep, hd, C * rep)
    assert pa._col_tiles(G, C * rep, rep, dtype) == tiles
    # a verify round's rows: idle, one proposal accepted so far, a row deep
    # in its sequence with all k proposals, partial rows
    rows = [_IDLE, (200, 1), (40, C), (96, 2), _IDLE, (130, C - 1), (3, C)]
    rng = np.random.default_rng(13)
    q, k_pool, tables, sp, nv = _paged_case(rng, rows, C, H, hd, lanes, dtype)
    v_pool = jnp.asarray(rng.standard_normal(k_pool.shape), dtype)
    want = pa.paged_attention_reference(
        q, k_pool, v_pool, tables, start_pos=sp, n_valid=nv)
    got = pa.paged_attention(
        q, k_pool, v_pool, tables, start_pos=sp, n_valid=nv,
        use_pallas=True, interpret=True)
    cols = pa.query_tile_columns(np.asarray(nv), C, H, hd, lanes, dtype)
    assert (cols >= np.asarray(nv)).all() and (cols <= C).all()
    _assert_live_columns(got, want, rows, cols,
                         3e-2 if dtype == jnp.bfloat16 else 2e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "G,rep", [(2, 1), (2, 4), (1, 8), (1, 32), (5, 1)],
    ids=["g2_rep1", "g2_rep4", "g1_rep8", "g1_rep32", "tp_shard_g5"])
def test_col_tiles_keep_every_read_inside_the_block(G, rep, dtype):
    """The rule of shapes over every width up to a chunk of 640 query
    columns: where it tiles a row, every slice the kernels take lies inside
    its block and starts where ``pl.multiple_of`` says it does."""
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    dtype = jnp.dtype(dtype)
    sub = pa._sublanes(dtype)
    tiled = 0
    for C in range(rep, 640 * rep + 1, rep):
        tiles = pa._col_tiles(G, C, rep, dtype)
        if C == rep:
            assert tiles is None  # one query column a row: the decode path
        if tiles is None:
            continue
        tiled += 1
        first, tile = tiles
        assert first < tile <= C and G * tile <= max(256, G * first * 2)
        for Tc in (first, tile):
            # the queries come in whole sublane tiles, inside the block
            assert -(-Tc // sub) * sub <= C and Tc % rep == 0
            # a tile's rows: whole f32 and query-dtype sublane tiles, at a
            # multiple of their number; its columns divide the row
            assert (G * Tc) % sub == 0 and Tc % 8 == 0 and C % Tc == 0
        assert tile % sub == 0 or tile == C and C % sub == 0
    assert tiled


@pytest.mark.parametrize("C", [64, 128], ids=["one_piece", "two_pieces"])
def test_tiled_latent_kernel_matches_reference_interpreted(C):
    """The latent form: one stored row of 128 lanes is key and value, the
    eight query heads folded on it (G = 1); a chunk of 128 goes in two
    pieces of 64 query columns, each a kernel row with tiles of its own."""
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    H, W, tiles = 8, 128, (8, 256)
    piece = C // pa._latent_pieces(C)
    assert pa._col_tiles(1, piece * H, H, jnp.float32) == tiles
    rows = [_IDLE, (200, 1), (40, 1), (96, piece - 3), _IDLE,
            (130, piece // 2 + 1), (30, C)]
    rng = np.random.default_rng(12)
    q, pool, tables, sp, nv = _paged_case(rng, rows, C, H, W, W, jnp.float32)
    want = pa.latent_attention_reference(
        q, pool, tables, start_pos=sp, n_valid=nv, scale=0.09)
    got = pa.latent_attention(
        q, pool, tables, start_pos=sp, n_valid=nv, scale=0.09,
        use_pallas=True, interpret=True)
    _assert_live_columns(got, want, rows, [C] * len(rows), 2e-5)
    # a row's pieces: its live columns' tiles, one first tile a dead piece
    cols = pa.query_tile_columns(np.asarray(nv), C, H, W, W, jnp.float32,
                                 latent=True)
    dead = (C // piece - 1) * (tiles[0] // H)
    assert [int(c) for c in cols][:3] == [1 + dead] * 3
    assert int(cols[-1]) == C


@pytest.mark.parametrize(
    "C,H,hd,D,dtype,latent,at",
    [(256, 20, 64, 1280, "bfloat16", False, {1: 8, 8: 8, 9: 128, 133: 256}),
     (512, 32, 64, 512, "bfloat16", False, {1: 2, 2: 2, 3: 32, 214: 224}),
     (256, 32, 128, 512, "bfloat16", False, {1: 2, 3: 32, 33: 64}),
     (512, 32, 640, 640, "bfloat16", True,
      {1: 8, 2: 15, 64: 71, 65: 71, 512: 512}),
     (32, 2, 64, 128, "float32", False, {1: 8, 9: 32}),
     (1, 20, 64, 1280, "bfloat16", False, {1: 1}),
     (1, 32, 64, 512, "bfloat16", False, {1: 1}),
     (40, 8, 128, 128, "float32", False, {1: 40, 40: 40})],
    ids=["gpt2_large_256", "lfm2_512", "trinity_256", "kimi_512", "toy_f32",
         "decode_row", "decode_row_folded", "width_no_tile_divides"],
)
def test_query_tile_columns_walks_the_kernels_tiles(C, H, hd, D, dtype,
                                                    latent, at):
    """``query_tile_columns`` (the engine's ``kv_query_tile_cols``) at the
    cells' published geometries: never under the live columns, never over
    the chunk, the whole chunk for a full row, and the readings a walk of
    ``_col_tiles`` by hand gives."""
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    ns = np.arange(1, C + 1)
    got = pa.query_tile_columns(ns, C, H, hd, D, jnp.dtype(dtype),
                                latent=latent)
    assert (got >= ns).all() and (got <= C).all() and int(got[-1]) == C
    assert (np.diff(got) >= 0).all()
    assert {n: int(got[n - 1]) for n in at} == at


@pytest.mark.parametrize("rep,hd", [(1, 64), (4, 64), (8, 128)],
                         ids=["g2_rep1", "g2_rep4", "g1_rep8"])
def test_tiled_kernel_does_not_grow_with_the_tiles(rep, hd):
    """The live tiles run in ONE loop: the kernel of a chunk four times as
    wide (four times the tiles) traces to as many equations."""
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")

    def n_eqns(C):
        def count(jaxpr):
            return sum(1 + sum(count(j) for j in jax.core.jaxprs_in_params(
                e.params)) for e in jaxpr.eqns)

        def S(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype)

        pool = S((1, 41, 16, 2 * hd), jnp.bfloat16)
        closed = jax.make_jaxpr(
            lambda *a: pa._paged_ragged_fn(*a, d_true=hd))(
            S((4, C, 2 * rep, hd), jnp.bfloat16), pool, pool, S((1,)),
            S((4, 20)), S((4,)), S((4,)))
        return count(closed.jaxpr)

    assert pa._col_tiles(128 // hd, 256 * rep, rep, jnp.bfloat16) is not None
    assert n_eqns(256) == n_eqns(1024)


# What the kernels at ONE query column a row (the decode step, the chains'
# fused append) traced to at PR 37's parent, after dead-code elimination,
# source positions removed: sha256 of the kernel's jaxpr, first 16 digits.
# They change with JAX's printer; a kernel change that is meant to touch
# the decode path prints the new ones in its failure.
_PARENT_DECODE_KERNELS = {
    "gpt2_append": "e33ec9f969e35854", "gpt2_ragged": "5f6ddc46bf3081cb",
    "lfm2_append": "e2eb5a47d51a06a7", "lfm2_ragged": "8c167802855c7e00",
    "trinity_append": "d5b261861bd195a2",
    "trinity_ragged": "81e99515700f2c90",
    "tp_shard_append": "10f429dbfe6f75c9",
    "tp_shard_ragged": "3ae40abf0ce419af",
    "latent_append": "3be7f08a2d0acd54",
}


@pytest.mark.parametrize("case", list(_PARENT_DECODE_KERNELS))
def test_decode_kernels_trace_to_the_parents_jaxpr(case):
    """``C == rep`` is one tile: the append kernels and the ragged kernel
    at one query column must lower to the kernel they lowered to before
    the column tiles."""
    import hashlib
    import re

    from jax._src.interpreters import partial_eval as pe

    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    B, BS, NB, bf = 4, 16, 20, jnp.bfloat16
    idx = (S((1,)), S((B, NB)), S((B,)), S((B,)))
    name, kind = case.rsplit("_", 1)
    if name == "latent":
        pool = S((2, 41, BS, 640), bf)
        fn, args, kw = pa._paged_latent_append_fn, (
            S((B, 1, 32, 640), bf), S((B, 640), bf), pool, *idx, idx[-1]), \
            {"scale": 0.07}
    else:
        H, kv, hd, window = {
            "gpt2": (20, 20, 64, None), "lfm2": (32, 8, 64, None),
            "trinity": (32, 4, 128, 2048), "tp_shard": (5, 5, 64, None),
        }[name]
        pool = S((2, 41, BS, kv * hd), bf)
        kw = {"d_true": hd, **({} if window is None else {"window": window})}
        q, new = S((B, 1, H, hd), bf), S((B, kv, hd), bf)
        fn, args = (pa._paged_append_fn, (q, new, new, pool, pool, *idx,
                                          idx[-1])) if kind == "append" \
            else (pa._paged_ragged_fn, (q, pool, pool, *idx))
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args)
    (call,) = [e for e in closed.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    kernel = call.params["jaxpr"]
    kernel, _ = pe.dce_jaxpr(kernel, [True] * len(kernel.outvars))
    text = re.sub(r" at [^ ]*:\d+", "", str(kernel))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _PARENT_DECODE_KERNELS[case]


# What the MIXED step's kernels (a chunk's columns in tiles; a verify
# width in one) and the K/V writer traced to at PR 40's parent, hashed as
# above: the kernels learned a V head of its own width and the sinks
# (PR 40), both absent from a call that gives neither.
_PARENT_MIXED_KERNELS = {
    "gpt2_256_ragged": "364d86c2946fb86b",
    "lfm2_512_ragged": "51a95f48766c3884",
    "trinity_256_ragged": "81a1b6d787799aaa",
    "qwen3next_512_ragged": "7c6e913f55c649a6",
    "verify_5_ragged": "a77c53f6148a33ad",
    "gpt2_write": "a831d69314074ac7", "trinity_write": "1e5196ff916eb8e4",
    "tp_shard_write": "7827ed98349a42c8",
}


@pytest.mark.parametrize("case", list(_PARENT_MIXED_KERNELS))
def test_mixed_kernels_trace_to_the_parents_jaxpr(case):
    """``sinks=None`` and a V pool as wide as the K pool: the ragged kernel
    at the five configurations' chunk geometries and the writer trace to
    the jaxprs they traced to before the two arguments existed."""
    import hashlib
    import re

    from jax._src.interpreters import partial_eval as pe

    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    B, BS, NB, bf = 4, 16, 20, jnp.bfloat16
    name, kind = case.rsplit("_", 1)
    if kind == "write":
        D = {"gpt2": 1280, "trinity": 512, "tp_shard": 320}[name]
        pool, rows = S((2, 41, BS, D), bf), S((37, D), bf)
        fn, args, kw = pa._paged_write_fn, (
            rows, rows, pool, pool, S((1,)), S((37,)), S((37,))), {}
    else:
        H, kv, hd, window, C = {
            "gpt2_256": (20, 20, 64, None, 256),
            "lfm2_512": (32, 8, 64, None, 512),
            "trinity_256": (32, 4, 128, 2048, 256),
            "qwen3next_512": (16, 2, 256, None, 512),
            "verify_5": (32, 4, 128, None, 5)}[name]
        pool = S((2, 41, BS, kv * hd), bf)
        kw = {"d_true": hd, **({} if window is None else {"window": window})}
        fn, args = pa._paged_ragged_fn, (
            S((B, C, H, hd), bf), pool, pool, S((1,)), S((B, NB)), S((B,)),
            S((B,)))
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args)
    (call,) = [e for e in closed.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    kernel = call.params["jaxpr"]
    kernel, _ = pe.dce_jaxpr(kernel, [True] * len(kernel.outvars))
    text = re.sub(r" at [^ ]*:\d+", "", str(kernel))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _PARENT_MIXED_KERNELS[case]


# The ragged kernel as ``paged_attention`` DISPATCHES it at each cell's own
# chunk (the rule's 256 / 512; ``trinity``'s given 256 and ``qwen3next``'s
# given 1,024), hashed at PR 40's parent with the kernel's output block: the
# dispatch cuts a row whose scratch would not fit VMEM into pieces
# (``query_pieces``), and none of these rows is cut - sixteen heads of 256 at
# 1,024 columns are the largest that fit.
_PARENT_DISPATCHED = {
    "gpt2_256": ((20, 20, 64, None), "364d86c2946fb86b", (4, 256, 1280)),
    "lfm2_512": ((32, 8, 64, None), "51a95f48766c3884", (4, 2048, 512)),
    "trinity_256": ((32, 4, 128, 2048), "81a1b6d787799aaa", (4, 2048, 512)),
    "qwen3next_1024": ((16, 2, 256, None), "b527ae2d5f83f6d3",
                       (4, 8192, 512)),
}


def _pallas_calls(jaxpr):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            yield e
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from _pallas_calls(j)


@pytest.mark.parametrize("case", list(_PARENT_DISPATCHED))
def test_dispatched_kernels_at_the_cells_chunks_are_the_parents(case):
    import hashlib
    import re

    from jax._src.interpreters import partial_eval as pe

    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    (H, kv, hd, window), parent, block = _PARENT_DISPATCHED[case]
    B, BS, NB, bf, C = 4, 16, 20, jnp.bfloat16, int(case.rsplit("_", 1)[1])
    assert pa.query_pieces(C, H, hd, kv * hd, bf) == 1
    pool = S((2, 41, BS, kv * hd), bf)
    kw = {} if window is None else {"window": window}
    closed = jax.make_jaxpr(lambda q, k, v, bt, sp, nv: pa.paged_attention(
        q, k, v, bt, start_pos=sp, n_valid=nv, layer=1, use_pallas=True,
        interpret=True, **kw))(
        S((B, C, H, hd), bf), pool, pool, S((B, NB)), S((B,)), S((B,)))
    (call,) = _pallas_calls(closed.jaxpr)
    assert call.outvars[0].aval.shape == block
    kernel = call.params["jaxpr"]
    kernel, _ = pe.dce_jaxpr(kernel, [True] * len(kernel.outvars))
    text = re.sub(r" at [^ ]*:\d+", "", str(kernel))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == parent


# -- the packed dispatch: kernel rows cut from the stream (PR 41) -------------

# A mixed step as ``_build_mixed`` packs it, (start, valid columns) a row: a
# decode row deep in its sequence, a chunk whose 37 columns cross piece
# boundaries, an idle row (context 1 on the null block), a decode row near
# its start, a short chunk inside one piece, two idle rows; T = B + C = 55
# tokens (no multiple of the pieces), 48 of them live, the rest padding.
_STREAM_ROWS = [(90, 1), (17, 37), _IDLE, (5, 1), (0, 9), _IDLE, _IDLE]


def _packed_case(rng, rows, C, H, KV, hd, hd_v, dtype, BS=16, NB=8):
    """(q (T, H, hd), K and V pools (1, blocks, BS, lanes), tables, start,
    valid, row_token_idx, tok_row, tok_col, live tokens)."""
    B, T = len(rows), len(rows) + C
    idx = np.zeros((B, C), np.int32)
    tok_row, tok_col = np.zeros(T, np.int32), np.zeros(T, np.int32)
    tables = np.zeros((B, NB), np.int32)
    t = 0
    for b, (start, n) in enumerate(rows):
        if (start, n) == _IDLE:  # no token of the stream: its first
            continue
        idx[b, :n], idx[b, n:] = np.arange(t, t + n), t + n - 1
        tok_row[t:t + n], tok_col[t:t + n] = b, np.arange(n)
        tables[b] = 1 + b * NB + rng.permutation(NB)
        t += n
    blocks = 1 + B * NB

    def draw(*dims):
        return jnp.asarray(rng.standard_normal(dims), dtype)

    return (draw(T, H, hd), draw(1, blocks, BS, KV * hd),
            draw(1, blocks, BS, KV * hd_v), jnp.asarray(tables),
            jnp.asarray([r[0] for r in rows], jnp.int32),
            jnp.asarray([r[1] for r in rows], jnp.int32), jnp.asarray(idx),
            jnp.asarray(tok_row), jnp.asarray(tok_col), t)


_PIECE_CASES = {
    # query heads, K/V heads, head, value head, window, sinks: each cell's
    # head geometry (its rep, its lanes a head) at a chunk of 48 columns
    "mimo_full_rep16": (32, 2, 192, 128, None, False),
    "mimo_sliding_rep8_sinks": (16, 2, 192, 128, 24, True),
    "qwen3next_hd256": (16, 2, 256, 256, None, False),
    "trinity_window": (16, 2, 128, 128, 40, False),
    "lfm2_rep4": (8, 2, 64, 64, None, False),
    "gpt2_rep1": (4, 4, 64, 64, None, False),
}


@pytest.mark.parametrize("case", list(_PIECE_CASES))
def test_piece_dispatch_is_the_row_dispatch_bit_for_bit(case):
    """The kernel rows cut from the packed stream in pieces of 16 and of 8
    query columns (``_pieces``; N = T // P + B of them) give every live
    token the bits the rows at the chunk's width give it (the same spans in
    the same order: a span behind a piece's window leaves its columns'
    softmax as it was), and both the gather reference's values, at every
    cell's head geometry, interpreted, in f32."""
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    H, KV, hd, hd_v, window, sinks = _PIECE_CASES[case]
    C, f32 = 48, jnp.float32
    rng = np.random.default_rng(41)
    q, kp, vp, tables, sp, nv, idx, tr, tc, t = _packed_case(
        rng, _STREAM_ROWS, C, H, KV, hd, hd_v, f32)
    snk = jnp.asarray(rng.standard_normal(H), f32) if sinks else None
    kw = {} if window is None else {"window": window}
    want = np.asarray(pa.paged_attention(
        q, kp[0], vp[0], tables, start_pos=sp, n_valid=nv,
        packed=(idx, tr, tc), use_pallas=False, sinks=snk, **kw))[:t]
    B, T = len(_STREAM_ROWS), q.shape[0]
    got = {}
    for P in (C, 16, 8):
        layout = (C, B) if P == C else (P, T // P + B)
        got[P] = np.asarray(pa._ragged_packed(
            q, kp, vp, jnp.zeros((1,), jnp.int32), tables, sp, nv, idx, tr,
            tc, snk, window=window, interpret=True, layout=layout))[:t]
        np.testing.assert_allclose(got[P], want, rtol=2e-5, atol=2e-5)
    for P in (16, 8):
        assert (got[P].view(np.uint32) == got[C].view(np.uint32)).all()
    # the dispatch itself, in the layout its rule gives these shapes
    rule = np.asarray(pa.paged_attention(
        q, kp, vp, tables, start_pos=sp, n_valid=nv, packed=(idx, tr, tc),
        layer=0, use_pallas=True, interpret=True, sinks=snk, **kw))[:t]
    assert (rule.view(np.uint32) == got[C].view(np.uint32)).all()


def test_pieces_by_hand():
    """``_pieces`` at P = 16 over ``_STREAM_ROWS``: each row's pieces in row
    order (the 37-column chunk in three, the others in one), the spare
    kernel rows idle at context 1, a piece's columns its row's tokens
    (padding: the row's last), its contexts ``_row_pieces``' rule, and the
    way back to every packed token."""
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    rng = np.random.default_rng(0)
    *_, tables, sp, nv, idx, tr, tc, t = _packed_case(
        rng, _STREAM_ROWS, 48, 2, 2, 64, 64, jnp.float32)
    P, N = 16, 55 // 16 + 7
    row, tok, c0, cl, piece, col = (np.asarray(x) for x in jax.tree.leaves(
        pa._pieces(sp, nv, idx, tr, tc, P, N)))
    assert row.tolist() == [0, 1, 1, 1, 2, 3, 4, 5, 6] + [6]
    assert c0.tolist() == [91, 18, 34, 50, 1, 6, 1, 1, 1, 1]
    assert cl.tolist() == [91, 33, 49, 54, 1, 6, 9, 1, 1, 1]
    assert tok[0].tolist() == [0] * P                   # a decode row
    assert tok[1].tolist() == list(range(1, 17))        # the chunk's first
    assert tok[3].tolist() == [33, 34, 35, 36, 37] + [37] * 11
    assert tok[6].tolist() == list(range(39, 48)) + [47] * 7
    assert piece[:t].tolist() == [0] + [1] * 16 + [2] * 16 + [3] * 5 \
        + [5] + [6] * 9
    assert col[:t].tolist() == [0] + list(range(16)) * 2 + list(range(5)) \
        + [0] + list(range(9))
    # the rows' pieces never outgrow N: a row of n columns takes ceil(n/P)
    # and the rows hold at most T + B columns
    for n in ([48] + [1] * 6, [16, 16, 16] + [1] * 4, [1] * 7):
        assert sum(-(-k // P) for k in n) <= N


# The rule's layout at each cell's geometry (sixteen rows, T = B + the
# chunk, bf16, the cell's table): the widths a chip sweep of one ragged call
# ranks within 4% of the fastest (PERF.md section 6, PR 41), the rows where
# pieces do not pay.  (query heads, K/V heads, head, value head, chunk,
# table keys) -> (P, N).
_CELL_LAYOUTS = {
    "gpt2_256": ((20, 20, 64, 64, 256, 1024), (256, 16)),
    "lfm2_512": ((32, 8, 64, 64, 512, 2048), (64, 24)),
    "trinity_256": ((32, 4, 128, 128, 256, 8192), (256, 16)),
    "qwen3next_1024": ((16, 2, 256, 256, 1024, 8192), (256, 20)),
    "mimo_full_512": ((64, 4, 192, 128, 512, 8192), (64, 24)),
    "mimo_window_512": ((64, 8, 192, 128, 512, 8192), (64, 24)),
}


@pytest.mark.parametrize("case", list(_CELL_LAYOUTS))
def test_query_layout_at_the_cells_geometries(case):
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    (H, kv, hd, hd_v, C, keys), want = _CELL_LAYOUTS[case]
    bf = jnp.bfloat16
    got = pa.query_layout(16 + C, 16, C, H, hd, kv * hd, bf, Dv=kv * hd_v,
                          keys=keys)
    assert got == want
    P, N = got
    # the pieces never outgrow N, and whole sublane tiles of bf16
    assert N == 16 or (N == (16 + C) // P + 16 and P % 16 == 0)
    # a stream of B x C tokens (the row form) never takes pieces where the
    # rows fit VMEM
    if P == C:
        assert pa.query_layout(16 * C, 16, C, H, hd, kv * hd, bf,
                               Dv=kv * hd_v, keys=keys) == (C, 16)


# The ragged kernel as ``paged_attention`` dispatches a packed step at each
# cell's own geometry: where the rule keeps the rows, the kernel of PR 40's
# parent (sha256 of its jaxpr as above, sixteen rows and the cell's table,
# hashed from the parent's checkout), else a block of P x rep folded columns
# a kernel row.
_PACKED_DISPATCH = {
    "gpt2_256": ((20, 20, 64, None, 256, 64), "6797903cc8f4a7fe",
                 (16, 256, 1280)),
    "trinity_256": ((32, 4, 128, None, 256, 512), "c17ef3b42a7204dc",
                    (16, 2048, 512)),
    "trinity_window_256": ((32, 4, 128, 2048, 256, 512), "e8718f2d1b2aac07",
                           (16, 2048, 512)),
    "lfm2_512": ((32, 8, 64, None, 512, 128), None, (24, 256, 512)),
    "qwen3next_1024": ((16, 2, 256, None, 1024, 512), None, (20, 2048, 512)),
}


@pytest.mark.parametrize("case", list(_PACKED_DISPATCH))
def test_packed_dispatch_at_the_cells_geometries(case):
    import hashlib
    import re

    from jax._src.interpreters import partial_eval as pe

    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    (H, kv, hd, window, C, NB), parent, block = _PACKED_DISPATCH[case]
    B, BS, bf = 16, 16, jnp.bfloat16
    T = B + C
    pool = S((2, 41, BS, kv * hd), bf)
    kw = {} if window is None else {"window": window}
    closed = jax.make_jaxpr(
        lambda q, k, v, bt, sp, nv, idx, tr, tc: pa.paged_attention(
            q, k, v, bt, start_pos=sp, n_valid=nv, packed=(idx, tr, tc),
            layer=1, use_pallas=True, interpret=True, **kw))(
        S((T, H, hd), bf), pool, pool, S((B, NB)), S((B,)), S((B,)),
        S((B, C)), S((T,)), S((T,)))
    (call,) = _pallas_calls(closed.jaxpr)
    assert call.outvars[0].aval.shape == block
    assert closed.out_avals[0].shape == (T, H, hd)
    if parent is not None:
        kernel = call.params["jaxpr"]
        kernel, _ = pe.dce_jaxpr(kernel, [True] * len(kernel.outvars))
        text = re.sub(r" at [^ ]*:\d+", "", str(kernel))
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == parent


@pytest.mark.parametrize(
    "n,C,cols,want",
    [([1, 1, 9, 32, 64], 512, 64, [2, 2, 32, 32, 64]),
     ([1, 65, 130], 512, 64, [2, 64 + 2, 64 + 64 + 2]),
     ([1, 9, 64], 64, None, [2, 32, 64])],
    ids=["one_piece_a_row", "rows_in_pieces", "the_row_whole"])
def test_query_tile_columns_walk_the_pieces_by_hand(n, C, cols, want):
    """``query_tile_columns`` with the layout's piece (``cols``) at LFM2's
    heads (two heads of 64 a group, rep 4, bf16): a piece of 64 columns is
    256 folded ones, a first tile of 8 (2 columns) or wide tiles of 128 (32
    columns); a row's pieces are its live columns' only (65 columns: a full
    piece and one of one column), each with its own tiles."""
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    bf = jnp.bfloat16
    assert pa._col_tiles(2, 64 * 4, 4, bf) == (8, 128)
    got = pa.query_tile_columns(np.asarray(n), C, 32, 64, 512, bf, cols=cols)
    assert [int(g) for g in got] == want


# -- the mixed step's K/V writer ---------------------------------------------

# Streams as ``_build_mixed`` packs them, (block, offset) a token over
# blocks of 16: decode rows first (one token, a block of their own), then
# chunk runs; 0 is the null block (padding, tokens diverted from a shared
# prefix), the only block a stream comes back to.
def _run(blocks, start, n):
    """A chunk run: ``n`` positions from ``start``, through ``blocks``
    (the row's table from the block that holds ``start`` on)."""
    return [(blocks[p // 16 - start // 16], p % 16)
            for p in range(start, start + n)]


_STREAMS = {
    # three decode rows, a run that starts mid-block and crosses one
    # boundary, a run that crosses two, padding at the tail
    "decode_then_runs": [(3, 4), (5, 15), (9, 0)] + _run([7, 8], 13, 9)
    + _run([11, 12, 13], 12, 22) + [(0, 0)] * 4,
    # a run that ends its block exactly, then a second chunk the budget
    # cut to five tokens, no padding
    "block_end_and_budget_cut": [(2, 7)] + _run([4, 6], 16, 16)
    + _run([10, 14], 30, 5),
    # a reader of a shared prefix: its first tokens go to the null block
    # between the writer's run and its own real ones, a decode row before
    "diverted_between_real": [(3, 1)] + _run([5, 6], 0, 20)
    + [(0, p) for p in range(6)] + _run([8, 9], 6, 12) + [(0, 0)] * 3,
    # every token a decode row: no block holds two
    "decode_only": [(b, (5 * b) % 16) for b in (1, 4, 2, 13, 7, 6)]
    + [(0, 0)] * 2,
}


@pytest.mark.parametrize("stream", list(_STREAMS))
@pytest.mark.parametrize("H,hd", [(20, 64), (8, 64), (5, 64)],
                         ids=["d1280", "d512", "d320_tp_shard"])
def test_write_kernel_matches_the_scatter_bit_for_bit(H, hd, stream):
    """The writer kernel (interpreted) against ``.at[layer, sb, so].set``
    on a bf16 pool in BlockPool's shape: every packed token's row where
    the scatter puts it, every other row of a touched block and every
    untouched block with the bits it had, the other layers untouched;
    the null block may hold any of the rows sent to it."""
    from pathway_tpu.kvcache.paged_attention import paged_write_rows

    rng = np.random.default_rng(7)
    L, NBLK, BS, layer = 3, 15, 16, 1
    sb, so = (jnp.asarray(x, jnp.int32) for x in zip(*_STREAMS[stream]))
    T = sb.shape[0]
    real = np.asarray(sb) > 0
    assert len(set(zip(np.asarray(sb)[real], np.asarray(so)[real]))) \
        == real.sum()  # the engine's contract: no real slot twice

    def draw(*dims):
        return jnp.asarray(rng.standard_normal(dims), jnp.bfloat16)

    k_pool, v_pool = draw(L, NBLK, BS, H * hd), draw(L, NBLK, BS, H * hd)
    k1, v1 = draw(T, H, hd), draw(T, H, hd)
    want = paged_write_rows(k_pool, v_pool, sb, so, k1, v1, layer=layer,
                            use_pallas=False)
    # the kernel's entry point donates its pools: hand it copies
    got = paged_write_rows(jnp.array(k_pool), jnp.array(v_pool), sb, so, k1,
                           v1, layer=layer, use_pallas=True, interpret=True)
    for before, w, g, rows in zip((k_pool, v_pool), want, got, (k1, v1)):
        before, w, g = (np.asarray(x).view(np.uint16) for x in (before, w, g))
        assert (g[:, 1:] == w[:, 1:]).all()
        assert (g[[0, 2]] == before[[0, 2]]).all()
        rows = np.asarray(rows).view(np.uint16).reshape(T, -1)
        assert (g[layer, np.asarray(sb)[real], np.asarray(so)[real]]
                == rows[real]).all()
        # the null block: each slot its old row or one sent to block 0
        sent = rows[~real]
        for r, old in zip(g[layer, 0], before[layer, 0]):
            assert (r == old).all() or (sent == r).all(axis=1).any()


# -- continuous batching: arrivals never stall in-flight decodes -------------


def test_arrival_mid_decode_interleaves_and_matches(params):
    """A long-prompt arrival injected mid-decode must complete correctly
    AND the in-flight short decodes must keep making progress between
    the arrival's chunk steps (no monolithic-prefill stall rounds)."""
    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=96, block_size=8, max_batch_size=4,
        seq_buckets=(16, 64), prefix_sharing=False, prefill_chunk=8,
        name="t_r8_arrival",
    )
    rng = np.random.default_rng(17)
    short = [
        [int(t) for t in rng.integers(0, _CFG.vocab_size, size=4)]
        for _ in range(2)
    ]
    longp = [int(t) for t in rng.integers(0, _CFG.vocab_size, size=40)]
    got = {}
    state = {"round": 0, "sent": False}

    def poll(n):
        state["round"] += 1
        if state["round"] == 3 and not state["sent"]:
            state["sent"] = True
            return [((longp, 3), 1, lambda r: got.setdefault("long", r),
                     lambda e: got.setdefault("err", e))]
        return []

    outs = eng.generate_batch([(p, 12) for p in short], poll=poll)
    assert "err" not in got
    assert outs == [_dense_greedy(params, p, 12) for p in short]
    assert got["long"] == _dense_greedy(params, longp, 3)
    # the 40-token prompt streamed as ceil(40/8)=5 chunks through the
    # mixed step instead of one whole-bucket dispatch
    assert eng.pool.stats.snapshot()["prefill_chunks"] >= 5


def test_continuous_batching_through_scheduler_chunked(params):
    from pathway_tpu.serve.scheduler import RequestScheduler

    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=96, block_size=8, max_batch_size=4,
        seq_buckets=(16, 32), prefill_chunk=16, name="t_r8_cbatch",
    )
    box = {}

    def batch_fn(reqs):
        return eng.serve_batch(reqs, scheduler=box["sched"])

    box["sched"] = sched = RequestScheduler(
        batch_fn, name="t_r8_cbatch_sched", max_batch_size=4,
        batch_linger_ms=20.0, max_queue=32,
    )
    try:
        rng = np.random.default_rng(11)
        prompts = [
            [int(t) for t in rng.integers(0, _CFG.vocab_size, size=5 + i)]
            for i in range(6)
        ]
        results = [None] * 6

        def submit(i):
            results[i] = sched.submit((prompts[i], 10))

        threads = [
            threading.Thread(target=submit, args=(i,)) for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert results == [_dense_greedy(params, p, 10) for p in prompts]
    finally:
        sched.shutdown()


# -- metrics surface ---------------------------------------------------------


def test_round8_metrics_render_and_export(params):
    from pathway_tpu.serve import metrics as M

    eng = PagedDecodeEngine(
        _CFG, params, num_blocks=64, block_size=8, max_batch_size=2,
        seq_buckets=(16,), name="t_r8_metrics",
    )
    eng.generate_batch([([1, 2, 3, 4, 5], 4), ([6, 7], 3)])
    snap = eng.pool.stats.snapshot()
    assert snap["prefill_chunks"] >= 2
    assert snap["mixed_steps"] >= 1
    assert snap["mixed_step_occupancy_avg"] > 0
    # one TTFT observation per request, histogram internally consistent
    assert snap["ttft_count"] == 2
    assert len(snap["recent_ttfts"]) == 2
    assert snap["ttft_sum"] >= sum(snap["recent_ttfts"]) * 0.99
    lines = "\n".join(M.render_prometheus_lines())
    lbl = f'pool="{eng.pool.name}"'
    assert f"pathway_kv_prefill_chunks_total{{{lbl}}}" in lines
    assert f"pathway_kv_mixed_step_occupancy_avg{{{lbl}}}" in lines
    assert f'pathway_kv_ttft_seconds_bucket{{{lbl},le="+Inf"}} 2' in lines
    assert f"pathway_kv_ttft_seconds_count{{{lbl}}} 2" in lines
    # cumulative bucket counts are monotone and end at the count
    bucket_vals = [
        int(line.rsplit(" ", 1)[1])
        for line in lines.splitlines()
        if line.startswith(f"pathway_kv_ttft_seconds_bucket{{{lbl}")
    ]
    assert bucket_vals == sorted(bucket_vals)
    assert bucket_vals[-1] == 2
    points = M.otlp_points("0")
    counters = {
        a["value"]["stringValue"]
        for p in points for a in p["attributes"]
        if a["key"] == "counter"
    }
    assert {"prefill_chunks", "mixed_steps", "ttft_count",
            "ttft_sum"} <= counters
    # dashboard renders the new columns without an engine scheduler
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
    assert "ttft p50 ms" in html and "chunks" in html
