#!/usr/bin/env python3
"""chip_smoke.py — the system's main path, once, on the chip.

One process drives what a user drives: a seeded corpus written into a
watched directory in waves, read by ``pw.io.fs.read(mode="streaming")``
into a ``DocumentStore`` whose vectors live in HBM, queried through REST
(``/v1/retrieve``, ``/v1/pw_ai_answer``), answered by the paged decode
engine at the published GPT-2-large shape; and the serving path directly
(``RequestScheduler`` + ``PagedDecodeEngine``).  Every check is an
exception: the script exits non-zero on the first one that fails, and the
``"ok": true`` line is printed only after all of them passed.

    python chip_smoke.py             one TPU chip, real sizes
    python chip_smoke.py --chips 4   the tensor-parallel phase and what it
                                     is compared with, nothing else
    python chip_smoke.py --rehearse  toy sizes through the same code on any
                                     platform; never prints "ok": true

Every line but the last is a JSON record of what is worth keeping.  None
of the timings is a benchmark number.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import shutil
import socket
import sys
import tempfile
import threading
import time
import urllib.request

NOTE = "smoke, not a measurement"

# -- sizes --------------------------------------------------------------------
# Published widths, never cut: all-MiniLM-L6-v2 (encoder) and GPT-2-large
# (decoder).  ``cuts`` lists every cut of scale or depth with its reason and
# is printed with the results.
REAL = dict(
    enc=dict(hidden_size=384, num_hidden_layers=6, num_attention_heads=12,
             intermediate_size=1536, vocab_size=30522,
             max_position_embeddings=512),
    dec=dict(n_embd=1280, n_layer=36, n_head=20, n_positions=1024,
             vocab_size=50257),
    tp_vocab=50304,  # 50257 rounded up to a multiple of 128: tp in {2, 4}
    n_waves=4, wave_docs=4096, n_rewrite=256, n_delete=256, doc_words=96,
    n_retrieve=8, k=10, n_answers=8, answer_tokens=64,
    n_direct=16, prompt_lo=32, prompt_hi=768, direct_tokens=64,
    n_reference=4, reference_tokens=16,
    vec_rows_per_doc=8, fleet_layers=2,
    cuts=[
        "the attn=\"reference\" engine runs the 4 shortest of the 16 direct "
        "requests for 16 tokens, not all 16 for 64: it exists to print an "
        "agreement share, and at this geometry its step programs copy both "
        "K/V pools whole around every layer (376 s for the full pass on "
        "the chip, PR 21, against 13 s with the kernels)",
        "ReplicaFleet placement is printed at depth 2 (widths unchanged): "
        "four full-depth replicas on one device would not fit, and "
        "placement does not depend on depth",
    ],
)
TOY = dict(
    enc=dict(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
             intermediate_size=64, vocab_size=512,
             max_position_embeddings=128),
    dec=dict(n_embd=64, n_layer=2, n_head=4, n_positions=128,
             vocab_size=1021),
    tp_vocab=1024,
    n_waves=4, wave_docs=16, n_rewrite=4, n_delete=4, doc_words=24,
    n_retrieve=8, k=5, n_answers=4, answer_tokens=8,
    n_direct=6, prompt_lo=4, prompt_hi=48, direct_tokens=8,
    n_reference=3, reference_tokens=4,
    vec_rows_per_doc=8, fleet_layers=2,
    cuts=["rehearsal: toy sizes throughout"],
)

# -- tolerances, each next to its reason ----------------------------------------
# Cosine scores from the device against numpy f32 on the host.  The TPU's
# default f32 matmul rounds its operands to bf16 (8 mantissa bits), so one
# unit-vector dot product is off by at most ~2**-8 = 4e-3; 1e-2 is 2.5x that.
SCORE_TOL = 1e-2
# Index rows against a fresh embedding of the same text: same arithmetic in
# another batch shape, so the same bound applies to their cosine.
EMBED_COS_TOL = 1e-2
# Attention kernel against the gather reference in the serving dtype: both
# keep the softmax in f32 and round probabilities and outputs to the pool's
# dtype (bf16 on the chip: ulp 2**-8 relative; f32 in a rehearsal), the
# kernel in block-wise online order.  Four bf16 ulps of the output's scale.
ATTN_TOL_REL = 2.0 ** -6
# Chained against stepwise decode is token identity where the arithmetic
# is the same.  Where two runs do diverge, the first divergence must be a
# tie the arithmetic cannot resolve: see tie_at_divergence.


def say(phase: str, **rec) -> None:
    print(json.dumps({"phase": phase, **rec, "note": NOTE}, default=str),
          flush=True)


class Check(AssertionError):
    """A gate of the smoke failed."""


def require(cond: bool, what: str, **ctx) -> None:
    if not cond:
        raise Check(f"{what}: {json.dumps(ctx, default=str)}")


def block(x):
    import jax

    return jax.block_until_ready(x)


def cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except FileNotFoundError:
        return 0


class CacheEvents:
    """JAX's own account of its persistent compilation cache: requests
    served from it, requests that had to compile, the compile seconds the
    hits saved (what those programs cost when they were compiled) and the
    seconds it took to read them back."""

    def __init__(self):
        from jax import monitoring

        self.hits = self.misses = 0
        self.saved_s = self.retrieval_s = 0.0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved_s += secs
        elif name == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieval_s += secs

    def since(self, before: dict | None = None) -> dict:
        now = {"hits": self.hits, "misses": self.misses,
               "compile_s_saved": self.saved_s,
               "retrieval_s": self.retrieval_s}
        return now if before is None else {
            k: now[k] - before[k] for k in now}


def released(dev) -> dict:
    """After an engine is dropped: collect the cycles that still hold its
    pools (scheduler <-> batch function <-> engine), and say what is left."""
    import gc

    gc.collect()
    return hbm(dev)


def hbm(dev) -> dict:
    st = dev.memory_stats() or {}
    return {k: st.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


# -- models ---------------------------------------------------------------------

def decoder_cfg(sz: dict, vocab: int | None = None, layers: int | None = None):
    import transformers

    from pathway_tpu.models import hf_import

    hf = dict(sz["dec"])
    if vocab is not None:
        hf["vocab_size"] = vocab
    if layers is not None:
        hf["n_layer"] = layers
    cfg = hf_import.config_from_gpt2(transformers.GPT2Config(**hf))
    # bf16 on the chip, f32 on the CPU (models/encoder._resolve_dtype)
    return dataclasses.replace(cfg, dtype="auto")


def encoder_cfg(sz: dict):
    import transformers

    from pathway_tpu.models import hf_import

    return hf_import.config_from_hf(transformers.BertConfig(**sz["enc"]))


def direct_prompts(sz: dict, vocab: int, seed: int) -> list[list[int]]:
    rng = random.Random(seed + 1)
    n, lo, hi = sz["n_direct"], sz["prompt_lo"], sz["prompt_hi"]
    lens = [lo + round(i * (hi - lo) / max(n - 1, 1)) for i in range(n)]
    rng.shuffle(lens)
    return [[rng.randrange(4, vocab) for _ in range(ln)] for ln in lens]


# -- the serving path, directly ---------------------------------------------------

def serve_requests(engine, prompts, n_new: int, name: str) -> dict:
    """The requests, concurrently, through RequestScheduler + the engine —
    the wiring of Int8DecoderHost.serving_executor, without its degrade
    target.  Returns tokens and the engine's own accounting."""
    from pathway_tpu.serve.scheduler import RequestScheduler

    holder: dict = {}
    sched = RequestScheduler(
        lambda reqs: engine.serve_batch(reqs, scheduler=holder["s"]),
        name=name, max_batch_size=engine.max_batch_size,
        batch_linger_ms=2.0, max_queue=4 * len(prompts),
    )
    holder["s"] = sched
    st = engine.pool.stats
    before = (st.ttft_count, st.engine_restarts, st.engine_degraded,
              sched.stats.completed)
    t0 = time.perf_counter()
    out = concurrently([
        (lambda p=p: sched.submit((p, n_new), timeout_s=900.0))
        for p in prompts], timeout=1000.0)
    wall = time.perf_counter() - t0
    sched.shutdown(drain=True)
    require(all(len(o) == n_new for o in out),
            "a request returned the wrong number of tokens", engine=name,
            got=[len(o) for o in out], want=n_new)
    acct = {
        "sequences_completed": st.ttft_count - before[0],
        "engine_restarts": st.engine_restarts - before[1],
        "engine_degrades": st.engine_degraded - before[2],
        "scheduler_completed": sched.stats.completed - before[3],
        "preemptions": st.preemptions,
        "mixed_steps": st.mixed_steps, "chains": st.chain_count,
    }
    require(acct["sequences_completed"] == len(prompts)
            and acct["scheduler_completed"] == len(prompts),
            "the engine's accounting does not show the requests served",
            engine=name, **acct)
    require(acct["engine_restarts"] == 0 and acct["engine_degrades"] == 0,
            "engine restarted or degraded", engine=name, **acct)
    return {"tokens": [list(map(int, o)) for o in out], "wall_s": wall,
            "accounting": acct}


def program_table(engine, expect_kernel: bool, since: float) -> list[dict]:
    """One row per step program this engine dispatched: is the Pallas
    kernel in its compiled text, what does it hold on the device, which
    collectives.  Absent where the engine said attn="pallas" fails."""
    import re

    from pathway_tpu.obs import profiler

    wrappers = {id(w) for w in (engine._step, engine._mixed,
                                engine._chained)}
    # what one device's program sees of the pool (a quarter under tp=4)
    shard_shape = engine.pool.k.addressable_shards[0].data.shape
    rows = []
    for rec in profiler.registry().records():
        w = rec._wrapper_ref() if rec._wrapper_ref else None
        if w is None or id(w) not in wrappers or not w.calls:
            continue
        compiled = rec._lowered().compile()
        # this engine's dispatch->sync windows (the registry merges engines
        # of one geometry): those that ended after it was built
        mine = [dur for t_end, dur, _n in rec.reservoir if t_end >= since]
        txt = compiled.as_text()
        ma = compiled.memory_analysis()
        row = {
            "program": rec.program, "attn": engine.attn, "tp": engine.tp,
            "tpu_custom_call": txt.count("tpu_custom_call"),
            "kernel": "present" if "tpu_custom_call" in txt else "absent",
            "all_reduce": len(re.findall(r"\ball-reduce(-start)?\(", txt)),
            "all_gather": len(re.findall(r"\ball-gather(-start)?\(", txt)),
            "argument_bytes": ma.argument_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            # whole-pool copies in the compiled text: none while a block
            # of the pool fills whole tiles (block_pool.py); four where it
            # does not (tp = 4: 320 lanes a shard), the way in and out
            # between XLA's layout of the pool and the kernel's
            "pool_sized_copies": len(re.findall(
                r"= \w+\[" + ",".join(map(str, shard_shape))
                + r"\]\{[^}]*\} copy\(", txt)),
            "calls": w.calls, "dispatches_timed": len(mine),
            "dispatch_ms_p50": (sorted(mine)[len(mine) // 2] * 1e3
                                if mine else None),
        }
        rows.append(row)
        if expect_kernel:
            require(row["kernel"] == "present",
                    'attn="pallas" but the compiled step program holds no '
                    "kernel", **row)
    require(bool(rows), "no dispatched step program found", engine=engine.attn)
    return rows


def kernels_on_live_pool(engine, seed: int) -> dict:
    """paged_attention and paged_append_attend, compiled, against
    paged_attention_reference on this engine's pool as the run left it."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    rng = np.random.default_rng(seed + 2)
    pool = engine.pool
    B, NB, BS = engine.max_batch_size, engine.max_blocks_per_seq, pool.block_size
    H, hd, dt = pool.n_heads, pool.head_dim, pool.k.dtype
    li = pool.n_layers // 2
    kp, vp = pool.k[li], pool.v[li]
    # the first B blocks of a shuffle are the rows' private tail blocks
    # (the fused append writes them); the tables read from the rest
    perm = rng.permutation(pool.num_blocks - 1) + 1
    tail = jnp.asarray(perm[:B], jnp.int32)
    bt = jnp.asarray(rng.choice(perm[B:], (B, NB)), jnp.int32)
    out = {"layer": li, "pool_dtype": str(dt),
           "pool_nonzero_share": float(jnp.mean(kp != 0))}
    on_tpu = jax.default_backend() == "tpu"

    def rel_err(a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))

    # decode form, C = 1
    ctx = jnp.asarray(rng.integers(1, NB * BS + 1, (B,)), jnp.int32)
    q1 = jnp.asarray(rng.standard_normal((B, 1, H, hd)), dt)
    got = block(pa.paged_attention(q1, kp, vp, bt, ctx, use_pallas=True))
    ref = block(pa.paged_attention_reference(q1, kp, vp, bt, ctx))
    out["paged_attention_c1_rel_err"] = rel_err(got, ref)
    # ragged form, the mixed step's chunk width
    C = engine.prefill_chunk
    start = jnp.asarray(rng.integers(0, NB * BS - C, (B,)), jnp.int32)
    nval = jnp.asarray(rng.integers(1, C + 1, (B,)), jnp.int32)
    qc = jnp.asarray(rng.standard_normal((B, C, H, hd)), dt)
    got = block(pa.paged_attention(qc, kp, vp, bt, start_pos=start,
                                   n_valid=nval, use_pallas=True))
    ref = block(pa.paged_attention_reference(qc, kp, vp, bt, start_pos=start,
                                             n_valid=nval))
    valid = np.arange(C)[None, :] < np.asarray(nval)[:, None]
    out["paged_attention_ragged_rel_err"] = rel_err(
        np.asarray(got, np.float32)[valid], np.asarray(ref, np.float32)[valid])
    # fused append+attend: the slot is the tail of each row's context
    k1 = jnp.asarray(rng.standard_normal((B, H, hd)), dt)
    v1 = jnp.asarray(rng.standard_normal((B, H, hd)), dt)
    last = (ctx - 1) // BS
    bt2 = bt.at[jnp.arange(B), last].set(tail)
    so = (ctx - 1) % BS
    a_f, k_f, v_f = block(pa.paged_append_attend(
        q1, k1, v1, pool.k[li], pool.v[li], bt2, ctx, tail, so,
        use_pallas=True))
    a_r, k_r, v_r = block(pa.paged_append_attend(
        q1, k1, v1, pool.k[li], pool.v[li], bt2, ctx, tail, so,
        use_pallas=False))
    out["append_attend_rel_err"] = rel_err(a_f, a_r)
    out["append_pool_equal"] = bool(jnp.array_equal(k_f, k_r)
                                    and jnp.array_equal(v_f, v_r))
    out["tolerance_rel"] = ATTN_TOL_REL
    out["kernel_mode"] = "compiled" if on_tpu else "interpreted"
    say("kernel_vs_reference", **out)
    for key in ("paged_attention_c1_rel_err", "paged_attention_ragged_rel_err",
                "append_attend_rel_err"):
        require(np.isfinite(out[key]) and out[key] <= ATTN_TOL_REL,
                "attention kernel disagrees with the reference", **out)
    require(out["append_pool_equal"],
            "fused append wrote another pool than the scatter", **out)
    return out


def first_divergence(a: list[list[int]], b: list[list[int]]):
    for r, (x, y) in enumerate(zip(a, b)):
        for p, (s, t) in enumerate(zip(x, y)):
            if s != t:
                return r, p, s, t
    return None


def agreement(a: list[list[int]], b: list[list[int]]) -> dict:
    """Token agreement of two runs: the share of requests that are equal
    throughout, and the share of positions before each first divergence."""
    same = sum(x == y for x, y in zip(a, b))
    pre, tot = 0, 0
    for x, y in zip(a, b):
        n = next((i for i, (s, t) in enumerate(zip(x, y)) if s != t), len(x))
        pre += n
        tot += len(x)
    return {"requests_identical": same, "requests": len(a),
            "share_before_divergence": pre / max(tot, 1)}


def tie_at_divergence(cfg, params, prompt, emitted_prefix, tok_a, tok_b):
    """Is the first divergence of two runs a tie the arithmetic cannot
    resolve?  The plain forward pass gives the logits at that position
    twice: in f32 at the highest matmul precision (the reference), and in
    the serving dtype.  ``noise`` is the largest deviation of the serving
    dtype's logits from the reference at that position; the two tokens
    are a tie when the reference separates them by less than twice that."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pathway_tpu.models.decoder import forward_logits

    toks = list(prompt) + list(emitted_prefix)
    n = len(toks)
    # right-padded to a bucket (causal: the padding changes nothing before
    # it), so that a few divergences do not each compile their own length
    width = min(-(-n // 256) * 256, cfg.max_len)
    ids = jnp.asarray([toks + [0] * (width - n)], jnp.int32)
    served = np.asarray(block(forward_logits(params, cfg, ids))[0, n - 1],
                        np.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(block(forward_logits(
            params, dataclasses.replace(cfg, dtype=jnp.float32),
            ids))[0, n - 1], np.float32)
    order = np.argsort(-ref)
    noise = float(np.max(np.abs(served - ref)))
    gap = float(abs(ref[tok_a] - ref[tok_b]))
    return {"context_tokens": n,
            "ref_logit_a": float(ref[tok_a]), "ref_logit_b": float(ref[tok_b]),
            "ref_gap": gap, "serving_dtype_noise": noise,
            "ref_rank_a": int(np.nonzero(order == tok_a)[0][0]),
            "ref_rank_b": int(np.nonzero(order == tok_b)[0][0]),
            "ref_top_margin": float(ref[order[0]] - ref[order[1]]),
            "is_tie": gap <= 2.0 * noise}


def compare_runs(what: str, a, b, cfg, params, prompts, gated: bool) -> dict:
    """Token agreement of two runs of the same requests.  Identity is the
    expectation where the arithmetic is the same; where it fails, the
    first divergence is diagnosed, and (when gated) must be a tie."""
    rec = {"identical": a == b, "gated": gated, **agreement(a, b)}
    div = first_divergence(a, b)
    if div is not None:
        r, p, ta, tb = div
        rec["first_divergence"] = {
            "request": r, "position": p, "a": ta, "b": tb,
            **tie_at_divergence(cfg, params, prompts[r], a[r][:p], ta, tb)}
    say(what, **rec)
    if gated and div is not None:
        require(rec["first_divergence"]["is_tie"],
                f"{what}: the runs diverge where the reference logits "
                "are not a tie", **rec)
    return rec


def phase_direct(sz: dict, seed: int, rehearse: bool, cache_dir: str,
                 cache_events: CacheEvents) -> None:
    import jax

    from pathway_tpu.kvcache.engine import PagedDecodeEngine
    from pathway_tpu.models.decoder import init_decoder_params
    from pathway_tpu.obs import profiler

    dev = jax.devices()[0]
    cfg = decoder_cfg(sz)
    params = block(init_decoder_params(cfg, jax.random.PRNGKey(seed)))
    prompts = direct_prompts(sz, cfg.vocab_size, seed)
    n_new = sz["direct_tokens"]
    # on the CPU the engine's own choice is the gather reference; a
    # rehearsal asks for the kernels (interpreted) to walk the same code
    kw = {"attn": "pallas"} if rehearse else {}
    reg = profiler.registry()

    def build(name, **extra):
        t0 = time.perf_counter()
        eng = PagedDecodeEngine(cfg, params, name=name, max_restarts=0,
                                **{**kw, **extra})
        block((eng.pool.k, eng.pool.v))
        return eng, t0

    # chained engine: first pass compiles, second is the steady state
    n0, c0 = cache_entries(cache_dir), reg.totals()["compile_s_total"]
    ev0 = cache_events.since()
    e8, t8 = build("smoke_k8", chain_steps=8)
    build_s = time.perf_counter() - t8
    say("engine", name="smoke_k8", attn=e8.attn, tp=e8.tp,
        auto_config=e8.auto_config, hbm_plan=e8.hbm_plan.as_dict(),
        prefill_chunk=e8.prefill_chunk, build_s=build_s,
        pool_device=str(next(iter(e8.pool.k.devices()))))
    require(rehearse or e8.attn == "pallas",
            'the engine did not choose attn="pallas" on the chip', attn=e8.attn)
    cold = serve_requests(e8, prompts, n_new, "smoke_k8_cold")
    first_compile_s = reg.totals()["compile_s_total"] - c0
    first_events = cache_events.since(ev0)
    n1 = cache_entries(cache_dir)
    warm = serve_requests(e8, prompts, n_new, "smoke_k8_warm")
    compare_runs("agreement_first_vs_second_pass", cold["tokens"],
                 warm["tokens"], cfg, params, prompts, gated=True)
    table = program_table(e8, not rehearse, t8)
    say("direct_serving", engine="smoke_k8", requests=len(prompts),
        new_tokens=n_new, prompt_lens=sorted(len(p) for p in prompts),
        first_pass_wall_s=cold["wall_s"], first_pass_compile_s=first_compile_s,
        steady_pass_wall_s=warm["wall_s"], accounting=warm["accounting"],
        cache_entries_added=n1 - n0, hbm=hbm(dev))
    say("kernel_table", rows=table)
    kernels_on_live_pool(e8, seed)
    # the first pass computed every prompt in full; the second found them
    # in the prefix cache.  What other engines are compared with is the
    # first: the same work in the same order.
    tokens_k8 = cold["tokens"]
    del e8, cold, warm
    say("released", engine="smoke_k8", hbm=released(dev))
    # stepwise engine: the same arithmetic, one step per dispatch
    e1, t1 = build("smoke_k1", chain_steps=1)
    tokens_k1 = serve_requests(e1, prompts, n_new, "smoke_k1")["tokens"]
    table1 = program_table(e1, not rehearse, t1)
    say("kernel_table", rows=table1)
    del e1
    say("released", engine="smoke_k1", hbm=released(dev))
    compare_runs("identity_chained_vs_stepwise", tokens_k8, tokens_k1, cfg,
                 params, prompts, gated=True)
    # gather-reference engine: printed, not gated — with random weights in
    # bf16 the argmax flips on rounding
    sub = sorted(range(len(prompts)), key=lambda i: len(prompts[i]))[
        :sz["n_reference"]]
    sub_prompts = [prompts[i] for i in sub]
    eref, tref = build("smoke_ref", chain_steps=8, attn="reference")
    ref_run = serve_requests(eref, sub_prompts, sz["reference_tokens"],
                             "smoke_ref")
    say("reference_engine", requests=len(sub),
        new_tokens=sz["reference_tokens"],
        wall_s_with_compile=ref_run["wall_s"],
        accounting=ref_run["accounting"])
    say("kernel_table", rows=program_table(eref, False, tref))
    compare_runs("agreement_pallas_vs_reference_engine",
                 [tokens_k8[i][:sz["reference_tokens"]] for i in sub],
                 ref_run["tokens"], cfg, params, sub_prompts, gated=False)
    del eref, ref_run
    say("released", engine="smoke_ref", hbm=released(dev))
    # the persistent cache, not jit's in-process one: after clear_caches a
    # second construction and warm-up compiles nothing anew
    jax.clear_caches()
    n2, c2 = cache_entries(cache_dir), reg.totals()["compile_s_total"]
    ev2 = cache_events.since()
    e8b, _ = build("smoke_k8_again", chain_steps=8)
    again = serve_requests(e8b, prompts, n_new, "smoke_k8_again")
    second_compile_s = reg.totals()["compile_s_total"] - c2
    second_events = cache_events.since(ev2)
    n3 = cache_entries(cache_dir)
    del e8b
    say("released", engine="smoke_k8_again", hbm=released(dev))
    say("compile_cache", dir=cache_dir, entries_before=n0,
        entries_after_first=n1, entries_before_second=n2,
        entries_after_second=n3, first_was_cold=n1 > n0,
        # trace + lower + compile-or-load, as the engine's programs saw it
        first_wall_compile_s=first_compile_s,
        second_wall_compile_s=second_compile_s,
        first=first_events, second=second_events)
    compare_runs("agreement_rebuilt_from_cache", again["tokens"], tokens_k8,
                 cfg, params, prompts, gated=True)
    require(n3 == n2, "the second construction added cache entries",
            before=n2, after=n3)
    require(second_events["misses"] == 0 and second_events["hits"] > 0,
            "the second construction compiled a program anew",
            **second_events)
    # reading the programs back costs a small fraction of what compiling
    # them cost (JAX keeps each entry's compile time with it, in whole
    # seconds: toy programs all read as zero)
    cost = second_events["compile_s_saved"] + second_events["retrieval_s"]
    require(rehearse or second_events["retrieval_s"] < 0.5 * cost,
            "reading the cache took a large part of a compile",
            **second_events)
    del params, again
    say("released", engine="direct phase", hbm=released(dev))


# -- the live-RAG pipeline ---------------------------------------------------------

class Corpus:
    """Seeded documents in a watched directory, and what is live in it."""

    def __init__(self, sz: dict, seed: int):
        self.rng = random.Random(seed)
        self.words = sz["doc_words"]
        self.vocab = [f"w{i:05d}" for i in range(20000)]
        self.dir = tempfile.mkdtemp(prefix="pw_smoke_docs_")
        # files are written here and renamed into the watched directory,
        # so the connector never lists one half-written
        self.stage = tempfile.mkdtemp(prefix="pw_smoke_stage_")
        self.live: dict[int, str] = {}      # doc id -> text the files hold
        self.deleted: dict[int, str] = {}
        self.rewritten: dict[int, str] = {}  # doc id -> the text before

    def _text(self, i: int) -> str:
        n = self.words + self.rng.randrange(-8, 9)
        return f"doc{i:06d} " + " ".join(
            self.rng.choice(self.vocab) for _ in range(n))

    def path(self, i: int) -> str:
        return os.path.join(self.dir, f"doc_{i:06d}.txt")

    @staticmethod
    def doc_id(path: str) -> int:
        return int(os.path.basename(path)[4:10])

    def write_wave(self, ids) -> None:
        for i in ids:
            self.live[i] = self._text(i)
            tmp = os.path.join(self.stage, os.path.basename(self.path(i)))
            with open(tmp, "w") as f:
                f.write(self.live[i])
            os.replace(tmp, self.path(i))

    def rewrite(self, ids) -> None:
        for i in ids:
            self.rewritten[i] = self.live[i]
        self.write_wave(ids)

    def delete(self, ids) -> None:
        for i in ids:
            self.deleted[i] = self.live.pop(i)
            os.unlink(self.path(i))

    def excerpt(self, text: str, n: int = 24) -> str:
        ws = text.split()[1:]
        s = self.rng.randrange(0, max(len(ws) - n, 1))
        return " ".join(ws[s:s + n])


def post(port: int, route: str, payload: dict, timeout: float = 300.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}", json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def concurrently(fns: list, timeout: float = 900.0) -> list:
    out: list = [None] * len(fns)
    errs: list = []

    def run(i):
        try:
            out[i] = fns[i]()
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            errs.append(exc)

    ths = [threading.Thread(target=run, args=(i,)) for i in range(len(fns))]
    for th in ths:
        th.start()
    for th in ths:
        th.join(timeout=timeout)
    if errs:
        raise errs[0]
    require(all(o is not None for o in out), "a REST request did not return")
    return out


def check_retrieval(wave: int, corpus: Corpus, index, enc, queries, replies,
                    k: int) -> dict:
    """ids from /v1/retrieve against a numpy top-k over the same embeddings,
    fetched once to the host in f32."""
    import numpy as np

    m_dev = index._device_matrix(prenorm=False)
    n = index._dev_valid
    M = np.asarray(block(m_dev), np.float32)[:n]
    ids = np.asarray([corpus.doc_id(index.metadata[key].value["path"])
                      for key in index.keys[:n]])
    require(set(ids.tolist()) == set(corpus.live),
            "the index does not hold exactly the live documents", wave=wave,
            in_index=len(ids), live=len(corpus.live))
    Mn = M / (np.linalg.norm(M, axis=1, keepdims=True) + 1e-12)
    Q = np.asarray(enc.embed_batch(queries), np.float32)
    Qn = Q / (np.linalg.norm(Q, axis=1, keepdims=True) + 1e-12)
    ref = Qn @ Mn.T  # (n_queries, n) f32 on the host
    exact, score_err = 0, 0.0
    pos = {int(d): j for j, d in enumerate(ids)}
    for qi, reply in enumerate(replies):
        got = [corpus.doc_id(r["metadata"]["path"]) for r in reply]
        require(len(got) == min(k, n) and len(set(got)) == len(got),
                "retrieve returned the wrong number of documents", wave=wave,
                query=qi, got=len(got))
        require(not set(got) & set(corpus.deleted),
                "a deleted document was returned", wave=wave, query=qi)
        row = ref[qi]
        order = np.argsort(-row)[:len(got)]
        kth = row[order[-1]]
        for r, d in zip(reply, got):
            s_ref = float(row[pos[d]])
            score_err = max(score_err, abs(float(r["score"]) - s_ref))
            require(s_ref >= kth - SCORE_TOL,
                    "retrieve returned a document outside the reference "
                    "top-k", wave=wave, query=qi, doc=d, ref_score=s_ref,
                    kth=float(kth))
        must = {int(ids[j]) for j in np.nonzero(row > kth + SCORE_TOL)[0]}
        require(must <= set(got),
                "retrieve missed a document of the reference top-k",
                wave=wave, query=qi, missing=sorted(must - set(got))[:5])
        exact += set(got) == {int(ids[j]) for j in order}
    require(score_err <= SCORE_TOL, "device scores off the host's f32",
            wave=wave, max_abs_err=score_err)
    # the index's rows are the encoder's output for the live texts
    sample = corpus.rng.sample(sorted(corpus.live), min(32, len(corpus.live)))
    stale = [d for d in sample if d in corpus.rewritten]
    fresh = [d for d in sample if d not in corpus.rewritten]
    E = np.asarray(enc.embed_batch([corpus.live[d] for d in fresh]), np.float32)
    cos = np.sum(E * Mn[[pos[d] for d in fresh]], axis=1)
    require(float(np.min(cos)) >= 1.0 - EMBED_COS_TOL,
            "an index row is not the embedding of its document", wave=wave,
            min_cos=float(np.min(cos)))
    return {
        "wave": wave, "docs_in_index": int(n), "queries": len(replies),
        "k": k, "exact_set_share": exact / len(replies),
        "max_abs_score_err": score_err, "score_tolerance": SCORE_TOL,
        "min_row_cos_vs_fresh_embedding": float(np.min(cos)),
        "rewritten_docs_in_sample_skipped": len(stale),
        "index_matrix": {
            "shape": list(m_dev.shape), "dtype": str(m_dev.dtype),
            "bytes": int(m_dev.nbytes),
            "device": str(next(iter(m_dev.devices()))),
        },
    }


def scores_kernel_on_live_index(index, enc, queries) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from pathway_tpu.ops.knn_pallas import pallas_scores

    import jax

    m = index._device_matrix(prenorm=False)
    q = jnp.asarray(enc.embed_batch(queries), jnp.float32)
    on_tpu = jax.default_backend() == "tpu"
    got = np.asarray(block(pallas_scores(q, m, interpret=not on_tpu)))
    xla = np.asarray(block(q @ m.T))
    ref = np.asarray(q, np.float32) @ np.asarray(m, np.float32).T
    out = {"shape": [int(q.shape[0]), int(m.shape[0]), int(m.shape[1])],
           "pallas_vs_host_f32": float(np.max(np.abs(got - ref))),
           "xla_vs_host_f32": float(np.max(np.abs(xla - ref))),
           "pallas_vs_xla": float(np.max(np.abs(got - xla))),
           "tolerance": SCORE_TOL,
           "kernel_mode": "compiled" if on_tpu else "interpreted"}
    say("pallas_scores_on_live_index", **out)
    require(np.isfinite(got).all() and out["pallas_vs_host_f32"] <= SCORE_TOL
            and out["pallas_vs_xla"] <= SCORE_TOL,
            "pallas_scores disagrees with the matmul", **out)
    return out


def phase_dataflow_tier(corpus: Corpus, enc, sz: dict) -> None:
    """A numeric select and groupby over rows of the ingested documents,
    through the dataflow's columnar planes."""
    import jax
    import numpy as np

    import pathway_tpu as pw
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.engine import vectorize
    from pathway_tpu.engine.runner import run_tables
    from pathway_tpu.internals import parse_graph as pg

    rows = []
    for d in sorted(corpus.live):
        toks = enc.tokenizer.encode(corpus.live[d])[:sz["vec_rows_per_doc"]]
        rows.extend((d, p, t) for p, t in enumerate(toks))

    class S(pw.Schema):
        doc: int
        pos: int
        tok: int

    pg.G.clear()
    for key in vectorize.STATS:
        vectorize.STATS[key] = 0
    t = table_from_rows(S, rows)
    sel = t.select(x=t.tok * 3 + t.pos, hi=t.tok > 15000)
    agg = sel.groupby(sel.hi).reduce(hi=sel.hi, n=pw.reducers.count(),
                                     total=pw.reducers.sum(sel.x))
    t0 = time.perf_counter()
    [cap] = run_tables(agg)
    wall = time.perf_counter() - t0
    pg.G.clear()
    got = {bool(hi): (int(n), int(total))
           for hi, n, total in cap.squash().values()}
    a = np.asarray(rows, np.int64)
    x, hi = a[:, 2] * 3 + a[:, 1], a[:, 2] > 15000
    want = {bool(b): (int((hi == b).sum()), int(x[hi == b].sum()))
            for b in (False, True) if (hi == b).any()}
    stats = dict(vectorize.STATS)
    tier_on = vectorize._jax_tier_on()
    threshold = vectorize._jax_threshold()
    say("dataflow_device_tier", rows=len(rows), wall_s=wall, stats=stats,
        tier_on=tier_on, float64_is_ieee=vectorize._f64_is_ieee(), rule=(
            "the jax tier is on when the default backend is not the CPU; "
            f"a micro-batch takes it from {threshold} rows; where float64 "
            "is emulated (a TPU) it takes integer plans only "
            "(engine/vectorize.py)"),
        backend=jax.default_backend(), result_equals_numpy=got == want)
    require(got == want, "select/groupby differs from numpy", got=got,
            want=want)
    require(stats["jax_failures"] == 0, "the jax tier failed", **stats)
    if tier_on and len(rows) >= threshold:
        require(stats["jax_batches"] > 0,
                "the jax tier is on and the batch is over its threshold, "
                "yet no batch took it", **stats)


def phase_rag(sz: dict, seed: int, rehearse: bool) -> None:
    import jax
    import numpy as np

    import pathway_tpu as pw
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.llms import JaxChat
    from pathway_tpu.xpacks.llm.question_answering import (
        AdaptiveRAGQuestionAnswerer,
    )

    dev = jax.devices()[0]
    corpus = Corpus(sz, seed)
    try:
        # on the chip the embedder keeps its vectors in HBM by its own
        # rule; a rehearsal on the CPU asks for the same path
        emb = SentenceTransformerEmbedder(
            config=encoder_cfg(sz), seed=seed,
            device_resident=True if rehearse else None)
        require(emb.device_resident, "the embedder does not keep its "
                "vectors on the device")
        enc = emb._enc
        docs = pw.io.fs.read(corpus.dir, format="binary", mode="streaming",
                             with_metadata=True)
        store = DocumentStore(docs, retriever_factory=BruteForceKnnFactory(
            dimensions=emb.get_embedding_dimension(), embedder=emb))
        # every route builds its own index from this factory; keep a
        # handle on each so the smoke can read the matrix it is checked
        # against
        indexes: list = []
        make_index = store.index.index_factory

        def recording_factory():
            indexes.append(make_index())
            return indexes[-1]

        store.index.index_factory = recording_factory
        chat = JaxChat(config=decoder_cfg(sz), seed=seed,
                       max_new_tokens=sz["answer_tokens"])
        rag = AdaptiveRAGQuestionAnswerer(chat, store, llm_scheduler=True)
        engine = chat.paged_engine()
        require(engine is not None, "paged_engine() returned None")
        say("rag_engine", attn=engine.attn, auto_config=engine.auto_config,
            hbm_plan=engine.hbm_plan.as_dict())
        # a server warms its programs before it takes traffic
        t0 = time.perf_counter()
        chat.generate_batch(["warm up " * 64, "warm"],
                            max_tokens=sz["answer_tokens"])
        warm_s = time.perf_counter() - t0
        st = engine.pool.stats
        served0 = st.ttft_count
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        rag.build_server("127.0.0.1", port)
        run_err: list = []

        def serve():
            try:
                pw.run(timeout_s=1100.0, idle_stop_s=45.0,
                       autocommit_duration_ms=50,
                       monitoring_level=pw.MonitoringLevel.NONE)
            except BaseException as exc:  # noqa: BLE001 - re-raised below
                run_err.append(exc)

        server = threading.Thread(target=serve, name="pw-run", daemon=True)
        server.start()

        def wait_indexed(want: int, deadline_s: float = 600.0) -> float:
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < deadline_s:
                if run_err:
                    raise run_err[0]
                try:
                    if post(port, "/v1/statistics", {},
                            timeout=60)["file_count"] == want:
                        return time.perf_counter() - t0
                except OSError:
                    pass  # the server is still coming up
                time.sleep(0.5)
            raise Check(f"{want} documents were not indexed in {deadline_s}s")

        n_waves, per = sz["n_waves"], sz["wave_docs"]
        for wave in range(1, n_waves + 2):
            t0 = time.perf_counter()
            if wave <= n_waves:
                corpus.write_wave(range((wave - 1) * per, wave * per))
            else:
                # the fifth wave: rewrite in place, and delete
                victims = corpus.rng.sample(sorted(corpus.live),
                                            sz["n_rewrite"] + sz["n_delete"])
                corpus.rewrite(victims[:sz["n_rewrite"]])
                corpus.delete(victims[sz["n_rewrite"]:])
            write_s = time.perf_counter() - t0
            indexed_s = wait_indexed(len(corpus.live))
            # a query is a document's own text: with random weights an
            # excerpt ranks by noise, the whole text ranks its document
            # first with cosine 1
            if wave <= n_waves:
                targets = corpus.rng.sample(sorted(corpus.live),
                                            sz["n_retrieve"])
                queries = [corpus.live[d] for d in targets]
            else:
                # half ask for deleted documents; the rest for rewritten
                # ones, by their old text and by their new text in turn
                gone = corpus.rng.sample(sorted(corpus.deleted),
                                         sz["n_retrieve"] // 2)
                redo = corpus.rng.sample(sorted(corpus.rewritten),
                                         sz["n_retrieve"] - len(gone))
                targets = gone + redo
                queries = ([corpus.deleted[d] for d in gone]
                           + [corpus.rewritten[d] if j % 2 == 0
                              else corpus.live[d]
                              for j, d in enumerate(redo)])
            t0 = time.perf_counter()
            replies = concurrently([
                (lambda q=q: post(port, "/v1/retrieve",
                                  {"query": q, "k": sz["k"]}))
                for q in queries])
            retrieve_s = time.perf_counter() - t0
            require(len(indexes) >= 1, "no index was built")
            require(len({ix.n for ix in indexes}) == 1,
                    "the routes' indexes disagree on their size",
                    sizes=[ix.n for ix in indexes])
            res = check_retrieval(wave, corpus, indexes[0], enc, queries,
                                  replies, sz["k"])
            top1 = sum(
                corpus.doc_id(r[0]["metadata"]["path"]) == d
                for r, d in zip(replies, targets))
            res.update(write_s=write_s, indexed_after_s=indexed_s,
                       retrieve_wall_s=retrieve_s,
                       source_doc_is_top1=f"{top1}/{len(targets)}",
                       hbm=hbm(dev))
            if wave <= n_waves:
                found = sum(
                    d in [corpus.doc_id(r["metadata"]["path"]) for r in reply]
                    for reply, d in zip(replies, targets))
                require(found == len(targets),
                        "a document is not among the answers to its own text",
                        wave=wave, found=found, first=top1)
            else:
                # a finding, not a gate: does a rewrite in place reach
                # the index?  The connector reads file content as
                # append-only (io/_utils.py FilePollingSource).
                tail = list(zip(replies[len(gone):], redo))

                def first_is(pairs, text_of):
                    hit = sum(reply[0]["text"] == text_of[d]
                              for reply, d in pairs)
                    return f"{hit}/{len(pairs)}"

                ix = indexes[0]
                stale = sum(
                    ix.metadata[key].value["size"]
                    != os.path.getsize(corpus.path(d))
                    for key in ix.keys[:ix.n]
                    for d in [corpus.doc_id(ix.metadata[key].value["path"])]
                    if d in corpus.rewritten)
                res.update(
                    rewritten=len(corpus.rewritten),
                    deleted=len(corpus.deleted),
                    asked_by_old_text_old_text_came_first=first_is(
                        tail[0::2], corpus.rewritten),
                    asked_by_new_text_new_text_came_first=first_is(
                        tail[1::2], corpus.live),
                    rewritten_docs_indexed_at_their_old_size=(
                        f"{stale}/{len(corpus.rewritten)}"))
            say("wave", **res)
        scores_kernel_on_live_index(indexes[0], enc, queries)
        # answers: concurrent, through the llm scheduler and the paged engine
        questions = [corpus.excerpt(corpus.live[d], 12)
                     for d in corpus.rng.sample(sorted(corpus.live),
                                                sz["n_answers"])]
        sched = rag._llm_scheduler
        done0, batches0 = sched.stats.completed, sched.stats.batches
        t0 = time.perf_counter()
        answers = concurrently([
            (lambda q=q: post(port, "/v1/pw_ai_answer", {"prompt": q}))
            for q in questions])
        answer_s = time.perf_counter() - t0
        n_tok = [len(a.split()) for a in answers]
        acct = {
            "requests": len(questions),
            "sequences_completed": st.ttft_count - served0,
            "engine_restarts": st.engine_restarts,
            "engine_degrades": st.engine_degraded,
            "scheduler_completed": sched.stats.completed - done0,
            "scheduler_batches": sched.stats.batches - batches0,
            "tokens_per_answer": n_tok,
        }
        say("answers", wall_s=answer_s, warm_up_s=warm_s, accounting=acct,
            hbm=hbm(dev))
        require(all(n == sz["answer_tokens"] for n in n_tok),
                "an answer has the wrong number of new tokens", **acct)
        require(acct["sequences_completed"] == len(questions)
                and acct["scheduler_completed"] == len(questions),
                "the answers did not all go through the paged engine", **acct)
        require(acct["engine_restarts"] == 0 and acct["engine_degrades"] == 0,
                "engine restarted or degraded", **acct)
        phase_dataflow_tier(corpus, enc, sz)
        if run_err:
            raise run_err[0]
    finally:
        shutil.rmtree(corpus.dir, ignore_errors=True)
        shutil.rmtree(corpus.stage, ignore_errors=True)


# -- four chips -----------------------------------------------------------------------

def phase_tp(sz: dict, seed: int, rehearse: bool) -> None:
    """Tensor-parallel paged decode over four devices against tp=1, and
    where ReplicaFleet puts its replicas.  Nothing else."""
    import jax
    import numpy as np

    from pathway_tpu.kvcache.engine import PagedDecodeEngine
    from pathway_tpu.models.decoder import init_decoder_params
    from pathway_tpu.serve import ReplicaFleet

    from pathway_tpu.obs import profiler

    reg = profiler.registry()
    devs = jax.devices()
    cfg = decoder_cfg(sz, vocab=sz["tp_vocab"])
    # weights are kept on the host, so that under tp=4 no device holds a
    # second, unsharded copy; the tp=1 engine gets them on its one device
    host_params = jax.device_get(
        init_decoder_params(cfg, jax.random.PRNGKey(seed)))
    prompts = direct_prompts(sz, cfg.vocab_size, seed)
    n_new = sz["direct_tokens"]
    kw = {"attn": "pallas"} if rehearse else {}
    results = {}
    for tp in (1, 4):
        base = [hbm(d)["bytes_in_use"] or 0 for d in devs]
        t0 = time.perf_counter()
        params = jax.device_put(host_params) if tp == 1 else host_params
        eng = PagedDecodeEngine(cfg, params, name=f"smoke_tp{tp}", tp=tp,
                                max_restarts=0, **kw)
        block((eng.pool.k, eng.pool.v, eng.params))
        build_s = time.perf_counter() - t0
        c0 = reg.totals()["compile_s_total"]
        run = serve_requests(eng, prompts, n_new, f"smoke_tp{tp}")
        compile_s = reg.totals()["compile_s_total"] - c0
        table = program_table(eng, not rehearse, t0)
        held = [(hbm(d)["bytes_in_use"] or 0) - b for d, b in zip(devs, base)]
        leaves = jax.tree_util.tree_leaves(eng.params)
        plan_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                         for l in leaves)
        pool_bytes = 2 * int(eng.pool.k.nbytes)
        wo = eng.params["layers"][0]["wo"]
        wqkv = eng.params["layers"][0]["wqkv"]
        placement = {
            "pool_k": sorted({str(s.device) for s in
                              eng.pool.k.addressable_shards}),
            "pool_shard_shape": list(
                eng.pool.k.addressable_shards[0].data.shape),
            "wqkv_column_parallel": sorted(
                {str(s.device) for s in wqkv.addressable_shards}),
            "wqkv_shard_shape": list(wqkv.addressable_shards[0].data.shape),
            "wo_row_parallel": sorted(
                {str(s.device) for s in wo.addressable_shards}),
            "wo_shard_shape": list(wo.addressable_shards[0].data.shape),
        }
        say("tp_engine", tp=tp, attn=eng.attn, auto_config=eng.auto_config,
            build_s=build_s, wall_s_with_compile=run["wall_s"],
            compile_s=compile_s, accounting=run["accounting"],
            placement=placement, plan_bytes=plan_bytes, pool_bytes=pool_bytes,
            bytes_held_per_device=held, kernel_table=table)
        if tp == 4:
            for name in ("pool_k", "wqkv_column_parallel", "wo_row_parallel"):
                require(len(placement[name]) == 4,
                        f"{name} is not on four distinct devices",
                        **placement)
            if not rehearse:
                quarter = (plan_bytes + pool_bytes) / 4
                require(all(0.5 * quarter <= h <= 2.0 * quarter
                            for h in held),
                        "a device does not hold about a quarter of pool + "
                        "weights", held=held, quarter=quarter)
            for row in table:
                # a psum after each row-parallel projection (two a layer)
                # and the gather of the two-stage argmax
                require(row["all_reduce"] >= 2 * cfg.n_layers
                        and row["all_gather"] >= 1,
                        "the compiled step lacks the expected collectives",
                        **row)
        results[tp] = run["tokens"]
        del eng, params, run
        say("released", engine=f"smoke_tp{tp}",
            hbm=[released(d) for d in devs])
    # the psum adds four partial products in another order than one
    # device's matmul, so in bf16 a near-tie argmax may flip, after which
    # the two continuations differ legitimately: the first divergence must
    # be such a tie
    compare_runs("agreement_tp4_vs_tp1", results[4], results[1], cfg,
                 jax.device_put(host_params), prompts, gated=True)
    # ReplicaFleet on four devices: recorded, not fixed
    fcfg = decoder_cfg(sz, vocab=sz["tp_vocab"], layers=sz["fleet_layers"])
    fparams = init_decoder_params(fcfg, jax.random.PRNGKey(seed))
    fleet = ReplicaFleet(fcfg, fparams, replicas=4, name="smoke_fleet",
                         max_restarts=0, **kw)
    def devices_of(leaf) -> set:
        shards = getattr(leaf, "addressable_shards", None)
        return ({str(s.device) for s in shards} if shards is not None
                else {"host (numpy)"})

    rows = []
    for rep in fleet._replicas:
        e = rep.engine
        rows.append({
            "replica": rep.idx, "tp": e.tp,
            "pool_devices": sorted(devices_of(e.pool.k)),
            "param_devices": sorted(set().union(*(
                devices_of(l) for l in jax.tree_util.tree_leaves(e.params)))),
        })
    say("replica_fleet_placement", depth=fcfg.n_layers, replicas=rows,
        devices=[str(d) for d in devs])
    fleet.shutdown()


# -- main -------------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="corpus, weights and prompts")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the tensor-parallel phase and its tp=1 "
                         "comparison, no other phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes, any platform, never prints ok")
    args = ap.parse_args()
    t_start = time.perf_counter()

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: no TPU here ({device}); nothing was run",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 1

    from importlib import metadata

    from pathway_tpu import native
    from pathway_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_events = CacheEvents()
    sz = TOY if args.rehearse else REAL
    t0 = time.perf_counter()
    require(native.get_lib() is not None,
            "the native library could not be built on this machine")
    native_s = time.perf_counter() - t0

    def version(pkg):
        try:
            return metadata.version(pkg)
        except metadata.PackageNotFoundError:
            return None

    say("environment", rehearsal=args.rehearse, seed=args.seed,
        chips=args.chips, device=device,
        versions={p: version(p) for p in ("jax", "jaxlib", "libtpu")},
        python=sys.version.split()[0], compile_cache_dir=cache_dir,
        compile_cache_entries=cache_entries(cache_dir),
        compile_cache_from_env=bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        native_lib_seconds=native_s, hbm=hbm(devs[0]), cuts=sz["cuts"])

    if args.chips == 4:
        phase_tp(sz, args.seed, args.rehearse)
    else:
        phase_direct(sz, args.seed, args.rehearse, cache_dir, cache_events)
        phase_rag(sz, args.seed, args.rehearse)
    say("done", wall_s=time.perf_counter() - t_start,
        compile_cache_entries=cache_entries(cache_dir),
        peak_hbm=[hbm(d)["peak_bytes_in_use"] for d in devs[:args.chips]])
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device,
                          "note": "a rehearsal proves nothing about the chip"}),
              flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
