"""On the chip, before the cell is measured: the ``lfm2_moe`` cell's system
built at its own size, its kernels held to their references on the live
weights, pool and arena, and what its three step programs keep on the
device.

    python3 benchmark/tests/smoke_lfm2.py [--seed N] [--rehearse]

Every line is a JSON record; the last is ``{"ok": ...}``.  ``--rehearse``:
toy widths, any platform (interpreted kernels).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def say(record: str, **fields) -> None:
    print(json.dumps({"record": record, **fields}, default=str), flush=True)


def rel_err(got, want) -> float:
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def kernels(sut, seed: int, interpret: bool) -> dict:
    """Each kernel against its reference, on what the engine holds."""
    import importlib

    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    eng, cfg = sut.engine, sut.cfg
    dtype = eng.pool.k.dtype
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    out = {}
    # the grouped matmul on the first expert layer's own weights, a mixed
    # step's tokens routed by that layer's own router
    lay = eng.params["layers"][cfg.n_dense_layers]
    T = eng.mixed_tokens
    h = jax.random.normal(ks[0], (T, cfg.d_model), jnp.float32).astype(dtype)
    experts, _w, _s = moe.route(h, lay["wg"], lay.get("expert_bias"),
                                top_k=cfg.top_k)
    g = moe.group_rows(experts, jnp.arange(T) < T - 3, cfg.n_experts)
    args = (h[g["row_token"]], lay["w1"], lay["w3"], lay["w2"],
            g["tile_expert"], g["n_live"])
    live = int(g["n_live"][0]) * moe.TM
    got = moe._moe_gmm(*args, interpret=interpret)[:live]
    want = moe.moe_gmm_reference(*args)[:live]
    out["moe_gmm"] = {"rel_err": rel_err(got, want), "live_rows": live,
                      "experts_touched": int((g["counts"] > 0).sum())}
    # both paged kernels on the live pool, grouped queries
    B, NB = eng.max_batch_size, eng.max_blocks_per_seq
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ctx = jax.random.randint(ks[1], (B,), 40, min(850, NB * 16))
    bt = (1 + (jnp.arange(B * NB, dtype=jnp.int32) * 7
               % (eng.pool.num_blocks - 1))).reshape(B, NB)
    for name, C in (("ragged_decode", 1), ("ragged_chunk", eng.prefill_chunk)):
        q = jax.random.normal(ks[2], (B, C, H, hd), jnp.float32).astype(dtype)
        start = ctx - C
        nv = jnp.full((B,), C, jnp.int32)
        got = pa.paged_attention(q, eng.pool.k, eng.pool.v, bt,
                                 start_pos=start, n_valid=nv, layer=1,
                                 use_pallas=True, interpret=interpret)
        want = pa.paged_attention_reference(
            q, eng.pool.k[1], eng.pool.v[1], bt, start_pos=start, n_valid=nv)
        out[name] = {"rel_err": rel_err(got, want)}
    q = jax.random.normal(ks[3], (B, 1, H, hd), jnp.float32).astype(dtype)
    k1 = jax.random.normal(ks[4], (B, KV, hd), jnp.float32).astype(dtype)
    v1 = jax.random.normal(ks[5], (B, KV, hd), jnp.float32).astype(dtype)
    sb = bt[jnp.arange(B), (ctx - 1) // 16]
    so = (ctx - 1) % 16
    kk, vv = jnp.copy(eng.pool.k), jnp.copy(eng.pool.v)
    last = len(cfg.attn_layers) - 1
    a0, k0, v0 = pa.paged_append_attend(q, k1, v1, kk, vv, bt, ctx, sb, so,
                                        layer=last, use_pallas=False)
    a1, k_, v_ = pa.paged_append_attend(
        q, k1, v1, jnp.copy(eng.pool.k), jnp.copy(eng.pool.v), bt, ctx, sb,
        so, layer=last, use_pallas=True, interpret=interpret)
    out["append"] = {"rel_err": rel_err(a1, a0),
                     "pool_equal": bool((k0 == k_).all() & (v0 == v_).all())}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147927001)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax

    import chip_smoke
    from benchmark import run
    from benchmark.generators import closed_loop_requests as gen
    from benchmark.systems import serve_lfm2
    from pathway_tpu.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        print("smoke_lfm2: no TPU here; nothing was run", file=sys.stderr)
        return 1
    enable_compile_cache()
    config = run.load_json(run.HERE, "configs", "lfm2-8b-a1b-serve.json")
    traffic = run.load_json(run.HERE, "traffic", "closed16_rag_prompts.json")
    if args.rehearse:
        config = run.merged(config, config["rehearse"])
        traffic = run.merged(traffic, traffic["rehearse"])
    t0 = time.perf_counter()
    since = time.perf_counter()
    sut = serve_lfm2.build(config, args.seed, args.rehearse)
    t1 = time.perf_counter()
    say("built", seconds=t1 - t0, info=sut.info,
        bytes_in_use=(dev.memory_stats() or {}).get("bytes_in_use"))
    try:
        gen.warm(sut, traffic, args.seed)
        say("warmed", seconds=time.perf_counter() - t1,
            counters=sut.counters())
        rows = chip_smoke.program_table(sut.engine, not args.rehearse, since)
        for row in rows:
            say("program", **row)
        checks = kernels(sut, args.seed, interpret=args.rehearse)
        say("kernel_vs_reference", **checks)
        sut.engine.pool.check_invariants()
        plan = sut.engine.hbm_plan
        live = sut.engine.pool.per_shard_bytes + sum(
            l.size * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(sut.engine.params))
        ok = (len(rows) == 3
              and (args.rehearse
                   or all(r["pool_sized_copies"] == 0 for r in rows))
              and all(c["rel_err"] < 0.02 for c in checks.values())
              and checks["append"]["pool_equal"]
              and plan.params_bytes + plan.kv_bytes + plan.conv_bytes == live)
        say("memory", plan=plan.as_dict(), live_bytes=live,
            peak_bytes_in_use=(dev.memory_stats() or {}).get(
                "peak_bytes_in_use"))
    finally:
        sut.close()
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": dev.platform, "kind": dev.device_kind}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
