"""On the chip, before the cell is measured: the ``kimi_linear`` cell's new
kernels held to their plain references at the cell's own widths, each
timed, and the two forms a prefill chunk's latent attention can take timed
against each other.

    python3 benchmark/tests/smoke_kimi_linear.py [--seed N] [--rehearse]

Every line is a JSON record; the last is ``{"ok": ...}``.  ``--rehearse``:
toy widths, any platform (interpreted kernels), no timing worth reading.
"""

from __future__ import annotations

import argparse
import importlib
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


def timed(fn, *args, n: int = 5) -> float:
    """Milliseconds a call, after one call that compiles."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


def kda(seed: int, toy: bool) -> dict:
    """A mixed step's KDA mixing (sixteen decode rows and one chunk of 256)
    and a decode step's, kernels against the token recurrence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pathway_tpu.ops import kda as k

    H, d, chunk, rows, run, layers = (2, 16, 16, 4, 40, 2) if toy \
        else (32, 128, 128, 16, 256, 10)
    n_dec = rows - 1
    T = n_dec + run
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    dt = jnp.float32 if toy else jnp.bfloat16

    def unit(key):
        x = jax.random.normal(key, (T, H, d), jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    beta = jax.nn.sigmoid(jax.random.normal(ks[0], (T, H, 1)))
    q, kk = unit(ks[1]) * d ** -0.5, unit(ks[2])
    v = jax.random.normal(ks[3], (T, H, d), jnp.float32)
    g = -jnp.exp(jnp.log(1e-4) + jax.random.uniform(ks[4], (T, H, d))
                 * (jnp.log(0.1) - jnp.log(1e-4)))
    q, kk, kb, vb = (x.astype(dt) for x in (q, kk, kk * beta, v * beta))
    state = jax.random.normal(ks[5], (layers, rows + 1, H, d, d), jnp.float32)
    first = np.arange(rows, dtype=np.int32)
    nvalid = np.ones(rows, np.int32)
    nvalid[-1] = run
    start = np.full(rows, 700, np.int32)
    J = jnp.asarray
    slot = J(np.arange(1, rows + 1, dtype=np.int32))
    live = J(np.ones(rows, bool))
    items = k.chunk_items(J(first), J(start == 0), J(nvalid), slot, live, T,
                          chunk)

    def mixed(state, pallas):
        return k.kda_mixed(q, kk, kb, vb, g, state, 1, items, J(first),
                           J(start == 0), J(nvalid), slot, live,
                           use_pallas=pallas)

    o, s1 = mixed(jnp.array(state), True)
    worst_o = worst_s = 0.0
    for r in (0, rows - 1):
        sl = slice(int(first[r]), int(first[r]) + int(nvalid[r]))
        want_o, want_s = k.kda_recurrence(q[sl], kk[sl], kb[sl], vb[sl],
                                          g[sl], state[1, r + 1])
        worst_o = max(worst_o, rel_err(o[sl], want_o))
        worst_s = max(worst_s, rel_err(s1[1, r + 1], want_s))
    out = {"mixed_o_rel_err": worst_o, "mixed_state_rel_err": worst_s}
    if not toy:
        # each kernel alone, on a live arena (the call donates it)
        li = jnp.ones((1,), jnp.int32)
        tok = items["token"]
        NW, n = tok.shape

        def gather(x):
            return x[tok].reshape(NW, n, -1)

        def per_call(kernel, args, tail):
            """Median milliseconds of a call that donates the arena and
            hands it back; every operand is on the device before."""
            arena = jax.block_until_ready(jnp.array(state))
            t = []
            for _ in range(6):
                t0 = time.perf_counter()
                _o, arena = kernel(*args, arena, *tail)
                jax.block_until_ready(arena)
                t.append(1e3 * (time.perf_counter() - t0))
            return sorted(t[1:])[len(t) // 2 - 1]

        out["chunk_kernel_ms"] = per_call(
            k._kda_chunk, jax.block_until_ready(
                (gather(q), gather(kk), gather(kb), gather(vb), gather(g))),
            jax.block_until_ready((li, items["slot"], items["flag"])))
        out["chunk_items_live"] = int(items["n_live"])
        out["step_kernel_ms"] = per_call(
            k._kda_step, jax.block_until_ready(
                (*(k._columns(x[:rows]) for x in (jnp.exp(g), kk, kb, q)),
                 vb[:rows])),
            jax.block_until_ready((li, slot, jnp.zeros((rows,), jnp.int32))))
    return out


def latent(seed: int, toy: bool) -> dict:
    """The latent pool's three kernels against their gather references, and
    a chunk of 256 queries at a context of 4,096 in both forms: absorbed
    (the kernel, all heads folded on the stored row) and expanded (the
    context's rows through W_kv_b, then attention a head, plain XLA)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    H, r, rope, nope, dv, W, BS, C, ctx = (4, 32, 8, 16, 16, 128, 8, 32, 96) \
        if toy else (32, 512, 64, 128, 128, 640, 16, 256, 4096)
    NB = ctx // BS + C // BS
    blocks = 3 * NB + 1
    dt = jnp.float32 if toy else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 8)
    pool = jax.random.normal(ks[0], (2, blocks, BS, W), jnp.float32)
    pool = pool.at[..., r + rope:].set(0).astype(dt)
    rows = 3
    tables = jnp.asarray(np.arange(1, rows * NB + 1, dtype=np.int32)
                         .reshape(rows, NB))
    q = (jax.random.normal(ks[1], (rows, C, H, W), jnp.float32)
         * W ** -0.25).astype(dt)
    start = jnp.asarray([ctx, 5, ctx // 2], jnp.int32)
    nv = jnp.asarray([C, 1, C // 2 + 3], jnp.int32)
    scale = float((nope + rope) ** -0.5)
    kw = dict(start_pos=start, n_valid=nv, scale=scale, layer=1)
    want = pa.latent_attention(q, pool, tables, use_pallas=False, **kw)
    got = pa.latent_attention(q, pool, tables, use_pallas=True, **kw)
    out = {"ragged_rel_err": max(
        rel_err(got[b, :int(nv[b])], want[b, :int(nv[b])])
        for b in range(rows))}
    new = jax.random.normal(ks[2], (rows, W), jnp.float32).astype(dt)
    cl = start + 1
    sb, so = tables[jnp.arange(rows), (cl - 1) // BS], (cl - 1) % BS
    a0, p0 = pa.latent_append_attend(q[:, :1], new, jnp.array(pool), tables,
                                     cl, sb, so, scale=scale, layer=1,
                                     use_pallas=False)
    a1, p1 = pa.latent_append_attend(q[:, :1], new, jnp.array(pool), tables,
                                     cl, sb, so, scale=scale, layer=1,
                                     use_pallas=True)
    out["append_rel_err"] = rel_err(a1, a0)
    out["append_pool_equal"] = bool((p0 == p1).all())
    T = 2 * BS + 3
    wsb = jnp.asarray([7] * BS + [9] * BS + [11] * 3, jnp.int32)
    wso = jnp.asarray(list(range(BS)) * 2 + [0, 1, 2], jnp.int32)
    rows_new = jax.random.normal(ks[3], (T, W), jnp.float32).astype(dt)
    w0 = pa.latent_write_rows(jnp.array(pool), wsb, wso, rows_new, layer=0,
                              use_pallas=False)
    w1 = pa.latent_write_rows(jnp.array(pool), wsb, wso, rows_new, layer=0,
                              use_pallas=True)
    out["write_pool_equal"] = bool((w0 == w1).all())
    if not toy:
        one = dict(start_pos=start[:1], n_valid=nv[:1], scale=scale, layer=1)
        out["absorbed_chunk_ms"] = timed(
            lambda: pa.latent_attention(q[:1], pool, tables[:1],
                                        use_pallas=True, **one))
        w_kvb = (jax.random.normal(ks[4], (r, H, nope + dv), jnp.float32)
                 * r ** -0.5).astype(dt)
        qe = q[0, :, :, :nope + rope]
        n_keys = ctx + C

        @jax.jit
        def expanded(pool, qe):
            lat = pool[1][tables[0]].reshape(-1, W)[:n_keys]
            kv = jnp.einsum("kr,rhd->khd", lat[:, :r], w_kvb)
            key = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(
                    lat[:, None, r:r + rope], (n_keys, H, rope))], -1)
            s = jnp.einsum("qhd,khd->hqk", qe, key,
                           preferred_element_type=jnp.float32) * scale
            seen = jnp.arange(n_keys)[None, :] <= ctx + jnp.arange(C)[:, None]
            p = jax.nn.softmax(jnp.where(seen[None], s, -1e9), -1)
            return jnp.einsum("hqk,khd->qhd", p.astype(dt), kv[..., nope:])

        out["expanded_chunk_ms"] = timed(expanded, pool, qe)
        out["chunk_queries"], out["chunk_keys"] = C, n_keys
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        print("smoke_kimi_linear: no TPU here; nothing was run",
              file=sys.stderr)
        return 1
    say("environment", platform=dev.platform, kind=dev.device_kind)
    k = kda(args.seed, args.rehearse)
    say("kda", **k)
    lat = latent(args.seed, args.rehearse)
    say("latent", **lat)
    tol = 1e-4 if args.rehearse else 3e-2
    ok = max(k["mixed_o_rel_err"], k["mixed_state_rel_err"],
             lat["ragged_rel_err"], lat["append_rel_err"]) < tol \
        and lat["append_pool_equal"] and lat["write_pool_equal"]
    print(json.dumps({"ok": bool(ok), "device": dev.device_kind}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
