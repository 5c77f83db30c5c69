"""The paged kernels at ``mimo_closed16_longshort``'s geometry against the
gather reference, on the chip, before the cell is run there.

    python3 benchmark/tests/smoke_mimo_v2_flash.py [--rehearse]

64 query heads of 192 over K/V heads of 192 (keys) and 128 (values): 4 on a
full layer, 8 on a sliding one (window 128, a sink a query head); bf16;
rows of a chunk of 512 (two kernel rows of 256 columns each), a short
tail, an idle row; one decode column through the fused append; the writer's
two row widths.  Prints one JSON line a case (the largest difference over
the live columns from the gather path over the same values in f32, in units
of its deviation) and exits 1 if one passes ``TOLERANCE`` (bf16
probabilities and values).
``--rehearse``: toy sizes, interpreted kernels, any platform.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TOLERANCE = 0.05


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    rehearse = "--rehearse" in sys.argv
    if not rehearse and jax.default_backend() != "tpu":
        print("smoke: no TPU here; nothing was run", file=sys.stderr)
        return 1
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")
    H, hd, hv, BS = (64, 192, 128, 16) if not rehearse else (16, 192, 128, 16)
    C, NB, B = (512, 64, 3) if not rehearse else (64, 8, 3)
    dtype = jnp.bfloat16 if not rehearse else jnp.float32
    kw = {"use_pallas": True, "interpret": rehearse}
    rng = np.random.default_rng(0)
    worst = 0.0

    def up(x):
        return x.astype(jnp.float32)

    def exact(fn):
        """The gather path over the same bf16 values in f32 (on bf16
        inputs it rounds its scores to bf16, which the kernels do not)."""
        with jax.default_matmul_precision("highest"):
            return np.asarray(fn(), np.float32)

    for kind, kv, window in (("full", H // 16, None), ("sliding", H // 8,
                                                       128 if not rehearse
                                                       else 24)):
        blocks = B * NB + 1
        kp = jnp.asarray(rng.standard_normal((blocks, BS, kv * hd)), dtype)
        vp = jnp.asarray(rng.standard_normal((blocks, BS, kv * hv)), dtype)
        bt = jnp.asarray(1 + np.arange(B * NB).reshape(B, NB), jnp.int32)
        # scores of deviation 2, as the cell's weights give them
        q = jnp.asarray(rng.standard_normal((B, C, H, hd)) * 2, dtype)
        sinks = None if window is None else jnp.asarray(
            rng.standard_normal(H) + np.log(window), jnp.float32)
        extra = {} if window is None else {"window": window, "sinks": sinks}
        start = np.array([NB * BS - C - 3, 40, 0])
        nvalid = np.array([C, 37, 1])
        args = dict(start_pos=jnp.asarray(start, jnp.int32),
                    n_valid=jnp.asarray(nvalid, jnp.int32), **extra)
        want = exact(lambda: pa.paged_attention(
            up(q), up(kp), up(vp), bt, use_pallas=False, **args))
        got = np.asarray(pa.paged_attention(q, kp, vp, bt, **kw, **args),
                         np.float32)
        err = max(float(np.abs(got[b, :n] - want[b, :n]).max()
                        / want[b, :n].std()) for b, n in enumerate(nvalid))
        print(json.dumps({"case": f"{kind}_ragged", "pieces":
                          pa.query_pieces(C, H, hd, kv * hd, dtype, hv),
                          "err": err}), flush=True)
        worst = max(worst, err)
        # one decode column through the fused append, and the writer
        ctx = np.array([NB * BS - 5, min(130, NB * BS - 20), 17])
        q1 = jnp.asarray(rng.standard_normal((B, 1, H, hd)) * 2, dtype)
        k1 = jnp.asarray(rng.standard_normal((B, kv, hd)), dtype)
        v1 = jnp.asarray(rng.standard_normal((B, kv, hv)), dtype)
        sb = jnp.asarray(np.asarray(bt)[np.arange(B), (ctx - 1) // BS])
        so = jnp.asarray((ctx - 1) % BS, jnp.int32)
        cl = jnp.asarray(ctx, jnp.int32)
        _a, k0, v0 = pa.paged_append_attend(q1, k1, v1, kp, vp, bt, cl, sb, so,
                                            use_pallas=False, **extra)
        a0 = exact(lambda: pa.paged_attention(
            up(q1), up(k0), up(v0), bt, cl, use_pallas=False, **extra))
        a1, k2, v2 = pa.paged_append_attend(q1, k1, v1, kp, vp, bt, cl, sb, so,
                                            **kw, **extra)
        err = float(np.abs(np.asarray(a1, np.float32) - a0).max()
                    / a0.std())
        same = bool(jnp.array_equal(k0, k2)) and bool(jnp.array_equal(v0, v2))
        print(json.dumps({"case": f"{kind}_append", "err": err,
                          "pools_equal": same}), flush=True)
        worst = max(worst, err, 0.0 if same else 1.0)
        T = 40
        kr = jnp.asarray(rng.standard_normal((T, kv, hd)), dtype)
        vr = jnp.asarray(rng.standard_normal((T, kv, hv)), dtype)
        wb = jnp.asarray([5] * 16 + [9] * 16 + [0] * 8, jnp.int32)
        wo = jnp.asarray(list(range(16)) * 2 + [0] * 8, jnp.int32)
        w0 = pa.paged_write_rows(kp, vp, wb, wo, kr, vr, use_pallas=False)
        w1 = pa.paged_write_rows(kp, vp, wb, wo, kr, vr, **kw)
        same = all(bool(jnp.array_equal(a[1:], b[1:])) for a, b in zip(w0, w1))
        print(json.dumps({"case": f"{kind}_write", "pools_equal": same}),
              flush=True)
        worst = max(worst, 0.0 if same else 1.0)
    print(json.dumps({"ok": worst < TOLERANCE, "worst": worst,
                      "device": jax.devices()[0].device_kind}), flush=True)
    return 0 if worst < TOLERANCE else 1


if __name__ == "__main__":
    sys.exit(main())
