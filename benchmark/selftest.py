"""``python -m benchmark.selftest``: the yardstick checked against numbers
worked out by hand, on the CPU.

The trace reduction on the small recorded trace; the FLOPs and bytes
functions at the GPT-2-large and MiniLM shapes; the trace's readers on
hand-made work; the generators'
determinism in the seed and their independence of the system's speed;
``run.py`` refusing to run without a TPU; ``--rehearse`` walking the cell and
the two mixes no cell names yet (``rag_live_mix``, ``serve_chat_open``) at
toy widths.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def near(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def check_trace() -> None:
    from benchmark import trace_reduce as T

    t = T.Trace(T.load(os.path.join(HERE, "trace_sample",
                                    "tiny_trace.textproto")), n_devices=1)
    assert near(t.busy_s(), 0.007), t.busy_s()
    s0, s1 = t.span()
    assert near(s1 - s0, 0.010), (s0, s1)
    d0, d1 = t.device_span()
    assert near(d1 - d0, 0.010), (d0, d1)
    assert [round(x, 9) for x in t.events(T.MODULES_LINE, "mixed")] \
        == [0.003, 0.003]
    assert [round(x, 9) for x in t.events(T.MODULES_LINE, "chained")] \
        == [0.002]
    assert [round(x, 9) for x in t.events(T.OPS_LINE, "paged")] \
        == [0.0015, 0.0015], "an operand's mention must not count"
    gaps = dict(t.idle_gaps(s0, s1))
    assert near(gaps["pw.mixed_step"], 0.0015, 1e-6), gaps
    assert near(gaps["pw.chain_dispatch"], 0.0015, 1e-6), gaps
    assert t.device_ops()[0][0].startswith("_paged_append_fn.204"), \
        t.device_ops()


def check_flops() -> None:
    from benchmark import flops

    gpt2 = {"vocab_size": 50257, "d_model": 1280, "n_layers": 36,
            "n_heads": 20, "d_ff": 5120, "max_len": 1024}
    assert flops.decoder_flops_per_token(gpt2, 0) == 1415577600 + 128657920
    assert flops.decoder_flops_per_token(gpt2, 300) == 1599531520
    assert flops.decoder_flops_prompt(gpt2, 1) \
        == flops.decoder_flops_per_token(gpt2, 1)
    minilm = {"vocab_size": 30522, "d_model": 384, "n_layers": 6,
              "n_heads": 12, "d_ff": 1536, "max_len": 512}
    assert flops.encoder_flops(minilm, 1, 128) == 2868903936
    # 16 rows at context 300, bf16: 2 * 300 * 1280 * 36 * 2 bytes a row
    peak = flops.peaks("TPU v5 lite")
    least = flops.paged_attention_least_s(gpt2, [300] * 16, [], 2, peak)
    assert least["bytes"] == 16 * 55296000 and least["bound"] == "memory"
    assert near(least["least_s"], 16 * 55296000 / 819e9)
    # half of a 300-token prompt's prefill: half of its K/V read once
    half = flops.paged_attention_least_s(gpt2, [], [(300, 0.5)], 2, peak)
    assert half["bytes"] == 55296000 / 2
    try:
        flops.peaks("TPU v9")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")


def check_readers() -> None:
    """``step_mfu`` and the kernel's roofline over the recorded trace: the
    work of the traced stretch over the trace's own 10 ms, whatever the
    host's clock says the stretch lasted."""
    import types

    from benchmark import flops
    from benchmark import trace_reduce as T
    from benchmark.readers import mfu, roofline_share

    gpt2 = {"vocab_size": 50257, "d_model": 1280, "n_layers": 36,
            "n_heads": 20, "d_ff": 5120, "max_len": 1024}
    run = types.SimpleNamespace(
        peaks=flops.peaks("TPU v5 lite"), chips=1,
        trace=T.Trace(T.load(os.path.join(
            HERE, "trace_sample", "tiny_trace.textproto")), n_devices=1),
        trace_window=(100.0, 100.02), info={"decoder": gpt2, "kv_itemsize": 2},
        # 16 tokens at context 300 inside the stretch and one outside; a
        # 100-token prompt whose prefill lies half inside
        events={"decode": [(100.01, 300)] * 16 + [(99.0, 300)],
                "prefill": [(99.98, 100.02, 100), (98.0, 99.0, 500)]})
    spec = {"flops": "decoder_flops_per_token", "shape": "decoder",
            "prompt_flops": "decoder_flops_prompt",
            "decode_events": "decode", "prefill_events": "prefill"}
    work = 16 * 1599531520 + 0.5 * flops.decoder_flops_prompt(gpt2, 100)
    assert near(mfu.read(spec, run), 100.0 * work / (0.010 * 197e12))
    spec.update(line=T.OPS_LINE, pattern="^_paged_append_fn",
                least="paged_attention_least_s")
    least = (16 * 55296000 + 0.5 * 55296000 / 3) / 819e9
    assert near(roofline_share.read(spec, run), 100.0 * least / 0.003)
    run.trace = None
    assert mfu.read(spec, run) is None, "no trace: no reading"


def check_generators() -> None:
    from benchmark.generators import closed_loop_requests as closed
    from benchmark.generators import open_loop_requests as open_loop

    with open(os.path.join(HERE, "traffic", "closed16_rag_prompts.json")) as f:
        params = json.load(f)

    def first(seed, n=128):
        g = closed.request_stream(params, 50257, seed)
        return [next(g) for _ in range(n)]

    def sizes(reqs):
        return [(len(p), o) for p, o in reqs]

    a, b, c = first(7), first(7), first(2**31 + 8)
    assert a == b, "the same seed must give the same requests"
    assert sizes(a) != sizes(c), "another seed: another order"
    for k in (0, 64):  # each pass through the pool: the same sizes
        assert sorted(sizes(a)[k: k + 64]) == sorted(sizes(c)[k: k + 64])
    for reqs in (a, c):  # any 16 in a row: nearly the same work
        for k in range(0, 128, 16):
            block = sizes(reqs)[k: k + 16]
            assert sum(o for _p, o in block) == 604, block
            assert abs(sum(p for p, _o in block) - 5934) < 60, block
    assert len({tuple(p) for p, _ in a + c}) == 256, "every prompt unique"
    assert all(224 <= len(p) <= 883 and o in (4, 64) and len(p) + o <= 1024
               for p, o in a)
    assert max(len(p) for p, _ in a) > 800, "the 8-document prompts are there"
    # a request that never answered misses the tail and never reads as fast
    from benchmark.readers.client_percentile import percentile
    assert percentile([1.0, 2.0, float("inf")], 95) == float("inf")
    assert percentile(list(range(100)) + [float("inf")], 95) == 95
    s1 = open_loop.schedule(5.0, 20.0, 3, {"size": 8, "every_s": 5.0})
    assert s1 == open_loop.schedule(5.0, 20.0, 3, {"size": 8, "every_s": 5.0})
    assert s1 == sorted(s1) and all(0 <= t < 20.0 for t in s1)
    assert abs(len(s1) - (100 + 24)) <= 2, len(s1)
    g3 = sorted(round(y - x, 9) for x, y in zip(
        [0.0] + open_loop.schedule(5.0, 20.0, 3), open_loop.schedule(5.0, 20.0, 3)))
    g4 = sorted(round(y - x, 9) for x, y in zip(
        [0.0] + open_loop.schedule(5.0, 20.0, 4), open_loop.schedule(5.0, 20.0, 4)))
    assert g3 == g4, "every seed: the same gaps in another order"


def run_py(*args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), *args], env=env,
        capture_output=True, text=True, timeout=600)


def check_run() -> None:
    r = run_py("--workload", "serve_closed16", "--seed", "1", "--seconds",
               "1", "--trace", "0")
    assert r.returncode != 0 and not r.stdout.strip(), \
        "without a TPU nothing may run and no line may be printed"
    for args in (("--workload", "serve_closed16"),
                 ("--config", "live-rag-minilm-gpt2-large", "--traffic",
                  "rag_live_mix"),
                 ("--config", "gpt2-large-serve", "--traffic",
                  "serve_chat_open")):
        r = run_py(*args, "--seed", "2", "--rehearse", "--trace", "0")
        assert r.returncode == 0, r.stderr[-2000:]
        last = json.loads(r.stdout.strip().splitlines()[-1])
        assert "correct" not in last and last["record"] == "rehearsal", \
            "a rehearsal must not print a result line"
        would = last["would_be"]
        assert would["compared"] and would["attempted"] > 0, would
        print("  rehearsed", " ".join(args), "correct:", would["correct"],
              "failed:", would["failed"])


def main() -> int:
    for fn in (check_trace, check_flops, check_readers, check_generators,
               check_run):
        print(fn.__name__, "...", flush=True)
        fn()
    print("selftest ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
