"""Generator ``closed_loop_requests``: N clients, each sends its next
request the moment its last one completes.

Parameters (the traffic file): ``clients`` (a number, or ``"engine_batch"``
for the system's own batch); ``classes``, the kinds of request of the mix,
each with ``count`` (how many of them the pool holds), ``prompt`` and
``output`` (a length: a number, or ``{"base", "pieces", "piece": [lo,
hi]}`` for ``base`` tokens plus ``pieces`` parts of ``lo``..``hi`` tokens
each); ``pool_seed`` and ``block`` (below); ``max_total``; ``warm`` (phases
of ``clients``/``prompt``/``output`` run before anything else, with ids
drawn apart from the window's); ``drain_s``.

The pool of (prompt, output) lengths is drawn once from ``pool_seed`` and
is the same for every ``--seed``; the seed orders it.  The order is
stratified: the pool is sorted by class and prompt length and dealt, a
stratum at a time, into pools/``block`` blocks of ``block`` requests, so
that every ``block`` consecutive requests hold one request of every
stratum and any stretch of the stream is nearly the same work, whichever
the seed.  Each pass through the pool is dealt and shuffled anew.  Token
ids (and the system's weights) come from the seed; every prompt is unique.
"""

from __future__ import annotations

import math
import random
import threading
import time

import numpy as np

WINDOW, WARM = 1, 2  # id streams, so warm-up prompts are apart from the window's


def _length(spec, rng: random.Random) -> int:
    if isinstance(spec, dict):
        lo, hi = spec["piece"]
        return int(spec["base"]) + sum(rng.randint(lo, hi)
                                       for _ in range(int(spec["pieces"])))
    return int(spec)


def length_pool(params: dict) -> list:
    """(class index, prompt, output) of every request of the pool, sorted:
    drawn from ``pool_seed`` alone, so every seed sends the same sizes."""
    rng = random.Random(int(params["pool_seed"]))
    pool = []
    for c, cls in enumerate(params["classes"]):
        for _ in range(int(cls["count"])):
            o = _length(cls["output"], rng)
            p = min(_length(cls["prompt"], rng), params["max_total"] - o)
            pool.append((c, p, o))
    if len(pool) % int(params["block"]):
        raise ValueError("the pool must hold a whole number of blocks")
    return sorted(pool)


def pass_order(pool: list, block: int, seed: int, n: int) -> list:
    """The ``n``-th pass through the pool, in the seed's order."""
    rng = random.Random(f"{int(seed)}:{n}")
    n_blocks = len(pool) // block
    blocks: list = [[] for _ in range(n_blocks)]
    for s in range(0, len(pool), n_blocks):
        stratum = pool[s: s + n_blocks]
        rng.shuffle(stratum)
        for b, item in zip(blocks, stratum):
            b.append(item)
    for b in blocks:
        rng.shuffle(b)
    return [item for b in blocks for item in b]


def request_stream(params: dict, vocab: int, seed: int):
    """Endless (prompt ids, n_out), deterministic in the seed."""
    pool = length_pool(params)
    idx = n = 0
    while True:
        for _c, p, o in pass_order(pool, int(params["block"]), seed, n):
            yield prompt_ids(vocab, seed, WINDOW, idx, p), o
            idx += 1
        n += 1


def prompt_ids(vocab: int, seed: int, stream: int, idx: int, n: int) -> list:
    g = np.random.default_rng([int(seed), stream, idx])
    return g.integers(4, vocab, n).tolist()


class Recorder:
    """One request's clock: due, first token, done, every token's time."""

    def __init__(self, idx: int, prompt: list, n_out: int, due: float):
        self.rec = {"idx": idx, "prompt": prompt, "n_out": n_out, "due": due,
                    "t_first": None, "t_done": None, "tokens": [],
                    "token_times": [], "error": None}

    def on_token(self, tok: int) -> None:
        now = time.perf_counter()
        r = self.rec
        if r["t_first"] is None:
            r["t_first"] = now
        r["token_times"].append(now)


def send(sut, rec: Recorder, timeout_s: float) -> dict:
    r = rec.rec
    try:
        r["tokens"] = [int(t) for t in sut.submit(
            r["prompt"], r["n_out"], rec.on_token, timeout_s)]
    except Exception as exc:  # noqa: BLE001 - a failed request is counted
        r["error"] = f"{type(exc).__name__}: {exc}"
    r["t_done"] = time.perf_counter()
    return r


def n_clients(sut, params: dict) -> int:
    c = params["clients"]
    return sut.clients if c == "engine_batch" else int(c)


def warm(sut, params: dict, seed: int) -> None:
    idx = 0
    for phase in params["warm"]:
        recs = []
        for c in range(int(phase["clients"])):
            n = phase["prompt"] + c
            recs.append(Recorder(idx, prompt_ids(sut.vocab_size, seed, WARM,
                                                 idx, n),
                                 phase["output"] + c % 7, time.perf_counter()))
            idx += 1
        ths = [threading.Thread(target=send, args=(sut, r, 600.0))
               for r in recs]
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        bad = [r.rec["error"] for r in recs if r.rec["error"]]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0]}")


def live_tokens(records: list, t0: float, t1: float, every_s: float) -> dict:
    """Tokens of context the requests in flight hold, polled through the
    window: a request's prompt fills between its due time and its first
    token, then every token delivered adds one.  What the traffic keeps
    live in the cache, whatever the cache itself retains."""
    levels = []
    t = t0
    while t <= t1:
        level = 0.0
        for r in records:
            if r["due"] <= t < r["t_done"]:
                p = len(r["prompt"])
                if r["t_first"] is None or t < r["t_first"]:
                    end = r["t_first"] if r["t_first"] is not None else r["t_done"]
                    level += p * (t - r["due"]) / (end - r["due"])
                else:
                    level += p + sum(x <= t for x in r["token_times"])
        levels.append(level)
        t += every_s
    return {"mean": sum(levels) / len(levels), "peak": max(levels)}


def summarize(records: list, t0: float, t1: float) -> dict:
    """Samples, counts and events of one window's requests."""
    failed = sum(r["error"] is not None or len(r["tokens"]) != r["n_out"]
                 for r in records)
    # a request that never gave a first token misses the tail: it reads inf
    ttft = [r["t_first"] - r["due"] if r["t_first"] is not None else math.inf
            for r in records]
    decode, prefill = [], []
    for r in records:
        p = len(r["prompt"])
        decode += [(t, p + j) for j, t in enumerate(r["token_times"]) if j]
        if r["t_first"] is not None:
            prefill.append((r["due"], r["t_first"], p))
    in_win = sum(t0 <= t <= t1 for r in records for t in r["token_times"])
    third = (t1 - t0) / 3
    thirds = [sorted(1e3 * x for x, r in zip(ttft, records)
                     if t0 + k * third <= r["due"] < t0 + (k + 1) * third)
              for k in range(3)]
    return {
        "requests": records, "attempted": len(records), "failed": failed,
        "samples": {"ttft_s": ttft,
                    "e2e_s": [r["t_done"] - r["due"] for r in records
                              if r["error"] is None]},
        "counters": {"client.tokens_in_window": float(in_win),
                     "client.window_s": t1 - t0,
                     "client.requests": float(len(records))},
        # decode: (when delivered, context); prefill: (due, first token,
        # prompt length) - a prompt is prefilled between those two
        "events": {"decode": decode, "prefill": prefill},
        # whether the window is one regime: the requests due in each third
        # of it, and the context they keep live
        "steady": {"ttft_ms_by_third": [
            {"n": len(v), "p50": v[len(v) // 2] if v else None,
             "max": v[-1] if v else None} for v in thirds],
            "live_context_tokens": live_tokens(records, t0, t1, 0.25)},
        "t0": t0, "t1": t1,
    }


def run(sut, params: dict, seed: int, seconds: float, on_start, on_end) -> dict:
    stream = request_stream(params, sut.vocab_size, seed)
    lock = threading.Lock()
    records: list = []
    t0 = time.perf_counter()
    t1 = t0 + seconds
    on_start(t0)

    def client() -> None:
        while True:
            with lock:
                due = time.perf_counter()
                if due >= t1:
                    return
                prompt, n_out = next(stream)
                rec = Recorder(len(records), prompt, n_out, due)
                records.append(rec.rec)
            send(sut, rec, seconds + params["drain_s"])

    ths = [threading.Thread(target=client, name=f"client-{i}")
           for i in range(n_clients(sut, params))]
    for th in ths:
        th.start()
    time.sleep(max(t1 - time.perf_counter(), 0.0))
    on_end(time.perf_counter())
    for th in ths:
        th.join(timeout=seconds + params["drain_s"] + 30.0)
    if any(th.is_alive() for th in ths):
        raise RuntimeError("a client did not return after the window")
    out = summarize(records, t0, t1)
    out["lateness_ms"] = {"note": "closed loop: a request is due the moment "
                          "its client's last one completed", "max": 0.0}
    return out
