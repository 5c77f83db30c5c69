"""Generator ``open_loop_requests``: requests sent on a schedule, whatever
the system does.

Parameters: ``rate_per_s`` (Poisson arrivals), optional ``burst`` (``size``
requests together every ``every_s`` seconds), and the length mix, warm-up
and ``drain_s`` of ``closed_loop_requests``.  The schedule is made before
the window from the seed alone: every seed has the same multiset of gaps
(quantiles of the exponential), in another order.  A request is timed from
when it was due, and how late the generator ran is reported.
"""

from __future__ import annotations

import math
import random
import time
from concurrent.futures import ThreadPoolExecutor

from benchmark.generators import closed_loop_requests as closed


def schedule(rate_per_s: float, seconds: float, seed: int,
             burst: dict | None = None) -> list:
    """Due times (seconds from the window's start), sorted."""
    n = int(rate_per_s * seconds)
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate_per_s for i in range(n)]
    # the quantile gaps sum to about n / rate: scale them onto the window,
    # with one mean gap of room after the last arrival
    scale = seconds / (sum(gaps) * (n + 1) / n) if n else 1.0
    random.Random(seed ^ 0xA771).shuffle(gaps)
    due, t = [], 0.0
    for g in gaps:
        t += g * scale
        due.append(t)
    if burst:
        k = 1
        while k * burst["every_s"] < seconds:
            due += [k * burst["every_s"]] * int(burst["size"])
            k += 1
    return sorted(d for d in due if d < seconds)


def warm(sut, params: dict, seed: int) -> None:
    closed.warm(sut, params, seed)


def run(sut, params: dict, seed: int, seconds: float, on_start, on_end) -> dict:
    due = schedule(params["rate_per_s"], seconds, seed, params.get("burst"))
    stream = closed.request_stream(params, sut.vocab_size, seed)
    work = [(d, *next(stream)) for d in due]
    records: list = []
    late: list = []
    timeout_s = seconds + params["drain_s"]
    t0 = time.perf_counter()
    on_start(t0)
    with ThreadPoolExecutor(max_workers=params.get("max_in_flight", 64)) as ex:
        futs = []
        for i, (d, prompt, n_out) in enumerate(work):
            wait = t0 + d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late.append(max(time.perf_counter() - (t0 + d), 0.0))
            rec = closed.Recorder(i, prompt, n_out, t0 + d)
            records.append(rec.rec)
            futs.append(ex.submit(closed.send, sut, rec, timeout_s))
        time.sleep(max(t0 + seconds - time.perf_counter(), 0.0))
        on_end(time.perf_counter())
        for f in futs:
            f.result(timeout=timeout_s + 30.0)
    out = closed.summarize(records, t0, t0 + seconds)
    late.sort()
    out["lateness_ms"] = {
        "p50": 1e3 * late[len(late) // 2] if late else 0.0,
        "max": 1e3 * late[-1] if late else 0.0}
    return out
