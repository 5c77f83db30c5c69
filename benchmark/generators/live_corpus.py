"""Generator ``live_corpus``: a corpus that changes while it is queried.

Parameters (the traffic file): ``add_rate_per_s`` (new files, Poisson, open
loop) with one delete for every ``delete_every`` adds; ``retrieve_rate_per_s``
(user ``/v1/retrieve`` with ``k``, the query a preloaded document's whole
text, Poisson, open loop); ``answer_clients`` closed-loop clients on
``/v1/pw_ai_answer``; ``tracked_per_s`` changed documents a second probed
every ``probe_every_s`` until they show (cap ``probe_cap_s``);
``poll_every_s`` statistics polls; ``warm``; ``sweep``.

The load runs in a child process (``live_corpus_load.py``) that never
imports JAX.  Every seed has the same multisets of gaps, in another order.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import tempfile
import threading
import time

from benchmark.generators import corpus
from benchmark.generators.open_loop_requests import schedule

LOAD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "live_corpus_load.py")
WARM_BASE = 900000  # warm-up documents: ids apart from the window's


def make_plan(sut, params: dict, seed: int, seconds: float,
              add_rate: float | None = None,
              retrieve_rate: float | None = None,
              answer_clients: int | None = None, victim_offset: int = 0,
              id_offset: int = 0) -> dict:
    pre = sut.preloaded
    add_rate = params["add_rate_per_s"] if add_rate is None else add_rate
    q_rate = params["retrieve_rate_per_s"] if retrieve_rate is None \
        else retrieve_rate
    n_ans = params["answer_clients"] if answer_clients is None \
        else answer_clients
    order = list(range(pre))
    random.Random(seed ^ 0xC0FFEE).shuffle(order)
    adds = schedule(add_rate, seconds, seed) if add_rate > 0 else []
    n_del = len(adds) // params["delete_every"]
    # a quarter of the preloaded documents may be deleted, the rest asked for
    victims = order[: pre // 4][victim_offset: victim_offset + n_del]
    if len(victims) < n_del:
        raise ValueError("more deletes than the victim pool holds")
    order = order[pre // 4:]
    per = max(min(64, len(order) // (4 * max(n_ans, 1))), 1)
    answer_docs = [order[c * per:(c + 1) * per] for c in range(n_ans)]
    order = order[n_ans * per:]
    targets = order[: max(len(order) // 2, 1)]
    changes = []
    for j, off in enumerate(adds):
        changes.append([off, "add", pre + id_offset + j])
        if (j + 1) % params["delete_every"] == 0:
            changes.append([off, "delete", victims[(j + 1) // params["delete_every"] - 1]])
    per_s = len(changes) / seconds if seconds else 0.0
    every = max(int(round(per_s / params["tracked_per_s"])), 1) \
        if params["tracked_per_s"] > 0 else 0
    for n, c in enumerate(changes):
        c.append(bool(every) and n % every == every - 1)
    retrieves = [[off, targets[n % len(targets)]] for n, off in enumerate(
        schedule(q_rate, seconds, seed + 1))] if q_rate > 0 else []
    rng = random.Random(seed ^ 0xF1AA1)
    final_live = rng.sample(order, min(params["final_live"], len(order)))
    final_live += [pre + id_offset + j for j in rng.sample(
        range(len(adds)), min(params["final_added"], len(adds)))]
    return {
        "seed": seed, "seconds": seconds, "port": sut.port, "dir": sut.dir,
        "stage": sut.stage, "doc_words": sut.words, "preloaded": pre,
        "live_at_start": pre,
        "k": params["k"], "probe_every_s": params["probe_every_s"],
        "probe_cap_s": params["probe_cap_s"],
        "poll_every_s": params["poll_every_s"],
        "max_in_flight": params["max_in_flight"], "changes": changes,
        "retrieves": retrieves, "answer_docs": answer_docs,
        "final_live": final_live,
        "final_gone": victims[: params["final_gone"]],
    }


def run_load(sut, plan: dict, on_start=None, on_end=None) -> dict:
    """Start the child, mark the window's two ends, wait for its result."""
    work = tempfile.mkdtemp(prefix="pw_bench_load_")
    plan_path = os.path.join(work, "plan.json")
    out_path = os.path.join(work, "result.json")
    plan["t0"] = time.perf_counter() + plan.get("lead_s", 1.5)
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    env = {k: v for k, v in os.environ.items() if not k.startswith("JAX_")}
    child = subprocess.Popen([sys.executable, LOAD, plan_path, out_path],
                             env=env)
    try:
        time.sleep(max(plan["t0"] - time.perf_counter(), 0.0))
        if on_start:
            on_start(plan["t0"])
        end = plan["t0"] + plan["seconds"]
        while time.perf_counter() < end:
            sut.check()
            if child.poll() is not None:
                raise RuntimeError(f"the load process ended early "
                                   f"({child.returncode})")
            time.sleep(min(0.25, max(end - time.perf_counter(), 0.0)))
        if on_end:
            on_end(time.perf_counter())
        rc = child.wait(timeout=plan["probe_cap_s"] + 300.0)
        if rc != 0:
            raise RuntimeError(f"the load process failed ({rc})")
        with open(out_path) as f:
            return json.load(f)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        for p in (plan_path, out_path):
            if os.path.exists(p):
                os.unlink(p)
        os.rmdir(work)


def warm(sut, params: dict, seed: int) -> None:
    """The window's shapes, with documents and questions drawn apart:
    answers and retrievals together, then batches of new files (one encoder
    batch bucket each) and their deletion."""
    w = params["warm"]
    t0 = time.perf_counter()
    posts = [("/v1/pw_ai_answer",
              {"prompt": corpus.excerpt(seed, WARM_BASE + c, sut.words, 12)})
             for c in range(w["answers"])]
    posts += [("/v1/retrieve", {"query": corpus.doc_text(
        seed, WARM_BASE + 100 + i, sut.words), "k": params["k"]})
        for i in range(w["retrieves"])]
    ths = [threading.Thread(target=sut.post, args=a) for a in posts]
    for th in ths:
        th.start()
    base, n_live, marks = WARM_BASE, sut.preloaded, []
    for n in w["add_batches"]:
        corpus.write_docs(sut.dir, sut.stage, seed, range(base, base + n),
                          sut.words)
        base += n
        n_live += n
        marks.append(round(sut.wait_indexed(n_live), 3))
    sut.post("/v1/retrieve", {"query": corpus.doc_text(
        seed, WARM_BASE, sut.words), "k": params["k"]})
    for i in range(WARM_BASE, base):
        os.unlink(os.path.join(sut.dir, corpus.doc_name(i)))
    marks.append(round(sut.wait_indexed(sut.preloaded), 3))
    for th in ths:
        th.join()
    sut.check()
    sut.note("warm", seconds=time.perf_counter() - t0, indexed_after_s=marks)


def summarize(res: dict, plan: dict, t0: float, t1: float) -> dict:
    cap = plan["probe_cap_s"]
    user = res["retrieves"]
    fresh = [(p["t_seen"] - p["t_change"]) if p["t_seen"] is not None
             else cap for p in res["probes"]]
    answers = [a for a in res["answers"] if a["sent"] < t1]
    polls = res["polls"]
    failed = sum(r["error"] is not None for r in user) \
        + sum(p["t_seen"] is None for p in res["probes"]) \
        + sum(a["error"] is not None for a in answers) \
        + sum(p["error"] is not None for p in polls)
    attempted = len(user) + sum(p["requests"] for p in res["probes"]) \
        + len(answers) + len(polls)
    late = res["lateness"]
    return {
        "attempted": attempted, "failed": failed,
        "samples": {
            "retrieve_s": [r["done"] - r["due"] for r in user],
            "fresh_s": fresh,
            "answer_s": [a["done"] - a["sent"] for a in answers
                         if a["error"] is None],
            "ingest_lag_docs": [abs(p["live"] - p["indexed"]) for p in polls
                                if p["indexed"] is not None],
        },
        "counters": {"client.window_s": t1 - t0,
                     "client.retrieves": float(len(user)),
                     "client.changes": float(len(res["changes"])),
                     "client.tracked": float(len(res["probes"])),
                     "client.answers": float(len(answers))},
        "events": {}, "t0": t0, "t1": t1, "result": res, "plan": plan,
        "lateness_ms": late,
    }


def run(sut, params: dict, seed: int, seconds: float, on_start, on_end) -> dict:
    plan = make_plan(sut, params, seed, seconds)
    res = run_load(sut, plan, on_start, on_end)
    return summarize(res, plan, plan["t0"], plan["t0"] + seconds)


def sweep(sut, params: dict, seed: int, say) -> None:
    """Rates stepped in one process after one set-up: first retrievals
    alone, then adds under the chosen retrieval rate and the answer
    clients.  Each step is a short window of its own; documents a step
    added stay, so later steps see a slightly larger corpus."""
    sw = params["sweep"]
    n_added = n_deleted = 0
    for phase, rates in (("retrieve", sw["retrieve_rates"]),
                         ("add", sw["add_rates"])):
        for rate in rates:
            kw = dict(add_rate=0.0, retrieve_rate=rate, answer_clients=0) \
                if phase == "retrieve" else dict(add_rate=rate)
            plan = make_plan(sut, params, seed, sw["seconds"],
                             victim_offset=n_deleted, id_offset=n_added, **kw)
            plan["final_live"], plan["final_gone"] = [], []
            plan["live_at_start"] = sut.preloaded + n_added - n_deleted
            res = run_load(sut, plan)
            s = summarize(res, plan, plan["t0"], plan["t0"] + sw["seconds"])
            n_add = sum(c["kind"] == "add" for c in res["changes"])
            n_added += n_add
            n_deleted += len(res["changes"]) - n_add
            lag = s["samples"]["ingest_lag_docs"]
            rs = sorted(s["samples"]["retrieve_s"])
            fs = sorted(s["samples"]["fresh_s"])
            say("sweep_step", phase=phase, rate=rate, seconds=sw["seconds"],
                attempted=s["attempted"], failed=s["failed"],
                retrieves=len(rs),
                retrieve_p50_ms=1e3 * rs[len(rs) // 2] if rs else None,
                retrieve_p95_ms=1e3 * rs[int(0.95 * (len(rs) - 1))] if rs else None,
                fresh_p50_ms=1e3 * fs[len(fs) // 2] if fs else None,
                fresh_max_ms=1e3 * fs[-1] if fs else None,
                lag_first_third=sum(lag[: len(lag) // 3]) / max(len(lag) // 3, 1),
                lag_last_third=sum(lag[-(len(lag) // 3):]) / max(len(lag) // 3, 1),
                lag_final=lag[-1] if lag else None,
                answers=len(s["samples"]["answer_s"]),
                lateness=res["lateness"])
            sut.wait_indexed(sut.preloaded + n_added - n_deleted,
                             deadline_s=120.0)
