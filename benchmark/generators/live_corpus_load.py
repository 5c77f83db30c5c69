"""The load of ``live_corpus``, in a process of its own that never imports
JAX: file writer, REST clients, freshness probes and statistics polls do
not share an interpreter lock with the system they load.

    python3 live_corpus_load.py <plan.json> <result.json>

Everything is scheduled before the window from the plan; every request is
timed from when it was due, on ``time.perf_counter`` (CLOCK_MONOTONIC, one
clock for every process of the machine).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.error
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.generators import corpus  # noqa: E402
from benchmark.rest import post  # noqa: E402


class Load:
    def __init__(self, plan: dict):
        self.p = plan
        self.t0 = plan["t0"]
        self.seed, self.words = plan["seed"], plan["doc_words"]
        self.lock = threading.Lock()
        self.retrieves: list = []
        self.answers: list = []
        self.changes: list = []
        self.probes: list = []
        self.polls: list = []
        self.late: dict = {"retrieve": [], "change": [], "poll": []}
        self.live_count = plan["live_at_start"]
        self.stop = threading.Event()

    def text(self, i: int) -> str:
        return corpus.doc_text(self.seed, i, self.words)

    def until(self, offset: float, kind: str | None = None) -> float:
        due = self.t0 + offset
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if kind:
            self.late[kind].append(max(time.perf_counter() - due, 0.0))
        return due

    def call(self, route: str, payload: dict, timeout: float) -> dict:
        """One request: its reply, or why it failed (429/503/504 and
        time-outs are failures, never retried)."""
        t = time.perf_counter()
        try:
            reply, err = post(self.p["port"], route, payload, timeout), None
        except urllib.error.HTTPError as exc:
            reply, err = None, f"HTTP {exc.code}"
        except Exception as exc:  # noqa: BLE001 - counted as failed
            reply, err = None, f"{type(exc).__name__}: {exc}"
        return {"sent": t, "done": time.perf_counter(), "reply": reply,
                "error": err}

    # -- user retrievals: open loop ---------------------------------------------
    def retrieve_one(self, due: float, target: int) -> None:
        r = self.call("/v1/retrieve", {"query": self.text(target),
                                       "k": self.p["k"]}, 30.0)
        rec = {"due": due, "sent": r["sent"], "done": r["done"],
               "target": target, "error": r["error"], "hits": None}
        if r["reply"] is not None:
            rec["hits"] = [[corpus.doc_id(h["metadata"]["path"]),
                            float(h["score"])] for h in r["reply"]]
        with self.lock:
            self.retrieves.append(rec)

    def retrieve_loop(self, pool: ThreadPoolExecutor) -> list:
        futs = []
        for off, target in self.p["retrieves"]:
            due = self.until(off, "retrieve")
            futs.append(pool.submit(self.retrieve_one, due, target))
        return futs

    # -- changes and their probes ---------------------------------------------------
    def probe(self, kind: str, i: int, t_change: float) -> None:
        """Ask for the document's own text every ``probe_every_s`` until
        the reply shows the change, or the cap passes."""
        text, n = self.text(i), 0
        seen = None
        while time.perf_counter() - t_change < self.p["probe_cap_s"]:
            n += 1
            r = self.call("/v1/retrieve", {"query": text, "k": self.p["k"]},
                          self.p["probe_cap_s"])
            if r["reply"] is not None:
                ids = [corpus.doc_id(h["metadata"]["path"])
                       for h in r["reply"]]
                if (ids[:1] == [i]) if kind == "add" else (i not in ids):
                    seen = r["done"]
                    break
            nxt = t_change + n * self.p["probe_every_s"]
            time.sleep(max(nxt - time.perf_counter(), 0.0))
        with self.lock:
            self.probes.append({"kind": kind, "doc": i, "t_change": t_change,
                                "t_seen": seen, "requests": n})

    def change_loop(self, pool: ThreadPoolExecutor) -> list:
        futs = []
        d, stage = self.p["dir"], self.p["stage"]
        for off, kind, i, tracked in self.p["changes"]:
            self.until(off, "change")
            if kind == "add":
                tmp = os.path.join(stage, corpus.doc_name(i))
                with open(tmp, "w") as f:
                    f.write(self.text(i))
                os.replace(tmp, os.path.join(d, corpus.doc_name(i)))
            else:
                os.unlink(os.path.join(d, corpus.doc_name(i)))
            t = time.perf_counter()
            with self.lock:
                self.live_count += 1 if kind == "add" else -1
                self.changes.append({"t": t, "kind": kind, "doc": i})
            if tracked:
                futs.append(pool.submit(self.probe, kind, i, t))
        return futs

    # -- answers: closed loop ---------------------------------------------------------
    def answer_client(self, c: int) -> None:
        n = 0
        while not self.stop.is_set():
            i = self.p["answer_docs"][c][n % len(self.p["answer_docs"][c])]
            n += 1
            q = corpus.excerpt(self.seed, i, self.words, 12)
            r = self.call("/v1/pw_ai_answer", {"prompt": q}, 120.0)
            with self.lock:
                self.answers.append({"sent": r["sent"], "done": r["done"],
                                     "doc": i, "question": q,
                                     "error": r["error"],
                                     "text": r["reply"]})

    # -- statistics polls: open loop ---------------------------------------------------------
    def poll_one(self) -> None:
        with self.lock:
            live = self.live_count
        r = self.call("/v1/statistics", {}, 30.0)
        with self.lock:
            self.polls.append({
                "t": r["done"], "live": live, "error": r["error"],
                "indexed": None if r["reply"] is None
                else r["reply"]["file_count"]})

    def poll_loop(self, pool: ThreadPoolExecutor) -> list:
        futs, k = [], 0
        while k * self.p["poll_every_s"] < self.p["seconds"]:
            self.until(k * self.p["poll_every_s"], "poll")
            k += 1
            futs.append(pool.submit(self.poll_one))
        return futs

    # -- after the window: what the index holds -------------------------------------------
    def final(self, pool: ThreadPoolExecutor) -> dict:
        deadline = time.perf_counter() + self.p["probe_cap_s"]
        count = None
        while time.perf_counter() < deadline:
            r = self.call("/v1/statistics", {}, 10.0)
            count = None if r["reply"] is None else r["reply"]["file_count"]
            if count == self.live_count:
                break
            time.sleep(0.2)
        r = self.call("/v1/inputs", {}, 120.0)
        paths = None if r["reply"] is None else [
            corpus.doc_id(m["path"]) for m in r["reply"]]

        def own_one(i: int) -> list:
            rr = self.call("/v1/retrieve", {"query": self.text(i), "k": 1},
                           60.0)
            return [i, None if rr["reply"] is None else [
                [corpus.doc_id(h["metadata"]["path"]), float(h["score"])]
                for h in rr["reply"]]]

        def gone_one(i: int) -> list:
            rr = self.call("/v1/retrieve", {"query": self.text(i),
                                            "k": self.p["k"]}, 60.0)
            return [i, None if rr["reply"] is None else [
                corpus.doc_id(h["metadata"]["path"]) for h in rr["reply"]]]

        own = list(pool.map(own_one, self.p["final_live"]))
        gone = list(pool.map(gone_one, self.p["final_gone"]))
        return {"live_on_disk": self.live_count, "file_count": count,
                "inputs": paths, "inputs_error": r["error"], "own": own,
                "gone": gone}

    def run(self) -> dict:
        with ThreadPoolExecutor(max_workers=self.p["max_in_flight"]) as pool:
            ans = [threading.Thread(target=self.answer_client, args=(c,))
                   for c in range(len(self.p["answer_docs"]))]
            out: dict = {}
            poll = threading.Thread(
                target=lambda: out.update(p=self.poll_loop(pool)))
            chg = threading.Thread(
                target=lambda: out.update(c=self.change_loop(pool)))
            self.until(0.0)
            for th in ans + [poll, chg]:
                th.start()
            futs = self.retrieve_loop(pool)
            self.until(self.p["seconds"])
            self.stop.set()
            chg.join()
            poll.join()
            for f in futs + out.get("c", []) + out.get("p", []):
                f.result()
            for th in ans:
                th.join()
            final = self.final(pool)

        def late(v):
            v = sorted(v)
            return {"n": len(v), "p50_ms": 1e3 * v[len(v) // 2] if v else 0.0,
                    "max_ms": 1e3 * v[-1] if v else 0.0}

        return {"retrieves": self.retrieves, "answers": self.answers,
                "changes": self.changes, "probes": self.probes,
                "polls": self.polls, "final": final,
                "lateness": {k: late(v) for k, v in self.late.items()}}


def main() -> int:
    with open(sys.argv[1]) as f:
        plan = json.load(f)
    result = Load(plan).run()
    tmp = sys.argv[2] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, sys.argv[2])
    return 0


if __name__ == "__main__":
    sys.exit(main())
