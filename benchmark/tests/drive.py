"""Drives one rehearsal of ``run.py`` (toy widths, any platform: the
harness's look for a chip is skipped) with the timed path broken
underneath, and prints what ``correct`` came to.

    python3 benchmark/tests/drive.py <fault|none> <run.py arguments ...> [--measure]
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def token_altered() -> None:
    """A token altered where it is produced: the ids the engine reads back
    from the device, first row, plus one."""
    from pathway_tpu.kvcache.engine import PagedDecodeEngine

    sync = PagedDecodeEngine._sync_host

    def altered(self, dev_array):
        ids = sync(self, dev_array).copy()
        ids.reshape(-1)[0] = (ids.reshape(-1)[0] + 1) % self.cfg.vocab_size
        return ids

    PagedDecodeEngine._sync_host = altered


def token_altered_once() -> None:
    """One served token altered where the engine records it, once, in the
    middle of the window: the first token emitted after half the window
    (the harness's ``window_start`` record arms it).  BENCH_FAULT_AFTER_S
    overrides the wait, for a window of another length."""
    import time

    from benchmark import run
    from pathway_tpu.kvcache.engine import PagedDecodeEngine

    state = {"at": None, "done": False}
    say, emit = run.say, PagedDecodeEngine._emit

    def armed_say(record, **fields):
        if record == "window_start":
            state["at"] = time.perf_counter() + float(
                os.environ.get("BENCH_FAULT_AFTER_S", "2.0"))
        say(record, **fields)

    def altered(self, req, token_id):
        if not state["done"] and state["at"] is not None \
                and time.perf_counter() >= state["at"]:
            state["done"] = True
            token_id = (token_id + 1) % self.cfg.vocab_size
            say("fault", planted="token_altered_once", token=token_id)
        emit(self, req, token_id)

    run.say = armed_say
    PagedDecodeEngine._emit = altered


def answer_altered() -> None:
    """A retrieval answer altered where it is produced: the top-k program's
    best row gives way to the row after it."""
    from pathway_tpu.ops import knn

    def shifted(fn):
        def run(matrix, query, k, *a, **kw):
            vals, idx = fn(matrix, query, k + 1, *a, **kw)
            return vals[..., 1:], idx[..., 1:]
        return run

    knn.device_topk = shifted(knn.device_topk)
    knn.batched_topk = shifted(knn.batched_topk)


FAULTS = {"none": lambda: None, "token_altered": token_altered,
          "token_altered_once": token_altered_once,
          "answer_altered": answer_altered}


def main() -> int:
    """``--measure`` in place of the rehearsal: the cell's own size, on the
    chip (how the faults' readings of PERF.md were taken)."""
    FAULTS[sys.argv[1]]()
    from benchmark import run

    rest = sys.argv[2:]
    if "--measure" in rest:
        rest.remove("--measure")
        return run.main(rest)
    return run.main(rest + ["--rehearse"])


if __name__ == "__main__":
    sys.exit(main())
