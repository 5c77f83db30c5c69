"""``correct`` has to come out false when it should: under each cell's
low-precision control, and with the timed path broken underneath.  Toy
widths on the CPU (``--rehearse``); the same readings at the cells' own
sizes on the chip are in PERF.md.

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def rehearse(fault: str, *args: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive.py"), fault, *args,
         "--seed", "3", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])["would_be"]


def failing(would: dict) -> set:
    return {k for k, c in would["compared"].items()
            if not c["value"] <= c["limit"]}


# rag_live is not a cell of BENCHMARK.json yet (PERF.md, Open questions 1):
# its files are driven by name
CELLS = {"serve_closed16": ["--workload", "serve_closed16"],
         "rag_live": ["--config", "live-rag-minilm-gpt2-large",
                      "--traffic", "rag_live_mix"]}

# the CPU's index path re-specialises its top-k as the corpus changes
# (host rows, no row bucket): not what these tests are about
NOT_ON_CPU = {"compiles_in_window"}


@pytest.mark.parametrize("cell,fault,variant,must_fail", [
    ("serve_closed16", "none", None, set()),
    ("serve_closed16", "none", "int8_control", {"served_gap_per_near_tie"}),
    ("serve_closed16", "token_altered", None, {"served_gap_per_near_tie"}),
    ("serve_closed16", "token_altered_once", None, {"widest_gap"}),
    ("rag_live", "none", None, set()),
    ("rag_live", "none", "bf16_control", {"retrieve_score_err"}),
    ("rag_live", "none", "int8_control", {"answer_gap_per_near_tie"}),
    ("rag_live", "answer_altered", None, {"own_not_first"}),
    ("rag_live", "token_altered", None, {"answer_gap_per_near_tie"}),
])
def test_correct_fails_when_it_should(cell, fault, variant, must_fail):
    args = CELLS[cell] + (["--variant", variant] if variant else [])
    would = rehearse(fault, *args)
    failed = failing(would) - NOT_ON_CPU
    if must_fail:
        assert must_fail <= failed, (failed, would["compared"])
        assert would["correct"] is False
    else:
        assert not failed, (failed, would["compared"])
