"""``trinity_closed16_longshort``: the rehearsal of the cell, and
``correct`` failing when it should under each planted fault of
``drive_afmoe.py``.  Toy widths on the CPU (a window of 24 under contexts
of up to 180); the same faults' readings at the cell's own size on the chip
stand in the configuration file beside the limits they set.

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = ["--workload", "trinity_closed16_longshort"]


def rehearse(fault: str, *args: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive_afmoe.py"), fault, *CELL,
         *args, "--seed", "3", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert not any(line.startswith('{"correct"') for line in lines), \
        "a rehearsal printed a result line"
    return json.loads(lines[-1])["would_be"]


def failing(would: dict) -> set:
    return {k for k, c in would["compared"].items()
            if not c["value"] <= c["limit"]}


def test_the_cell_rehearses():
    """``run.py --workload trinity_closed16_longshort --rehearse``: the
    harness finds the cell's configuration, system, traffic and metrics by
    name, runs them at toy widths and compares against the reference; no
    result line."""
    would = rehearse("none")
    assert would["attempted"] > 0 and would["failed"] == 0
    assert would["compared"] and would["correct"] is True, would["compared"]
    assert {"out_tok_per_s", "ttft_p95_ms", "setup_s"} <= set(
        would["metrics"])
    assert would["compared"]["beyond_window_gap"]["compared_tokens"] > 0


@pytest.mark.parametrize("fault,args,must_fail", [
    ("window_ignored", (), {"beyond_window_gap"}),
    ("window_block_freed_early", (), {"beyond_window_gap"}),
    ("rope_on_full_layers", (), {"served_gap_per_near_tie"}),
    ("gate_left_out", (), {"served_gap_per_near_tie"}),
    ("shared_expert_left_out", (), {"served_gap_per_near_tie"}),
    ("top_k_less_one", (), {"served_gap_per_near_tie"}),
    ("route_scale_left_out", (), {"served_gap_per_near_tie"}),
    ("token_altered_once", (), {"widest_gap"}),
    # no fault in the program: the configuration's low-precision control
    # (the program's matrices rounded to 8 bits a weight)
    ("none", ("--variant", "int8_control"), {"served_gap_per_near_tie"}),
])
def test_correct_fails_under_a_planted_fault(fault, args, must_fail):
    would = rehearse(fault, *args)
    assert would["failed"] == 0, "a planted fault must not fail requests"
    assert must_fail <= failing(would), would["compared"]
    assert would["correct"] is False
