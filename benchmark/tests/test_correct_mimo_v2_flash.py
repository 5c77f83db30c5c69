"""``mimo_closed16_longshort``: the rehearsal of the cell, and ``correct``
failing when it should under each planted fault of
``drive_mimo_v2_flash.py`` and under the low-precision control.  Toy widths
on the CPU (a window of 24 under contexts of up to 180, 4 of 16 experts
held); the same faults' readings at the cell's own size on the chip stand
in the configuration file beside the limits they set.

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = ["--workload", "mimo_closed16_longshort"]


def rehearse(fault: str, *args: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive_mimo_v2_flash.py"), fault,
         *CELL, *args, "--seed", "3", "--trace", "0"],
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
    """``run.py --workload mimo_closed16_longshort --rehearse``: the harness
    finds the cell's configuration, system, traffic and metrics by name,
    runs them at toy widths and compares against the reference; no result
    line."""
    would = rehearse("none")
    assert would["attempted"] > 0 and would["failed"] == 0
    assert would["compared"] and would["correct"] is True, would["compared"]
    assert {"out_tok_per_s", "ttft_p95_ms", "setup_s"} <= set(
        would["metrics"])
    assert would["compared"]["long_context_gap"]["compared_tokens"] > 0
    router = would["compared"]["router_weight_gap"]
    assert router["compared_pairs"] > 0.9 * router["position_layer_pairs"]


@pytest.mark.parametrize("fault,args,must_fail", [
    ("sink_left_out", (), {"served_gap_per_near_tie"}),
    ("sink_on_full_layers", (), {"served_gap_per_near_tie"}),
    ("window_127", (), {"served_gap_per_near_tie"}),
    ("window_129", (), {"served_gap_per_near_tie"}),
    ("sliding_heads_by_16", (), {"served_gap_per_near_tie"}),
    ("rotary_on_all_192", (), {"served_gap_per_near_tie"}),
    ("thetas_swapped", (), {"long_context_gap"}),
    ("value_scale_left_out", (), {"served_gap_per_near_tie"}),
    ("scores_over_sqrt_128", (), {"served_gap_per_near_tie"}),
    ("v_at_k_stride", (), {"served_gap_per_near_tie"}),
    ("bias_in_the_weights", (), {"served_gap_per_near_tie",
                                 "router_weight_gap"}),
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
