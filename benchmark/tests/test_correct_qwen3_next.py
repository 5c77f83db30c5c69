"""``qwen3next_closed16_longshort``: the rehearsal of the cell, and
``correct`` failing when it should under each planted fault of
``drive_qwen3_next.py``.  Toy widths on the CPU (4 of 16 experts held, eight
layers ``L L L F`` x 2, contexts of up to 180 in chunks of 32); the same
faults' readings at the cell's own size on the chip stand in the
configuration file beside the limits they set.

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = ["--workload", "qwen3next_closed16_longshort"]


def rehearse(fault: str, *args: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive_qwen3_next.py"), fault,
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
    """``run.py --workload qwen3next_closed16_longshort --rehearse``: the
    harness finds the cell's configuration, system, traffic and metrics by
    name, runs them at toy widths and compares against the reference; no
    result line."""
    would = rehearse("none")
    assert would["attempted"] > 0 and would["failed"] == 0
    assert would["compared"] and would["correct"] is True, would["compared"]
    assert {"out_tok_per_s", "ttft_p95_ms", "setup_s"} <= set(
        would["metrics"])
    assert would["compared"]["long_context_gap"]["compared_tokens"] > 0
    # the state probe: 16 prompt tokens and the 32 served ones fed back
    probe = would["compared"]["state_gap"]
    assert probe["tokens"] == 48 and len(probe["by_layer"]) == 6


BOTH = {"served_gap_per_near_tie", "long_context_gap"}


@pytest.mark.parametrize("fault,args,must_fail", [
    ("rotary_on_the_whole_head", (), BOTH),
    ("attention_gate_left_out", (), BOTH),
    ("decay_left_out", (), BOTH),
    ("scales_not_zero_centred", (), BOTH),
    ("shared_gate_left_out", (), BOTH),
    ("sigmoid_router", (), BOTH),
    ("key_head_modulo", (), BOTH),
    ("token_altered_once", (), {"widest_gap"}),
    # the configuration's two low-precision controls, as the variants
    # run.py takes.  Every state through bf16 a step: at these f32 widths
    # the tokens show it too; at the cell's own (bf16 operands, replies of
    # 4 or 64 tokens) state_gap alone does, which is why it is compared
    ("none", ("--variant", "bf16_state"), BOTH | {"state_gap"}),
    ("none", ("--variant", "int8_control"), BOTH),
])
def test_correct_fails_under_a_planted_fault(fault, args, must_fail):
    would = rehearse(fault, *args)
    assert would["failed"] == 0, "a planted fault must not fail requests"
    assert must_fail <= failing(would), would["compared"]
    assert would["correct"] is False
