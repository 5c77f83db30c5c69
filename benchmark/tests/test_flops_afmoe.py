"""``benchmark/flops_afmoe.py`` against numbers worked out by hand at
Trinity-Mini's published widths, the cell's eight layers (six window, two
full; one dense, seven expert layers).

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import pytest

from benchmark import flops, flops_afmoe as F

S, A = "sliding_attention", "full_attention"
SHAPE = {"vocab_size": 200192, "d_model": 2048, "n_heads": 32,
         "n_kv_heads": 4, "head_dim": 128, "d_ff": 6144, "d_ff_expert": 1024,
         "n_experts": 128, "top_k": 8, "n_shared_experts": 1,
         "n_dense_layers": 1, "layer_types": [S, S, A, S, S, S, A, S],
         "sliding_window": 2048, "clients": 16}
# projections a layer: q, gate, out 2048 x 4096 each, k and v 2048 x 512
PROJ = 2 * (3 * 2048 * 4096 + 2 * 2048 * 512)
DENSE = 6 * 2048 * 6144
MOE = 2 * 2048 * 128 + 9 * 6 * 2048 * 1024   # router, 8 routed + 1 shared
HEAD = 2 * 2048 * 200192
REST = 8 * PROJ + DENSE + 7 * MOE + HEAD


def test_layers_and_window_keys():
    assert F._layers(SHAPE) == (6, 2, 1, 7)
    assert F._widths(SHAPE) == (4096, 512)
    assert F.window_keys(SHAPE, 300) == 300
    assert F.window_keys(SHAPE, 5000) == 2048
    assert F.window_keys_prompt(SHAPE, 100) == 100 * 101 / 2
    assert F.window_keys_prompt(SHAPE, 3048) \
        == 2048 * 2049 / 2 + 1000 * 2048


def test_flops_per_token_and_prompt():
    # inside the window both layer kinds attend the whole context
    assert F.afmoe_flops_per_token(SHAPE, 300) \
        == REST + 4 * 4096 * 8 * 300
    # past it the six window layers attend 2,048 keys
    assert F.afmoe_flops_per_token(SHAPE, 6000) \
        == REST + 4 * 4096 * (2 * 6000 + 6 * 2048)
    assert F.afmoe_flops_prompt(SHAPE, 1) \
        == F.afmoe_flops_per_token(SHAPE, 1)
    p = 3048
    assert F.afmoe_flops_prompt(SHAPE, p) == pytest.approx(
        p * (REST - HEAD) + HEAD + 4 * 4096 * (
            2 * p * (p + 1) / 2 + 6 * (2048 * 2049 / 2 + 1000 * 2048)))
    # ~11.3 GFLOP a token of experts over seven layers
    assert 7 * MOE == pytest.approx(0.795e9, rel=0.01)


def test_attention_least_time_counts_the_window():
    peak = flops.peaks("TPU v5 lite")
    # 16 decoded tokens at context 6,000, bf16: K + V of 512 lanes; the
    # full layers read 6,000 positions, the window layers 2,048
    least = F.paged_attention_least_s(SHAPE, [6000] * 16, [], 2, peak)
    assert least["bytes"] == 16 * 2 * 512 * 2 * (2 * 6000 + 6 * 2048)
    assert least["flops"] == 16 * 4 * 4096 * (2 * 6000 + 6 * 2048)
    assert least["bound"] == "memory"
    assert least["least_s"] == pytest.approx(least["bytes"] / 819e9)
    short = F.paged_attention_least_s(SHAPE, [300], [], 2, peak)
    assert short["bytes"] == 2 * 512 * 2 * 8 * 300
    # half of a 4,096-token prompt: half its K/V read once on all eight
    # layers, half its causal / windowed scores and mix
    half = F.paged_attention_least_s(SHAPE, [], [(4096, 0.5)], 2, peak)
    assert half["bytes"] == 0.5 * 2 * 512 * 2 * 8 * 4096
    assert half["flops"] == pytest.approx(0.5 * 4 * 4096 * (
        2 * 4096 * 4097 / 2 + 6 * (2048 * 2049 / 2 + 2048 * 2048)))
    assert half["bound"] == "compute"


def test_expert_least_time_reads_every_touched_expert_once():
    peak = flops.peaks("TPU v5 lite")
    one = 3 * 2048 * 1024 * 2
    assert F.expert_bytes(SHAPE, 2) == one
    # 32 decoded tokens = two passes of 16 callers: 16 x 8 = 128 pairs
    # touch at most all 128 experts, in each of seven layers
    dec = F.moe_expert_least_s(SHAPE, [500] * 32, [], 2, peak)
    assert dec["bytes"] == 2 * 128 * one * 7
    assert dec["flops"] == 32 * 8 * 6 * 2048 * 1024 * 7
    assert dec["bound"] == "memory"
    # a prompt's prefilled part goes through in one pass
    pre = F.moe_expert_least_s(SHAPE, [], [(6000, 0.25)], 2, peak)
    assert pre["bytes"] == 128 * one * 7
    assert pre["flops"] == 1500 * 8 * 6 * 2048 * 1024 * 7
