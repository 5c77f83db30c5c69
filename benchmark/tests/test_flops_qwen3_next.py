"""``benchmark/flops_qwen3_next.py`` against numbers worked out by hand at
Qwen3-Next-80B-A3B's published widths, the cell's twelve layers (nine gated
DeltaNet, three full; every one with the expert block, 128 of 512 experts
held).

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import pytest

from benchmark import flops, flops_qwen3_next as F

L, A = "linear_attention", "full_attention"
SHAPE = {"vocab_size": 151936, "d_model": 2048, "n_heads": 16,
         "n_kv_heads": 2, "head_dim": 256, "rotary_dim": 64,
         "gdn_key_heads": 16, "gdn_value_heads": 32, "gdn_key_dim": 128,
         "gdn_value_dim": 128, "conv_kernel": 4, "d_ff_expert": 512,
         "d_ff_shared": 512, "n_experts": 512, "n_held_experts": 128,
         "first_expert": 0, "top_k": 10,
         "layer_types": [L, L, L, A] * 3, "gdn_chunk": 128, "clients": 16}
# a DeltaNet layer but its delta rule: W_qkvz 2048 x 12288, W_ba 2048 x 64,
# out 4096 x 2048, 4 taps over 8192 channels
GDN_PROJ = 2 * (2048 * 12288 + 2048 * 64 + 4096 * 2048) + 2 * 4 * 8192
# a full layer but its attention: W_q 2048 x 8192 (query and gate), W_k and
# W_v 2048 x 512, out 4096 x 2048
FULL_PROJ = 2 * (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048)
# router 2048 x 512, the shared gate 2048 x 1, 10 x 128 / 512 = 2.5 held
# pairs and one shared expert of 3 x 2048 x 512
MOE = 2 * 2048 * 512 + 2 * 2048 + (2.5 + 1) * 6 * 2048 * 512
HEAD = 2 * 2048 * 151936
REST = 9 * GDN_PROJ + 3 * FULL_PROJ + 12 * MOE + HEAD
PAIR = 4 * 16 * 256
STEP = 32 * 7 * 128 * 128
CHUNKED = 32 * (8 * 128 * 128 + 6 * 128 * 128)


def test_layers_and_the_share():
    assert F._layers(SHAPE) == (9, 3)
    assert F.held_experts(SHAPE) == 128
    assert F.held_pairs_per_token(SHAPE) == 2.5
    assert F._widths(SHAPE) == (2048, 4096)
    assert F.held_experts({**SHAPE, "n_held_experts": None}) == 512
    assert F.attn_pair_flops(SHAPE) == PAIR
    assert F.gdn_mix_flops(SHAPE, chunked=False) == STEP
    assert F.gdn_mix_flops(SHAPE, chunked=True) == CHUNKED


def test_flops_per_token_and_prompt():
    # a decoded token: the recurrence in nine layers whatever the context,
    # scores and mix over the context in three
    assert F.qwen3next_flops_per_token(SHAPE, 300) \
        == REST + 9 * STEP + 3 * PAIR * 300
    assert F.qwen3next_flops_per_token(SHAPE, 6000) \
        - F.qwen3next_flops_per_token(SHAPE, 300) == 3 * PAIR * 5700
    p = 3048
    assert F.qwen3next_flops_prompt(SHAPE, p) == pytest.approx(
        p * (REST - HEAD + 9 * CHUNKED) + HEAD + 3 * PAIR * p * (p + 1) / 2)
    # 0.29 GFLOP a token of router, held and shared experts in twelve layers
    assert 12 * MOE == pytest.approx(0.290e9, rel=0.01)
    # the DeltaNet layers' projections are 0.61 GFLOP a token, the scan 0.07
    assert 9 * GDN_PROJ == pytest.approx(0.607e9, rel=0.01)
    assert 9 * CHUNKED == pytest.approx(0.0661e9, rel=0.01)


def test_gdn_scan_least_time_reads_one_decay_a_head():
    peak = flops.peaks("TPU v5 lite")
    # q and k of 16 heads, v and o of 32, bf16; beta and g one f32 a head
    tok = (2 * 2048 + 2 * 4096) * 2 + 2 * 4 * 32
    state = 2 * 32 * 128 * 128 * 4
    dec = F.gdn_scan_least_s(SHAPE, [500] * 16, [], 2, peak)
    assert dec["bytes"] == 9 * 16 * (tok + state)
    assert dec["flops"] == 9 * 16 * STEP
    assert dec["bound"] == "memory"
    assert dec["least_s"] == pytest.approx(dec["bytes"] / 819e9)
    # half of a 4,096-token prompt: 2,048 tokens in 16 chunks of 128
    pre = F.gdn_scan_least_s(SHAPE, [], [(4096, 0.5)], 2, peak)
    assert pre["bytes"] == 9 * (2048 * tok + 16 * state)
    assert pre["flops"] == 9 * 2048 * CHUNKED
    # 135 GFLOP (0.7 ms) against 1.06 GB (1.3 ms): operands and states bound it
    assert pre["bound"] == "memory"


def test_attention_least_time_reads_two_heads_of_256_twice():
    peak = flops.peaks("TPU v5 lite")
    dec = F.attention_least_s(SHAPE, [6000] * 16, [], 2, peak)
    assert dec["bytes"] == 3 * 16 * 6000 * 2 * 2 * 256 * 2
    assert dec["flops"] == 3 * 16 * 6000 * PAIR
    # 16 heads on a position of 2,048 bytes: 8 operations a byte
    assert dec["bound"] == "memory"
    half = F.attention_least_s(SHAPE, [], [(4096, 0.5)], 2, peak)
    assert half["bytes"] == 3 * 0.5 * 4096 * 2048
    assert half["flops"] == pytest.approx(3 * 0.5 * PAIR * 4096 * 4097 / 2)
    assert half["bound"] == "compute"


def test_expert_least_time_counts_the_experts_a_pass_touches():
    peak = flops.peaks("TPU v5 lite")
    one = 3 * 2048 * 512 * 2
    assert F.expert_bytes(SHAPE, 2) == one
    # a pass of 16 tokens leaves a held expert out with (502/512)^16
    touched = 128 * (1 - (502 / 512) ** 16)
    assert F.experts_touched(SHAPE, 16) == pytest.approx(touched)
    assert 34 < touched < 36
    assert F.experts_touched(SHAPE, 528) > 127.99
    # 32 decoded tokens = two passes of 16 callers, in each of twelve layers
    dec = F.moe_expert_least_s(SHAPE, [500] * 32, [], 2, peak)
    assert dec["bytes"] == pytest.approx(2 * touched * one * 12)
    assert dec["flops"] == 32 * 2.5 * 6 * 2048 * 512 * 12
    assert dec["bound"] == "memory"
    # a prompt's prefilled part goes through in one pass: all 128 touched
    pre = F.moe_expert_least_s(SHAPE, [], [(6000, 0.25)], 2, peak)
    assert pre["bytes"] == pytest.approx(128 * one * 12, rel=1e-6)
    assert pre["flops"] == 1500 * 2.5 * 6 * 2048 * 512 * 12
