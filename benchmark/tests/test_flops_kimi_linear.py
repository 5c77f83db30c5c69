"""``benchmark/flops_kimi_linear.py`` against numbers worked out by hand at
Kimi-Linear-48B-A3B's published widths, the cell's thirteen layers (ten
KDA, three latent; one dense, twelve expert layers, 64 of 256 experts
held).

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import pytest

from benchmark import flops, flops_kimi_linear as F

K, M = "kda", "mla"
SHAPE = {"vocab_size": 163840, "d_model": 2304, "n_heads": 32,
         "kda_head_dim": 128, "conv_kernel": 4, "kv_lora_rank": 512,
         "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
         "d_ff": 9216, "d_ff_expert": 1024, "n_experts": 256,
         "n_held_experts": 64, "first_expert": 0, "top_k": 8,
         "n_shared_experts": 1, "n_dense_layers": 1,
         "layer_types": [K, K, K, M, K, K, K, M, K, K, K, M, K],
         "kda_chunk": 128, "clients": 16}
# a KDA layer but its delta rule: q, k, v and out 2304 x 4096, the decay's
# and the gate's pairs 2304 x 128 and 128 x 4096, beta 2304 x 32, 12 taps
KDA_PROJ = 2 * (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32) \
    + 2 * 4 * 3 * 4096
# a latent layer but its attention: q 2304 x 6144, kv_a 2304 x 576, the
# absorbed halves 32 x 128 x 512 twice, out 4096 x 2304
MLA_PROJ = 2 * (2304 * 6144 + 2304 * 576 + 2 * 32 * 128 * 512 + 4096 * 2304)
DENSE = 6 * 2304 * 9216
MOE = 2 * 2304 * 256 + (2 + 1) * 6 * 2304 * 1024  # 8 x 64 / 256 = 2 pairs
HEAD = 2 * 2304 * 163840
REST = 10 * KDA_PROJ + 3 * MLA_PROJ + DENSE + 12 * MOE + HEAD
PAIR = 2 * 32 * (576 + 512)
STEP = 32 * 7 * 128 * 128
CHUNKED = 32 * (8 * 128 * 128 + 6 * 128 * 128)


def test_layers_and_the_share():
    assert F._layers(SHAPE) == (10, 3, 1, 12)
    assert F.held_experts(SHAPE) == 64
    assert F.held_pairs_per_token(SHAPE) == 2.0
    assert F.latent_width(SHAPE) == 576
    assert F.held_experts({**SHAPE, "n_held_experts": None}) == 256
    assert F.mla_pair_flops(SHAPE) == PAIR
    assert F.kda_mix_flops(SHAPE, chunked=False) == STEP
    assert F.kda_mix_flops(SHAPE, chunked=True) == CHUNKED


def test_flops_per_token_and_prompt():
    # a decoded token: the recurrence in ten layers whatever the context,
    # scores and mix over the context in three
    assert F.kimi_flops_per_token(SHAPE, 300) \
        == REST + 10 * STEP + 3 * PAIR * 300
    assert F.kimi_flops_per_token(SHAPE, 6000) \
        - F.kimi_flops_per_token(SHAPE, 300) == 3 * PAIR * 5700
    p = 3048
    assert F.kimi_flops_prompt(SHAPE, p) == pytest.approx(
        p * (REST - HEAD + 10 * CHUNKED) + HEAD + 3 * PAIR * p * (p + 1) / 2)
    # ~0.5 GFLOP a token of held and shared experts over twelve layers
    assert 12 * MOE == pytest.approx(0.524e9, rel=0.01)
    # the KDA layers' projections are 0.78 GFLOP a token, the scan 0.07
    assert 10 * KDA_PROJ == pytest.approx(0.790e9, rel=0.01)
    assert 10 * CHUNKED == pytest.approx(0.0734e9, rel=0.01)


def test_kda_scan_least_time():
    peak = flops.peaks("TPU v5 lite")
    tok = 4 * 4096 * 2 + 4 * 4096 + 4 * 32      # q k v o, g in f32, beta
    state = 2 * 32 * 128 * 128 * 4
    # 16 decoded tokens: each reads and writes its state in ten layers
    dec = F.kda_scan_least_s(SHAPE, [500] * 16, [], 2, peak)
    assert dec["bytes"] == 10 * 16 * (tok + state)
    assert dec["flops"] == 10 * 16 * STEP
    assert dec["bound"] == "memory"
    assert dec["least_s"] == pytest.approx(dec["bytes"] / 819e9)
    # half of a 4,096-token prompt: 2,048 tokens in 16 chunks of 128
    pre = F.kda_scan_least_s(SHAPE, [], [(4096, 0.5)], 2, peak)
    assert pre["bytes"] == 10 * (2048 * tok + 16 * state)
    assert pre["flops"] == 10 * 2048 * CHUNKED
    # 150 GFLOP against 1.7 GB: the operands and the states bound it
    assert pre["bound"] == "memory"


def test_latent_attention_least_time_reads_576_values_a_key():
    peak = flops.peaks("TPU v5 lite")
    dec = F.mla_attention_least_s(SHAPE, [6000] * 16, [], 2, peak)
    assert dec["bytes"] == 3 * 16 * 6000 * 576 * 2
    assert dec["flops"] == 3 * 16 * 6000 * PAIR
    # 32 heads on one key of 1,152 bytes: 60 operations a byte, under the
    # chip's 240
    assert dec["bound"] == "memory"
    half = F.mla_attention_least_s(SHAPE, [], [(4096, 0.5)], 2, peak)
    assert half["bytes"] == 3 * 0.5 * 4096 * 576 * 2
    assert half["flops"] == pytest.approx(
        3 * 0.5 * PAIR * 4096 * 4097 / 2)
    assert half["bound"] == "compute"


def test_expert_least_time_counts_the_held_experts():
    peak = flops.peaks("TPU v5 lite")
    one = 3 * 2304 * 1024 * 2
    assert F.expert_bytes(SHAPE, 2) == one
    # 32 decoded tokens = two passes of 16 callers: 16 x 2 = 32 held pairs
    # touch at most 32 of the 64 held experts, in each of twelve layers
    dec = F.moe_expert_least_s(SHAPE, [500] * 32, [], 2, peak)
    assert dec["bytes"] == 2 * 32 * one * 12
    assert dec["flops"] == 32 * 2 * 6 * 2304 * 1024 * 12
    assert dec["bound"] == "memory"
    # a prompt's prefilled part goes through in one pass: all 64 touched
    pre = F.moe_expert_least_s(SHAPE, [], [(6000, 0.25)], 2, peak)
    assert pre["bytes"] == 64 * one * 12
    assert pre["flops"] == 1500 * 2 * 6 * 2304 * 1024 * 12
