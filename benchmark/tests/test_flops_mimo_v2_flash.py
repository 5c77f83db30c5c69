"""``benchmark/flops_mimo_v2_flash.py`` against numbers worked out by hand at
MiMo-V2-Flash's published widths, the cell's eleven layers (two full, nine
sliding; the first with the dense feed-forward, ten with 16 of 256 experts
held).

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import pytest

from benchmark import flops_mimo_v2_flash as F

S, A = "sliding_attention", "full_attention"
SHAPE = {"vocab_size": 152576, "d_model": 4096, "n_heads": 64,
         "n_kv_heads": 4, "window_kv_heads": 8, "head_dim": 192,
         "v_head_dim": 128, "rotary_dim": 64, "d_ff": 16384,
         "d_ff_expert": 2048, "n_experts": 256, "n_held_experts": 16,
         "first_expert": 0, "top_k": 8, "n_dense_layers": 1,
         "layer_types": [A, S, S, S, S, A, S, S, S, S, S],
         "sliding_window": 128, "clients": 16}
# one layer of each kind: q 4096 x 12288, k 4096 x (n_kv x 192), v 4096 x
# (n_kv x 128), out 8192 x 4096
FULL_W = 4096 * (12288 + 768 + 512) + 8192 * 4096     # 89.13M
SLIDE_W = 4096 * (12288 + 1536 + 1024) + 8192 * 4096  # 94.37M
DENSE = 6 * 4096 * 16384
# router 4096 x 256 and 8 x 16 / 256 = 0.5 held pairs of 3 x 4096 x 2048
MOE = 2 * 4096 * 256 + 0.5 * 6 * 4096 * 2048
HEAD = 2 * 4096 * 152576
REST = 2 * (2 * FULL_W + 9 * SLIDE_W) + DENSE + 10 * MOE + HEAD
PAIR = 2 * 64 * (192 + 128)


def test_layers_and_the_share():
    assert F._layers(SHAPE) == (9, 2, 1, 10)
    assert FULL_W == 89_128_960 and SLIDE_W == 94_371_840
    assert F.attn_weights(SHAPE, 4) == FULL_W
    assert F.attn_weights(SHAPE, 8) == SLIDE_W
    assert F.held_experts(SHAPE) == 16
    assert F.held_pairs_per_token(SHAPE) == 0.5
    assert F.held_experts({**SHAPE, "n_held_experts": None}) == 256
    assert F.attn_pair_flops(SHAPE) == PAIR == 40960
    assert F.window_keys(SHAPE, 50) == 50 and F.window_keys(SHAPE, 900) == 128
    assert F.window_keys_prompt(SHAPE, 100) == 100 * 101 / 2
    assert F.window_keys_prompt(SHAPE, 1000) == 128 * 129 / 2 + 872 * 128


def test_flops_per_token_and_prompt():
    # a decoded token: scores and mix over the context in two layers, over
    # 128 keys in nine
    assert F.mimo_flops_per_token(SHAPE, 3000) \
        == REST + PAIR * (2 * 3000 + 9 * 128)
    assert F.mimo_flops_per_token(SHAPE, 6000) \
        - F.mimo_flops_per_token(SHAPE, 3000) == 2 * PAIR * 3000
    assert F.mimo_flops_per_token(SHAPE, 40) == REST + PAIR * 11 * 40
    p = 3048
    assert F.mimo_flops_prompt(SHAPE, p) == pytest.approx(
        p * (REST - HEAD) + HEAD
        + PAIR * (2 * p * (p + 1) / 2 + 9 * F.window_keys_prompt(SHAPE, p)))
    # 2.06 GFLOP a token of attention projections in eleven layers, 0.40 of
    # the dense feed-forward, 0.27 of router and held pairs, 1.25 of head
    assert 2 * (2 * FULL_W + 9 * SLIDE_W) == pytest.approx(2.055e9, rel=0.01)
    assert 10 * MOE == pytest.approx(0.273e9, rel=0.01)


PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_attention_least_time_one_layer_of_each_kind():
    one_full = {**SHAPE, "layer_types": [A], "n_dense_layers": 0}
    one_slide = {**SHAPE, "layer_types": [S], "n_dense_layers": 0}
    # a decoded token at context 3,000: 4 x 320 values a key on the full
    # layer, the whole context; 8 x 320 on the sliding one, 128 keys
    full = F.paged_attention_least_s(one_full, [3000], [], 2, PEAK)
    assert full["bytes"] == 3000 * 4 * 320 * 2
    assert full["flops"] == 3000 * PAIR and full["bound"] == "memory"
    slide = F.paged_attention_least_s(one_slide, [3000], [], 2, PEAK)
    assert slide["bytes"] == 128 * 8 * 320 * 2
    assert slide["flops"] == 128 * PAIR
    # half of a prompt of 2,000: read once, causal pairs / windowed pairs
    full = F.paged_attention_least_s(one_full, [], [(2000, 0.5)], 2, PEAK)
    assert full["bytes"] == 0.5 * 2000 * 4 * 320 * 2
    assert full["flops"] == 0.5 * PAIR * 2000 * 2001 / 2
    assert full["bound"] == "compute"
    slide = F.paged_attention_least_s(one_slide, [], [(2000, 0.5)], 2, PEAK)
    assert slide["flops"] == 0.5 * PAIR * (128 * 129 / 2 + 1872 * 128)
    both = F.paged_attention_least_s(SHAPE, [3000], [(2000, 0.5)], 2, PEAK)
    assert both["flops"] == pytest.approx(
        PAIR * (2 * 3000 + 9 * 128)
        + 0.5 * PAIR * (2 * 2000 * 2001 / 2
                        + 9 * (128 * 129 / 2 + 1872 * 128)))


def test_expert_least_time():
    # sixteen decoded tokens in one pass: 16 x (1 - (248/256)^16) = 6.37
    # held experts touched in each of ten layers, 8 pairs on them
    touched = 16 * (1 - (248 / 256) ** 16)
    assert F.experts_touched(SHAPE, 16) == pytest.approx(touched)
    assert touched == pytest.approx(6.37, abs=0.01)
    one = 3 * 4096 * 2048 * 2
    assert F.expert_bytes(SHAPE, 2) == one
    dec = F.moe_expert_least_s(SHAPE, [100] * 16, [], 2, PEAK)
    assert dec["bytes"] == pytest.approx(10 * touched * one)
    assert dec["flops"] == pytest.approx(10 * 16 * 0.5 * 6 * 4096 * 2048)
    assert dec["bound"] == "memory"
    # a whole prompt of 3,000 in one pass: every held expert read once
    pre = F.moe_expert_least_s(SHAPE, [], [(3000, 1.0)], 2, PEAK)
    assert pre["bytes"] == pytest.approx(10 * 16 * one, rel=1e-6)
    assert pre["flops"] == pytest.approx(10 * 1500 * 6 * 4096 * 2048)
