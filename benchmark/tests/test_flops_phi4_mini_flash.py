"""``benchmark/flops_phi4_mini_flash.py`` against numbers worked out by hand at
Phi-4-mini-flash-reasoning's published widths: 32 layers, the self-decoder
and the memory and full layers (9 Mamba, 8 window, 1 full) on every token,
the cross-decoder (7 memory units, 7 cross-attention layers) and the tied
head on the emitting tokens only.

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import pytest

from benchmark import flops_phi4_mini_flash as F

M, S, A, G, C = F.MAMBA, F.SLIDING, F.FULL, F.GMU, F.CROSS
KINDS = [M, S] * 8 + [M, A] + [G, C] * 7
SHAPE = {"vocab_size": 200064, "d_model": 2560, "n_layers": 32,
         "n_heads": 40, "n_kv_heads": 20, "head_dim": 64, "d_ff": 10240,
         "sliding_window": 512, "d_state": 16, "d_conv": 4, "expand": 2,
         "dt_rank": 160, "layer_types": KINDS, "clients": 16}
# a token's matrices a layer: Mamba in 2560 x 10240, x 5120 x 192, dt 160 x
# 5120, out 5120 x 2560; attention q 2560 x 2560, k and v 2560 x 1280, out
# 2560 x 2560; a memory unit 2560 x 5120 twice; cross q and out
MAMBA_W = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560  # 41.12M
ATTN_W = 2 * 2560 * 2560 + 2 * 2560 * 1280                      # 19.66M
GMU_W = 2 * 2560 * 5120                                         # 26.21M
CROSS_W = 2 * 2560 * 2560                                       # 13.11M
FFN = 6 * 2560 * 10240
SCAN = 7 * 16 * 5120
SELF = 9 * (2 * MAMBA_W + FFN + SCAN) + 9 * (2 * ATTN_W + FFN)
CROSS_ = 7 * (2 * GMU_W + FFN) + 7 * (2 * CROSS_W + FFN)
HEAD = 2 * 2560 * 200064
PAIR = 2 * 40 * (64 + 128)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_layers_and_weights():
    assert F.self_layers(SHAPE) == {M: 9, S: 8, A: 1, G: 0, C: 0}
    assert F.cross_layers(SHAPE) == {M: 0, S: 0, A: 0, G: 7, C: 7}
    assert F.mixer_weights(SHAPE, M) == MAMBA_W == 41_123_840
    assert F.mixer_weights(SHAPE, S) == F.mixer_weights(SHAPE, A) == ATTN_W
    assert F.mixer_weights(SHAPE, G) == GMU_W
    assert F.mixer_weights(SHAPE, C) == CROSS_W
    assert F.attn_pair_flops(SHAPE) == PAIR == 15360


def test_the_skip_is_41_percent_of_a_prompt_tokens_matmuls():
    """The cross-decoder's matmuls are 2.75 of a token's 6.68 GFLOP."""
    mm = SELF - 9 * SCAN
    assert CROSS_ == pytest.approx(2.75e9, rel=0.01)
    assert mm + CROSS_ == pytest.approx(6.68e9, rel=0.01)
    assert CROSS_ / (mm + CROSS_) == pytest.approx(0.41, abs=0.01)


def test_flops_per_token_and_prompt():
    # a decoded token: every layer and the head; 512 keys on 8 window
    # layers, the context on the full layer and 7 cross layers
    assert F.phi4_flops_per_token(SHAPE, 3000) \
        == SELF + CROSS_ + HEAD + PAIR * (8 * 512 + 8 * 3000)
    assert F.phi4_flops_per_token(SHAPE, 100) \
        == SELF + CROSS_ + HEAD + PAIR * 16 * 100
    p = 3000
    want = p * SELF + CROSS_ + HEAD + PAIR * (
        8 * (512 * 513 / 2 + (p - 512) * 512) + p * (p + 1) / 2 + 7 * p)
    assert F.phi4_flops_prompt(SHAPE, p) == pytest.approx(want)


def test_attention_least_time():
    # one decoded token at 1,000: 8 x 512 + 8 x 1,000 keys of 2 x 1,280
    # bf16 values; a prompt of 600 read once on 16 layers
    pos = 2 * 1280 * 2
    got = F.paged_attention_least_s(SHAPE, [1000], [], 2, PEAK)
    assert got["bytes"] == (8 * 512 + 8 * 1000) * pos
    assert got["flops"] == PAIR * (8 * 512 + 8 * 1000)
    got = F.paged_attention_least_s(SHAPE, [], [(600, 0.5)], 2, PEAK)
    assert got["bytes"] == pytest.approx(0.5 * 600 * 16 * pos)
    pairs = 8 * (512 * 513 / 2 + 88 * 512) + 600 * 601 / 2 + 7 * 600
    assert got["flops"] == pytest.approx(0.5 * pairs * PAIR)


def test_scan_least_time():
    # per token and layer: u', dt, y of 5,120 f32 and B, C of 16; the state
    # of 16 x 5,120 f32 in and out once a decoded token, once 128 prompt
    # tokens; the vector units at a 32nd of the bf16 peak
    tok = (3 * 5120 + 2 * 16) * 4
    state = 2 * 16 * 5120 * 4
    got = F.mamba_scan_least_s(SHAPE, [100] * 16, [], 2, PEAK)
    assert got["bytes"] == 9 * 16 * (tok + state)
    assert got["bound"] == "memory"
    got = F.mamba_scan_least_s(SHAPE, [], [(1280, 1.0)], 2, PEAK)
    assert got["bytes"] == pytest.approx(9 * (1280 * tok + 10 * state))
    vpu = 9 * 1280 * 6 * 16 * 5120 / (197e12 / 32)
    assert got["vpu_s"] == pytest.approx(vpu)
    assert got["least_s"] == max(got["bytes"] / 819e9, vpu, got["eup_s"])
