"""``flops_lfm2``'s grouped-query attention count and ``weights_lfm2``'s
scales and low-precision rounding, at toy shapes on the CPU.

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import flops, flops_lfm2, weights_lfm2

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
A, C = "full_attention", "conv"


def _shape(**over) -> dict:
    shape = {"vocab_size": 257, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "d_ff": 128, "d_ff_expert": 32, "n_experts": 8, "top_k": 2,
             "n_dense_layers": 1, "layer_types": [C, A, C, C, C], "clients": 4}
    shape.update(over)
    return shape


@pytest.mark.parametrize("work", [
    ([600] * 50, []), ([], [(450, 1.0), (800, 0.25)]),
    ([17, 900], [(230, 0.5)])], ids=["decode", "prefill", "both"])
def test_gqa_attention_count_is_the_plain_one_without_grouping(work):
    """As many K/V heads as query heads, attention in every layer: the
    count of ``flops.paged_attention_least_s``."""
    dec, pre = work
    shape = _shape(n_kv_heads=4, layer_types=[A] * 3, n_dense_layers=0)
    got = flops_lfm2.paged_attention_least_s(shape, dec, pre, 2, PEAK)
    want = flops.paged_attention_least_s(dict(shape, n_layers=3), dec, pre,
                                         2, PEAK)
    for k in ("flops", "bytes", "least_s"):
        assert got[k] == pytest.approx(want[k])
    assert got["bound"] == want["bound"]


def test_gqa_attention_bytes_follow_the_kv_heads_and_attention_layers():
    one = flops_lfm2.paged_attention_least_s(_shape(), [100], [], 2, PEAK)
    # K and V of 100 positions, 2 K/V heads of 16, one attention layer
    assert one["bytes"] == 2 * 100 * 32 * 2
    # the scores and the mix are paid for all four query heads
    assert one["flops"] == 4 * 100 * 64
    two = flops_lfm2.paged_attention_least_s(
        _shape(layer_types=[C, A, C, A, C]), [100], [], 2, PEAK)
    assert two["bytes"] == 2 * one["bytes"]


@pytest.mark.parametrize("rounding", [None, "int8"])
def test_output_projections_are_scaled_after_the_first_layer(rounding):
    import jax.numpy as jnp

    shape = _shape()
    p = weights_lfm2.lfm2_params(shape, 7, jnp.float32, rounding)
    L = len(shape["layer_types"])
    late = 1.0 / np.sqrt(2.0 * (L - 1))
    assert weights_lfm2.out_scale(0, L) == 1.0
    assert weights_lfm2.out_scale(L - 1, L) == pytest.approx(late)
    first, last = p["layers"][0], p["layers"][-1]
    # N(0, 1/fan_in) in the first layer, scaled down after it
    assert np.std(first["w_out"]) * 8 == pytest.approx(1.0, rel=0.1)
    assert np.std(last["w_out"]) * 8 == pytest.approx(late, rel=0.1)
    assert np.std(last["w2"]) * np.sqrt(32) == pytest.approx(late, rel=0.1)
    assert np.std(last["w1"]) * 8 == pytest.approx(1.0, rel=0.1)


def test_int8_rounding_moves_the_matrices_only():
    import jax.numpy as jnp

    shape = _shape()
    a = weights_lfm2.lfm2_params(shape, 7, jnp.float32)
    b = weights_lfm2.lfm2_params(shape, 7, jnp.float32, "int8")
    for la, lb in zip(a["layers"], b["layers"]):
        for name in la:
            x, y = np.asarray(la[name]), np.asarray(lb[name])
            if name in ("wg", "expert_bias", "conv_w") or x.ndim == 1:
                assert (x == y).all(), name
                continue
            step = np.abs(x).max(axis=-2, keepdims=True) / 127.0
            assert (x != y).any(), name
            assert (np.abs(x - y) <= step / 2 * 1.001).all(), name
            # 255 levels a column at the most
            col = y[..., :, 0].reshape(-1, y.shape[-2])[0]
            assert len(np.unique(col)) <= 255
    assert (np.asarray(a["embed"]) == np.asarray(b["embed"])).all()
