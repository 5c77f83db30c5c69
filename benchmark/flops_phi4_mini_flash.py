"""Operations and bytes of the ``phi4_mini_flash`` block family, from shapes
alone (the counting module of ``phi4flash_step_mfu``,
``mamba_scan_roofline`` and ``paged_attn_diff_roofline``).  ``shape`` is the
system's ``decoder`` constant: the fields of ``Phi4MiniFlashConfig``, its
``layer_types`` and ``clients``.

The work is counted as the architecture needs it: the layers of the
self-decoder, the memory layer and the full layer (``n_layers / 2 + 2``) on
every token, the cross-decoder and the head on the tokens that emit only (a
decoded token; a prompt's last).  A program that ran the cross-decoder over
every prompt token would read the same here and take longer.
"""

from __future__ import annotations

from benchmark.flops_afmoe import window_keys, window_keys_prompt
from benchmark.flops_qwen3_next import _least

MAMBA, SLIDING, FULL, GMU, CROSS = ("mamba", "sliding_attention",
                                    "full_attention", "gmu", "cross")
STATE_ITEMSIZE = 4   # the scan state and its inputs are f32
# the vector units against the MXUs (v5e: four f32 ALU slots of 8 x 128
# lanes a cycle beside four 128 x 128 MXUs, two operations a multiply-add):
# a 32nd of the bf16 peak for elementwise f32 operations, a 128th for exp
VPU_SHARE, EUP_SHARE = 1 / 32, 1 / 128


def _count(shape: dict, lo: int, hi: int) -> dict:
    kinds = shape["layer_types"][lo:hi]
    return {k: kinds.count(k) for k in (MAMBA, SLIDING, FULL, GMU, CROSS)}


def self_layers(shape: dict) -> dict:
    """Layers every token runs, by kind."""
    return _count(shape, 0, shape["n_layers"] // 2 + 2)


def cross_layers(shape: dict) -> dict:
    """Layers only an emitting token runs, by kind."""
    return _count(shape, shape["n_layers"] // 2 + 2, shape["n_layers"])


def _inner(shape: dict) -> int:
    return shape["expand"] * shape["d_model"]


def mixer_weights(shape: dict, kind: str) -> int:
    """The matrices a token multiplies in one mixer of ``kind``."""
    d, E, H, KV, hd = (shape["d_model"], _inner(shape), shape["n_heads"],
                       shape["n_kv_heads"], shape["head_dim"])
    return {MAMBA: d * 2 * E + E * (shape["dt_rank"] + 2 * shape["d_state"])
            + shape["dt_rank"] * E + E * d,
            SLIDING: 2 * d * H * hd + 2 * d * KV * hd,
            FULL: 2 * d * H * hd + 2 * d * KV * hd,
            GMU: 2 * d * E,
            CROSS: 2 * d * H * hd}[kind]


def scan_ops(shape: dict) -> int:
    """The recurrence's operations a token and Mamba layer: per (state,
    channel) the step times ``a``, its exp, the decay, the input term, the
    sum and the read-out's multiply-add."""
    return 7 * shape["d_state"] * _inner(shape)


def attn_pair_flops(shape: dict) -> int:
    """One (query, key) pair of a differential layer over all query heads:
    both maps' scores over ``head_dim`` and their mixes of a value of
    ``2 head_dim``, 2 operations each."""
    return 2 * shape["n_heads"] * 3 * shape["head_dim"]


def _matmul_flops(shape: dict, layers: dict) -> float:
    ffn = 6 * shape["d_model"] * shape["d_ff"]
    return sum(n * (2 * mixer_weights(shape, k) + ffn)
               for k, n in layers.items()) \
        + layers[MAMBA] * scan_ops(shape)


def _head(shape: dict) -> int:
    return 2 * shape["d_model"] * shape["vocab_size"]


def phi4_flops_per_token(shape: dict, ctx: float) -> float:
    """A decoded token against ``ctx`` cached positions: every layer, the
    head, and its attention: the window's keys on the window layers, all
    ``ctx`` on the full layer and on every cross-attention layer."""
    s, c = self_layers(shape), cross_layers(shape)
    pairs = s[SLIDING] * window_keys(shape, ctx) + (s[FULL] + c[CROSS]) * ctx
    return _matmul_flops(shape, s) + _matmul_flops(shape, c) + _head(shape) \
        + attn_pair_flops(shape) * pairs


def phi4_flops_prompt(shape: dict, p: int) -> float:
    """A prompt of ``p`` tokens: the layers every token runs over all of
    them, each at its own context; the cross-decoder and the head once, for
    the prompt's last token, which emits."""
    s, c = self_layers(shape), cross_layers(shape)
    pairs = s[SLIDING] * window_keys_prompt(shape, p) \
        + s[FULL] * p * (p + 1) / 2.0 + c[CROSS] * p
    return p * _matmul_flops(shape, s) + _matmul_flops(shape, c) \
        + _head(shape) + attn_pair_flops(shape) * pairs


def mamba_scan_least_s(shape: dict, decode_ctx: list, prefill: list,
                       itemsize: int, peak: dict) -> dict:
    """Least time for the Mamba layers' scans of the traffic.  Per token and
    layer the kernels read ``u'`` and ``dt`` (``E`` f32 each) and ``B``,
    ``C`` (``d_state`` f32 each) and write ``y`` (``E`` f32); the state
    (``d_state x E`` f32) is read and written once a decoded token and once
    an item of 128 tokens of a prompt's prefilled part.  The operations are
    the vector units': :func:`scan_ops` of them at :data:`VPU_SHARE` of the
    bf16 peak, the ``d_state x E`` exps at :data:`EUP_SHARE`; the least time
    is the largest of the three."""
    E, N = _inner(shape), shape["d_state"]
    n = self_layers(shape)[MAMBA]
    tok_bytes = (3 * E + 2 * N) * STATE_ITEMSIZE
    state_bytes = 2 * N * E * STATE_ITEMSIZE
    n_dec = len(decode_ctx)
    n_pre = sum(p * share for p, share in prefill)
    byts = n * ((n_dec + n_pre) * tok_bytes
                + (n_dec + n_pre / 128.0) * state_bytes)
    toks = n * (n_dec + n_pre)
    t_ops = toks * (scan_ops(shape) - N * E) \
        / (VPU_SHARE * peak["bf16_flops_per_s"])
    t_exp = toks * N * E / (EUP_SHARE * peak["bf16_flops_per_s"])
    out = _least(0.0, byts, peak)
    least = max(out["least_s"], t_ops, t_exp)
    return {**out, "least_s": least, "vpu_s": t_ops, "eup_s": t_exp,
            "bound": "memory" if least == out["least_s"] else "vector"}


def paged_attention_least_s(shape: dict, decode_ctx: list, prefill: list,
                            itemsize: int, peak: dict) -> dict:
    """Least time for the differential attention of the traffic.  A
    position is ``n_kv_heads x head_dim`` keys beside ``n_kv_heads / 2 x 2
    head_dim`` values; a decoded token reads its window's on the window
    layers and its whole context on the full layer and again on each
    cross-attention layer; a prompt is read once whole on the window and
    full layers, and once a cross-attention layer by its last token; every
    visible (query, key) pair pays :func:`attn_pair_flops`."""
    s, c = self_layers(shape), cross_layers(shape)
    pos_b = 2 * shape["n_kv_heads"] * shape["head_dim"] * itemsize
    n_win, n_all = s[SLIDING], s[FULL] + c[CROSS]
    pairs = sum(n_win * window_keys(shape, ctx) + n_all * ctx
                for ctx in decode_ctx) + sum(
        share * (n_win * window_keys_prompt(shape, p)
                 + s[FULL] * p * (p + 1) / 2.0 + c[CROSS] * p)
        for p, share in prefill)
    keys = sum(n_win * window_keys(shape, ctx) + n_all * ctx
               for ctx in decode_ctx) + sum(
        share * p * (n_win + n_all) for p, share in prefill)
    return _least(pairs * attn_pair_flops(shape), keys * pos_b, peak)
