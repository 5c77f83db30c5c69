"""Operations and bytes an algorithm needs, from shapes alone.

Copied arithmetic: ``decoder_flops_per_token`` and ``encoder_flops`` are
``bench.py``'s ``_decoder_flops_per_token`` / ``_encoder_flops_per_batch``.
Nothing here looks at a kernel's grid or layout: a roofline share is the
least time the chip could take for the work the traffic asked for.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The table row of ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


def decoder_flops_per_token(shape: dict, ctx: float) -> float:
    """One token through the decoder against ``ctx`` cached positions:
    projections and FFN (2 per weight), scores and mix, the vocab head."""
    d, ff, n = shape["d_model"], shape["d_ff"], shape["n_layers"]
    proj_ffn = 2 * (4 * d * d + 2 * d * ff) * n
    attn = 4 * ctx * d * n
    head = 2 * d * shape["vocab_size"]
    return proj_ffn + attn + head


def decoder_flops_prompt(shape: dict, p: int) -> float:
    """A prompt of ``p`` tokens: every token at its own context, the vocab
    head once (only the last position's logits are needed)."""
    head = 2 * shape["d_model"] * shape["vocab_size"]
    return p * (decoder_flops_per_token(shape, (p + 1) / 2.0) - head) + head


def encoder_flops(shape: dict, batch: int, tokens: int) -> float:
    """One encoder forward over ``batch`` rows of ``tokens`` positions."""
    d, ff = shape["d_model"], shape["d_ff"]
    per_token = 2 * (4 * d * d + 2 * d * ff) + 4 * tokens * d
    return batch * tokens * shape["n_layers"] * per_token


def attention_flops(shape: dict, ctx: float) -> float:
    """Scores and mix of one query token over ``ctx`` keys, all layers."""
    return 4 * ctx * shape["d_model"] * shape["n_layers"]


def kv_bytes(shape: dict, ctx: float, itemsize: int) -> float:
    """K and V of ``ctx`` positions, all layers, read once."""
    return 2 * ctx * shape["d_model"] * shape["n_layers"] * itemsize


def paged_attention_least_s(shape: dict, decode_ctx: list, prefill: list,
                            itemsize: int, peak: dict) -> dict:
    """Least time for the attention the live contexts needed: every decoded
    token reads its context's K/V once; every prompt, ``(length, share of
    it prefilled)``, is read once whole (its tokens could share one pass)
    and pays causal scores and mix."""
    flops = sum(attention_flops(shape, c) for c in decode_ctx)
    flops += sum(attention_flops(shape, (p + 1) / 2.0) * p * share
                 for p, share in prefill)
    byts = sum(kv_bytes(shape, c, itemsize) for c in decode_ctx)
    byts += sum(kv_bytes(shape, p, itemsize) * share for p, share in prefill)
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = byts / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": byts, "least_s": max(t_f, t_b),
            "bound": "compute" if t_f >= t_b else "memory"}
