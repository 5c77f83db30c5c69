"""Operations and bytes of the ``lfm2_moe`` block family, from shapes
alone (the counting module of ``moe_step_mfu`` and
``moe_expert_roofline``).  ``shape`` is the system's ``decoder`` constant:
the keys of ``benchmark/systems/serve_lfm2.decoder_shape``.
"""

from __future__ import annotations

ATTENTION = "full_attention"


def _layers(shape: dict) -> tuple:
    """(attention layers, conv layers, dense FFN layers, expert layers)."""
    kinds = shape["layer_types"]
    n_attn = sum(k == ATTENTION for k in kinds)
    n_dense = min(shape["n_dense_layers"], len(kinds))
    return n_attn, len(kinds) - n_attn, n_dense, len(kinds) - n_dense


def lfm2_flops_per_token(shape: dict, ctx: float) -> float:
    """One token against ``ctx`` cached positions: the mixers' projections
    (2 per weight), attention scores and mix in the attention layers, the
    conv taps, the dense FFNs, the router and the ``top_k`` routed experts
    of every expert layer, and the vocab head."""
    d = shape["d_model"]
    kv = shape["n_kv_heads"] * (d // shape["n_heads"])
    n_attn, n_conv, n_dense, n_moe = _layers(shape)
    attn = n_attn * (2 * (2 * d * d + 2 * d * kv) + 4 * ctx * d)
    conv = n_conv * (2 * 4 * d * d + 6 * d)
    dense = n_dense * 6 * d * shape["d_ff"]
    moe = n_moe * (2 * d * shape["n_experts"]
                   + shape["top_k"] * 6 * d * shape["d_ff_expert"])
    return attn + conv + dense + moe + 2 * d * shape["vocab_size"]


def lfm2_flops_prompt(shape: dict, p: int) -> float:
    """A prompt of ``p`` tokens: every token at its own context, the vocab
    head once."""
    head = 2 * shape["d_model"] * shape["vocab_size"]
    return p * (lfm2_flops_per_token(shape, (p + 1) / 2.0) - head) + head


def paged_attention_least_s(shape: dict, decode_ctx: list, prefill: list,
                            itemsize: int, peak: dict) -> dict:
    """Least time for the attention the live contexts needed, grouped
    queries: the K/V of a position is ``n_kv_heads x head_dim`` wide and
    stands in the attention layers only; the scores and the mix are paid
    for every one of the ``n_heads`` query heads.  As
    ``flops.paged_attention_least_s`` otherwise: every decoded token reads
    its context's K/V once; every prompt, ``(length, share of it
    prefilled)``, is read once whole and pays causal scores and mix."""
    d = shape["d_model"]
    kv = shape["n_kv_heads"] * (d // shape["n_heads"])
    n_attn = _layers(shape)[0]

    def flops_at(ctx):  # one query token: scores and mix, all query heads
        return 4 * ctx * d * n_attn

    def bytes_at(ctx):  # K and V of ctx positions, read once
        return 2 * ctx * kv * n_attn * itemsize

    flops = sum(flops_at(c) for c in decode_ctx) + sum(
        flops_at((p + 1) / 2.0) * p * share for p, share in prefill)
    byts = sum(bytes_at(c) for c in decode_ctx) + sum(
        bytes_at(p) * share for p, share in prefill)
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = byts / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": byts, "least_s": max(t_f, t_b),
            "bound": "compute" if t_f >= t_b else "memory"}


def expert_bytes(shape: dict, itemsize: int) -> float:
    """One expert's three matrices."""
    return 3 * shape["d_model"] * shape["d_ff_expert"] * itemsize


def moe_expert_least_s(shape: dict, decode_ctx: list, prefill: list,
                       itemsize: int, peak: dict) -> dict:
    """Least time for the expert layers' work of the traffic.

    The batching assumed: the decoded tokens go through in passes of
    ``clients`` tokens (one a caller, as a closed loop of ``clients``
    callers can at best offer), and the part of a prompt that was
    prefilled goes through in one pass of its own.  A pass of ``n`` tokens
    reads ``min(n_experts, n x top_k)`` experts' matrices once in every
    expert layer - the most it can touch, so the bytes are an upper bound
    of an ideal pass's and the share a little generous to the kernel where
    routing leaves experts untouched - and pays ``n x top_k`` routed
    pairs' operations (6 x d_model x d_ff_expert each).  Router and
    combine are not the kernel's."""
    _a, _c, _d, n_moe = _layers(shape)
    E, k = shape["n_experts"], shape["top_k"]
    clients = shape["clients"]
    passes = [(clients, len(decode_ctx) / clients)] if decode_ctx else []
    passes += [(p * share, 1.0) for p, share in prefill]
    one = expert_bytes(shape, itemsize)
    byts = sum(n_pass * min(E, n * k) * one for n, n_pass in passes) * n_moe
    flops = sum(n_pass * n * k for n, n_pass in passes) \
        * 6 * shape["d_model"] * shape["d_ff_expert"] * n_moe
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = byts / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": byts, "least_s": max(t_f, t_b),
            "bound": "compute" if t_f >= t_b else "memory"}
