"""Operations and bytes of the ``afmoe`` block family, from shapes alone
(the counting module of ``afmoe_step_mfu``, ``paged_attn_swa_roofline`` and
``afmoe_expert_roofline``).  ``shape`` is the system's ``decoder``
constant: the keys of ``benchmark/systems/serve_afmoe.decoder_shape``.
"""

from __future__ import annotations

from benchmark import flops_lfm2

SLIDING = "sliding_attention"


def _layers(shape: dict) -> tuple:
    """(window layers, full layers, dense FFN layers, expert layers)."""
    kinds = shape["layer_types"]
    n_win = sum(k == SLIDING for k in kinds)
    n_dense = min(shape["n_dense_layers"], len(kinds))
    return n_win, len(kinds) - n_win, n_dense, len(kinds) - n_dense


def _widths(shape: dict) -> tuple:
    """(query lanes, K/V lanes): heads x head_dim, which here is not
    d_model / n_heads."""
    return (shape["n_heads"] * shape["head_dim"],
            shape["n_kv_heads"] * shape["head_dim"])


def window_keys(shape: dict, ctx: float) -> float:
    """Keys a query with ``ctx`` positions behind and at it sees on a
    window layer."""
    return min(ctx, shape["sliding_window"])


def window_keys_prompt(shape: dict, p: int) -> float:
    """The same summed over a prompt's ``p`` queries: ``i + 1`` keys at
    position ``i`` until the window is full, the window from there on."""
    w = min(p, shape["sliding_window"])
    return w * (w + 1) / 2.0 + (p - w) * shape["sliding_window"]


def _per_token_but_attention(shape: dict) -> float:
    """Projections (q, k, v, gate, out: 2 per weight), the dense FFNs, the
    router, the ``top_k`` routed experts and the shared one of every expert
    layer, and the vocab head."""
    d = shape["d_model"]
    q, kv = _widths(shape)
    n_win, n_full, n_dense, n_moe = _layers(shape)
    proj = (n_win + n_full) * 2 * (3 * d * q + 2 * d * kv)
    dense = n_dense * 6 * d * shape["d_ff"]
    moe = n_moe * (2 * d * shape["n_experts"]
                   + (shape["top_k"] + shape["n_shared_experts"])
                   * 6 * d * shape["d_ff_expert"])
    return proj + dense + moe + 2 * d * shape["vocab_size"]


def afmoe_flops_per_token(shape: dict, ctx: float) -> float:
    """One token against ``ctx`` cached positions: scores and mix over all
    of them on the full layers, over the window's on the window layers."""
    q, _kv = _widths(shape)
    n_win, n_full, _d, _m = _layers(shape)
    attn = 4 * q * (n_full * ctx + n_win * window_keys(shape, ctx))
    return _per_token_but_attention(shape) + attn


def afmoe_flops_prompt(shape: dict, p: int) -> float:
    """A prompt of ``p`` tokens: every token at its own context, the vocab
    head once."""
    q, _kv = _widths(shape)
    n_win, n_full, _d, _m = _layers(shape)
    head = 2 * shape["d_model"] * shape["vocab_size"]
    attn = 4 * q * (n_full * p * (p + 1) / 2.0
                    + n_win * window_keys_prompt(shape, p))
    return p * (_per_token_but_attention(shape) - head) + head + attn


def paged_attention_least_s(shape: dict, decode_ctx: list, prefill: list,
                            itemsize: int, peak: dict) -> dict:
    """Least time for the attention the live contexts needed.  The K/V of a
    position is ``n_kv_heads x head_dim`` wide; the scores and the mix are
    paid for every one of the ``n_heads`` query heads.  A decoded token
    reads its context's K/V once on a full layer and its window's
    (``min(ctx, W)``) on a window layer; every prompt, ``(length, share of
    it prefilled)``, is read once whole on either kind (each key is some
    query's) and pays causal, or windowed, scores and mix."""
    q, kv = _widths(shape)
    n_win, n_full, _d, _m = _layers(shape)
    flops = sum(4 * q * (n_full * c + n_win * window_keys(shape, c))
                for c in decode_ctx) + sum(
        4 * q * share * (n_full * p * (p + 1) / 2.0
                         + n_win * window_keys_prompt(shape, p))
        for p, share in prefill)
    byts = sum(2 * kv * itemsize * (n_full * c + n_win * window_keys(shape, c))
               for c in decode_ctx) + sum(
        2 * kv * itemsize * (n_full + n_win) * p * share
        for p, share in prefill)
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = byts / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": byts, "least_s": max(t_f, t_b),
            "bound": "compute" if t_f >= t_b else "memory"}


def expert_bytes(shape: dict, itemsize: int) -> float:
    """One expert's three matrices."""
    return flops_lfm2.expert_bytes(shape, itemsize)


def moe_expert_least_s(shape: dict, decode_ctx: list, prefill: list,
                       itemsize: int, peak: dict) -> dict:
    """Least time for the routed experts' work of the traffic (the shared
    expert is a plain matmul, not the grouped kernel's), counted and
    batched as ``flops_lfm2.moe_expert_least_s`` does from the same keys
    (``n_experts``, ``top_k``, ``d_ff_expert``, ``clients``, the expert
    layers of ``layer_types``): the decoded tokens go through in passes of
    ``clients`` tokens, the part of a prompt that was prefilled in one pass
    of its own; a pass of ``n`` tokens reads ``min(n_experts, n x top_k)``
    experts' matrices once in every expert layer and pays ``n x top_k``
    routed pairs' operations."""
    return flops_lfm2.moe_expert_least_s(shape, decode_ctx, prefill,
                                         itemsize, peak)
