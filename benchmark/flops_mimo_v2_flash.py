"""Operations and bytes of the ``mimo_v2_flash`` block family, from shapes
alone (the counting module of ``mimo_step_mfu``, ``paged_attn_qk192_roofline``
and ``mimo_expert_roofline``).  ``shape`` is the system's ``decoder``
constant: the fields of ``MimoV2FlashConfig`` and ``clients``.
"""

from __future__ import annotations

from benchmark.flops_afmoe import (SLIDING, window_keys,
                                   window_keys_prompt)
from benchmark.flops_qwen3_next import (_least, expert_bytes,
                                        experts_touched, held_experts,
                                        held_pairs_per_token)


def _layers(shape: dict) -> tuple:
    """(window layers, full layers, dense FFN layers, expert layers)."""
    kinds = shape["layer_types"]
    n_win = sum(k == SLIDING for k in kinds)
    n_dense = min(shape["n_dense_layers"], len(kinds))
    return n_win, len(kinds) - n_win, n_dense, len(kinds) - n_dense


def attn_weights(shape: dict, kv_heads: int) -> int:
    """Weights of one layer's four projections: q at ``n_heads x head_dim``,
    k at ``kv_heads x head_dim``, v at ``kv_heads x v_head_dim``, out from
    ``n_heads x v_head_dim``."""
    d, H = shape["d_model"], shape["n_heads"]
    return d * (H * shape["head_dim"] + kv_heads * shape["head_dim"]
                + kv_heads * shape["v_head_dim"]) \
        + H * shape["v_head_dim"] * d


def attn_pair_flops(shape: dict) -> int:
    """One (query, key) pair over all query heads: the score over
    ``head_dim`` and the mix over ``v_head_dim``, 2 each a value."""
    return 2 * shape["n_heads"] * (shape["head_dim"] + shape["v_head_dim"])


def _per_token_but_attention(shape: dict) -> float:
    """Projections of both layer kinds (2 a weight), the dense FFN, the
    router over all ``n_experts``, the routed pairs that fall on the held
    experts in expectation (no shared expert), and the vocab head."""
    d = shape["d_model"]
    n_win, n_full, n_dense, n_moe = _layers(shape)
    proj = 2 * (n_win * attn_weights(shape, shape["window_kv_heads"])
                + n_full * attn_weights(shape, shape["n_kv_heads"]))
    dense = n_dense * 6 * d * shape["d_ff"]
    moe = n_moe * (2 * d * shape["n_experts"]
                   + held_pairs_per_token(shape) * 6 * d
                   * shape["d_ff_expert"])
    return proj + dense + moe + 2 * d * shape["vocab_size"]


def mimo_flops_per_token(shape: dict, ctx: float) -> float:
    """One token against ``ctx`` cached positions: scores and mix over all
    of them on the full layers, over the window's on the window layers."""
    n_win, n_full, _d, _m = _layers(shape)
    return _per_token_but_attention(shape) + attn_pair_flops(shape) * (
        n_full * ctx + n_win * window_keys(shape, ctx))


def mimo_flops_prompt(shape: dict, p: int) -> float:
    """A prompt of ``p`` tokens: every token at its own context, the vocab
    head once."""
    n_win, n_full, _d, _m = _layers(shape)
    head = 2 * shape["d_model"] * shape["vocab_size"]
    return p * (_per_token_but_attention(shape) - head) + head \
        + attn_pair_flops(shape) * (n_full * p * (p + 1) / 2.0
                                    + n_win * window_keys_prompt(shape, p))


def paged_attention_least_s(shape: dict, decode_ctx: list, prefill: list,
                            itemsize: int, peak: dict) -> dict:
    """Least time for the attention the live contexts needed.  A position is
    ``n_kv x (head_dim + v_head_dim)`` values, ``n_kv`` the layer kind's own
    (4 full, 8 sliding); a decoded token reads its whole context on a full
    layer and its window's (``min(ctx, W)``) on a sliding one; every prompt,
    ``(length, share of it prefilled)``, is read once whole on either kind
    (each key is some query's); every visible (query, key) pair pays all
    query heads' score over ``head_dim`` and mix over ``v_head_dim``."""
    n_win, n_full, _d, _m = _layers(shape)
    lanes = shape["head_dim"] + shape["v_head_dim"]
    full_b = shape["n_kv_heads"] * lanes * itemsize
    win_b = shape["window_kv_heads"] * lanes * itemsize
    pairs = sum(n_full * c + n_win * window_keys(shape, c)
                for c in decode_ctx) + sum(
        share * (n_full * p * (p + 1) / 2.0
                 + n_win * window_keys_prompt(shape, p))
        for p, share in prefill)
    byts = sum(n_full * full_b * c + n_win * win_b * window_keys(shape, c)
               for c in decode_ctx) + sum(
        (n_full * full_b + n_win * win_b) * p * share for p, share in prefill)
    return _least(pairs * attn_pair_flops(shape), byts, peak)


def moe_expert_least_s(shape: dict, decode_ctx: list, prefill: list,
                       itemsize: int, peak: dict) -> dict:
    """Least time for the held experts' work of the traffic, batched as
    ``flops_qwen3_next.moe_expert_least_s`` batches it: the decoded tokens
    in passes of ``clients`` tokens, the prefilled part of a prompt in ONE
    pass of its own.  A pass of ``n`` tokens reads the matrices of the held
    experts it TOUCHES in expectation (:func:`experts_touched`) once in
    every expert layer, and pays the operations of the ``n x top_k x held /
    n_experts`` pairs that fall on them.  Router and combine are not the
    kernel's."""
    n_moe = _layers(shape)[3]
    per_tok = held_pairs_per_token(shape)
    clients = shape["clients"]
    passes = [(clients, len(decode_ctx) / clients)] if decode_ctx else []
    passes += [(p * share, 1.0) for p, share in prefill]
    one = expert_bytes(shape, itemsize)
    byts = sum(n_pass * experts_touched(shape, n) * one
               for n, n_pass in passes) * n_moe
    flops = sum(n_pass * n * per_tok for n, n_pass in passes) \
        * 6 * shape["d_model"] * shape["d_ff_expert"] * n_moe
    return _least(flops, byts, peak)
