"""Operations and bytes of the ``kimi_linear`` block family, from shapes
alone (the counting module of ``kimi_step_mfu``, ``kda_scan_roofline``,
``mla_attn_roofline`` and ``kimi_expert_roofline``).  ``shape`` is the
system's ``decoder`` constant: the fields of ``KimiLinearConfig`` and
``clients``.  The counts are of the work the traffic needs, whatever
implements it.
"""

from __future__ import annotations

KDA = "kda"
STATE_ITEMSIZE = 4  # the matrix state is kept in f32


def _layers(shape: dict) -> tuple:
    """(KDA layers, latent layers, dense FFN layers, expert layers)."""
    kinds = shape["layer_types"]
    n_kda = sum(k == KDA for k in kinds)
    n_dense = min(shape["n_dense_layers"], len(kinds))
    return n_kda, len(kinds) - n_kda, n_dense, len(kinds) - n_dense


def held_experts(shape: dict) -> int:
    return shape["n_experts"] if shape["n_held_experts"] is None \
        else shape["n_held_experts"]


def held_pairs_per_token(shape: dict) -> float:
    """Routed pairs of a token that fall on the experts held here, in
    expectation: ``top_k`` times the share of the experts held."""
    return shape["top_k"] * held_experts(shape) / shape["n_experts"]


def latent_width(shape: dict) -> int:
    """Values a token leaves in a latent layer's cache (``c`` and
    ``k_r``)."""
    return shape["kv_lora_rank"] + shape["qk_rope_head_dim"]


def kda_mix_flops(shape: dict, chunked: bool) -> float:
    """The delta rule of one token in one KDA layer, all heads.  The
    recurrence: decay, ``S^T k``, the rank-one update and ``S^T q`` on a
    state of ``dk x dv`` (7 dk dv).  The chunkwise form at the chunk ``n``
    the configuration states, a token's share of a chunk's work: ``K K^T``,
    ``Q K^T``, the unit triangular solve applied to ``V`` and ``K``, and
    ``P U`` (2 n^2 dk each), ``W S0``, ``Q S0`` and ``K^T U`` (2 n dk dv
    each)."""
    H, d, n = shape["n_heads"], shape["kda_head_dim"], shape["kda_chunk"]
    return H * ((8 * n * d + 6 * d * d) if chunked else 7 * d * d)


def _kda_projections(shape: dict) -> float:
    """A KDA layer but its delta rule: q, k, v, the decay's and the gate's
    low-rank pairs, beta, the output projection, the conv taps."""
    d, H, lo = shape["d_model"], shape["n_heads"], shape["kda_head_dim"]
    w = H * lo
    return 2 * (3 * d * w + 2 * (d * lo + lo * w) + d * H + w * d) \
        + 2 * shape["conv_kernel"] * 3 * w


def _mla_projections(shape: dict) -> float:
    """A latent layer but its attention: q, the latent and k_r, the
    absorbed halves of W_kv_b (to the queries, to the mix), the output
    projection."""
    d, H = shape["d_model"], shape["n_heads"]
    nope, rope = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"]
    r, dv = shape["kv_lora_rank"], shape["v_head_dim"]
    return 2 * (d * H * (nope + rope) + d * (r + rope) + H * nope * r
                + H * r * dv + H * dv * d)


def mla_pair_flops(shape: dict) -> float:
    """One query token against one key in one latent layer, all heads, in
    the absorbed form: a score over ``c`` and ``k_r``, a mix over ``c``."""
    return 2 * shape["n_heads"] * (latent_width(shape)
                                   + shape["kv_lora_rank"])


def _per_token_but_mixing(shape: dict) -> float:
    """Projections of both kinds of layer, the dense FFNs, the router (all
    ``n_experts`` outputs), the routed pairs that fall on the held experts
    and the shared expert of every expert layer, and the vocab head."""
    d = shape["d_model"]
    n_kda, n_mla, n_dense, n_moe = _layers(shape)
    moe = n_moe * (2 * d * shape["n_experts"]
                   + (held_pairs_per_token(shape) + shape["n_shared_experts"])
                   * 6 * d * shape["d_ff_expert"])
    return n_kda * _kda_projections(shape) + n_mla * _mla_projections(shape) \
        + n_dense * 6 * d * shape["d_ff"] + moe \
        + 2 * d * shape["vocab_size"]


def kimi_flops_per_token(shape: dict, ctx: float) -> float:
    """One decoded token against ``ctx`` cached positions: the recurrence
    in the KDA layers (no context), scores and mix over the context in the
    latent layers."""
    n_kda, n_mla, _d, _m = _layers(shape)
    return _per_token_but_mixing(shape) \
        + n_kda * kda_mix_flops(shape, chunked=False) \
        + n_mla * mla_pair_flops(shape) * ctx


def kimi_flops_prompt(shape: dict, p: int) -> float:
    """A prompt of ``p`` tokens: the chunkwise form in the KDA layers,
    causal scores and mix in the latent layers, the vocab head once."""
    n_kda, n_mla, _d, _m = _layers(shape)
    head = 2 * shape["d_model"] * shape["vocab_size"]
    return p * (_per_token_but_mixing(shape) - head
                + n_kda * kda_mix_flops(shape, chunked=True)) + head \
        + n_mla * mla_pair_flops(shape) * p * (p + 1) / 2.0


def _least(flops: float, byts: float, peak: dict) -> dict:
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = byts / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": byts, "least_s": max(t_f, t_b),
            "bound": "compute" if t_f >= t_b else "memory"}


def kda_scan_least_s(shape: dict, decode_ctx: list, prefill: list,
                     itemsize: int, peak: dict) -> dict:
    """Least time for the KDA layers' mixing of the traffic.  Per token and
    layer: q, k, v in and o out in the activations' ``itemsize``, the log
    decay in f32 and beta (one a head); the state (``heads x dk x dv``, f32)
    read and written once a decode step of a token, and once a chunk of
    ``kda_chunk`` tokens of a prompt's prefilled part; the recurrence's
    operations for a decoded token, the chunkwise form's for a prompt
    token."""
    H, d = shape["n_heads"], shape["kda_head_dim"]
    n_kda = _layers(shape)[0]
    w = H * d
    tok_bytes = 4 * w * itemsize + 4 * w + 4 * H
    state_bytes = 2 * H * d * d * STATE_ITEMSIZE
    n_dec = len(decode_ctx)
    n_pre = sum(p * share for p, share in prefill)
    flops = n_dec * kda_mix_flops(shape, False) \
        + n_pre * kda_mix_flops(shape, True)
    byts = (n_dec + n_pre) * tok_bytes \
        + (n_dec + n_pre / shape["kda_chunk"]) * state_bytes
    return _least(n_kda * flops, n_kda * byts, peak)


def mla_attention_least_s(shape: dict, decode_ctx: list, prefill: list,
                          itemsize: int, peak: dict) -> dict:
    """Least time for the latent layers' attention of the traffic, in the
    absorbed form: a key is ``c`` and ``k_r`` (``kv_lora_rank +
    qk_rope_head_dim`` values), read once a decoded token for its whole
    context and once for a prompt, whole; every (query, key) pair pays all
    heads' score and mix."""
    n_mla = _layers(shape)[1]
    key_bytes = latent_width(shape) * itemsize
    pairs = sum(decode_ctx) + sum(share * p * (p + 1) / 2.0
                                  for p, share in prefill)
    keys = sum(decode_ctx) + sum(p * share for p, share in prefill)
    return _least(n_mla * pairs * mla_pair_flops(shape),
                  n_mla * keys * key_bytes, peak)


def expert_bytes(shape: dict, itemsize: int) -> float:
    """One expert's three matrices."""
    return 3 * shape["d_model"] * shape["d_ff_expert"] * itemsize


def moe_expert_least_s(shape: dict, decode_ctx: list, prefill: list,
                       itemsize: int, peak: dict) -> dict:
    """Least time for the held experts' work of the traffic, batched as
    ``flops_lfm2.moe_expert_least_s`` batches it: the decoded tokens in
    passes of ``clients`` tokens, the prefilled part of a prompt in one pass
    of its own.  A pass of ``n`` tokens routes ``n x top_k x held /
    n_experts`` pairs to the experts held here (in expectation), reads at
    most that many of the ``held`` experts' matrices once in every expert
    layer, and pays those pairs' operations.  Router, shared expert and
    combine are not the kernel's."""
    n_moe = _layers(shape)[3]
    held, per_tok = held_experts(shape), held_pairs_per_token(shape)
    clients = shape["clients"]
    passes = [(clients, len(decode_ctx) / clients)] if decode_ctx else []
    passes += [(p * share, 1.0) for p, share in prefill]
    one = expert_bytes(shape, itemsize)
    byts = sum(n_pass * min(held, n * per_tok) * one
               for n, n_pass in passes) * n_moe
    flops = sum(n_pass * n * per_tok for n, n_pass in passes) \
        * 6 * shape["d_model"] * shape["d_ff_expert"] * n_moe
    return _least(flops, byts, peak)
