"""Operations and bytes of the ``qwen3_next`` block family, from shapes
alone (the counting module of ``qwen3next_step_mfu``, ``gdn_scan_roofline``,
``paged_attn_hd256_roofline`` and ``qwen3next_expert_roofline``).  ``shape``
is the system's ``decoder`` constant: the fields of ``Qwen3NextConfig`` and
``clients``.  The counts are of the work the traffic needs, whatever
implements it.
"""

from __future__ import annotations

GDN = "linear_attention"
STATE_ITEMSIZE = 4  # the matrix state is kept in f32


def _layers(shape: dict) -> tuple:
    """(gated DeltaNet layers, full-attention layers); every layer has the
    expert block."""
    kinds = shape["layer_types"]
    n_gdn = sum(k == GDN for k in kinds)
    return n_gdn, len(kinds) - n_gdn


def held_experts(shape: dict) -> int:
    return shape["n_experts"] if shape["n_held_experts"] is None \
        else shape["n_held_experts"]


def held_pairs_per_token(shape: dict) -> float:
    """Routed pairs of a token that fall on the experts held here, in
    expectation: ``top_k`` times the share of the experts held."""
    return shape["top_k"] * held_experts(shape) / shape["n_experts"]


def _widths(shape: dict) -> tuple:
    """(key width, value width) of a DeltaNet layer's streams."""
    return (shape["gdn_key_heads"] * shape["gdn_key_dim"],
            shape["gdn_value_heads"] * shape["gdn_value_dim"])


def gdn_mix_flops(shape: dict, chunked: bool) -> float:
    """The delta rule of one token in one DeltaNet layer, all value heads.
    The recurrence: decay, ``S^T k``, the rank-one update and ``S^T q`` on a
    state of ``dk x dv`` (7 dk dv).  The chunkwise form at the chunk ``n``
    the configuration states, a token's share of a chunk's work, counted as
    ``flops_kimi_linear.kda_mix_flops`` counts the same kernel: ``K K^T``,
    ``Q K^T``, the unit triangular solve applied to ``V`` and ``K`` and
    ``P U`` (8 n dk), ``W S0``, ``Q S0`` and ``K^T U`` (2 dk dv each)."""
    H, dk, dv = shape["gdn_value_heads"], shape["gdn_key_dim"], \
        shape["gdn_value_dim"]
    n = shape["gdn_chunk"]
    return H * ((8 * n * dk + 6 * dk * dv) if chunked else 7 * dk * dv)


def _gdn_projections(shape: dict) -> float:
    """A DeltaNet layer but its delta rule: ``W_qkvz``, ``W_ba``, the
    output projection, the conv taps."""
    d, kw_vw = shape["d_model"], _widths(shape)
    kw, vw = kw_vw
    return 2 * (d * (2 * kw + 2 * vw) + d * 2 * shape["gdn_value_heads"]
                + vw * d) + 2 * shape["conv_kernel"] * (2 * kw + vw)


def _full_projections(shape: dict) -> float:
    """A full-attention layer but its attention: the doubled ``W_q``
    (query and gate), ``W_k``, ``W_v``, ``W_o``."""
    d, hd = shape["d_model"], shape["head_dim"]
    H, KV = shape["n_heads"], shape["n_kv_heads"]
    return 2 * (d * 2 * H * hd + 2 * d * KV * hd + H * hd * d)


def attn_pair_flops(shape: dict) -> float:
    """One query token against one key in one full layer, all query heads:
    a score and a mix over ``head_dim``."""
    return 4 * shape["n_heads"] * shape["head_dim"]


def _per_token_but_mixing(shape: dict) -> float:
    """Projections of both kinds of layer, the router (all ``n_experts``
    outputs), the routed pairs that fall on the held experts, the shared
    expert and its gate of every layer, and the vocab head."""
    d = shape["d_model"]
    n_gdn, n_full = _layers(shape)
    moe = 2 * d * shape["n_experts"] + 2 * d \
        + held_pairs_per_token(shape) * 6 * d * shape["d_ff_expert"] \
        + 6 * d * shape["d_ff_shared"]
    return n_gdn * _gdn_projections(shape) \
        + n_full * _full_projections(shape) + (n_gdn + n_full) * moe \
        + 2 * d * shape["vocab_size"]


def qwen3next_flops_per_token(shape: dict, ctx: float) -> float:
    """One decoded token against ``ctx`` cached positions: the recurrence
    in the DeltaNet layers (no context), scores and mix over the context in
    the full layers."""
    n_gdn, n_full = _layers(shape)
    return _per_token_but_mixing(shape) \
        + n_gdn * gdn_mix_flops(shape, chunked=False) \
        + n_full * attn_pair_flops(shape) * ctx


def qwen3next_flops_prompt(shape: dict, p: int) -> float:
    """A prompt of ``p`` tokens: the chunkwise form in the DeltaNet layers,
    causal scores and mix in the full layers, the vocab head once."""
    n_gdn, n_full = _layers(shape)
    head = 2 * shape["d_model"] * shape["vocab_size"]
    return p * (_per_token_but_mixing(shape) - head
                + n_gdn * gdn_mix_flops(shape, chunked=True)) + head \
        + n_full * attn_pair_flops(shape) * p * (p + 1) / 2.0


def _least(flops: float, byts: float, peak: dict) -> dict:
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = byts / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": byts, "least_s": max(t_f, t_b),
            "bound": "compute" if t_f >= t_b else "memory"}


def gdn_scan_least_s(shape: dict, decode_ctx: list, prefill: list,
                     itemsize: int, peak: dict) -> dict:
    """Least time for the DeltaNet layers' mixing of the traffic.  Per token
    and layer: q and k (the key heads') and v in, o out, in the activations'
    ``itemsize``; beta and ONE log decay a value head, f32; the state
    (``value heads x dk x dv``, f32) read and written once a decode step of
    a token, and once a chunk of ``gdn_chunk`` tokens of a prompt's
    prefilled part; the recurrence's operations for a decoded token, the
    chunkwise form's for a prompt token."""
    Hv = shape["gdn_value_heads"]
    kw, vw = _widths(shape)
    n_gdn = _layers(shape)[0]
    tok_bytes = (2 * kw + 2 * vw) * itemsize + 2 * 4 * Hv
    state_bytes = 2 * Hv * shape["gdn_key_dim"] * shape["gdn_value_dim"] \
        * STATE_ITEMSIZE
    n_dec = len(decode_ctx)
    n_pre = sum(p * share for p, share in prefill)
    flops = n_dec * gdn_mix_flops(shape, False) \
        + n_pre * gdn_mix_flops(shape, True)
    byts = (n_dec + n_pre) * tok_bytes \
        + (n_dec + n_pre / shape["gdn_chunk"]) * state_bytes
    return _least(n_gdn * flops, n_gdn * byts, peak)


def attention_least_s(shape: dict, decode_ctx: list, prefill: list,
                      itemsize: int, peak: dict) -> dict:
    """Least time for the full layers' attention of the traffic: a position
    is ``n_kv_heads x head_dim`` values of K and as many of V, read once a
    decoded token for its whole context and once for a prompt, whole; every
    (query, key) pair pays all query heads' score and mix."""
    n_full = _layers(shape)[1]
    key_bytes = 2 * shape["n_kv_heads"] * shape["head_dim"] * itemsize
    pairs = sum(decode_ctx) + sum(share * p * (p + 1) / 2.0
                                  for p, share in prefill)
    keys = sum(decode_ctx) + sum(p * share for p, share in prefill)
    return _least(n_full * pairs * attn_pair_flops(shape),
                  n_full * keys * key_bytes, peak)


def expert_bytes(shape: dict, itemsize: int) -> float:
    """One expert's three matrices."""
    return 3 * shape["d_model"] * shape["d_ff_expert"] * itemsize


def experts_touched(shape: dict, tokens: float) -> float:
    """Held experts with at least one pair after a pass of ``tokens``
    tokens, in expectation: a token leaves an expert out with probability
    ``1 - top_k / n_experts``."""
    miss = 1.0 - shape["top_k"] / shape["n_experts"]
    return held_experts(shape) * (1.0 - miss ** tokens)


def moe_expert_least_s(shape: dict, decode_ctx: list, prefill: list,
                       itemsize: int, peak: dict) -> dict:
    """Least time for the held experts' work of the traffic, batched as
    ``flops_lfm2.moe_expert_least_s`` batches it: the decoded tokens in
    passes of ``clients`` tokens, the prefilled part of a prompt in ONE
    pass of its own (the engine cuts it into chunks and reads a touched
    expert once a chunk: that is the kernel's cost, not the traffic's
    need).  A pass of ``n`` tokens reads the matrices of the held experts it
    TOUCHES in expectation (:func:`experts_touched`: 35 of 128 at 16
    tokens, all of them past ~300) once in every layer, and pays the
    operations of the ``n x top_k x held / n_experts`` pairs that fall on
    them.  Router, shared expert and combine are not the kernel's."""
    n_layers = sum(_layers(shape))
    per_tok = held_pairs_per_token(shape)
    clients = shape["clients"]
    passes = [(clients, len(decode_ctx) / clients)] if decode_ctx else []
    passes += [(p * share, 1.0) for p, share in prefill]
    one = expert_bytes(shape, itemsize)
    byts = sum(n_pass * experts_touched(shape, n) * one
               for n, n_pass in passes) * n_layers
    flops = sum(n_pass * n * per_tok for n, n_pass in passes) \
        * 6 * shape["d_model"] * shape["d_ff_expert"] * n_layers
    return _least(flops, byts, peak)
