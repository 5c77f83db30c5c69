"""Import BERT-family HuggingFace weights into the JAX encoder.

Lets real pretrained embedders (MiniLM / BERT / sentence-transformers
encoders stored locally) run on the TPU compute path: the state dict maps
onto EncoderConfig(ln_placement="post") parameters and `encode_tokens`
reproduces the torch forward pass.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from .encoder import EncoderConfig


_ACT_MAP = {
    "gelu": "gelu",  # HF "gelu" is the exact erf form
    "gelu_new": "gelu_tanh",
    "gelu_pytorch_tanh": "gelu_tanh",
    "gelu_fast": "gelu_tanh",
    "relu": "relu",
}


def config_from_hf(hf_config) -> EncoderConfig:
    import jax.numpy as jnp

    if getattr(hf_config, "model_type", None) != "bert":
        raise ValueError(
            f"expected a BERT-family config, got model_type="
            f"{getattr(hf_config, 'model_type', None)!r} (GPT-2-family models "
            "load via JaxDecoderLM.from_hf)"
        )
    act = getattr(hf_config, "hidden_act", "gelu")
    if act not in _ACT_MAP:
        raise ValueError(
            f"unsupported hidden_act {act!r}; supported: {sorted(_ACT_MAP)}"
        )
    pos_type = getattr(hf_config, "position_embedding_type", "absolute")
    if pos_type != "absolute":
        raise ValueError(
            f"unsupported position_embedding_type {pos_type!r}; only "
            "'absolute' BERT-family models map onto this encoder"
        )
    return EncoderConfig(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        d_ff=hf_config.intermediate_size,
        max_len=hf_config.max_position_embeddings,
        dtype=jnp.float32,
        ln_placement="post",
        act=_ACT_MAP[act],
        ln_eps=float(getattr(hf_config, "layer_norm_eps", 1e-12)),
    )


def params_from_bert_state_dict(state: dict[str, Any], cfg: EncoderConfig) -> dict:
    """Map a (torch) BERT state dict onto the encoder's param pytree.

    Accepts both `bert.encoder.layer...` and `encoder.layer...` prefixes.
    Linear weights transpose (torch stores out x in)."""
    import jax.numpy as jnp

    def get(name: str) -> np.ndarray:
        for prefix in ("", "bert."):
            key = prefix + name
            if key in state:
                v = state[key]
                return np.asarray(v.detach().cpu().numpy() if hasattr(v, "detach") else v)
        raise KeyError(name)

    def lin_w(name: str) -> np.ndarray:
        return get(name).T  # torch Linear: (out, in) -> (in, out)

    params: dict = {
        "embed": jnp.asarray(get("embeddings.word_embeddings.weight")),
        "pos_embed": jnp.asarray(get("embeddings.position_embeddings.weight")),
        "ln_e_scale": jnp.asarray(get("embeddings.LayerNorm.weight")),
        "ln_e_bias": jnp.asarray(get("embeddings.LayerNorm.bias")),
        # post-LN models have no final LN; keep identity for API shape
        "ln_f_scale": jnp.ones((cfg.d_model,), jnp.float32),
        "ln_f_bias": jnp.zeros((cfg.d_model,), jnp.float32),
        "layers": [],
    }
    # token_type embeddings fold into the embedding table when all inputs are
    # segment 0 (the embedding lookup adds them per token)
    try:
        tt = get("embeddings.token_type_embeddings.weight")
        params["embed"] = params["embed"] + jnp.asarray(tt[0])[None, :]
    except KeyError:
        pass
    for i in range(cfg.n_layers):
        p = f"encoder.layer.{i}."
        layer = {
            "wq": jnp.asarray(lin_w(p + "attention.self.query.weight")),
            "bq": jnp.asarray(get(p + "attention.self.query.bias")),
            "wk": jnp.asarray(lin_w(p + "attention.self.key.weight")),
            "bk": jnp.asarray(get(p + "attention.self.key.bias")),
            "wv": jnp.asarray(lin_w(p + "attention.self.value.weight")),
            "bv": jnp.asarray(get(p + "attention.self.value.bias")),
            "wo": jnp.asarray(lin_w(p + "attention.output.dense.weight")),
            "bo": jnp.asarray(get(p + "attention.output.dense.bias")),
            "w_up": jnp.asarray(lin_w(p + "intermediate.dense.weight")),
            "b_up": jnp.asarray(get(p + "intermediate.dense.bias")),
            "w_down": jnp.asarray(lin_w(p + "output.dense.weight")),
            "b_down": jnp.asarray(get(p + "output.dense.bias")),
            "ln1_scale": jnp.asarray(get(p + "attention.output.LayerNorm.weight")),
            "ln1_bias": jnp.asarray(get(p + "attention.output.LayerNorm.bias")),
            "ln2_scale": jnp.asarray(get(p + "output.LayerNorm.weight")),
            "ln2_bias": jnp.asarray(get(p + "output.LayerNorm.bias")),
        }
        params["layers"].append(layer)
    return params


def config_from_gpt2(hf_config):
    """GPT-2-family config -> DecoderConfig (pre-LN, tanh gelu, tied head)."""
    import jax.numpy as jnp

    from .decoder import DecoderConfig

    if getattr(hf_config, "model_type", None) != "gpt2":
        raise ValueError(
            f"expected a GPT-2-family config, got model_type="
            f"{getattr(hf_config, 'model_type', None)!r} (BERT-family models "
            "load via JaxEncoder.from_hf)"
        )
    act = getattr(hf_config, "activation_function", "gelu_new")
    if act not in _ACT_MAP:
        raise ValueError(f"unsupported activation_function {act!r}")
    return DecoderConfig(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.n_embd,
        n_layers=hf_config.n_layer,
        n_heads=hf_config.n_head,
        d_ff=getattr(hf_config, "n_inner", None) or 4 * hf_config.n_embd,
        max_len=hf_config.n_positions,
        dtype=jnp.float32,
        ln_eps=float(getattr(hf_config, "layer_norm_epsilon", 1e-5)),
        act=_ACT_MAP[act],
    )


def config_from_lfm2_moe(hf_config, *, max_len: int | None = None,
                         dtype="auto"):
    """``lfm2_moe`` config (LiquidAI/LFM2-8B-A1B's ``config.json`` keys) ->
    :class:`~pathway_tpu.models.lfm2.Lfm2Config`.  ``layer_types`` is read
    as published and decides the depth; ``max_len`` caps the served
    context below ``max_position_embeddings`` (rotary positions: any cap
    is exact).  The head is tied to the embedding unless
    ``tie_word_embeddings`` (or LFM2's ``tie_embedding``) says otherwise;
    the published config gives neither key."""
    from .lfm2 import Lfm2Config

    def get(name, default=None):
        return getattr(hf_config, name, default)

    if get("model_type") != "lfm2_moe":
        raise ValueError(
            f"expected an lfm2_moe config, got model_type="
            f"{get('model_type')!r}")
    if get("conv_bias", False):
        raise ValueError("conv_bias=True is not written down here")
    layer_types = tuple(hf_config.layer_types)
    n_layers = get("num_hidden_layers", len(layer_types))
    if n_layers != len(layer_types):
        raise ValueError(
            f"num_hidden_layers={n_layers} but layer_types names "
            f"{len(layer_types)} layers")
    positions = int(hf_config.max_position_embeddings)
    tied = get("tie_word_embeddings", get("tie_embedding", True))
    return Lfm2Config(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        d_ff=hf_config.intermediate_size,
        d_ff_expert=hf_config.moe_intermediate_size,
        n_experts=hf_config.num_experts,
        top_k=hf_config.num_experts_per_tok,
        n_dense_layers=hf_config.num_dense_layers,
        layer_types=layer_types,
        conv_kernel=int(get("conv_L_cache", 3)),
        rope_theta=float(get("rope_theta", 1e6)),
        norm_eps=float(get("norm_eps", 1e-5)),
        max_len=min(positions, int(max_len)) if max_len else positions,
        dtype=dtype,
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        use_expert_bias=bool(get("use_expert_bias", True)),
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
        tie_embedding=bool(tied),
    )


def config_from_afmoe(hf_config, *, max_len: int | None = None,
                      dtype="auto"):
    """``afmoe`` config (arcee-ai/Trinity-Mini's ``config.json`` keys) ->
    :class:`~pathway_tpu.models.afmoe.AfmoeConfig`.  ``layer_types`` is
    read as published and decides the depth; ``max_len`` caps the served
    context below ``max_position_embeddings`` (rotary on the window layers,
    no positions on the full ones: any cap is exact).  What the keys can
    say and this family has not written down is refused: a score function
    other than sigmoid, a group limit on the router (``n_group`` /
    ``topk_group`` above 1), rotary scaling, a tied head, another
    activation than SiLU."""
    from .afmoe import AfmoeConfig

    def get(name, default=None):
        return getattr(hf_config, name, default)

    if get("model_type") != "afmoe":
        raise ValueError(
            f"expected an afmoe config, got model_type="
            f"{get('model_type')!r}")
    refused = [what for what, bad in (
        ("score_func other than sigmoid", get("score_func", "sigmoid")
         != "sigmoid"),
        ("a group limit on the router (n_group / topk_group > 1)",
         max(get("n_group", 1) or 1, get("topk_group", 1) or 1) > 1),
        ("rope_scaling", get("rope_scaling") is not None),
        ("tie_word_embeddings", bool(get("tie_word_embeddings", False))),
        ("hidden_act other than silu", get("hidden_act", "silu") != "silu"),
    ) if bad]
    if refused:
        raise ValueError("afmoe: not written down here: " + "; ".join(refused))
    layer_types = tuple(hf_config.layer_types)
    n_layers = get("num_hidden_layers", len(layer_types))
    if n_layers != len(layer_types):
        raise ValueError(
            f"num_hidden_layers={n_layers} but layer_types names "
            f"{len(layer_types)} layers")
    positions = int(hf_config.max_position_embeddings)
    return AfmoeConfig(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        head_dim=int(get("head_dim", hf_config.hidden_size
                         // hf_config.num_attention_heads)),
        d_ff=hf_config.intermediate_size,
        d_ff_expert=hf_config.moe_intermediate_size,
        n_experts=hf_config.num_experts,
        top_k=hf_config.num_experts_per_tok,
        n_shared_experts=int(get("num_shared_experts", 1)),
        n_dense_layers=hf_config.num_dense_layers,
        layer_types=layer_types,
        sliding_window=int(hf_config.sliding_window),
        rope_theta=float(get("rope_theta", 1e4)),
        norm_eps=float(get("rms_norm_eps", 1e-5)),
        max_len=min(positions, int(max_len)) if max_len else positions,
        dtype=dtype,
        route_norm=bool(get("route_norm", True)),
        route_scale=float(get("route_scale", 1.0)),
        mup_enabled=bool(get("mup_enabled", False)),
    )


def config_from_kimi_linear(hf_config, *, max_len: int | None = None,
                            dtype="auto", router_experts: int | None = None,
                            first_expert: int = 0):
    """``kimi_linear`` config (moonshotai/Kimi-Linear-48B-A3B-Instruct's
    ``config.json`` keys) ->
    :class:`~pathway_tpu.models.kimi_linear.KimiLinearConfig`.
    ``linear_attn_config`` (a mapping or an object) names the ``kda_layers``
    and the ``full_attn_layers`` by their 1-based numbers, the KDA heads,
    their size and the conv's taps; the layers up to ``num_hidden_layers``
    are taken.  ``max_len`` caps the served context below
    ``model_max_length`` (no positions anywhere: any cap is exact).

    ``router_experts``: the router's published width where ``num_experts``
    counts the experts this share HOLDS (one chip of an expert-parallel
    deployment: experts ``first_expert .. first_expert + num_experts``).

    What the keys can say and this family has not written down is refused:
    a router activation other than sigmoid, a group limit on the router,
    a low-rank query (``q_lora_rank``), rotary on the latent layers
    (``mla_use_nope`` false), rotary scaling, a tied head, another
    activation than SiLU, an expert layer in a part of the layers only
    (``moe_layer_freq``), multi-token prediction layers."""
    from .kimi_linear import KDA, MLA, KimiLinearConfig

    def get(name, default=None):
        return getattr(hf_config, name, default)

    if get("model_type") != "kimi_linear":
        raise ValueError(
            f"expected a kimi_linear config, got model_type="
            f"{get('model_type')!r}")
    refused = [what for what, bad in (
        ("moe_router_activation_func other than sigmoid",
         get("moe_router_activation_func", "sigmoid") != "sigmoid"),
        ("a group limit on the router (num_expert_group / topk_group > 1)",
         max(get("num_expert_group", 1) or 1, get("topk_group", 1) or 1) > 1),
        ("q_lora_rank", get("q_lora_rank") is not None),
        ("rotary on the latent layers (mla_use_nope false)",
         not get("mla_use_nope", True)),
        ("rope_scaling", get("rope_scaling") is not None),
        ("tie_word_embeddings", bool(get("tie_word_embeddings", False))),
        ("hidden_act other than silu", get("hidden_act", "silu") != "silu"),
        ("moe_layer_freq other than 1", get("moe_layer_freq", 1) != 1),
        ("num_nextn_predict_layers", bool(get("num_nextn_predict_layers", 0))),
        ("num_key_value_heads other than num_attention_heads",
         get("num_key_value_heads", hf_config.num_attention_heads)
         != hf_config.num_attention_heads),
    ) if bad]
    if refused:
        raise ValueError(
            "kimi_linear: not written down here: " + "; ".join(refused))
    lin = hf_config.linear_attn_config
    if not isinstance(lin, dict):
        lin = vars(lin)
    if lin["num_heads"] != hf_config.num_attention_heads:
        raise ValueError(
            "kimi_linear: KDA and latent layers with different head counts "
            "are not written down here")
    n_layers = int(hf_config.num_hidden_layers)
    kinds = {int(i): KDA for i in lin["kda_layers"]}
    kinds.update({int(i): MLA for i in lin["full_attn_layers"]})
    missing = [i for i in range(1, n_layers + 1) if i not in kinds]
    if missing:
        raise ValueError(
            f"linear_attn_config names no kind for layer(s) {missing}")
    positions = int(get("model_max_length", 0)
                    or get("max_position_embeddings"))
    held = int(hf_config.num_experts)
    return KimiLinearConfig(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_heads=hf_config.num_attention_heads,
        kda_head_dim=int(lin["head_dim"]),
        conv_kernel=int(lin["short_conv_kernel_size"]),
        kv_lora_rank=int(hf_config.kv_lora_rank),
        qk_nope_head_dim=int(hf_config.qk_nope_head_dim),
        qk_rope_head_dim=int(hf_config.qk_rope_head_dim),
        v_head_dim=int(hf_config.v_head_dim),
        d_ff=hf_config.intermediate_size,
        d_ff_expert=hf_config.moe_intermediate_size,
        n_experts=held if router_experts is None else int(router_experts),
        n_held_experts=None if router_experts is None else held,
        first_expert=int(first_expert),
        top_k=hf_config.num_experts_per_token,
        n_shared_experts=int(get("num_shared_experts", 1)),
        n_dense_layers=int(get("first_k_dense_replace", 0)),
        layer_types=tuple(kinds[i] for i in range(1, n_layers + 1)),
        norm_eps=float(get("rms_norm_eps", 1e-5)),
        max_len=min(positions, int(max_len)) if max_len else positions,
        dtype=dtype,
        route_norm=bool(get("moe_renormalize", True)),
        route_scale=float(get("routed_scaling_factor", 1.0)),
    )


def config_from_qwen3_next(hf_config, *, max_len: int | None = None,
                           dtype="auto", router_experts: int | None = None,
                           first_expert: int = 0):
    """``qwen3_next`` config (Qwen/Qwen3-Next-80B-A3B-Instruct's
    ``config.json`` keys) ->
    :class:`~pathway_tpu.models.qwen3_next.Qwen3NextConfig`.  Layer ``i``
    (1-based) is full attention where ``i % full_attention_interval == 0``,
    else gated DeltaNet; ``partial_rotary_factor`` of ``head_dim`` takes the
    rotary; every layer has the expert block.  ``max_len`` caps the served
    context below ``max_position_embeddings``.

    ``router_experts``: the router's published width where ``num_experts``
    counts the experts this share HOLDS (one chip of an expert-parallel
    deployment: experts ``first_expert .. first_expert + num_experts``).

    The published checkpoint lays ``W_qkvz`` and ``W_ba`` out interleaved
    by key head (a key head's q, k, then its value heads' v, z; b, a
    likewise) and ``W_q`` a head as ``[query ; gate]``; the programs take
    ``wqkvz`` in the split form ``[q ; k ; v ; z]``, ``wba`` as ``[b ; a]``
    and ``wq`` a head as published.

    What the keys can say and this family has not written down is refused:
    dense layers (``mlp_only_layers`` not empty, ``decoder_sparse_step``
    other than 1), rotary scaling, a sliding window, a tied head, another
    activation than SiLU, unnormalised router weights (``norm_topk_prob``
    false), multi-token prediction layers."""
    from .qwen3_next import FULL, GDN, Qwen3NextConfig

    def get(name, default=None):
        return getattr(hf_config, name, default)

    if get("model_type") != "qwen3_next":
        raise ValueError(
            f"expected a qwen3_next config, got model_type="
            f"{get('model_type')!r}")
    refused = [what for what, bad in (
        ("mlp_only_layers", bool(get("mlp_only_layers", []))),
        ("decoder_sparse_step other than 1",
         get("decoder_sparse_step", 1) != 1),
        ("rope_scaling", get("rope_scaling") is not None),
        ("use_sliding_window", bool(get("use_sliding_window", False))),
        ("tie_word_embeddings", bool(get("tie_word_embeddings", False))),
        ("hidden_act other than silu", get("hidden_act", "silu") != "silu"),
        ("norm_topk_prob false", not get("norm_topk_prob", True)),
        ("multi-token prediction layers (num_nextn_predict_layers / "
         "mtp_num_hidden_layers)",
         bool(get("num_nextn_predict_layers", 0)
              or get("mtp_num_hidden_layers", 0))),
    ) if bad]
    if refused:
        raise ValueError(
            "qwen3_next: not written down here: " + "; ".join(refused))
    n_layers = int(hf_config.num_hidden_layers)
    every = int(get("full_attention_interval", 4))
    hd = int(get("head_dim", hf_config.hidden_size
                 // hf_config.num_attention_heads))
    positions = int(hf_config.max_position_embeddings)
    held = int(hf_config.num_experts)
    return Qwen3NextConfig(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        head_dim=hd,
        rotary_dim=int(hd * float(get("partial_rotary_factor", 1.0))),
        gdn_key_heads=int(hf_config.linear_num_key_heads),
        gdn_value_heads=int(hf_config.linear_num_value_heads),
        gdn_key_dim=int(hf_config.linear_key_head_dim),
        gdn_value_dim=int(hf_config.linear_value_head_dim),
        conv_kernel=int(hf_config.linear_conv_kernel_dim),
        d_ff_expert=hf_config.moe_intermediate_size,
        d_ff_shared=hf_config.shared_expert_intermediate_size,
        n_experts=held if router_experts is None else int(router_experts),
        n_held_experts=None if router_experts is None else held,
        first_expert=int(first_expert),
        top_k=hf_config.num_experts_per_tok,
        layer_types=tuple(FULL if i % every == 0 else GDN
                          for i in range(1, n_layers + 1)),
        rope_theta=float(get("rope_theta", 1e7)),
        norm_eps=float(get("rms_norm_eps", 1e-6)),
        max_len=min(positions, int(max_len)) if max_len else positions,
        dtype=dtype,
    )


def config_from_mimo_v2_flash(hf_config, *, max_len: int | None = None,
                              dtype="auto", router_experts: int | None = None,
                              first_expert: int = 0):
    """``mimo_v2_flash`` config (XiaomiMiMo/MiMo-V2-Flash's ``config.json``
    keys) -> :class:`~pathway_tpu.models.mimo_v2_flash.MimoV2FlashConfig`.
    Layer ``i`` is full attention where ``hybrid_layer_pattern[i] == 0`` and
    sliding-window where it is 1 (``num_key_value_heads`` K/V heads on the
    former, ``swa_num_key_value_heads`` on the latter, ``rope_theta`` /
    ``swa_rope_theta``); ``int(head_dim x partial_rotary_factor)`` leading
    values of a head take the rotary; the layers where ``moe_layer_freq`` is
    0 (leading ones only) have the dense feed-forward.  ``max_len`` caps the
    served context below ``max_position_embeddings``.

    ``router_experts``: the router's published width where
    ``n_routed_experts`` counts the experts this share HOLDS (one chip of an
    expert-parallel deployment: experts ``first_expert .. first_expert +
    n_routed_experts``).

    What the keys can say and this family has not written down is refused:
    rotary scaling, grouped routing (``n_group`` / ``topk_group`` other than
    1), another ``topk_method`` or ``scoring_func``, shared experts, an
    attention bias, a sink on the full layers or none on the sliding ones,
    sliding layers of other head counts or widths than the full ones' query
    side, unnormalised router weights, a tied head, another activation than
    SiLU, a dense layer after an expert layer, multi-token prediction
    layers."""
    from .afmoe import FULL, SLIDING
    from .mimo_v2_flash import MimoV2FlashConfig

    def get(name, default=None):
        return getattr(hf_config, name, default)

    if get("model_type") != "mimo_v2_flash":
        raise ValueError(
            f"expected a mimo_v2_flash config, got model_type="
            f"{get('model_type')!r}")
    n_layers = int(hf_config.num_hidden_layers)
    pattern = [int(p) for p in hf_config.hybrid_layer_pattern][:n_layers]
    moe = [int(m) for m in hf_config.moe_layer_freq][:n_layers]
    n_dense = moe.index(1) if 1 in moe else len(moe)
    hd = int(hf_config.head_dim)
    scaling = get("rope_scaling")
    refused = [what for what, bad in (
        ("hybrid_layer_pattern / moe_layer_freq shorter than "
         "num_hidden_layers", len(pattern) < n_layers or len(moe) < n_layers),
        ("rope_scaling", scaling is not None and (scaling.get(
            "rope_type", scaling.get("type")) != "default")),
        ("n_group / topk_group other than 1",
         get("n_group", 1) != 1 or get("topk_group", 1) != 1),
        ("topk_method other than noaux_tc",
         get("topk_method", "noaux_tc") != "noaux_tc"),
        ("scoring_func other than sigmoid",
         get("scoring_func", "sigmoid") != "sigmoid"),
        ("n_shared_experts", bool(get("n_shared_experts"))),
        ("attention_bias", bool(get("attention_bias", False))),
        ("add_full_attention_sink_bias",
         bool(get("add_full_attention_sink_bias", False))),
        ("add_swa_attention_sink_bias false",
         not get("add_swa_attention_sink_bias", True)),
        ("swa_num_attention_heads / swa_head_dim / swa_v_head_dim other "
         "than the full layers'",
         get("swa_num_attention_heads", hf_config.num_attention_heads)
         != hf_config.num_attention_heads
         or get("swa_head_dim", hd) != hd
         or get("swa_v_head_dim", hf_config.v_head_dim)
         != hf_config.v_head_dim),
        ("sliding_window_size other than sliding_window",
         get("sliding_window_size", hf_config.sliding_window)
         != hf_config.sliding_window),
        ("norm_topk_prob false", not get("norm_topk_prob", True)),
        ("tie_word_embeddings", bool(get("tie_word_embeddings", False))),
        ("hidden_act other than silu", get("hidden_act", "silu") != "silu"),
        ("a dense layer after an expert layer (moe_layer_freq)",
         0 in moe[n_dense:]),
        ("multi-token prediction layers (num_nextn_predict_layers / "
         "mtp_num_hidden_layers)",
         bool(get("num_nextn_predict_layers", 0)
              or get("mtp_num_hidden_layers", 0))),
    ) if bad]
    if refused:
        raise ValueError(
            "mimo_v2_flash: not written down here: " + "; ".join(refused))
    positions = int(hf_config.max_position_embeddings)
    held = int(hf_config.n_routed_experts)
    scale = get("routed_scaling_factor")
    return MimoV2FlashConfig(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        window_kv_heads=int(get("swa_num_key_value_heads",
                                hf_config.num_key_value_heads)),
        head_dim=hd,
        v_head_dim=int(hf_config.v_head_dim),
        rotary_dim=int(hd * float(get("partial_rotary_factor", 1.0))),
        d_ff=hf_config.intermediate_size,
        d_ff_expert=hf_config.moe_intermediate_size,
        n_experts=held if router_experts is None else int(router_experts),
        n_held_experts=None if router_experts is None else held,
        first_expert=int(first_expert),
        top_k=hf_config.num_experts_per_tok,
        n_dense_layers=n_dense,
        layer_types=tuple(SLIDING if p else FULL for p in pattern),
        sliding_window=int(hf_config.sliding_window),
        rope_theta=float(get("rope_theta", 5e6)),
        window_rope_theta=float(get("swa_rope_theta", 1e4)),
        value_scale=float(get("attention_value_scale", 1.0)),
        route_scale=1.0 if scale is None else float(scale),
        norm_eps=float(get("layernorm_epsilon", 1e-5)),
        max_len=min(positions, int(max_len)) if max_len else positions,
        dtype=dtype,
    )


def config_from_phi4flash(hf_config, *, max_len: int | None = None,
                          dtype="auto", d_state: int = 16, d_conv: int = 4,
                          expand: int = 2, dt_rank: int | None = None):
    """``phi4flash`` config (microsoft/Phi-4-mini-flash-reasoning's
    ``config.json`` keys) -> :class:`~pathway_tpu.models.phi4_mini_flash
    .Phi4MiniFlashConfig`.  The Mamba sizes are not keys of the config but
    the modeling code's own (``d_state`` 16, ``d_conv`` 4, ``expand`` 2,
    ``dt_rank = ceil(hidden_size / 16)``), given here; ``head_dim`` is
    ``hidden_size / num_attention_heads``.  ``max_len`` caps the served
    context below ``max_position_embeddings``.

    What the keys can say and this family has not written down is refused:
    an untied head, biases in the feed-forward or the head, another
    activation than SiLU, a Mamba layer other than every second one."""
    from .phi4_mini_flash import Phi4MiniFlashConfig

    def get(name, default=None):
        return getattr(hf_config, name, default)

    if get("model_type") != "phi4flash":
        raise ValueError(
            f"expected a phi4flash config, got model_type="
            f"{get('model_type')!r}")
    refused = [what for what, bad in (
        ("tie_word_embeddings false", not get("tie_word_embeddings", True)),
        ("mlp_bias", bool(get("mlp_bias", False))),
        ("lm_head_bias", bool(get("lm_head_bias", False))),
        (f"hidden_act {get('hidden_act')!r}",
         get("hidden_act", "silu") != "silu"),
        ("mb_per_layer other than 2", int(get("mb_per_layer", 2)) != 2),
    ) if bad]
    if refused:
        raise ValueError("the phi4_mini_flash family does not support "
                         + "; ".join(refused))
    d = int(hf_config.hidden_size)
    n_heads = int(hf_config.num_attention_heads)
    positions = int(hf_config.max_position_embeddings)
    return Phi4MiniFlashConfig(
        vocab_size=int(hf_config.vocab_size), d_model=d,
        n_layers=int(hf_config.num_hidden_layers), n_heads=n_heads,
        n_kv_heads=int(hf_config.num_key_value_heads),
        head_dim=d // n_heads, d_ff=int(hf_config.intermediate_size),
        sliding_window=int(hf_config.sliding_window), mb_per_layer=2,
        d_state=d_state, d_conv=d_conv, expand=expand,
        dt_rank=-(-d // 16) if dt_rank is None else dt_rank,
        norm_eps=float(get("layer_norm_eps", 1e-5)),
        max_len=positions if max_len is None else min(max_len, positions),
        dtype=dtype)


def params_from_lfm2_state_dict(state: dict[str, Any], cfg) -> dict:
    """Map a (torch) LFM2-family state dict onto
    :mod:`pathway_tpu.models.lfm2`'s parameter pytree, in ``cfg``'s dtype.
    Linear weights transpose (torch stores out x in); the depthwise conv's
    ``(d, 1, 3)`` taps become ``(d, 3)``.  Dense feed-forwards are
    ``feed_forward.w1/w2/w3``; an expert layer is read as
    ``feed_forward.gate``, ``feed_forward.expert_bias`` and
    ``feed_forward.experts.<e>.w1/w2/w3`` (the names of the ``lfm2_moe``
    checkpoints; the installed ``transformers`` has only the dense
    family, which is what the parity test holds this to)."""
    import jax.numpy as jnp

    from .encoder import _resolve_dtype
    from .lfm2 import ATTENTION

    dtype = _resolve_dtype(cfg.dtype)

    def get(name: str) -> np.ndarray:
        for prefix in ("", "model."):
            if prefix + name in state:
                v = state[prefix + name]
                return np.asarray(v.detach().cpu().float().numpy()
                                  if hasattr(v, "detach") else v)
        raise KeyError(name)

    def arr(x, keep_f32=False):
        return jnp.asarray(x, jnp.float32 if keep_f32 else dtype)

    def lin(name: str):
        return arr(get(name + ".weight").T)

    params: dict = {"embed": arr(get("embed_tokens.weight")),
                    "norm_out": arr(get("embedding_norm.weight")),
                    "layers": []}
    if not cfg.tie_embedding:
        params["head"] = arr(np.asarray(state["lm_head.weight"]).T)
    for li, kind in enumerate(cfg.layer_types):
        pre = f"layers.{li}."
        lay = {"norm_op": arr(get(pre + "operator_norm.weight")),
               "norm_ffn": arr(get(pre + "ffn_norm.weight"))}
        if kind == ATTENTION:
            at = pre + "self_attn."
            lay.update(wq=lin(at + "q_proj"), wk=lin(at + "k_proj"),
                       wv=lin(at + "v_proj"), wo=lin(at + "out_proj"),
                       q_norm=arr(get(at + "q_layernorm.weight")),
                       k_norm=arr(get(at + "k_layernorm.weight")))
        else:
            cv = pre + "conv."
            lay.update(w_in=lin(cv + "in_proj"), w_out=lin(cv + "out_proj"),
                       conv_w=arr(get(cv + "conv.weight")[:, 0, :]))
        ff = pre + "feed_forward."
        if li < cfg.n_dense_layers:
            lay.update(w1=lin(ff + "w1"), w3=lin(ff + "w3"),
                       w2=lin(ff + "w2"))
        else:
            def experts(w):
                return arr(np.stack([
                    get(f"{ff}experts.{e}.{w}.weight").T
                    for e in range(cfg.n_experts)]))

            lay.update(wg=lin(ff + "gate"), w1=experts("w1"),
                       w3=experts("w3"), w2=experts("w2"))
            if cfg.use_expert_bias:
                lay["expert_bias"] = arr(get(ff + "expert_bias"),
                                         keep_f32=True)
        params["layers"].append(lay)
    return params


def params_from_gpt2_state_dict(state: dict[str, Any], cfg) -> dict:
    """Map a (torch) GPT-2 state dict onto the decoder's param pytree.

    GPT-2 uses Conv1D (weights already (in, out)) and a fused qkv
    projection; the lm head is tied to wte (as is our logits head)."""
    import jax.numpy as jnp

    def get(name: str) -> np.ndarray:
        for prefix in ("", "transformer."):
            key = prefix + name
            if key in state:
                v = state[key]
                return np.asarray(v.detach().cpu().numpy() if hasattr(v, "detach") else v)
        raise KeyError(name)

    D = cfg.d_model
    params: dict = {
        "embed": jnp.asarray(get("wte.weight")),
        "pos_embed": jnp.asarray(get("wpe.weight")),
        "ln_f_scale": jnp.asarray(get("ln_f.weight")),
        "ln_f_bias": jnp.asarray(get("ln_f.bias")),
        "layers": [],
    }
    for i in range(cfg.n_layers):
        p = f"h.{i}."
        c_attn_w = get(p + "attn.c_attn.weight")  # (D, 3D)
        c_attn_b = get(p + "attn.c_attn.bias")  # (3D,)
        layer = {
            "wq": jnp.asarray(c_attn_w[:, :D]),
            "bq": jnp.asarray(c_attn_b[:D]),
            "wk": jnp.asarray(c_attn_w[:, D : 2 * D]),
            "bk": jnp.asarray(c_attn_b[D : 2 * D]),
            "wv": jnp.asarray(c_attn_w[:, 2 * D :]),
            "bv": jnp.asarray(c_attn_b[2 * D :]),
            "wo": jnp.asarray(get(p + "attn.c_proj.weight")),
            "bo": jnp.asarray(get(p + "attn.c_proj.bias")),
            "w_up": jnp.asarray(get(p + "mlp.c_fc.weight")),
            "b_up": jnp.asarray(get(p + "mlp.c_fc.bias")),
            "w_down": jnp.asarray(get(p + "mlp.c_proj.weight")),
            "b_down": jnp.asarray(get(p + "mlp.c_proj.bias")),
            "ln1_scale": jnp.asarray(get(p + "ln_1.weight")),
            "ln1_bias": jnp.asarray(get(p + "ln_1.bias")),
            "ln2_scale": jnp.asarray(get(p + "ln_2.weight")),
            "ln2_bias": jnp.asarray(get(p + "ln_2.bias")),
        }
        params["layers"].append(layer)
    return params


def load_hf_decoder(model_name_or_path: str):
    """Load a local GPT-2-family model into (params, cfg, hf_tokenizer)."""
    from transformers import AutoConfig, AutoModel, AutoTokenizer

    hf_cfg = AutoConfig.from_pretrained(model_name_or_path)
    cfg = config_from_gpt2(hf_cfg)  # validates BEFORE the heavy model load
    model = AutoModel.from_pretrained(model_name_or_path)
    params = params_from_gpt2_state_dict(model.state_dict(), cfg)
    try:
        tok = AutoTokenizer.from_pretrained(model_name_or_path)
    except Exception:
        tok = None
    return params, cfg, tok


def load_hf_encoder(model_name_or_path: str):
    """Load a local BERT-family model into (params, cfg, hf_tokenizer).

    No network access: the model must be importable locally (a saved
    directory, or a randomly-initialized config for testing)."""
    from transformers import AutoConfig, AutoModel, AutoTokenizer

    hf_cfg = AutoConfig.from_pretrained(model_name_or_path)
    cfg = config_from_hf(hf_cfg)  # validates BEFORE the heavy model load
    model = AutoModel.from_pretrained(model_name_or_path)
    params = params_from_bert_state_dict(model.state_dict(), cfg)
    try:
        tok = AutoTokenizer.from_pretrained(model_name_or_path)
    except Exception:
        tok = None
    return params, cfg, tok
