"""HF weight import parity: our post-LN encoder must reproduce torch
BertModel's forward pass (random weights; no network)."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


def _tiny_bert():
    from transformers import BertConfig, BertModel

    torch.manual_seed(0)
    cfg = BertConfig(
        vocab_size=200, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=40, hidden_act="gelu",
    )
    return cfg, BertModel(cfg).eval()


def test_bert_forward_parity():
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import encode_tokens
    from pathway_tpu.models.hf_import import (
        config_from_hf,
        params_from_bert_state_dict,
    )

    hf_cfg, model = _tiny_bert()
    cfg = config_from_hf(hf_cfg)
    params = params_from_bert_state_dict(model.state_dict(), cfg)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 200, (2, 12))
    mask = np.ones((2, 12), dtype=np.int64)
    mask[1, 8:] = 0
    with torch.no_grad():
        ref = model(
            input_ids=torch.tensor(ids), attention_mask=torch.tensor(mask)
        ).last_hidden_state.numpy()
    ours = np.asarray(
        encode_tokens(params, cfg, jnp.asarray(ids, jnp.int32), jnp.asarray(mask, bool))
    )
    diff = np.abs(ours - ref)[mask.astype(bool)]
    assert diff.max() < 2e-4, diff.max()


def test_gpt2_logits_parity():
    import jax.numpy as jnp
    from transformers import GPT2Config, GPT2LMHeadModel

    from pathway_tpu.models.decoder import forward_logits
    from pathway_tpu.models.hf_import import (
        config_from_gpt2,
        params_from_gpt2_state_dict,
    )

    torch.manual_seed(0)
    hf = GPT2Config(vocab_size=150, n_embd=32, n_layer=2, n_head=4, n_positions=24)
    model = GPT2LMHeadModel(hf).eval()
    cfg = config_from_gpt2(hf)
    params = params_from_gpt2_state_dict(model.transformer.state_dict(), cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 150, (2, 10))
    with torch.no_grad():
        ref = model(input_ids=torch.tensor(ids)).logits.numpy()
    ours = np.asarray(forward_logits(params, cfg, jnp.asarray(ids, jnp.int32)))
    assert np.abs(ours - ref).max() < 5e-4
    assert (ours[:, -1].argmax(-1) == ref[:, -1].argmax(-1)).all()


def test_gpt2_generate_from_saved(tmp_path):
    from transformers import GPT2Config, GPT2LMHeadModel

    torch.manual_seed(0)
    hf = GPT2Config(vocab_size=150, n_embd=32, n_layer=2, n_head=4, n_positions=64)
    GPT2LMHeadModel(hf).transformer.save_pretrained(str(tmp_path / "tinygpt"))

    from pathway_tpu.models.decoder import JaxDecoderLM

    lm = JaxDecoderLM.from_hf(str(tmp_path / "tinygpt"))
    out = lm.generate("hello", max_new_tokens=3)
    assert isinstance(out, str) and out


def test_hf_encoder_end_to_end(tmp_path):
    """Save a random tiny BERT locally, load via JaxEncoder.from_hf, embed."""
    hf_cfg, model = _tiny_bert()
    path = str(tmp_path / "tinybert")
    model.save_pretrained(path)

    from pathway_tpu.models.encoder import JaxEncoder

    enc = JaxEncoder.from_hf(path)
    # no tokenizer assets saved -> deterministic hash tokenizer fallback
    assert enc.cfg.ln_placement == "post"
    v = enc.embed("hello world")
    assert v.shape == (32,)
    assert abs(float(np.linalg.norm(v)) - 1.0) < 1e-3


def test_lfm2_mixers_match_transformers():
    """The gated short conv, grouped-query attention with q/k norms and
    rotate-half rotary, RMSNorm and SwiGLU of the ``lfm2`` block family
    against ``transformers``' Lfm2 (the dense sibling of ``lfm2_moe``: the
    installed version has no expert variant): the plain f32 reference on
    the imported weights gives torch's logits, and so does the program's
    mixed step fed the prompt in runs of 5."""
    import importlib
    import types

    import jax.numpy as jnp
    from transformers import Lfm2Config, Lfm2ForCausalLM

    from pathway_tpu.models import hf_import

    torch.manual_seed(0)
    kinds = ["conv", "full_attention", "conv", "conv"]
    hf = Lfm2Config(vocab_size=180, hidden_size=32, intermediate_size=64,
                    num_hidden_layers=4, num_attention_heads=4,
                    num_key_value_heads=2, max_position_embeddings=64,
                    layer_types=kinds, block_auto_adjust_ff_dim=False,
                    attn_implementation="eager")
    model = Lfm2ForCausalLM(hf).eval()
    published = types.SimpleNamespace(
        model_type="lfm2_moe", vocab_size=180, hidden_size=32,
        intermediate_size=64, moe_intermediate_size=16, num_experts=4,
        num_experts_per_tok=2, num_dense_layers=4, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, layer_types=kinds,
        max_position_embeddings=64, norm_eps=hf.norm_eps,
        rope_theta=hf.rope_theta, conv_L_cache=3, conv_bias=False)
    cfg = hf_import.config_from_lfm2_moe(published, dtype=jnp.float32)
    params = hf_import.params_from_lfm2_state_dict(model.state_dict(), cfg)
    ids = np.random.default_rng(0).integers(0, 180, (2, 17))
    with torch.no_grad():
        want = model(input_ids=torch.tensor(ids)).logits.numpy()
    ref = importlib.import_module("benchmark.reference.lfm2_moe_f32")
    from benchmark.systems.serve_lfm2 import decoder_shape

    rows, cols = np.divmod(np.arange(2 * 17), 17)
    got, _margin = ref.logits_at(params, decoder_shape(cfg, 0), ids, rows,
                                 cols)
    assert np.abs(np.asarray(got).reshape(2, 17, -1) - want).max() < 2e-4
    # the program: one row, the first prompt in runs of 5 tokens
    from .utils import lfm2_feed

    runs, _state = lfm2_feed(cfg, params, ids[0].tolist(), 5)
    assert [n for n, _l in runs] == [5, 10, 15, 17]
    for n, logits in runs:
        assert np.abs(logits - want[0, n - 1]).max() < 2e-4


@pytest.mark.parametrize("case", ["published", "cut_to_13", "untied",
                                  "depth_mismatch", "wrong_family"])
def test_lfm2_moe_config_import(case):
    """``config_from_lfm2_moe`` on LiquidAI/LFM2-8B-A1B's published keys,
    on the benchmark's cut of them, and on configs it must refuse."""
    import types

    from pathway_tpu.models import hf_import

    kinds = ["conv", "conv", "full_attention", "conv", "conv", "conv",
             "full_attention", "conv", "conv", "conv", "full_attention",
             "conv", "conv", "conv", "full_attention", "conv", "conv",
             "conv", "full_attention", "conv", "conv", "full_attention",
             "conv", "conv"]
    pub = dict(
        model_type="lfm2_moe", conv_L_cache=3, conv_bias=False,
        hidden_size=2048, intermediate_size=7168, layer_types=kinds,
        max_position_embeddings=128000, moe_intermediate_size=1792,
        norm_eps=1e-05, norm_topk_prob=True, num_attention_heads=32,
        num_dense_layers=2, num_experts=32, num_experts_per_tok=4,
        num_hidden_layers=24, num_key_value_heads=8, rope_theta=1000000,
        routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536)
    if case == "cut_to_13":
        pub.update(num_hidden_layers=13, num_dense_layers=1,
                   layer_types=kinds[1:14])
    elif case == "untied":
        pub["tie_word_embeddings"] = False
    elif case == "depth_mismatch":
        pub["num_hidden_layers"] = 23
    elif case == "wrong_family":
        pub["model_type"] = "gpt2"
    if case in ("depth_mismatch", "wrong_family"):
        with pytest.raises(ValueError):
            hf_import.config_from_lfm2_moe(types.SimpleNamespace(**pub))
        return
    cfg = hf_import.config_from_lfm2_moe(types.SimpleNamespace(**pub),
                                         max_len=2048)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            cfg.d_ff_expert, cfg.n_experts, cfg.top_k, cfg.vocab_size) \
        == (2048, 32, 8, 7168, 1792, 32, 4, 65536)
    assert cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-5
    assert cfg.max_len == 2048 and cfg.family == "lfm2"
    assert cfg.tie_embedding == (case != "untied")
    if case == "cut_to_13":
        assert cfg.n_layers == 13 and cfg.attn_layers == (1, 5, 9)
        assert len(cfg.conv_layers) == 10 and cfg.n_dense_layers == 1
        assert 4.60e9 < cfg.param_count() < 4.61e9  # 9.2 GB in bf16
    else:
        assert cfg.n_layers == 24 and len(cfg.attn_layers) == 6


# -- qwen3_next (PR 38) --------------------------------------------------------


def _qwen3_next_row():
    import json

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(path) as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("the catalog is not here")
    return next(r for r in rows
                if r["name"] == "Qwen3-Next-80B-A3B-Instruct")["config"]


def test_config_from_qwen3_next_on_the_catalog_row():
    import types

    from pathway_tpu.models import hf_import
    from pathway_tpu.models.qwen3_next import FULL, GDN

    cfg = hf_import.config_from_qwen3_next(
        types.SimpleNamespace(**_qwen3_next_row()), dtype="bfloat16")
    assert cfg.family == "qwen3_next" and cfg.n_layers == 48
    assert cfg.layer_types == (GDN, GDN, GDN, FULL) * 12
    assert cfg.full_layers == tuple(range(3, 48, 4))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.rotary_dim) == (2048, 16, 2, 256, 64)
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.conv_kernel) == (16, 32, 128, 128, 4)
    assert (cfg.key_width, cfg.value_width, cfg.conv_width) \
        == (2048, 4096, 8192)
    assert (cfg.n_experts, cfg.held_experts, cfg.share, cfg.top_k) \
        == (512, 512, None, 10)
    assert (cfg.d_ff_expert, cfg.d_ff_shared) == (512, 512)
    assert cfg.rope_theta == 1e7 and cfg.norm_eps == 1e-6
    assert cfg.max_len == 262144 and cfg.vocab_size == 151936
    # 79.7 B parameters: 159 GB of bf16
    assert round(cfg.param_count() / 1e9, 1) == 79.7


def test_config_from_qwen3_next_takes_a_share_and_a_cut():
    """The cell's cut: published layers 1-12, 128 of the 512 experts held,
    a served context of 8,192; the issue's arithmetic to the parameter."""
    import types

    from pathway_tpu.models import hf_import

    pub = dict(_qwen3_next_row(), num_hidden_layers=12, num_experts=128)
    cfg = hf_import.config_from_qwen3_next(
        types.SimpleNamespace(**pub), max_len=8192, router_experts=512,
        first_expert=0)
    assert len(cfg.gdn_layers) == 9 and cfg.full_layers == (3, 7, 11)
    assert (cfg.n_experts, cfg.held_experts, cfg.share) == (512, 128, 0)
    assert cfg.max_len == 8192
    assert cfg.param_count() == 5_889_832_128       # 11.78 GB of bf16
    other = hf_import.config_from_qwen3_next(
        types.SimpleNamespace(**pub), router_experts=512, first_expert=384)
    assert other.share == 384
    with pytest.raises(ValueError, match="not a share"):
        hf_import.config_from_qwen3_next(
            types.SimpleNamespace(**pub), router_experts=512,
            first_expert=385)


@pytest.mark.parametrize("key,value,named", [
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("hidden_act", "gelu", "hidden_act"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("num_nextn_predict_layers", 1, "multi-token prediction"),
    ("mtp_num_hidden_layers", 1, "multi-token prediction"),
])
def test_config_from_qwen3_next_refuses_what_is_not_written_down(
        key, value, named):
    import types

    from pathway_tpu.models import hf_import

    pub = dict(_qwen3_next_row(), **{key: value})
    with pytest.raises(ValueError, match="not written down") as e:
        hf_import.config_from_qwen3_next(types.SimpleNamespace(**pub))
    assert named in str(e.value)


def test_config_from_qwen3_next_refuses_another_model_type():
    import types

    from pathway_tpu.models import hf_import

    pub = dict(_qwen3_next_row(), model_type="qwen3_moe")
    with pytest.raises(ValueError, match="expected a qwen3_next config"):
        hf_import.config_from_qwen3_next(types.SimpleNamespace(**pub))


# -- mimo_v2_flash (PR 40) -------------------------------------------------------


def _mimo_row():
    import json

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(path) as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("the catalog is not here")
    return next(r for r in rows if r["name"] == "MiMo-V2-Flash")["config"]


def test_config_from_mimo_v2_flash_on_the_catalog_row():
    import types

    from pathway_tpu.models import hf_import
    from pathway_tpu.models.afmoe import FULL, SLIDING

    cfg = hf_import.config_from_mimo_v2_flash(
        types.SimpleNamespace(**_mimo_row()), dtype="bfloat16")
    assert cfg.family == "mimo_v2_flash" and cfg.n_layers == 48
    assert cfg.full_layers == (0, 5, 11, 17, 23, 29, 35, 41, 47)
    assert len(cfg.window_layers) == 39
    assert cfg.layer_types[:6] == (FULL,) + (SLIDING,) * 4 + (FULL,)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.window_kv_heads,
            cfg.head_dim, cfg.v_head_dim, cfg.rotary_dim) \
        == (4096, 64, 4, 8, 192, 128, 64)
    assert (cfg.n_experts, cfg.held_experts, cfg.share, cfg.top_k,
            cfg.n_dense_layers) == (256, 256, None, 8, 1)
    assert (cfg.d_ff, cfg.d_ff_expert, cfg.sliding_window) \
        == (16384, 2048, 128)
    assert (cfg.rope_theta, cfg.window_rope_theta) == (5e6, 1e4)
    assert (cfg.value_scale, cfg.route_scale, cfg.norm_eps) \
        == (0.707, 1.0, 1e-5)
    assert cfg.max_len == 262144 and cfg.vocab_size == 152576
    # 308.8 B parameters: 617.6 GB of bf16
    assert round(cfg.param_count() / 1e9, 1) == 308.8


def test_config_from_mimo_v2_flash_takes_a_share_and_a_cut():
    """The cell's cut: published layers 0-10, 16 of the 256 experts held, a
    served context of 8,192; the issue's arithmetic (6,516M parameters =
    13.03 GB of bf16)."""
    import types

    from pathway_tpu.models import hf_import

    row = _mimo_row()
    pub = dict(row, num_hidden_layers=11, n_routed_experts=16,
               hybrid_layer_pattern=row["hybrid_layer_pattern"][:11],
               moe_layer_freq=row["moe_layer_freq"][:11])
    cfg = hf_import.config_from_mimo_v2_flash(
        types.SimpleNamespace(**pub), max_len=8192, router_experts=256,
        first_expert=0)
    assert cfg.full_layers == (0, 5) and len(cfg.window_layers) == 9
    assert (cfg.n_experts, cfg.held_experts, cfg.share) == (256, 16, 0)
    assert cfg.max_len == 8192
    assert round(cfg.param_count() / 1e6) == 6516
    other = hf_import.config_from_mimo_v2_flash(
        types.SimpleNamespace(**pub), router_experts=256, first_expert=240)
    assert other.share == 240
    with pytest.raises(ValueError, match="not a share"):
        hf_import.config_from_mimo_v2_flash(
            types.SimpleNamespace(**pub), router_experts=256,
            first_expert=241)


@pytest.mark.parametrize("key,value,named", [
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("n_group", 8, "n_group"),
    ("topk_group", 4, "n_group"),
    ("n_shared_experts", 1, "n_shared_experts"),
    ("attention_bias", True, "attention_bias"),
    ("add_full_attention_sink_bias", True, "add_full_attention_sink_bias"),
    ("add_swa_attention_sink_bias", False, "add_swa_attention_sink_bias"),
    ("swa_head_dim", 128, "swa_num_attention_heads"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("hidden_act", "gelu", "hidden_act"),
    ("scoring_func", "softmax", "scoring_func"),
    ("moe_layer_freq", [0, 1, 0] + [1] * 45, "dense layer after"),
    ("num_nextn_predict_layers", 3, "multi-token prediction"),
])
def test_config_from_mimo_v2_flash_refuses_what_is_not_written_down(
        key, value, named):
    import types

    from pathway_tpu.models import hf_import

    pub = dict(_mimo_row(), **{key: value})
    with pytest.raises(ValueError, match="not written down") as e:
        hf_import.config_from_mimo_v2_flash(types.SimpleNamespace(**pub))
    assert named in str(e.value)


def test_config_from_mimo_v2_flash_refuses_another_model_type():
    import types

    from pathway_tpu.models import hf_import

    pub = dict(_mimo_row(), model_type="mimo_v2")
    with pytest.raises(ValueError, match="expected a mimo_v2_flash"):
        hf_import.config_from_mimo_v2_flash(types.SimpleNamespace(**pub))


def test_the_cells_configuration_keeps_the_catalogs_widths():
    """Every number of the catalog row's ``config`` stands in
    ``mimo-v2-flash-serve.json`` under the same key, but the four keys
    ``reduced`` names."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mimo-v2-flash-serve.json")) as f:
        ours = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "mimo-v2-flash-serve")
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "hybrid_layer_pattern",
                       "moe_layer_freq", "n_routed_experts"}
    for key, value in _mimo_row().items():
        if key in reduced:
            assert ours[key] != value
        else:
            assert ours[key] == value, key
    assert ours["hybrid_layer_pattern"] == _mimo_row()[
        "hybrid_layer_pattern"][:11]
    assert ours["router_experts"] == 256 and len(ours["reduced"]) == 4
