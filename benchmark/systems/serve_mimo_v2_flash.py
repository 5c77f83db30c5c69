"""System ``serve_mimo_v2_flash``: a ``mimo_v2_flash`` model (XiaomiMiMo
MiMo-V2-Flash) served as a user serves it.

The wiring, interface and counters of ``serve_engine`` (``RequestScheduler
.submit`` with ``on_token`` -> ``engine.serve_batch`` -> ``PagedDecodeEngine``
with ``max_restarts=0``), with the model built from the published
``mimo_v2_flash`` keys through the program's own
``hf_import.config_from_mimo_v2_flash``; the family is read from the
configuration, the engine is given no keyword that names it.  A program
without that import (a commit before the family) fails at once, by name.

A configuration file of this system holds the published keys at its top
level (as the catalog lists them), ``router_experts`` and ``first_expert``
(the router's published width and the first expert held, where
``n_routed_experts`` counts the experts this chip HOLDS of an
expert-parallel deployment), ``serve`` (``max_len``: the served context),
``dtype``, ``engine`` and ``scheduler`` keyword arguments, and ``correct``.
``weights.rounding`` (the variant ``int8_control``) hands the program the
seed's weights rounded further (``weights_mimo_v2_flash
.mimo_v2_flash_params``); the reference always takes them as the
configuration states them, and is given the same share of the experts:
``correct``'s low-precision control, which has to come out as not correct.

``correct`` compares, over prompt + served tokens of a sample of the
finished requests (``serve_afmoe.sample_requests``: the longest, two of
every size class, the rest drawn from the seed), against
``reference/mimo_v2_flash_f32.py``, what ``serve_qwen3_next`` compares on
the served tokens (``serve_qwen3_next.gap_comparisons``):
``router_near_tie_share``, ``served_gap_per_near_tie``, ``long_context_gap``
(the mean over the served positions past ``long_context_tokens``: every one
of them dozens of windows long, its full layers attending the most keys)
and ``widest_gap``; and ``router_weight_gap`` (:func:`router_weight_gap`):
the program's router alone, on the reference's own stream.  The chip holds
a sixteenth of a token's experts, so a router that weighs them a few
percent wrong moves the served tokens less than a bf16 stream's flips of
router near-ties do: the logits' gaps cannot see it, this number can.

Counters: ``serve_afmoe``'s (the window pool's and the window layers'
keys) and ``serve_qwen3_next``'s of the expert layers (the programs' device
counters and the two divisors made of them), and
``engine.kv_window_band_pairs`` / ``engine.kv_window_span_pairs``.
"""

from __future__ import annotations

import types

import numpy as np

from benchmark import weights_mimo_v2_flash
from benchmark.systems import serve_engine
from benchmark.systems.serve_afmoe import sample_requests
from benchmark.systems.serve_lfm2 import decoder_shape
from benchmark.systems.serve_qwen3_next import gap_comparisons

ENGINE_COUNTERS = serve_engine.ENGINE_COUNTERS + (
    "moe_routed_pairs", "moe_fullest_expert_tokens", "moe_pairs_elsewhere",
    "moe_live_tiles", "moe_experts_touched", "moe_expert_passes",
    "kv_window_blocks_allocated", "kv_window_blocks_freed",
    "kv_window_keys", "kv_window_ctx_keys", "kv_window_band_pairs",
    "kv_window_span_pairs")

PUBLISHED_KEYS = (
    "add_full_attention_sink_bias", "add_swa_attention_sink_bias",
    "attention_bias", "attention_chunk_size", "attention_value_scale",
    "head_dim", "hidden_act", "hidden_size", "hybrid_layer_pattern",
    "intermediate_size", "layernorm_epsilon", "max_position_embeddings",
    "model_type", "moe_intermediate_size", "moe_layer_freq", "n_group",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "partial_rotary_factor", "rope_theta",
    "routed_scaling_factor", "scoring_func", "sliding_window",
    "sliding_window_size", "swa_head_dim", "swa_num_attention_heads",
    "swa_num_key_value_heads", "swa_rope_theta", "swa_v_head_dim",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim",
    "vocab_size")


def decoder_config(config: dict):
    """MimoV2FlashConfig through the program's own ``mimo_v2_flash``
    import."""
    from pathway_tpu.models import hf_import

    if not hasattr(hf_import, "config_from_mimo_v2_flash"):
        raise SystemExit(
            "serve_mimo_v2_flash: this program has no mimo_v2_flash block "
            "family (pathway_tpu.models.hf_import.config_from_mimo_v2_flash "
            "is missing); nothing was run")
    published = types.SimpleNamespace(
        **{k: config[k] for k in PUBLISHED_KEYS})
    return hf_import.config_from_mimo_v2_flash(
        published, max_len=config["serve"]["max_len"], dtype=config["dtype"],
        router_experts=config.get("router_experts"),
        first_expert=config.get("first_expert", 0))


def router_weight_gap(params: dict, shape: dict, probe: dict,
                      want: dict) -> dict:
    """The program's router against the reference's, expert layer by expert
    layer at the sampled served positions: ``ops.moe.route`` as
    ``expert_ffn`` calls it (the layer's ``wg`` and ``expert_bias`` as the
    plan holds them; ``top_k``, the renormalised weights, ``route_scale``,
    1e-20) on the REFERENCE's normed stream (``probe``:
    ``mimo_v2_flash_f32.served_gaps``'s ``router_probe``), so that nothing
    upstream of the router reaches the comparison.  A (position, layer) is
    compared where the layer's margin between the ``top_k``-th and the next
    selection score is at least ``router_margin``: both routers then choose
    the same experts unless one of them is wrong.  The value is the mean,
    over those, of the largest difference between the two routers' combine
    weights of an expert this chip holds, in units of a mean weight (times
    ``top_k``): 0.01 is a held expert's weight 1% of ``1 / top_k`` off."""
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    limit = want["router_weight_gap"]["limit"]
    k = shape["top_k"]
    first = shape["first_expert"] if shape["n_held_experts"] is not None \
        else 0
    worst, pairs = [], 0
    for li, (stream, held_w, margin) in sorted(probe.items()):
        lay = params["layers"][li]
        experts, w, _scores = moe.route(
            jnp.asarray(stream), lay["wg"], lay.get("expert_bias"), top_k=k,
            norm_topk=True, scale=shape["route_scale"], renorm_eps=1e-20)
        local = np.asarray(experts)[:, :, None] - first \
            == np.arange(held_w.shape[1])[None, None, :]
        mine = (local * np.asarray(w, np.float32)[:, :, None]).sum(1)
        clear = margin >= want["router_margin"]
        worst.append(np.abs(mine - held_w).max(-1)[clear] * k)
        pairs += len(margin)
    worst = np.concatenate(worst) if worst else np.zeros(0)
    return {"name": "router_weight_gap", "limit": limit,
            "value": float(worst.mean()) if len(worst) else float("inf"),
            "widest": float(worst.max()) if len(worst) else None,
            "compared_pairs": len(worst), "position_layer_pairs": pairs}


class ServeMimoV2Flash(serve_engine.ServeEngine):
    def __init__(self, config: dict, seed: int, rehearse: bool):
        self.config = config
        self.cfg = decoder_config(config)  # first: a program without it

        import jax

        from pathway_tpu.kvcache.engine import PagedDecodeEngine
        from pathway_tpu.models.encoder import _resolve_dtype
        from pathway_tpu.obs import profiler
        from pathway_tpu.serve.scheduler import RequestScheduler

        dtype = self.params_dtype = _resolve_dtype(self.cfg.dtype)
        self.shape = decoder_shape(self.cfg, 0)
        self.rounding = config.get("weights", {}).get("rounding")
        self.params = jax.block_until_ready(
            weights_mimo_v2_flash.mimo_v2_flash_params(
                self.shape, seed, dtype, self.rounding))
        name = "bench_" + config["name"].replace("-", "_")
        self.engine = PagedDecodeEngine(self.cfg, self.params, name=name,
                                        **config.get("engine", {}))
        jax.block_until_ready(self.engine.pool.device_state())
        if not rehearse and self.engine.attn != "pallas":
            raise RuntimeError("the engine did not choose attn='pallas'")
        holder: dict = {}
        self.sched = RequestScheduler(
            lambda reqs: self.engine.serve_batch(reqs, scheduler=holder["s"]),
            name=name, max_batch_size=self.engine.max_batch_size,
            max_queue=1024, **config.get("scheduler", {}))
        holder["s"] = self.sched
        self._registry = profiler.registry()
        eng = self.engine
        self.shape["clients"] = eng.max_batch_size
        self.info = {
            "engine.chain_steps": eng.chain_steps,
            "engine.max_batch_size": eng.max_batch_size,
            "engine.prefill_chunk": eng.prefill_chunk,
            "engine.max_seq_tokens": eng.max_seq_tokens,
            "engine.attn": eng.attn, "engine.auto_config": eng.auto_config,
            "engine.hbm_plan": eng.hbm_plan.as_dict(),
            "engine.window_blocks": eng.pool.window_blocks - 1,
            "engine.pool_part_bytes": eng.pool.pool_part_bytes,
            "decoder": self.shape,
            "kv_itemsize": eng.pool.k.dtype.itemsize,
            "weight_itemsize": np.dtype(dtype).itemsize,
        }

    def counters(self) -> dict:
        from pathway_tpu.ops.moe import TM  # rows a tile of the kernel

        st = self.engine.pool.stats
        out = {"engine." + k: float(getattr(st, k)) for k in ENGINE_COUNTERS}
        out["engine.moe_mean_expert_tokens"] = \
            st.moe_routed_pairs / self.cfg.held_experts
        out["engine.moe_tile_rows"] = float(TM * st.moe_live_tiles)
        out["engine.moe_held_expert_passes"] = float(
            self.cfg.held_experts * st.moe_expert_passes)
        sched = self.sched.stats
        out["scheduler.completed"] = float(sched.completed)
        out["scheduler.batches"] = float(sched.batches)
        out["scheduler.batched_requests"] = float(sched.batched_requests)
        return out

    def gauges(self) -> dict:
        st = self.engine.pool.stats
        return {"engine.blocks_in_use": st.blocks_in_use,
                "engine.blocks_total": st.blocks_total,
                "engine.window_blocks_in_use": st.window_blocks_in_use,
                "engine.window_blocks_total": st.window_blocks_total}

    def verify(self, observed: dict, seed: int) -> list:
        from benchmark.reference import mimo_v2_flash_f32

        want = self.config["correct"]
        done = [r for r in observed["requests"] if r["error"] is None]
        out = [{"name": "wrong_token_count", "limit": 0, "value": sum(
            len(r["tokens"]) != r["n_out"] for r in done)}]
        restarts = observed["counters"].get("engine.engine_restarts", 0) \
            + observed["counters"].get("engine.engine_degraded", 0)
        out.append({"name": "engine_restarts", "value": restarts, "limit": 0})
        sample = sample_requests(done, want["sample_requests"], seed)
        if self.rounding:  # the program's are gone with the engine; one
            self.params = None  # copy of the weights at a time
            self.params = weights_mimo_v2_flash.mimo_v2_flash_params(
                self.shape, seed, self.params_dtype)
        kept: dict = {}  # the reference's pass, once, for both comparisons

        def served_gaps(*args):
            kept["gaps"], kept["stats"] = mimo_v2_flash_f32.served_gaps(*args)
            return kept["gaps"], kept["stats"]

        out += gap_comparisons(self.params, self.shape, sample, want,
                               types.SimpleNamespace(served_gaps=served_gaps))
        if kept:
            out.append(router_weight_gap(
                self.params, self.shape, kept["stats"]["router_probe"], want))
        return out


def build(config: dict, seed: int, rehearse: bool) -> ServeMimoV2Flash:
    return ServeMimoV2Flash(config, seed, rehearse)
