"""System ``serve_kimi_linear``: a ``kimi_linear`` model (moonshotai Kimi
Linear) served as a user serves it.

The wiring, interface and counters of ``serve_engine`` (``RequestScheduler
.submit`` with ``on_token`` -> ``engine.serve_batch`` -> ``PagedDecodeEngine``
with ``max_restarts=0``), with the model built from the published
``kimi_linear`` keys through the program's own
``hf_import.config_from_kimi_linear``; the family is read from the
configuration, the engine is given no keyword that names it.

A configuration file of this system holds the published keys at its top
level (as the catalog lists them), ``router_experts`` and ``first_expert``
(the router's published width and the first expert held, where
``num_experts`` counts the experts this chip HOLDS of an expert-parallel
deployment), ``serve`` (``max_len``: the served context), ``dtype``,
``engine`` and ``scheduler`` keyword arguments, and ``correct``.
``weights.rounding`` (the variant ``int8_control``) hands the program the
seed's weights rounded further (``weights_kimi_linear.kimi_linear_params``);
the reference always takes them as the configuration states them, and is
given the same share of the experts.

``correct`` compares, over prompt + served tokens of a sample of the
finished requests (``serve_afmoe.sample_requests``: the longest, two of
every size class, the rest drawn from the seed), against
``reference/kimi_linear_f32.py``, what ``serve_lfm2`` compares
(``router_near_tie_share``, ``served_gap_per_near_tie``, ``widest_gap``) and

- ``long_context_gap``: the mean of ``served_gap_per_near_tie`` over the
  served positions past ``long_context_tokens`` only, where a matrix state
  has been carried through the most chunks and steps, so that a fault that
  grows with the length carried is not diluted by the short requests.
"""

from __future__ import annotations

import types

import numpy as np

from benchmark import weights_kimi_linear
from benchmark.systems import serve_engine
from benchmark.systems.serve_afmoe import sample_requests
from benchmark.systems.serve_lfm2 import decoder_shape

ENGINE_COUNTERS = serve_engine.ENGINE_COUNTERS + (
    "moe_routed_pairs", "moe_fullest_expert_tokens", "moe_pairs_elsewhere",
    "kda_state_resets")

PUBLISHED_KEYS = (
    "first_k_dense_replace", "head_dim", "hidden_act", "hidden_size",
    "intermediate_size", "kv_lora_rank", "linear_attn_config",
    "mla_use_nope", "model_max_length", "model_type",
    "moe_intermediate_size", "moe_layer_freq", "moe_renormalize",
    "moe_router_activation_func", "num_attention_heads", "num_expert_group",
    "num_experts", "num_experts_per_token", "num_hidden_layers",
    "num_key_value_heads", "num_nextn_predict_layers", "num_shared_experts",
    "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps",
    "rope_scaling", "rope_theta", "routed_scaling_factor",
    "tie_word_embeddings", "topk_group", "use_grouped_topk", "v_head_dim",
    "vocab_size")


def decoder_config(config: dict):
    """KimiLinearConfig through the program's own ``kimi_linear`` import."""
    from pathway_tpu.models import hf_import

    published = types.SimpleNamespace(
        **{k: config[k] for k in PUBLISHED_KEYS})
    return hf_import.config_from_kimi_linear(
        published, max_len=config["serve"]["max_len"], dtype=config["dtype"],
        router_experts=config.get("router_experts"),
        first_expert=config.get("first_expert", 0))


def gap_comparisons(params, shape: dict, sample: list, want: dict) -> list:
    from benchmark.reference import kimi_linear_f32

    limit = want["served_gap_per_near_tie"]["limit"]
    if not sample:
        return [{"name": "served_gap_per_near_tie", "value": float("inf"),
                 "limit": limit, "served_tokens": 0}]
    gaps, ref = kimi_linear_f32.served_gaps(
        params, shape, [(r["prompt"], r["tokens"]) for r in sample])
    flat = np.asarray([g for r in ref["order"] for g in gaps[r]])
    tie = ref["router_margin"] < want["router_margin"]
    clear = ~tie
    far = clear & (ref["context"] > want["long_context_tokens"])
    rel = flat / ref["std"]

    def near_ties(where, t):
        return float(np.mean((ref["margin"] / ref["std"])[where] < t)) \
            if where.any() else 0.0

    def per_near_tie(where):
        return float(rel[where].mean() / max(near_ties(where, 0.05), 1e-9)) \
            if where.any() else float("inf")

    near = {f"near_tie_{t}": near_ties(clear, t) for t in (0.02, 0.05, 0.1)}
    worst = int(flat.argmax())
    return [
        {"name": "router_near_tie_share", "value": float(tie.mean()),
         "limit": want["router_near_tie_share"]["limit"],
         "router_margin": want["router_margin"],
         "router_margin_p10": float(np.quantile(ref["router_margin"], 0.1)),
         "share_within": {str(m): float((ref["router_margin"] < m).mean())
                          for m in (5e-5, 1e-4, 2e-4, 5e-4, 1e-3)},
         "not_best_at_ties": int((flat[tie] > 0).sum()),
         "gap_rel_mean_at_ties": float(rel[tie].mean()) if tie.any() else 0.0},
        {"name": "served_gap_per_near_tie", "limit": limit,
         "value": per_near_tie(clear),
         "gap_rel_mean": float(rel[clear].mean()) if clear.any() else None,
         "not_best": int((flat[clear] > 0).sum()),
         "logit_std_mean": float(ref["std"].mean()), **near,
         "served_tokens": len(flat), "compared_tokens": int(clear.sum()),
         "requests": len(sample),
         "prompt_tokens": [len(r["prompt"]) for r in sample]},
        {"name": "long_context_gap",
         "limit": want["long_context_gap"]["limit"],
         "value": per_near_tie(far),
         "gap_rel_mean": float(rel[far].mean()) if far.any() else None,
         "not_best": int((flat[far] > 0).sum()),
         "near_tie_0.05": near_ties(far, 0.05),
         "compared_tokens": int(far.sum())},
        {"name": "widest_gap", "limit": want["widest_gap"]["limit"],
         "value": float(flat.max()), "in_logit_std": float(rel[worst]),
         "at_router_near_tie": bool(tie[worst]),
         "context": int(ref["context"][worst])},
    ]


class ServeKimiLinear(serve_engine.ServeEngine):
    def __init__(self, config: dict, seed: int, rehearse: bool):
        import jax

        from pathway_tpu.kvcache.engine import PagedDecodeEngine
        from pathway_tpu.models.encoder import _resolve_dtype
        from pathway_tpu.obs import profiler
        from pathway_tpu.serve.scheduler import RequestScheduler

        self.config = config
        self.cfg = decoder_config(config)
        dtype = self.params_dtype = _resolve_dtype(self.cfg.dtype)
        self.shape = decoder_shape(self.cfg, 0)
        self.rounding = config.get("weights", {}).get("rounding")
        self.params = jax.block_until_ready(
            weights_kimi_linear.kimi_linear_params(
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
            "engine.latent_lanes": self.cfg.latent_lanes,
            "engine.pool_bytes": eng.pool.per_shard_bytes,
            "decoder": self.shape,
            "kv_itemsize": eng.pool.k.dtype.itemsize,
            "weight_itemsize": np.dtype(dtype).itemsize,
        }

    def counters(self) -> dict:
        st = self.engine.pool.stats
        out = {"engine." + k: float(getattr(st, k)) for k in ENGINE_COUNTERS}
        out["engine.moe_mean_expert_tokens"] = \
            st.moe_routed_pairs / self.cfg.held_experts
        sched = self.sched.stats
        out["scheduler.completed"] = float(sched.completed)
        out["scheduler.batches"] = float(sched.batches)
        out["scheduler.batched_requests"] = float(sched.batched_requests)
        return out

    def gauges(self) -> dict:
        st = self.engine.pool.stats
        return {"engine.blocks_in_use": st.blocks_in_use,
                "engine.blocks_total": st.blocks_total,
                "engine.conv_slots_in_use": st.conv_slots_in_use,
                "engine.conv_slots_total": st.conv_slots_total,
                "engine.state_slots_in_use": st.state_slots_in_use,
                "engine.state_slots_total": st.state_slots_total}

    def verify(self, observed: dict, seed: int) -> list:
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
            self.params = weights_kimi_linear.kimi_linear_params(
                self.shape, seed, self.params_dtype)
        return out + gap_comparisons(self.params, self.shape, sample, want)


def build(config: dict, seed: int, rehearse: bool) -> ServeKimiLinear:
    return ServeKimiLinear(config, seed, rehearse)
