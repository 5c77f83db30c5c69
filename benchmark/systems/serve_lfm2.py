"""System ``serve_lfm2``: an ``lfm2_moe`` model served as a user serves it.

The wiring, interface and counters of ``serve_engine`` (``RequestScheduler
.submit`` with ``on_token`` -> ``engine.serve_batch`` -> ``PagedDecodeEngine``
with its own ``auto_config``, ``max_restarts=0``), with the model built
from the published ``lfm2_moe`` keys through the program's own
``hf_import.config_from_lfm2_moe``; the family is read from the
configuration, the engine is given no keyword that names it.

A configuration file of this system holds the published keys at its top
level (as the catalog lists them), ``serve`` (``max_len``: the served
context), ``dtype``, ``engine`` and ``scheduler`` keyword arguments, and
``correct``.  ``weights.rounding`` (the variant ``int8_control``) hands the
program the seed's weights rounded further (``weights_lfm2.lfm2_params``);
the reference always takes them as the configuration states them.

``correct`` compares, over prompt + served tokens of the finished
requests, against ``reference/lfm2_moe_f32.py``:

- ``router_near_tie_share``: the share of served positions at which the
  reference's own router, in some expert layer, has the ``top_k``-th and
  the next selection score closer than ``router_margin``.  There a program
  in the configuration's precision may choose the other expert, and the
  position's logits then differ from the reference's by far more than
  rounding; such positions are counted, their share is limited, and they
  stay out of the mean below (as logit near-ties normalise it);
- ``served_gap_per_near_tie``: over the other positions, the mean gap of
  the served token's reference logit below the reference's best, in logit
  standard deviations, over the share of logit near-ties among them;
- ``widest_gap``: the largest gap of all served positions, router
  near-ties included (one wrong token among thousands moves no mean).
"""

from __future__ import annotations

import dataclasses
import types

import numpy as np

from benchmark import weights_lfm2
from benchmark.systems import serve_engine

ENGINE_COUNTERS = serve_engine.ENGINE_COUNTERS + (
    "moe_routed_pairs", "moe_fullest_expert_tokens")

PUBLISHED_KEYS = (
    "model_type", "conv_L_cache", "conv_bias", "hidden_size",
    "intermediate_size", "layer_types", "max_position_embeddings",
    "moe_intermediate_size", "norm_eps", "norm_topk_prob",
    "num_attention_heads", "num_dense_layers", "num_experts",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "rope_theta", "routed_scaling_factor", "use_expert_bias", "vocab_size")


def decoder_config(config: dict):
    """Lfm2Config through the program's own ``lfm2_moe`` import."""
    from pathway_tpu.models import hf_import

    published = types.SimpleNamespace(
        **{k: config[k] for k in PUBLISHED_KEYS})
    return hf_import.config_from_lfm2_moe(
        published, max_len=config["serve"]["max_len"], dtype=config["dtype"])


def decoder_shape(cfg, clients: int) -> dict:
    shape = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
             if f.name != "dtype"}
    shape["layer_types"] = list(cfg.layer_types)
    shape["clients"] = clients
    return shape


def gap_comparisons(params, shape: dict, sample: list, want: dict) -> list:
    from benchmark.reference import lfm2_moe_f32

    limit = want["served_gap_per_near_tie"]["limit"]
    if not sample:
        return [{"name": "served_gap_per_near_tie", "value": float("inf"),
                 "limit": limit, "served_tokens": 0}]
    gaps, ref = lfm2_moe_f32.served_gaps(
        params, shape, [(r["prompt"], r["tokens"]) for r in sample])
    flat = np.asarray([g for r in ref["order"] for g in gaps[r]])
    tie = ref["router_margin"] < want["router_margin"]
    clear = ~tie
    rel = flat / ref["std"]
    near = {f"near_tie_{t}": float(np.mean(
        (ref["margin"] / ref["std"])[clear] < t)) if clear.any() else 0.0
        for t in (0.02, 0.05, 0.1)}
    worst = int(flat.argmax())
    return [
        {"name": "router_near_tie_share", "value": float(tie.mean()),
         "limit": want["router_near_tie_share"]["limit"],
         "router_margin": want["router_margin"],
         "router_margin_p10": float(np.quantile(ref["router_margin"], 0.1)),
         "not_best_at_ties": int((flat[tie] > 0).sum()),
         "gap_rel_mean_at_ties": float(rel[tie].mean()) if tie.any() else 0.0},
        {"name": "served_gap_per_near_tie", "limit": limit,
         "value": float(rel[clear].mean() / max(near["near_tie_0.05"], 1e-9))
         if clear.any() else float("inf"),
         "gap_rel_mean": float(rel[clear].mean()) if clear.any() else None,
         "not_best": int((flat[clear] > 0).sum()),
         "logit_std_mean": float(ref["std"].mean()), **near,
         "served_tokens": len(flat), "compared_tokens": int(clear.sum()),
         "requests": len(sample)},
        {"name": "widest_gap", "limit": want["widest_gap"]["limit"],
         "value": float(flat.max()), "in_logit_std": float(rel[worst]),
         "at_router_near_tie": bool(tie[worst])},
    ]


class ServeLfm2(serve_engine.ServeEngine):
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
        self.params = jax.block_until_ready(weights_lfm2.lfm2_params(
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
            "engine.attn": eng.attn, "engine.auto_config": eng.auto_config,
            "engine.hbm_plan": eng.hbm_plan.as_dict(),
            "decoder": self.shape,
            "kv_itemsize": eng.pool.k.dtype.itemsize,
            "weight_itemsize": np.dtype(dtype).itemsize,
        }

    def counters(self) -> dict:
        st = self.engine.pool.stats
        out = {"engine." + k: float(getattr(st, k)) for k in ENGINE_COUNTERS}
        out["engine.moe_mean_expert_tokens"] = \
            st.moe_routed_pairs / self.cfg.n_experts
        out["scheduler.completed"] = float(self.sched.stats.completed)
        return out

    def gauges(self) -> dict:
        st = self.engine.pool.stats
        return {"engine.blocks_in_use": st.blocks_in_use,
                "engine.blocks_total": st.blocks_total,
                "engine.conv_slots_in_use": st.conv_slots_in_use,
                "engine.conv_slots_total": st.conv_slots_total}

    def verify(self, observed: dict, seed: int) -> list:
        want = self.config["correct"]
        done = [r for r in observed["requests"] if r["error"] is None]
        out = [{"name": "wrong_token_count", "limit": 0, "value": sum(
            len(r["tokens"]) != r["n_out"] for r in done)}]
        restarts = observed["counters"].get("engine.engine_restarts", 0) \
            + observed["counters"].get("engine.engine_degraded", 0)
        out.append({"name": "engine_restarts", "value": restarts, "limit": 0})
        sample = serve_engine.sample_requests(done, want["sample_requests"],
                                              seed)
        if self.rounding:  # the program's are gone with the engine; one
            self.params = None  # copy of the weights at a time
            self.params = weights_lfm2.lfm2_params(
                self.shape, seed, self.params_dtype)
        return out + gap_comparisons(self.params, self.shape, sample, want)


def build(config: dict, seed: int, rehearse: bool) -> ServeLfm2:
    return ServeLfm2(config, seed, rehearse)
