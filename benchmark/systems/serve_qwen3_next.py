"""System ``serve_qwen3_next``: a ``qwen3_next`` model (Qwen3-Next) served as
a user serves it.

The wiring, interface and counters of ``serve_engine`` (``RequestScheduler
.submit`` with ``on_token`` -> ``engine.serve_batch`` -> ``PagedDecodeEngine``
with ``max_restarts=0``), with the model built from the published
``qwen3_next`` keys through the program's own
``hf_import.config_from_qwen3_next``; the family is read from the
configuration, the engine is given no keyword that names it.

A configuration file of this system holds the published keys at its top
level (as the catalog lists them), ``router_experts`` and ``first_expert``
(the router's published width and the first expert held, where
``num_experts`` counts the experts this chip HOLDS of an expert-parallel
deployment), ``serve`` (``max_len``: the served context), ``dtype``,
``engine`` and ``scheduler`` keyword arguments, and ``correct``.
``weights.rounding`` (the variant ``int8_control``) hands the program the
seed's weights rounded further (``weights_qwen3_next.qwen3_next_params``);
the reference always takes them as the configuration states them, and is
given the same share of the experts: ``correct``'s low-precision control,
which has to come out as not correct.  ``program.state_rounding`` (the
variant ``bf16_state``) has the program keep its delta-rule states rounded
to that dtype after every layer's update (:func:`round_states_in_program`):
the precision below the f32 state the configuration states, the control
of ``state_gap`` below.  The served tokens do not show it under this
traffic (PERF.md, PR 38: replies of 4 or 64 tokens round a state too few
times), which is why the state itself is compared.

``correct`` compares, over prompt + served tokens of a sample of the
finished requests (``serve_afmoe.sample_requests``: the longest, two of
every size class, the rest drawn from the seed), against
``reference/qwen3_next_f32.py``, what ``serve_kimi_linear`` compares:
``router_near_tie_share`` (here on the router's LOGITS: the softmax keeps
their order), ``served_gap_per_near_tie``, ``long_context_gap`` (the mean
over the served positions past ``long_context_tokens``, where a state has
been carried through the most chunks and the full layers attend the most
keys) and ``widest_gap``.  And, because the configuration states an f32
state and no served token of this traffic shows a state kept at less:
``state_gap``, from one request of ``correct.state_probe`` served alone
after the window (:func:`state_probe`: a prompt from the seed and a reply
of hundreds of tokens, so that the state is updated, and would be rounded,
hundreds of times): the distance of the state its slot of the arena is
left with from ``final_states`` of the reference over the same tokens, as
a share of its norm, in the first gated-DeltaNet layer
(:func:`state_comparison`).

Counters beyond ``serve_kimi_linear``'s: ``engine.moe_live_tiles``,
``engine.moe_experts_touched`` and ``engine.moe_expert_passes`` (the
programs' device counters: the first two summed over expert layers and
steps, the third the number of those passes, counted where they happen)
and two divisors made of them: ``engine.moe_tile_rows`` (16 rows a live
tile) and ``engine.moe_held_expert_passes`` (held experts x passes).
"""

from __future__ import annotations

import gc
import types

import numpy as np

from benchmark import weights_qwen3_next
from benchmark.systems import serve_engine
from benchmark.systems.serve_afmoe import sample_requests
from benchmark.systems.serve_lfm2 import decoder_shape

ENGINE_COUNTERS = serve_engine.ENGINE_COUNTERS + (
    "moe_routed_pairs", "moe_fullest_expert_tokens", "moe_pairs_elsewhere",
    "kda_state_resets", "moe_live_tiles", "moe_experts_touched",
    "moe_expert_passes")

PUBLISHED_KEYS = (
    "decoder_sparse_step", "full_attention_interval", "head_dim",
    "hidden_act", "hidden_size", "intermediate_size",
    "linear_conv_kernel_dim", "linear_key_head_dim", "linear_num_key_heads",
    "linear_num_value_heads", "linear_value_head_dim",
    "max_position_embeddings", "mlp_only_layers", "model_type",
    "moe_intermediate_size", "norm_topk_prob", "num_attention_heads",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "partial_rotary_factor", "rms_norm_eps",
    "rope_scaling", "rope_theta", "shared_expert_intermediate_size",
    "tie_word_embeddings", "use_sliding_window", "vocab_size")


def decoder_config(config: dict):
    """Qwen3NextConfig through the program's own ``qwen3_next`` import."""
    from pathway_tpu.models import hf_import

    published = types.SimpleNamespace(
        **{k: config[k] for k in PUBLISHED_KEYS})
    return hf_import.config_from_qwen3_next(
        published, max_len=config["serve"]["max_len"], dtype=config["dtype"],
        router_experts=config.get("router_experts"),
        first_expert=config.get("first_expert", 0))


def round_states_in_program(dtype: str) -> None:
    """A state of lower precision: the program's two delta-rule entry points
    (``ops/kda.py`` ``kda_mixed`` / ``kda_decode``) give back the layer's
    states rounded to ``dtype`` (and held in the arena's f32), so that every
    token reads a state that went through ``dtype`` once a step.  Through
    ``lax.reduce_precision``: a cast there and back is a pair of converts
    that the TPU's compiler folds away inside the step programs (measured
    on the chip, not visible in a compile's text), and the variant then
    rounds nothing there while it does on the CPU (PERF.md, PR 38: its
    first readings were of that)."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import kda

    kind = jnp.finfo(jnp.dtype(dtype))

    def rounding(fn):
        def call(q, k, kb, vb, g, state, layer, *rest, **kw):
            o, state = fn(q, k, kb, vb, g, state, layer, *rest, **kw)
            kept = jax.lax.reduce_precision(state[layer], kind.nexp,
                                            kind.nmant)
            return o, state.at[layer].set(kept)

        return call

    kda.kda_mixed = rounding(kda.kda_mixed)
    kda.kda_decode = rounding(kda.kda_decode)


def state_probe(engine, want: dict, seed: int) -> dict:
    """One request alone through the idle engine (every slot free): a
    prompt of ``prompt_tokens`` from the seed, ``decode_tokens`` served.
    Returns the ``tokens`` the program was given (the last served one is
    output only) and the ``state`` float32 [gated-DeltaNet layers, Hv, dk,
    dv] its slot is left with.  The arena is zeroed first, so the slot is
    the one past the idle rows' slot 0 that holds anything."""
    import jax.numpy as jnp

    pool = engine.pool
    pool.state = jnp.zeros_like(pool.state)
    prompt = np.random.default_rng(seed).integers(
        0, engine.cfg.vocab_size, want["prompt_tokens"]).tolist()
    served = engine.generate(prompt, want["decode_tokens"])
    written = jnp.abs(pool.state[0, 1:]).sum((1, 2, 3))
    return {"tokens": prompt + served[:-1],
            "state": np.asarray(pool.state[:, 1 + int(written.argmax())])}


def state_comparison(params, shape: dict, probe: dict, want: dict,
                     reference) -> dict:
    """``state_gap``: the distance of the probe's states from the
    reference's as a share of their norm, in the FIRST gated-DeltaNet
    layer: its inputs are the embeddings, so the reading is of the
    state's own arithmetic (the same on every seed to a hundredth).  The
    later layers' (``by_layer``) read four to ten times more, what the
    bf16 matmuls and the router's near-ties of the layers before them put
    into their inputs, and are reported, not limited."""
    import jax.numpy as jnp

    ref = reference.final_states(params, shape, probe["tokens"])
    err = np.sqrt(((probe["state"] - ref) ** 2).sum((1, 2, 3)))
    by_layer = [float(e) for e in err / np.sqrt((ref ** 2).sum((1, 2, 3)))]
    first = probe["state"][0]
    as_bf16 = np.asarray(jnp.asarray(first).astype(jnp.bfloat16), np.float32)
    return {"name": "state_gap", "limit": want["limit"],
            "value": by_layer[0], "by_layer": by_layer,
            "tokens": len(probe["tokens"]),
            # ~0 for an f32 state, 1 where the state went through bf16
            "exact_in_bf16": float((as_bf16 == first).mean())}


def gap_comparisons(params, shape: dict, sample: list, want: dict,
                    reference) -> list:
    """``serve_kimi_linear.gap_comparisons`` against ``reference`` (a module
    with ``served_gaps``)."""
    limit = want["served_gap_per_near_tie"]["limit"]
    if not sample:
        return [{"name": "served_gap_per_near_tie", "value": float("inf"),
                 "limit": limit, "served_tokens": 0}]
    gaps, ref = reference.served_gaps(
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
                          for m in (1e-4, 2e-4, 5e-4, 1e-3, 2e-3, 5e-3)},
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


class ServeQwen3Next(serve_engine.ServeEngine):
    def __init__(self, config: dict, seed: int, rehearse: bool):
        import jax

        from pathway_tpu.kvcache.engine import PagedDecodeEngine
        from pathway_tpu.models.encoder import _resolve_dtype
        from pathway_tpu.obs import profiler
        from pathway_tpu.serve.scheduler import RequestScheduler

        self.config, self.seed = config, seed
        self.cfg = decoder_config(config)
        dtype = self.params_dtype = _resolve_dtype(self.cfg.dtype)
        self.shape = decoder_shape(self.cfg, 0)
        state_rounding = config.get("program", {}).get("state_rounding")
        if state_rounding:
            round_states_in_program(state_rounding)
        self.rounding = config.get("weights", {}).get("rounding")
        self.params = jax.block_until_ready(
            weights_qwen3_next.qwen3_next_params(
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
            "engine.pool_bytes": eng.pool.per_shard_bytes,
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
                "engine.conv_slots_in_use": st.conv_slots_in_use,
                "engine.conv_slots_total": st.conv_slots_total,
                "engine.state_slots_in_use": st.state_slots_in_use,
                "engine.state_slots_total": st.state_slots_total}

    def release(self) -> None:
        """As ``ServeEngine.release``, the state probe served first."""
        self.sched.shutdown(drain=True)
        self.probe = state_probe(
            self.engine, self.config["correct"]["state_probe"], self.seed)
        self.sched = self.engine = None
        gc.collect()

    def verify(self, observed: dict, seed: int) -> list:
        from benchmark.reference import qwen3_next_f32

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
            self.params = weights_qwen3_next.qwen3_next_params(
                self.shape, seed, self.params_dtype)
        out.append(state_comparison(self.params, self.shape, self.probe,
                                    want["state_probe"], qwen3_next_f32))
        return out + gap_comparisons(self.params, self.shape, sample, want,
                                     qwen3_next_f32)


def build(config: dict, seed: int, rehearse: bool) -> ServeQwen3Next:
    return ServeQwen3Next(config, seed, rehearse)
