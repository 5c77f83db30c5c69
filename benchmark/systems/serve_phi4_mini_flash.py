"""System ``serve_phi4_mini_flash``: a ``phi4_mini_flash`` model (microsoft
Phi-4-mini-flash-reasoning) served as a user serves it.

The wiring, interface and counters of ``serve_engine`` (``RequestScheduler
.submit`` with ``on_token`` -> ``engine.serve_batch`` -> ``PagedDecodeEngine``
with ``max_restarts=0``), with the model built from the published
``phi4flash`` keys through the program's own
``hf_import.config_from_phi4flash``; the family is read from the
configuration, the engine is given no keyword that names it.  A program
without the family (a commit before it) fails at once: the family table's
own error for an unknown family, before a weight is drawn.

A configuration file of this system holds the published keys at its top
level (as the catalog lists them), ``mamba`` (the modeling code's sizes the
config has no key for), ``serve`` (``max_len``: the served context),
``dtype``, ``engine`` and ``scheduler`` keyword arguments, and ``correct``.
``weights.rounding`` (the variant ``int8_control``) hands the program the
seed's weights rounded further; ``program.state_rounding`` (the variant
``bf16_state``) rounds the program's scan states after every update
(:func:`round_scan_states_in_program`).  The reference always takes the
weights as the configuration states them and keeps an f32 state.

``correct`` compares, against ``reference/phi4_mini_flash_f32.py`` (every
token through every layer: no skip), over prompt + served tokens of a sample
of the finished requests (``serve_afmoe.sample_requests``), what
``serve_qwen3_next.gap_comparisons`` compares on the served tokens but the
router's near-ties (no router here: no position is one):
``served_gap_per_near_tie``, ``long_context_gap`` and ``widest_gap``; and
``state_gap`` (``serve_qwen3_next.state_probe`` / ``state_comparison``):
one request served alone after the window, the state its slot of the scan
arena is left with against the reference's, in the first Mamba layer.

Counters: ``serve_engine``'s, the window pool's and the window layers' keys
(``serve_afmoe``'s), the scan's resets, and the rows the mixed steps ran
each decoder over (counted on the device).
"""

from __future__ import annotations

import gc
import types

import numpy as np

from benchmark import weights_phi4_mini_flash
from benchmark.systems import serve_engine
from benchmark.systems.serve_afmoe import sample_requests
from benchmark.systems.serve_lfm2 import decoder_shape
from benchmark.systems.serve_qwen3_next import (
    gap_comparisons, state_comparison, state_probe)

FAMILY = "phi4_mini_flash"

ENGINE_COUNTERS = serve_engine.ENGINE_COUNTERS + (
    "kv_window_blocks_allocated", "kv_window_blocks_freed",
    "kv_window_keys", "kv_window_ctx_keys", "kv_window_band_pairs",
    "kv_window_span_pairs", "kda_state_resets", "cross_decoder_rows",
    "self_decoder_rows")

PUBLISHED_KEYS = (
    "embd_pdrop", "hidden_act", "hidden_size", "intermediate_size",
    "layer_norm_eps", "max_position_embeddings", "mb_per_layer",
    "model_type", "num_attention_heads", "num_hidden_layers",
    "num_key_value_heads", "resid_pdrop", "sliding_window",
    "tie_word_embeddings", "mlp_bias", "lm_head_bias", "vocab_size")


def decoder_config(config: dict):
    """Phi4MiniFlashConfig through the program's own family table and
    ``phi4flash`` import: a program without the family fails here, by the
    table's own error, before anything is built."""
    from pathway_tpu.models import families, hf_import

    families.step_family(types.SimpleNamespace(family=FAMILY))
    published = types.SimpleNamespace(
        **{k: config[k] for k in PUBLISHED_KEYS})
    return hf_import.config_from_phi4flash(
        published, max_len=config["serve"]["max_len"], dtype=config["dtype"],
        **config["mamba"])


def round_scan_states_in_program(dtype: str) -> None:
    """A scan state of lower precision: the program's two scan entry points
    (``ops/ssm.py`` ``mamba_mixed`` / ``mamba_decode``) give back the
    layer's states rounded to ``dtype`` (held in the arena's f32), so that
    every token reads a state that went through ``dtype`` once a step;
    through ``lax.reduce_precision``, which the TPU's compiler does not fold
    away (``serve_qwen3_next.round_states_in_program``)."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import ssm

    kind = jnp.finfo(jnp.dtype(dtype))

    def rounding(fn):
        def call(u, dt, b, c, a, state, layer, *rest, **kw):
            y, state = fn(u, dt, b, c, a, state, layer, *rest, **kw)
            kept = jax.lax.reduce_precision(state[layer], kind.nexp,
                                            kind.nmant)
            return y, state.at[layer].set(kept)

        return call

    ssm.mamba_mixed = rounding(ssm.mamba_mixed)
    ssm.mamba_decode = rounding(ssm.mamba_decode)


class ServePhi4MiniFlash(serve_engine.ServeEngine):
    def __init__(self, config: dict, seed: int, rehearse: bool):
        self.config, self.seed = config, seed
        self.cfg = decoder_config(config)  # first: a program without it

        import jax

        from pathway_tpu.kvcache.engine import PagedDecodeEngine
        from pathway_tpu.models.encoder import _resolve_dtype
        from pathway_tpu.obs import profiler
        from pathway_tpu.serve.scheduler import RequestScheduler

        dtype = self.params_dtype = _resolve_dtype(self.cfg.dtype)
        self.shape = decoder_shape(self.cfg, 0)
        state_rounding = config.get("program", {}).get("state_rounding")
        if state_rounding:
            round_scan_states_in_program(state_rounding)
        self.rounding = config.get("weights", {}).get("rounding")
        self.params = jax.block_until_ready(
            weights_phi4_mini_flash.phi4_mini_flash_params(
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
            "engine.pool_bytes": eng.pool.per_shard_bytes,
            "decoder": self.shape,
            "kv_itemsize": eng.pool.k.dtype.itemsize,
            "weight_itemsize": np.dtype(dtype).itemsize,
        }

    def counters(self) -> dict:
        st = self.engine.pool.stats
        out = {"engine." + k: float(getattr(st, k)) for k in ENGINE_COUNTERS}
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
                "engine.window_blocks_total": st.window_blocks_total,
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
        from benchmark.reference import phi4_mini_flash_f32

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
            self.params = weights_phi4_mini_flash.phi4_mini_flash_params(
                self.shape, seed, self.params_dtype)
        out.append(state_comparison(self.params, self.shape, self.probe,
                                    want["state_probe"], phi4_mini_flash_f32))
        # no router: no position is a router near-tie, and the share of them
        # is no comparison here
        return out + [c for c in gap_comparisons(
            self.params, self.shape, sample,
            dict(want, router_margin=0.0,
                 router_near_tie_share={"limit": 1.0}),
            phi4_mini_flash_f32) if c["name"] != "router_near_tie_share"]


def build(config: dict, seed: int, rehearse: bool) -> ServePhi4MiniFlash:
    return ServePhi4MiniFlash(config, seed, rehearse)
