"""System ``serve_engine``: a decoder served as a user serves it.

``RequestScheduler.submit`` with ``on_token`` -> ``engine.serve_batch`` ->
``PagedDecodeEngine`` with its own ``auto_config`` (the wiring of
``Int8DecoderHost.serving_executor``, without a degrade target and with
``max_restarts=0`` so that no fallback hides a fault).  Copied from
``chip_smoke.py``'s ``serve_requests`` / ``phase_direct``.

A configuration file of this system gives ``model`` (the published GPT-2
``config.json`` keys), ``dtype``, ``engine`` and ``scheduler`` keyword
arguments, and ``correct`` (limits and the size of the compared sample).
"""

from __future__ import annotations

import dataclasses
import gc
import random
import types

import numpy as np

from benchmark import weights

ENGINE_COUNTERS = (
    "chain_slots", "chain_emitted", "chain_count", "chain_steps_sum",
    "mixed_steps", "mixed_step_rows", "prefill_chunks", "preemptions",
    "prefix_hits", "prefix_misses", "ttft_count", "host_gap_s",
    "engine_restarts", "engine_degraded",
)


def decoder_config(config: dict):
    """DecoderConfig through the program's own GPT-2 import."""
    from pathway_tpu.models import hf_import

    cfg = hf_import.config_from_gpt2(types.SimpleNamespace(**config["model"]))
    return dataclasses.replace(cfg, dtype=config["dtype"])


def decoder_shape(cfg) -> dict:
    return {"vocab_size": cfg.vocab_size, "d_model": cfg.d_model,
            "n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
            "d_ff": cfg.d_ff, "max_len": cfg.max_len,
            "ln_eps": float(cfg.ln_eps)}


def sample_requests(finished: list, n: int, seed: int) -> list:
    """``n`` of the finished requests (all of them: ``n`` None), drawn from
    the seed, the longest (prompt + served) among them."""
    if not finished or n is None:
        return list(finished)
    longest = max(finished, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in finished if r is not longest]
    random.Random(seed ^ 0x5EED).shuffle(rest)
    return [longest] + rest[: max(n - 1, 0)]


def gap_comparisons(params, shape: dict, sample: list, limit: float,
                    widest_limit: float | None = None) -> list:
    """How far the served tokens' reference logits lie below the
    reference's best, over the sample (valid for greedy tokens).  Compared:
    the mean gap, in units of the reference logits' standard deviation at
    its position, over the share of positions where the reference itself
    is a near-tie (best minus second under 0.05 standard deviations): a
    served token can only leave the reference's best at a near-tie, and how
    many of those a random-weight model has swings with the seed.  That
    mean catches a fault in every token (lower precision); one wrong
    token among thousands moves it too little, so the widest gap is held
    to ``widest_limit``, a loose limit of its own, where one is given."""
    from benchmark.reference import decoder_f32

    if not sample:
        return [{"name": "served_gap_per_near_tie", "value": float("inf"),
                 "limit": limit, "served_tokens": 0}]
    gaps, ref = decoder_f32.served_gaps(
        params, shape, [(r["prompt"], r["tokens"]) for r in sample])
    # positions in the reference's block order, as margin and std are
    flat = np.asarray([g for r in ref["order"] for g in gaps[r]])
    rel = flat / ref["std"]
    near = {f"near_tie_{t}": float(np.mean(ref["margin"] / ref["std"] < t))
            for t in (0.02, 0.05, 0.1)}
    out = [{"name": "served_gap_per_near_tie", "limit": limit,
             "value": float(rel.mean() / max(near["near_tie_0.05"], 1e-9)),
             "gap_mean": float(flat.mean()), "widest_gap": float(flat.max()),
             "not_best": int((flat > 0).sum()),
             "gap_rel_mean": float(rel.mean()),
             "logit_std_mean": float(ref["std"].mean()), **near,
             "served_tokens": len(flat), "requests": len(sample)}]
    if widest_limit is not None:
        out.append({"name": "widest_gap", "limit": widest_limit,
                    "value": float(flat.max()),
                    "in_logit_std": float(rel[int(flat.argmax())])})
    return out


class ServeEngine:
    def __init__(self, config: dict, seed: int, rehearse: bool):
        import jax

        from pathway_tpu.kvcache.engine import PagedDecodeEngine
        from pathway_tpu.obs import profiler
        from pathway_tpu.serve.scheduler import RequestScheduler

        self.config = config
        self.cfg = decoder_config(config)
        self.shape = decoder_shape(self.cfg)
        self.params = jax.block_until_ready(
            weights.transformer_params(self.shape, seed))
        name = "bench_" + config["name"].replace("-", "_")
        self.engine = PagedDecodeEngine(self.cfg, self.params, name=name,
                                        **config.get("engine", {}))
        jax.block_until_ready((self.engine.pool.k, self.engine.pool.v))
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
        self.info = {
            "engine.chain_steps": eng.chain_steps,
            "engine.max_batch_size": eng.max_batch_size,
            "engine.prefill_chunk": eng.prefill_chunk,
            "engine.attn": eng.attn, "engine.auto_config": eng.auto_config,
            "decoder": self.shape,
            "kv_itemsize": eng.pool.k.dtype.itemsize,
        }

    # -- what the generator drives ------------------------------------------
    def submit(self, prompt: list, n_new: int, on_token, timeout_s: float):
        return self.sched.submit((prompt, n_new, {"on_token": on_token}),
                                 timeout_s=timeout_s)

    @property
    def vocab_size(self) -> int:
        return self.cfg.vocab_size

    @property
    def clients(self) -> int:
        return self.engine.max_batch_size

    # -- what the harness reads -----------------------------------------------
    def counters(self) -> dict:
        st = self.engine.pool.stats
        out = {"engine." + k: float(getattr(st, k)) for k in ENGINE_COUNTERS}
        out["scheduler.completed"] = float(self.sched.stats.completed)
        return out

    def gauges(self) -> dict:
        st = self.engine.pool.stats
        return {"engine.blocks_in_use": st.blocks_in_use,
                "engine.blocks_total": st.blocks_total}

    def samples(self, delta: dict) -> dict:
        n = int(min(delta.get("engine.ttft_count", 0), 256))
        recent = list(self.engine.pool.stats.recent_ttfts)
        return {"engine.ttft_s": recent[len(recent) - n:] if n else []}

    def compile_count(self) -> int:
        return self._registry.total_compiles()

    def release(self) -> None:
        """Free the program's state; the benchmark's own weights stay for
        the reference."""
        self.sched.shutdown(drain=True)
        self.sched = None
        self.engine = None
        gc.collect()

    def verify(self, observed: dict, seed: int) -> list:
        want = self.config["correct"]
        done = [r for r in observed["requests"] if r["error"] is None]
        out = [{"name": "wrong_token_count", "limit": 0, "value": sum(
            len(r["tokens"]) != r["n_out"] for r in done)}]
        restarts = observed["counters"].get("engine.engine_restarts", 0) \
            + observed["counters"].get("engine.engine_degraded", 0)
        out.append({"name": "engine_restarts", "value": restarts, "limit": 0})
        sample = sample_requests(done, want["sample_requests"], seed)
        out += gap_comparisons(self.params, self.shape, sample,
                               want["served_gap_per_near_tie"]["limit"],
                               want["widest_gap"]["limit"])
        return out

    def close(self) -> None:
        if self.sched is not None:
            self.sched.shutdown(drain=False)


def build(config: dict, seed: int, rehearse: bool) -> ServeEngine:
    return ServeEngine(config, seed, rehearse)
