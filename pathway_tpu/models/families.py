"""Block families: what the paged engine needs to know of a model.

The engine (kvcache/engine.py) owns rounds, admission, chunked prefill and
chained dispatch; which math a round runs, and what state a sequence
keeps, is the model's.  A family is read from the configuration
(``cfg.family``, absent: the GPT-2-shaped decoder of models/decoder.py)
and gives the engine:

- ``plan(cfg, params, tp, quantize)``: the pytree it dispatches with;
- ``cache_kind`` and ``cache_kwargs(cfg, max_batch_size)``: the cache
  backend of :func:`pathway_tpu.kvcache.backend.make_backend` and its
  geometry (the K/V pool's layers and heads are the family's to say);
- ``programs(cfg, attn, mesh)``: the greedy step programs ``step``,
  ``mixed``, ``chained`` (and ``prefill`` where the family has a
  whole-bucket one), each ``(params, *cache arrays, *host arrays) ->
  (ids, *cache arrays[, device counters])`` with the cache arrays donated;
- ``unsupported(...)``: what to refuse at construction, by name (the one
  place a family refuses); ``greedy_only``: a sampled request fails alone,
  typed; ``tensor_parallel``: whether an unasked ``tp`` may take every
  local chip.

The function names ``_step_fn`` / ``_mixed_fn`` / ``_chained_fn`` are the
device trace's (``jit__mixed_fn`` on ``XLA Modules``): the benchmark's
readers find the programs by them, for every family alike.
"""

from __future__ import annotations

import jax.numpy as jnp


def _ids(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class DecoderFamily:
    """models/decoder.py: LayerNorm, learned positions, full multi-head
    attention, GELU; sampled, tensor-parallel, int8 and speculative
    variants are the engine's own."""

    name = "decoder"
    cache_kind = "paged"
    greedy_only = False
    tensor_parallel = True

    @staticmethod
    def plan(cfg, params, *, tp: int, quantize):
        from .decoder import plan_decode_params

        return plan_decode_params(cfg, params, tp=tp, quantize=quantize)

    @staticmethod
    def cache_kwargs(cfg, max_batch_size: int) -> dict:
        return {"n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
                "head_dim": cfg.d_model // cfg.n_heads}

    @staticmethod
    def unsupported(**asked) -> None:
        return None

    @staticmethod
    def programs(cfg, attn: str, mesh) -> dict:
        # device-side sampling: every wrapper argmaxes INSIDE the jitted
        # program, so only [B] int32 ids (not [B, vocab] logits) cross the
        # device->host boundary per round.  Under tp the shard_map variants
        # return ids directly (an exact two-stage argmax over the sharded
        # vocab head, decoder._head_out)
        from . import decoder as d

        def _step_fn(p, k_pool, v_pool, token, positions, bt, sb, so):
            if mesh is not None:
                return d.paged_decode_step_tp(
                    p, cfg, mesh, k_pool, v_pool, token, positions, bt,
                    sb, so, attn=attn)
            logits, k_pool, v_pool = d.paged_decode_step(
                p, cfg, k_pool, v_pool, token, positions, bt, sb, so,
                attn=attn)
            return _ids(logits), k_pool, v_pool

        def _mixed_fn(p, k_pool, v_pool, tokens, positions, row_tables,
                      row_start, row_nvalid, row_token_idx, tok_row,
                      tok_col, sb, so, logit_idx):
            if mesh is not None:
                return d.paged_mixed_step_tp(
                    p, cfg, mesh, k_pool, v_pool, tokens, positions,
                    row_tables, row_start, row_nvalid, row_token_idx,
                    tok_row, tok_col, sb, so, logit_idx, attn=attn)
            logits, k_pool, v_pool = d.paged_mixed_step(
                p, cfg, k_pool, v_pool, tokens, positions, row_tables,
                row_start, row_nvalid, row_token_idx, tok_row, tok_col,
                sb, so, logit_idx, attn=attn)
            return _ids(logits), k_pool, v_pool

        def _chained_fn(p, k_pool, v_pool, token, positions, bt, sb, so):
            if mesh is not None:
                return d.paged_chained_decode_tp(
                    p, cfg, mesh, k_pool, v_pool, token, positions, bt,
                    sb, so, attn=attn)
            return d.paged_chained_decode(
                p, cfg, k_pool, v_pool, token, positions, bt, sb, so,
                attn=attn)

        def _prefill_fn(p, token_ids, n_valid, k_pool, v_pool, bt):
            if mesh is not None:
                return d.paged_prefill_tp(
                    p, cfg, mesh, token_ids, n_valid, k_pool, v_pool, bt)
            logits, k_pool, v_pool = d.paged_prefill(
                p, cfg, token_ids, n_valid, k_pool, v_pool, bt)
            return _ids(logits), k_pool, v_pool

        return {"step": (_step_fn, (1, 2)), "mixed": (_mixed_fn, (1, 2)),
                "chained": (_chained_fn, (1, 2)),
                "prefill": (_prefill_fn, (3, 4))}


class Lfm2Family:
    """models/lfm2.py: conv and grouped-query attention mixers, SwiGLU and
    routed experts, on the hybrid cache.  Greedy on one device; the
    chunked mixed step is its only prefill."""

    name = "lfm2"
    cache_kind = "hybrid"
    greedy_only = True
    tensor_parallel = False

    @staticmethod
    def plan(cfg, params, *, tp: int, quantize):
        from .lfm2 import plan_params

        return plan_params(cfg, params)

    @staticmethod
    def cache_kwargs(cfg, max_batch_size: int) -> dict:
        return {"n_layers": len(cfg.attn_layers), "n_heads": cfg.n_kv_heads,
                "head_dim": cfg.head_dim,
                "conv_layers": len(cfg.conv_layers),
                "conv_width": cfg.d_model, "conv_slots": max_batch_size}

    @staticmethod
    def unsupported(*, tp, quantize, speculative, session_store,
                    chunked_prefill) -> None:
        missing = [what for what, asked in (
            ("tensor parallelism (tp > 1): the conv arena and the expert "
             "weights have no sharded layout", tp is not None and tp > 1),
            (f"quantize={quantize!r}: no quantized plan of the expert "
             "weights", quantize is not None),
            ("speculative drafting: a rejected draft would have to roll "
             "the conv state back", speculative not in (None, False)),
            ("host tiering (session_store): a resumed block skips the "
             "tokens that build the conv state", session_store is not None),
            ("whole-bucket prefill (chunked_prefill=False): this family "
             "prefills through the mixed step only", not chunked_prefill),
        ) if asked]
        if missing:
            raise ValueError(
                "the lfm2 block family does not support "
                + "; ".join(missing))

    @staticmethod
    def programs(cfg, attn: str, mesh) -> dict:
        from . import lfm2 as m

        def _step_fn(p, k_pool, v_pool, conv, token, positions, bt, sb, so,
                     slots):
            logits, *state = m.hybrid_decode_step(
                p, cfg, k_pool, v_pool, conv, token, positions, bt, sb, so,
                slots, attn=attn)
            return (m.greedy_ids(logits), *state)

        def _mixed_fn(p, k_pool, v_pool, conv, tokens, positions,
                      row_tables, row_start, row_nvalid, row_token_idx,
                      tok_row, tok_col, sb, so, logit_idx, slots):
            logits, *state = m.hybrid_mixed_step(
                p, cfg, k_pool, v_pool, conv, tokens, positions, row_tables,
                row_start, row_nvalid, row_token_idx, tok_row, tok_col, sb,
                so, logit_idx, slots, attn=attn)
            return (m.greedy_ids(logits), *state)

        def _chained_fn(p, k_pool, v_pool, conv, token, positions, bt, sb,
                        so, slots):
            return m.hybrid_chained_decode(
                p, cfg, k_pool, v_pool, conv, token, positions, bt, sb, so,
                slots, attn=attn)

        return {"step": (_step_fn, (1, 2, 3)), "mixed": (_mixed_fn, (1, 2, 3)),
                "chained": (_chained_fn, (1, 2, 3))}


_FAMILIES = {f.name: f for f in (DecoderFamily, Lfm2Family)}


def step_family(cfg):
    """The family of a configuration: ``cfg.family``, or the decoder's."""
    name = getattr(cfg, "family", "decoder")
    if name not in _FAMILIES:
        raise ValueError(f"unknown block family {name!r}; known: "
                         f"{sorted(_FAMILIES)}")
    return _FAMILIES[name]
