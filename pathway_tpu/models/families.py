"""Block families: what the paged engine needs to know of a model.

The engine (kvcache/engine.py) owns rounds, admission, chunked prefill and
chained dispatch; which math a round runs, and what state a sequence
keeps, is the model's.  A family is read from the configuration
(``cfg.family``, absent: the GPT-2-shaped decoder of models/decoder.py)
and gives the engine:

- ``plan(cfg, params, tp, quantize)``: the pytree it dispatches with;
- ``cache_kind`` and ``cache_kwargs(cfg, max_batch_size, round_tokens)``:
  the cache backend of :func:`pathway_tpu.kvcache.backend.make_backend`
  and its geometry (the K/V pool's layers and heads are the family's to
  say; ``round_tokens``: the most one round adds to a sequence, which
  sizes a windowed cache's second pool);
- ``programs(cfg, attn, mesh, sampled=False)``: the table of step
  programs ``step``, ``mixed``, ``chained`` as ``(function, donated
  argument numbers)``, each ``(params, *cache arrays, *host arrays) ->
  (ids, *cache arrays[, device counters])`` with the cache arrays donated;
  ``sampled=True`` gives the table for rounds with sampled rows, whose
  functions take five further ``(B,)`` arrays (temperature, top_k, top_p,
  seed, emit index).  The engine jits what it is given, the verify
  program of speculative rounds being ``mixed`` once more;
- ``unsupported(...)``: what to refuse at construction, by name (the one
  place a family refuses); ``greedy_only``: a sampled request fails alone,
  typed, and the sampled table is never asked for; ``tensor_parallel``:
  whether an unasked ``tp`` may take every local chip.

A family is the only place that names a model's programs: the engine
imports none of models/decoder.py, models/lfm2.py, models/afmoe.py,
models/kimi_linear.py, models/qwen3_next.py, models/mimo_v2_flash.py.  The
function
names ``_step_fn`` / ``_mixed_fn`` / ``_chained_fn`` are the
device trace's (``jit__mixed_fn`` on ``XLA Modules``): the benchmark's
readers find the programs by them, for every family alike.
"""

from __future__ import annotations

import jax.numpy as jnp


def _ids(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _refuse(family: str, asked: tuple) -> None:
    """``asked``: (what is missing and why, whether it was asked for)
    pairs; one ``ValueError`` that names the family and every one asked."""
    missing = [what for what, wanted in asked if wanted]
    if missing:
        raise ValueError(f"the {family} block family does not support "
                         + "; ".join(missing))


class DecoderFamily:
    """models/decoder.py: LayerNorm, learned positions, full multi-head
    attention, GELU; greedy and sampled rows, one device or a tp mesh,
    f32/bf16 or an int8 plan (the plan's keys say which)."""

    name = "decoder"
    cache_kind = "paged"
    greedy_only = False
    tensor_parallel = True

    @staticmethod
    def plan(cfg, params, *, tp: int, quantize):
        from .decoder import plan_decode_params

        return plan_decode_params(cfg, params, tp=tp, quantize=quantize)

    @staticmethod
    def cache_kwargs(cfg, max_batch_size: int, round_tokens: int) -> dict:
        return {"n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
                "head_dim": cfg.d_model // cfg.n_heads}

    @staticmethod
    def unsupported(**asked) -> None:
        return None

    @staticmethod
    def programs(cfg, attn: str, mesh, sampled: bool = False) -> dict:
        # every program samples INSIDE the jitted program, so only [B]
        # int32 ids (not [B, vocab] logits) cross the device->host
        # boundary per round.  The three paged functions of
        # models/decoder.py are the math; the two things a program adds to
        # them are put on here, once each: the sampling head and the
        # shard map
        from . import decoder as d

        tp_axis = None if mesh is None else "tp"
        # one device and greedy rows: the paged function gives logits;
        # under tp its vocab head has argmaxed already (an exact two-stage
        # argmax over the sharded head, decoder._head_out), and a sampling
        # head gives ids
        logits_out = tp_axis is None and not sampled

        def head_at(samp, t=0):
            """The vocab head of a sampled table's rows at the ``t``-th
            token a dispatch emits, from the five (B,) arrays a sampled
            program takes after its greedy twin's: temperature (f32),
            top_k (int32, <= 0 disables), top_p (f32, 1.0 disables), seed
            (int32, the request's) and the absolute index of the token a
            row emits first.  Temperature-0 rows take the exact argmax."""
            if not sampled:
                return None
            temp, top_k, top_p, seed, emit = samp
            return d._sampling_head(temp, top_k, top_p,
                                    d._row_sample_keys(seed, emit + t))

        def over_mesh(body, *operands):
            if mesh is None:
                return body(*operands)
            return d._tp_shard_map(body, mesh, *operands)

        def _step_fn(p, k_pool, v_pool, *host):
            def body(p, k_pool, v_pool, token, positions, bt, sb, so, *samp):
                out, k_pool, v_pool = d.paged_decode_step(
                    p, cfg, k_pool, v_pool, token, positions, bt, sb, so,
                    attn=attn, tp_axis=tp_axis, head_fn=head_at(samp))
                return (_ids(out) if logits_out else out), k_pool, v_pool

            return over_mesh(body, p, k_pool, v_pool, *host)

        def _mixed_fn(p, k_pool, v_pool, *host):
            def body(p, k_pool, v_pool, tokens, positions, row_tables,
                     row_start, row_nvalid, row_token_idx, tok_row, tok_col,
                     sb, so, logit_idx, *samp):
                out, k_pool, v_pool = d.paged_mixed_step(
                    p, cfg, k_pool, v_pool, tokens, positions, row_tables,
                    row_start, row_nvalid, row_token_idx, tok_row, tok_col,
                    sb, so, logit_idx, attn=attn, tp_axis=tp_axis,
                    head_fn=head_at(samp))
                return (_ids(out) if logits_out else out), k_pool, v_pool

            return over_mesh(body, p, k_pool, v_pool, *host)

        def _chained_fn(p, k_pool, v_pool, *host):
            def body(p, k_pool, v_pool, token, positions, bt, sb, so, *samp):
                return d.paged_chained_decode(
                    p, cfg, k_pool, v_pool, token, positions, bt, sb, so,
                    attn=attn, tp_axis=tp_axis,
                    head_at=(lambda t: head_at(samp, t)) if sampled
                    else None)

            return over_mesh(body, p, k_pool, v_pool, *host)

        return {"step": (_step_fn, (1, 2)), "mixed": (_mixed_fn, (1, 2)),
                "chained": (_chained_fn, (1, 2))}


class Lfm2Family:
    """models/lfm2.py: conv and grouped-query attention mixers, SwiGLU and
    routed experts, on the hybrid cache.  Greedy on one device."""

    name = "lfm2"
    cache_kind = "hybrid"
    greedy_only = True
    tensor_parallel = False

    @staticmethod
    def plan(cfg, params, *, tp: int, quantize):
        from .lfm2 import plan_params

        return plan_params(cfg, params)

    @staticmethod
    def cache_kwargs(cfg, max_batch_size: int, round_tokens: int) -> dict:
        return {"n_layers": len(cfg.attn_layers), "n_heads": cfg.n_kv_heads,
                "head_dim": cfg.head_dim,
                "conv_layers": len(cfg.conv_layers),
                "conv_width": cfg.d_model, "conv_slots": max_batch_size}

    @staticmethod
    def unsupported(*, tp, quantize, speculative, session_store) -> None:
        _refuse("lfm2", (
            ("tensor parallelism (tp > 1): the conv arena and the expert "
             "weights have no sharded layout", tp is not None and tp > 1),
            (f"quantize={quantize!r}: no quantized plan of the expert "
             "weights", quantize is not None),
            ("speculative drafting: a rejected draft would have to roll "
             "the conv state back", speculative not in (None, False)),
            ("host tiering (session_store): a resumed block skips the "
             "tokens that build the conv state", session_store is not None),
        ))

    @staticmethod
    def programs(cfg, attn: str, mesh, sampled: bool = False) -> dict:
        if sampled:
            raise ValueError("the lfm2 block family decodes greedily: it "
                             "has no sampled step programs")
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


class AfmoeFamily:
    """models/afmoe.py: sliding-window and full attention layers with a
    gated output, sandwich norms, SwiGLU and routed experts beside a shared
    one, on the windowed cache.  Greedy on one device.  Beyond what
    :meth:`unsupported` refuses, the cache kind runs without a prefix cache
    whatever was asked for (a shared block may be freed behind one
    sequence's window while another still reads it), and a sampled request
    fails alone (``greedy_only``)."""

    name = "afmoe"
    cache_kind = "windowed"
    greedy_only = True
    tensor_parallel = False

    @staticmethod
    def _model():
        """The module that holds the family's three windowed step programs
        and ``plan_params`` (a second family of window and full layers
        names its own)."""
        from . import afmoe

        return afmoe

    @classmethod
    def plan(cls, cfg, params, *, tp: int, quantize):
        return cls._model().plan_params(cfg, params)

    @staticmethod
    def cache_kwargs(cfg, max_batch_size: int, round_tokens: int) -> dict:
        return {"n_layers": len(cfg.full_layers), "n_heads": cfg.n_kv_heads,
                "head_dim": cfg.head_dim, "window": cfg.sliding_window,
                "window_layers": len(cfg.window_layers),
                "round_tokens": round_tokens, "max_seqs": max_batch_size}

    @classmethod
    def unsupported(cls, *, tp, quantize, speculative, session_store) -> None:
        _refuse(cls.name, (
            ("tensor parallelism (tp > 1): the window pool and the expert "
             "weights have no sharded layout", tp is not None and tp > 1),
            (f"quantize={quantize!r}: no quantized plan of the expert "
             "weights", quantize is not None),
            ("speculative drafting: a rejected draft's slots cannot be "
             "rolled back behind the window", speculative not in (None, False)),
            ("host tiering (session_store): a suspended sequence has lost "
             "the window layers' keys behind its window",
             session_store is not None),
        ))

    @classmethod
    def programs(cls, cfg, attn: str, mesh, sampled: bool = False) -> dict:
        if sampled:
            raise ValueError(f"the {cls.name} block family decodes "
                             "greedily: it has no sampled step programs")
        m = cls._model()

        def _step_fn(p, k_pool, v_pool, kw_pool, vw_pool, token, positions,
                     bt, sb, so, wt):
            logits, *state = m.windowed_decode_step(
                p, cfg, k_pool, v_pool, kw_pool, vw_pool, token, positions,
                bt, sb, so, wt, attn=attn)
            return (m.greedy_ids(logits), *state)

        def _mixed_fn(p, k_pool, v_pool, kw_pool, vw_pool, tokens, positions,
                      row_tables, row_start, row_nvalid, row_token_idx,
                      tok_row, tok_col, sb, so, logit_idx, wt):
            logits, *state = m.windowed_mixed_step(
                p, cfg, k_pool, v_pool, kw_pool, vw_pool, tokens, positions,
                row_tables, row_start, row_nvalid, row_token_idx, tok_row,
                tok_col, sb, so, logit_idx, wt, attn=attn)
            return (m.greedy_ids(logits), *state)

        def _chained_fn(p, k_pool, v_pool, kw_pool, vw_pool, token,
                        positions, bt, sb, so, wt):
            return m.windowed_chained_decode(
                p, cfg, k_pool, v_pool, kw_pool, vw_pool, token, positions,
                bt, sb, so, wt, attn=attn)

        donated = (1, 2, 3, 4)
        return {"step": (_step_fn, donated), "mixed": (_mixed_fn, donated),
                "chained": (_chained_fn, donated)}


class KimiLinearFamily:
    """models/kimi_linear.py: Kimi Delta Attention and latent attention
    layers, SwiGLU and routed experts (all of them, or the share a chip of
    an expert-parallel deployment holds) beside a shared one, on the
    latent-and-state cache.  Greedy on one device.  Beyond what
    :meth:`unsupported` refuses, the cache kind runs without a prefix cache
    and without fork whatever was asked for (a shared block would skip the
    tokens that build the matrix state), and a sampled request fails alone
    (``greedy_only``)."""

    name = "kimi_linear"
    cache_kind = "latent_state"
    greedy_only = True
    tensor_parallel = False

    @staticmethod
    def plan(cfg, params, *, tp: int, quantize):
        from .kimi_linear import plan_params

        return plan_params(cfg, params)

    @staticmethod
    def cache_kwargs(cfg, max_batch_size: int, round_tokens: int) -> dict:
        return {"n_layers": len(cfg.mla_layers), "n_heads": 1,
                "head_dim": cfg.latent_lanes,
                "conv_layers": len(cfg.kda_layers),
                "conv_width": 3 * cfg.kda_width,
                "conv_taps": cfg.conv_kernel - 1,
                "conv_slots": max_batch_size, "state_heads": cfg.n_heads,
                "state_dk": cfg.kda_head_dim, "state_dv": cfg.kda_head_dim}

    @staticmethod
    def scan_items(cfg) -> tuple:
        """(tokens, f32 lanes a token) of a work item of the chunked scan:
        what obs/memory.py bills the mixed step's temporaries by."""
        return cfg.kda_chunk, cfg.kda_width

    @staticmethod
    def unsupported(*, tp, quantize, speculative, session_store) -> None:
        _refuse("kimi_linear", (
            ("tensor parallelism (tp > 1): the state arena, the latent pool "
             "and the held experts have no sharded layout and no exchange",
             tp is not None and tp > 1),
            (f"quantize={quantize!r}: no quantized plan of the expert "
             "weights", quantize is not None),
            ("speculative drafting: a rejected draft would have to roll "
             "the matrix state back", speculative not in (None, False)),
            ("host tiering (session_store): a resumed block skips the "
             "tokens that build the matrix state (it would take a snapshot "
             "of the state at every block boundary, as prefix sharing and "
             "fork would)", session_store is not None),
        ))

    @staticmethod
    def programs(cfg, attn: str, mesh, sampled: bool = False) -> dict:
        if sampled:
            raise ValueError("the kimi_linear block family decodes greedily: "
                             "it has no sampled step programs")
        from . import kimi_linear as m

        def _step_fn(p, pool, conv, state, token, positions, bt, sb, so,
                     slots):
            logits, *cache = m.state_decode_step(
                p, cfg, pool, conv, state, token, positions, bt, sb, so,
                slots, attn=attn)
            return (m.greedy_ids(logits), *cache)

        def _mixed_fn(p, pool, conv, state, tokens, positions, row_tables,
                      row_start, row_nvalid, row_token_idx, tok_row, tok_col,
                      sb, so, logit_idx, slots):
            logits, *cache = m.state_mixed_step(
                p, cfg, pool, conv, state, tokens, positions, row_tables,
                row_start, row_nvalid, row_token_idx, tok_row, tok_col, sb,
                so, logit_idx, slots, attn=attn)
            return (m.greedy_ids(logits), *cache)

        def _chained_fn(p, pool, conv, state, token, positions, bt, sb, so,
                        slots):
            return m.state_chained_decode(
                p, cfg, pool, conv, state, token, positions, bt, sb, so,
                slots, attn=attn)

        return {"step": (_step_fn, (1, 2, 3)), "mixed": (_mixed_fn, (1, 2, 3)),
                "chained": (_chained_fn, (1, 2, 3))}


class Qwen3NextFamily:
    """models/qwen3_next.py: gated DeltaNet (one decay a head) and gated
    full-attention layers (rotary on a part of the head), a softmax router
    over routed experts (all of them, or the share a chip of an
    expert-parallel deployment holds) beside a sigmoid-gated shared one, on
    the K/V-and-state cache.  Greedy on one device.  Beyond what
    :meth:`unsupported` refuses, the cache kind runs without a prefix cache
    and without fork whatever was asked for (a shared block would skip the
    tokens that build the matrix state), and a sampled request fails alone
    (``greedy_only``)."""

    name = "qwen3_next"
    cache_kind = "kv_state"
    greedy_only = True
    tensor_parallel = False

    @staticmethod
    def plan(cfg, params, *, tp: int, quantize):
        from .qwen3_next import plan_params

        return plan_params(cfg, params)

    @staticmethod
    def cache_kwargs(cfg, max_batch_size: int, round_tokens: int) -> dict:
        return {"n_layers": len(cfg.full_layers), "n_heads": cfg.n_kv_heads,
                "head_dim": cfg.head_dim,
                "conv_layers": len(cfg.gdn_layers),
                "conv_width": cfg.conv_width,
                "conv_taps": cfg.conv_kernel - 1,
                "conv_slots": max_batch_size,
                "state_heads": cfg.gdn_value_heads,
                "state_dk": cfg.gdn_key_dim, "state_dv": cfg.gdn_value_dim}

    @staticmethod
    def scan_items(cfg) -> tuple:
        """As :meth:`KimiLinearFamily.scan_items`."""
        return cfg.gdn_chunk, cfg.value_width

    @staticmethod
    def unsupported(*, tp, quantize, speculative, session_store) -> None:
        _refuse("qwen3_next", (
            ("tensor parallelism (tp > 1): the state arena, the K/V pool of "
             "two heads and the held experts have no sharded layout and no "
             "exchange", tp is not None and tp > 1),
            (f"quantize={quantize!r}: no quantized plan of the expert "
             "weights", quantize is not None),
            ("speculative drafting: a rejected draft would have to roll "
             "the matrix state back", speculative not in (None, False)),
            ("host tiering (session_store): a resumed block skips the "
             "tokens that build the matrix state (it would take a snapshot "
             "of the state at every block boundary, as prefix sharing and "
             "fork would)", session_store is not None),
        ))

    @staticmethod
    def programs(cfg, attn: str, mesh, sampled: bool = False) -> dict:
        if sampled:
            raise ValueError("the qwen3_next block family decodes greedily: "
                             "it has no sampled step programs")
        from . import qwen3_next as m

        def _step_fn(p, k_pool, v_pool, conv, state, token, positions, bt,
                     sb, so, slots):
            logits, *cache = m.kv_state_decode_step(
                p, cfg, k_pool, v_pool, conv, state, token, positions, bt,
                sb, so, slots, attn=attn)
            return (m.greedy_ids(logits), *cache)

        def _mixed_fn(p, k_pool, v_pool, conv, state, tokens, positions,
                      row_tables, row_start, row_nvalid, row_token_idx,
                      tok_row, tok_col, sb, so, logit_idx, slots):
            logits, *cache = m.kv_state_mixed_step(
                p, cfg, k_pool, v_pool, conv, state, tokens, positions,
                row_tables, row_start, row_nvalid, row_token_idx, tok_row,
                tok_col, sb, so, logit_idx, slots, attn=attn)
            return (m.greedy_ids(logits), *cache)

        def _chained_fn(p, k_pool, v_pool, conv, state, token, positions, bt,
                        sb, so, slots):
            return m.kv_state_chained_decode(
                p, cfg, k_pool, v_pool, conv, state, token, positions, bt,
                sb, so, slots, attn=attn)

        donated = (1, 2, 3, 4)
        return {"step": (_step_fn, donated), "mixed": (_mixed_fn, donated),
                "chained": (_chained_fn, donated)}


class MimoV2FlashFamily(AfmoeFamily):
    """models/mimo_v2_flash.py: sliding-window layers (a learned sink a
    query head, K/V heads of their own number) and full attention layers,
    keys wider than values, SwiGLU and routed experts (all of them, or the
    share a chip of an expert-parallel deployment holds) with no shared
    one, on the windowed cache, whose two pool pairs take their geometry
    from here.  The step contract, the refusals and what the cache kind
    rules out are :class:`AfmoeFamily`'s."""

    name = "mimo_v2_flash"

    @staticmethod
    def _model():
        from . import mimo_v2_flash

        return mimo_v2_flash

    @staticmethod
    def cache_kwargs(cfg, max_batch_size: int, round_tokens: int) -> dict:
        return {**AfmoeFamily.cache_kwargs(cfg, max_batch_size, round_tokens),
                "v_head_dim": cfg.v_head_dim,
                "window_heads": cfg.window_kv_heads}


_FAMILIES = {f.name: f for f in (DecoderFamily, Lfm2Family, AfmoeFamily,
                                 KimiLinearFamily, Qwen3NextFamily,
                                 MimoV2FlashFamily)}


def step_family(cfg):
    """The family of a configuration: ``cfg.family``, or the decoder's."""
    name = getattr(cfg, "family", "decoder")
    if name not in _FAMILIES:
        raise ValueError(f"unknown block family {name!r}; known: "
                         f"{sorted(_FAMILIES)}")
    return _FAMILIES[name]
