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
- ``attn_v_heads(cfg)``: the paged kernels' ``v_heads`` (a differential
  family's value heads, shared by a pair of key heads), None elsewhere;
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
models/kimi_linear.py, models/qwen3_next.py, models/mimo_v2_flash.py,
models/phi4_mini_flash.py, models/blocks.py.  The six routed families share
one step contract (:class:`_RoutedFamily` over
:func:`pathway_tpu.models.blocks.greedy_steps`); five of them have expert
layers, and ``phi4_mini_flash`` has none (its programs count zero experts).
The function names ``_step_fn`` / ``_mixed_fn`` / ``_chained_fn`` are the
device trace's (``jit__mixed_fn`` on ``XLA Modules``): the benchmark's
readers find the programs by them, for every family alike.
"""

from __future__ import annotations

import importlib

from . import blocks


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
    def attn_v_heads(cfg) -> int | None:
        """The value heads the paged kernels' ``v_heads`` names, for the
        engine's layout of a call; None: a value head a key head."""
        return None

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
                return (blocks.greedy_ids(out) if logits_out else out), \
                    k_pool, v_pool

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
                return (blocks.greedy_ids(out) if logits_out else out), \
                    k_pool, v_pool

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


# a routed cache kind: the prefix of its step functions' names, and its
# device arrays
_STEP_CONTRACT = {"hybrid": ("hybrid", 3), "windowed": ("windowed", 4),
                  "latent_state": ("state", 3), "kv_state": ("kv_state", 4),
                  "window_state": ("window_state", 6)}


class _RoutedFamily:
    """A routed family (models/blocks.py): greedy on one device, its three
    step programs made by :func:`pathway_tpu.models.blocks.greedy_steps` in
    its module, ``models/<name>.py``, under the names of its cache kind,
    every cache array donated.  A subclass declares its ``name``,
    ``cache_kind``, ``cache_kwargs``, ``scan_items`` where it has a chunked
    scan, and ``refusals``: why it has no tensor parallelism, no speculative
    drafting and no host tiering (and, where it has no expert layer, no
    quantized plan: ``quantize``)."""

    name: str
    cache_kind: str
    refusals: dict
    greedy_only = True
    tensor_parallel = False
    attn_v_heads = staticmethod(DecoderFamily.attn_v_heads)

    @classmethod
    def _model(cls):
        return importlib.import_module(f"{__package__}.{cls.name}")

    @classmethod
    def plan(cls, cfg, params, *, tp: int, quantize):
        return cls._model().plan_params(cfg, params)

    @classmethod
    def unsupported(cls, *, tp, quantize, speculative, session_store) -> None:
        why = cls.refusals
        _refuse(cls.name, (
            (f"tensor parallelism (tp > 1): {why['tp']}",
             tp is not None and tp > 1),
            (f"quantize={quantize!r}: "
             + why.get("quantize", "no quantized plan of the expert weights"),
             quantize is not None),
            (f"speculative drafting: {why['speculative']}",
             speculative not in (None, False)),
            (f"host tiering (session_store): {why['session_store']}",
             session_store is not None),
        ))

    @classmethod
    def programs(cls, cfg, attn: str, mesh, sampled: bool = False) -> dict:
        if sampled:
            raise ValueError(f"the {cls.name} block family decodes "
                             "greedily: it has no sampled step programs")
        m = cls._model()
        prefix, n = _STEP_CONTRACT[cls.cache_kind]

        # the module's step functions are looked up at trace time
        def _step_fn(p, *arrays):
            logits, *cache = getattr(m, f"{prefix}_decode_step")(
                p, cfg, *arrays, attn=attn)
            return (blocks.greedy_ids(logits), *cache)

        def _mixed_fn(p, *arrays):
            logits, *cache = getattr(m, f"{prefix}_mixed_step")(
                p, cfg, *arrays, attn=attn)
            return (blocks.greedy_ids(logits), *cache)

        def _chained_fn(p, *arrays):
            return getattr(m, f"{prefix}_chained_decode")(
                p, cfg, *arrays, attn=attn)

        donated = tuple(range(1, n + 1))
        return {"step": (_step_fn, donated), "mixed": (_mixed_fn, donated),
                "chained": (_chained_fn, donated)}


class Lfm2Family(_RoutedFamily):
    """models/lfm2.py: conv and grouped-query attention mixers, SwiGLU and
    routed experts, on the hybrid cache."""

    name = "lfm2"
    cache_kind = "hybrid"
    refusals = {
        "tp": "the conv arena and the expert weights have no sharded layout",
        "speculative": "a rejected draft would have to roll the conv state "
                       "back",
        "session_store": "a resumed block skips the tokens that build the "
                         "conv state"}

    @staticmethod
    def cache_kwargs(cfg, max_batch_size: int, round_tokens: int) -> dict:
        return {"n_layers": len(cfg.attn_layers), "n_heads": cfg.n_kv_heads,
                "head_dim": cfg.head_dim,
                "conv_layers": len(cfg.conv_layers),
                "conv_width": cfg.d_model, "conv_slots": max_batch_size}


class AfmoeFamily(_RoutedFamily):
    """models/afmoe.py: sliding-window and full attention layers with a
    gated output, sandwich norms, SwiGLU and routed experts beside a shared
    one, on the windowed cache.  Beyond what :meth:`unsupported` refuses,
    the cache kind runs without a prefix cache whatever was asked for (a
    shared block may be freed behind one sequence's window while another
    still reads it), and a sampled request fails alone
    (``greedy_only``)."""

    name = "afmoe"
    cache_kind = "windowed"
    refusals = {
        "tp": "the window pool and the expert weights have no sharded "
              "layout",
        "speculative": "a rejected draft's slots cannot be rolled back "
                       "behind the window",
        "session_store": "a suspended sequence has lost the window layers' "
                         "keys behind its window"}

    @staticmethod
    def cache_kwargs(cfg, max_batch_size: int, round_tokens: int) -> dict:
        return {"n_layers": len(cfg.full_layers), "n_heads": cfg.n_kv_heads,
                "head_dim": cfg.head_dim, "window": cfg.sliding_window,
                "window_layers": len(cfg.window_layers),
                "round_tokens": round_tokens, "max_seqs": max_batch_size}


# why a family with a matrix state a sequence has no drafting and no tiering
_STATE_REFUSALS = {
    "speculative": "a rejected draft would have to roll the matrix state "
                   "back",
    "session_store": "a resumed block skips the tokens that build the "
                     "matrix state (it would take a snapshot of the state "
                     "at every block boundary, as prefix sharing and fork "
                     "would)"}


class KimiLinearFamily(_RoutedFamily):
    """models/kimi_linear.py: Kimi Delta Attention and latent attention
    layers, SwiGLU and routed experts (all of them, or the share a chip of
    an expert-parallel deployment holds) beside a shared one, on the
    latent-and-state cache.  Beyond what :meth:`unsupported` refuses, the
    cache kind runs without a prefix cache and without fork whatever was
    asked for (a shared block would skip the tokens that build the matrix
    state), and a sampled request fails alone (``greedy_only``)."""

    name = "kimi_linear"
    cache_kind = "latent_state"
    refusals = {"tp": "the state arena, the latent pool and the held "
                      "experts have no sharded layout and no exchange",
                **_STATE_REFUSALS}

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


class Qwen3NextFamily(_RoutedFamily):
    """models/qwen3_next.py: gated DeltaNet (one decay a head) and gated
    full-attention layers (rotary on a part of the head), a softmax router
    over routed experts (all of them, or the share a chip of an
    expert-parallel deployment holds) beside a sigmoid-gated shared one, on
    the K/V-and-state cache.  What the cache kind rules out is
    :class:`KimiLinearFamily`'s."""

    name = "qwen3_next"
    cache_kind = "kv_state"
    refusals = {"tp": "the state arena, the K/V pool of two heads and the "
                      "held experts have no sharded layout and no exchange",
                **_STATE_REFUSALS}

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


class MimoV2FlashFamily(AfmoeFamily):
    """models/mimo_v2_flash.py: sliding-window layers (a learned sink a
    query head, K/V heads of their own number) and full attention layers,
    keys wider than values, SwiGLU and routed experts (all of them, or the
    share a chip of an expert-parallel deployment holds) with no shared
    one, on the windowed cache, whose two pool pairs take their geometry
    from here.  The refusals and what the cache kind rules out are
    :class:`AfmoeFamily`'s."""

    name = "mimo_v2_flash"

    @staticmethod
    def cache_kwargs(cfg, max_batch_size: int, round_tokens: int) -> dict:
        return {**AfmoeFamily.cache_kwargs(cfg, max_batch_size, round_tokens),
                "v_head_dim": cfg.v_head_dim,
                "window_heads": cfg.window_kv_heads}


class Phi4MiniFlashFamily(_RoutedFamily):
    """models/phi4_mini_flash.py: Mamba selective-scan layers and
    sliding-window differential attention, one full layer whose K/V the
    cross-decoder's attention reads, gated memory units, SwiGLU and no
    expert layer, on the window-and-state cache: the full layer's K/V in
    the paged pool (one layer), the window layers' in the window pool, the
    Mamba layers' carried conv inputs and f32 state in a slot a sequence.
    Its step programs run the cross-decoder and the head over the rows'
    emitting tokens alone, and count on the device the rows each decoder
    ran (``COUNTERS`` of its module, folded by the cache).  Beyond what
    :meth:`unsupported` refuses, the cache kind runs without a prefix cache
    and without fork (a shared block may be freed behind a window, and
    would skip the tokens that build the scan state), and a sampled request
    fails alone (``greedy_only``)."""

    name = "phi4_mini_flash"
    cache_kind = "window_state"
    refusals = {"tp": "the window pool, the scan arena and the differential "
                      "heads have no sharded layout",
                "quantize": "no quantized plan of its weights",
                "speculative": "a rejected draft would have to roll the scan "
                               "state back",
                "session_store": "a resumed block skips the tokens that "
                                 "build the scan state, and a suspended "
                                 "sequence has lost its window's keys"}

    @classmethod
    def cache_kwargs(cls, cfg, max_batch_size: int,
                     round_tokens: int) -> dict:
        return {"n_layers": len(cfg.full_layers), "n_heads": cfg.n_kv_heads,
                "head_dim": cfg.head_dim, "window": cfg.sliding_window,
                "window_layers": len(cfg.window_layers),
                "round_tokens": round_tokens, "max_seqs": max_batch_size,
                "conv_layers": len(cfg.mamba_layers),
                "conv_width": cfg.d_inner, "conv_taps": cfg.d_conv - 1,
                "conv_slots": max_batch_size, "state_heads": 1,
                "state_dk": cfg.d_state, "state_dv": cfg.d_inner,
                "counters": cls._model().COUNTERS}

    @staticmethod
    def scan_items(cfg) -> tuple:
        """As :meth:`KimiLinearFamily.scan_items`: the selective scan's
        items (ops/ssm.py)."""
        from ..ops.ssm import CHUNK

        return CHUNK, cfg.d_inner

    @staticmethod
    def attn_v_heads(cfg) -> int:
        """The value heads every key head's pair shares (the paged
        kernels' ``v_heads``), for the engine's layout of a call."""
        return cfg.v_heads


_FAMILIES = {f.name: f for f in (DecoderFamily, Lfm2Family, AfmoeFamily,
                                 KimiLinearFamily, Qwen3NextFamily,
                                 MimoV2FlashFamily, Phi4MiniFlashFamily)}


def step_family(cfg):
    """The family of a configuration: ``cfg.family``, or the decoder's."""
    name = getattr(cfg, "family", "decoder")
    if name not in _FAMILIES:
        raise ValueError(f"unknown block family {name!r}; known: "
                         f"{sorted(_FAMILIES)}")
    return _FAMILIES[name]
