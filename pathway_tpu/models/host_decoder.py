"""Int8 host decode tier for the causal decoder LM (models/decoder.py).

Single-token decoding is a pure weight-streaming problem: every token
reads all ~124M-class parameters once, so tokens/sec is bounded by bytes
per parameter, not FLOPs.  On the serving host the measured matvec
ladder is int8 ~2x f32 and bf16 SLOWER than f32 (no AMX tiling at
batch 1), so this tier stores all projection weights as per-channel
dynamically-quantized int8 Linears (fbgemm, AVX512-VNNI) and runs
attention/normalization in f32.  Weight-only quantization: activations
are quantized per-batch by fbgemm internally; logits parity vs the f32
JAX forward is cosine >0.99 (tests/test_host_decoder.py) — the
standard weight-int8 serving trade.

Reference context: the reference's generation path calls external HTTP
LLMs (xpacks/llm/llms.py); this framework serves its own decoder, so
the host tier is the CPU analogue of the fused TPU decode loop.
"""

from __future__ import annotations

import math

import numpy as np


def _q8_linear(torch, w: np.ndarray):
    """Per-channel int8 dynamic Linear from a (in, out) jax-layout matrix."""
    wt = torch.from_numpy(np.ascontiguousarray(w.T.astype(np.float32)))
    out_f, in_f = wt.shape
    lin = torch.ao.nn.quantized.dynamic.Linear(in_f, out_f)
    scales = wt.abs().amax(dim=1).clamp(min=1e-8) / 127.0
    qw = torch.quantize_per_channel(
        wt, scales, torch.zeros(out_f, dtype=torch.int64), 0, torch.qint8
    )
    lin.set_weight_bias(qw, None)
    return lin


class Int8DecoderHost:
    """Weight-int8 greedy decoding over a fixed-capacity f32 KV cache."""

    def __init__(self, cfg, params, cache_capacity: int | None = None):
        import torch

        self._torch = torch
        # NOTE: no torch.set_num_threads here — this tier is constructed
        # implicitly by auto routing and must not clobber the process-wide
        # thread pool other torch users configured
        self.cfg = cfg
        # kept (references only) so the paged serving tier can build the
        # JAX-side engine from the same weights (serving_executor(paged=True))
        self._jax_params = params
        self._paged_engine = None
        # clamp: positions beyond max_len have no positional embedding
        self.cap = min(int(cache_capacity or cfg.max_len), cfg.max_len)
        f32 = np.float32

        def t(a):
            # copy: jax-exported arrays are non-writable; torch wants owned
            return torch.from_numpy(np.array(a, dtype=f32, copy=True))

        self._emb = t(params["embed"])
        self._pos = t(params["pos_embed"])
        self._lnf = (t(params["ln_f_scale"]), t(params["ln_f_bias"]))
        self._layers = []
        for L in params["layers"]:
            wqkv = np.concatenate(
                [np.asarray(L["wq"]), np.asarray(L["wk"]),
                 np.asarray(L["wv"])], axis=1,
            )
            self._layers.append({
                "qkv": _q8_linear(torch, wqkv),
                "o": _q8_linear(torch, np.asarray(L["wo"])),
                "up": _q8_linear(torch, np.asarray(L["w_up"])),
                "down": _q8_linear(torch, np.asarray(L["w_down"])),
                "ln1": (t(L["ln1_scale"]), t(L["ln1_bias"])),
                "ln2": (t(L["ln2_scale"]), t(L["ln2_bias"])),
            })
        self._head = _q8_linear(torch, np.asarray(params["embed"]).T)
        H, D = cfg.n_heads, cfg.d_model
        self._hd = D // H
        self._K = torch.zeros(cfg.n_layers, H, self.cap, self._hd)
        self._V = torch.zeros(cfg.n_layers, H, self.cap, self._hd)
        self._scale = 1.0 / math.sqrt(self._hd)
        self.n_past = 0

    # -- shared blocks -----------------------------------------------------

    def _act(self, v):
        F = self._torch.nn.functional
        if self.cfg.act == "gelu":
            return F.gelu(v)
        if self.cfg.act == "relu":
            return self._torch.relu(v)
        return F.gelu(v, approximate="tanh")

    def _ln(self, x, sb):
        F = self._torch.nn.functional
        return F.layer_norm(x, (self.cfg.d_model,), sb[0], sb[1],
                            self.cfg.ln_eps)

    # -- prefill -----------------------------------------------------------

    def prefill(self, token_ids) -> np.ndarray:
        """Run the prompt through the int8 blocks, filling the KV cache;
        returns the next-token logits (f32 numpy)."""
        torch = self._torch
        ids = torch.as_tensor(np.asarray(token_ids, np.int64))
        T = len(ids)
        if T > self.cap:
            raise ValueError(f"prompt {T} exceeds cache capacity {self.cap}")
        H, hd = self.cfg.n_heads, self._hd
        with torch.no_grad():
            x = self._emb[ids] + self._pos[:T]
            causal = torch.tril(torch.ones(T, T, dtype=torch.bool))
            for li, w in enumerate(self._layers):
                h = self._ln(x, w["ln1"])
                qkv = w["qkv"](h)
                q, k, v = qkv.view(T, 3, H, hd).permute(1, 2, 0, 3)
                self._K[li, :, :T] = k
                self._V[li, :, :T] = v
                sc = (q @ k.transpose(-1, -2)) * self._scale
                sc = sc.masked_fill(~causal, float("-inf"))
                att = torch.softmax(sc, dim=-1)
                o = (att @ v).permute(1, 0, 2).reshape(T, self.cfg.d_model)
                x = x + w["o"](o)
                h = self._ln(x, w["ln2"])
                x = x + w["down"](self._act(w["up"](h)))
            x = self._ln(x[-1:], self._lnf)
            logits = self._head(x)[0]
        self.n_past = T
        return logits.numpy()

    # -- decode ------------------------------------------------------------

    def decode_step(self, token_id: int) -> np.ndarray:
        """Append one token against the cache; returns next-token logits."""
        torch = self._torch
        n = self.n_past
        if n >= self.cap:
            raise ValueError("KV cache full")
        H, hd = self.cfg.n_heads, self._hd
        with torch.no_grad():
            x = (self._emb[token_id] + self._pos[n]).unsqueeze(0)
            for li, w in enumerate(self._layers):
                h = self._ln(x, w["ln1"])
                qkv = w["qkv"](h)
                q, k, v = qkv.view(3, H, hd)
                self._K[li, :, n] = k
                self._V[li, :, n] = v
                keys = self._K[li, :, : n + 1]
                vals = self._V[li, :, : n + 1]
                att = torch.softmax(
                    (keys @ q.unsqueeze(-1)).squeeze(-1) * self._scale,
                    dim=-1,
                )
                o = (att.unsqueeze(1) @ vals).squeeze(1).reshape(
                    1, self.cfg.d_model
                )
                x = x + w["o"](o)
                h = self._ln(x, w["ln2"])
                x = x + w["down"](self._act(w["up"](h)))
            x = self._ln(x, self._lnf)
            logits = self._head(x)[0]
        self.n_past = n + 1
        return logits.numpy()

    def generate(self, prompt_ids, n_new: int) -> list[int]:
        """Greedy completion: prefill + n_new cached decode steps."""
        logits = self.prefill(prompt_ids)
        out = []
        tok = int(np.argmax(logits))
        for _ in range(n_new):
            out.append(tok)
            if len(out) == n_new:
                break
            tok = int(np.argmax(self.decode_step(tok)))
        return out

    # -- serving -----------------------------------------------------------

    def paged_engine(self, **kwargs):
        """The paged-KV batched decode engine (kvcache/engine.py) built
        from this host's weights, lazily constructed.  On the CPU backend
        a construction failure yields None (the serialized int8 tier
        serves); on a TPU backend it raises (kvcache.engine.build_engine)."""
        if self._paged_engine is not None:
            cached_kwargs = getattr(self, "_paged_engine_kwargs", None)
            if kwargs and self._paged_engine and kwargs != cached_kwargs:
                import logging

                logging.getLogger(__name__).warning(
                    "paged_engine(%r) ignored: engine already built with "
                    "%r — the shared instance is returned unchanged",
                    kwargs, cached_kwargs,
                )
        if self._paged_engine is None:
            self._paged_engine_kwargs = dict(kwargs)
            from ..kvcache.engine import build_engine

            kwargs.setdefault("name", "host_decoder_kv")
            # Round-13: when the engine's supervised restarts are
            # exhausted, stranded requests hand off to THIS host's serial
            # int8 tier (the degrade-to-host-tier path) — tokens the dead
            # engine already emitted are kept, the serial tier continues
            # the sequence over prompt + emitted
            kwargs.setdefault(
                "degrade_fn",
                lambda prompt, n_remaining, emitted: self.generate(
                    list(prompt) + list(emitted), n_remaining
                ),
            )
            engine = build_engine(
                self.cfg, self._jax_params,
                "serving falls back to serialized batch-1 decode",
                __name__, **kwargs,
            )
            if engine is None:
                self._paged_engine = False
                # the failure is sticky, so the f32 weights kept for the
                # engine have no further use — release the pin
                self._jax_params = None
            else:
                self._paged_engine = engine
        return self._paged_engine or None

    def serving_executor(self, *, paged: bool | None = None,
                         max_batch_size: int | None = None,
                         tp: int | None = None,
                         chain_steps: int | None = None,
                         quantize: str | None = None,
                         speculative=None, **kwargs):
        """Single shared executor for this decode tier (serve/scheduler.py).

        ``paged=True`` (default when the kvcache engine is constructible)
        routes generation through the paged KV-cache engine: the KV cache
        is a shared block pool rather than per-instance mutable state, so
        the executor runs TRUE multi-sequence continuous batching —
        ``max_batch_size`` > 1 per device step, with queued requests
        admitted into the in-flight decode batch at step boundaries
        (``RequestScheduler.poll_inflight``).  Round-8: admissions
        stream their prompts through the ragged fused step in chunks
        (no whole-bucket prefill stalling in-flight decodes; N
        same-round arrivals ride one dispatch) and sampling runs
        device-side.

        ``tp=`` (Round-9) shards the paged engine over the local device
        mesh — the KV block pool's head axis and every step program split
        tensor-parallel, so aggregate KV HBM (and therefore the number of
        live sequences) scales with the mesh.  Default (None): all local
        devices on a TPU backend (stepping down to the largest degree
        that divides n_kv_heads and vocab), 1 elsewhere; an explicit tp
        that cannot shard the model raises ValueError naming the
        offending dims and the legal values.

        ``chain_steps=`` (Round-10) bounds the device-resident decode
        chain: when the queue is quiet the engine runs up to this many
        greedy steps per dispatch (one [B, K] ids sync per chain, host
        bookkeeping overlapped with device execution), adapting back to
        1 the moment arrivals or preemption are pending.  Default 8;
        ``chain_steps=1`` restores the per-step round-9 hot loop.

        ``paged=False`` keeps the legacy serialized tier: the int8 host
        cache (`self._K/_V/n_past`) is per-instance mutable state, so
        concurrent `generate` callers would interleave prefill/decode
        steps and corrupt each other — the executor pins
        ``max_batch_size=1`` while still providing priority classes,
        deadline shedding, bounded queueing and backpressure metrics.

        Memory note: the paged tier decodes through the full-precision
        JAX weights plus a float KV block pool — throughput, not
        footprint.  Deployments that chose this class to shed the f32
        weights should pass ``paged=False``, which releases the retained
        f32 params (sticky: the paged tier is then unavailable on this
        instance).

        ``quantize="int8"`` (Round-17) runs the paged engine's device
        matmuls through int8 weights with per-output-channel scales and
        f32 accumulation (models/decoder.plan_decode_params) — roughly
        half the weight HBM traffic per decode step on TPU, with the
        serial int8 host tier unchanged as the degrade target.  Greedy
        and fixed-seed sampled tokens stay deterministic across engine
        restarts and fleet failover (the int8 plan is a pure function of
        the checkpoint).  Default (None): full-precision device weights.

        ``speculative=`` (Round-18) turns on speculative decoding in the
        paged engine: a cheap drafter proposes up to K tokens per row
        and ONE ragged verify dispatch checks them all, so decode stays
        multi-token even while arrivals are pending — with greedy output
        TOKEN-IDENTICAL to non-speculative decode.  ``"ngram"`` is the
        zero-HBM host-side drafter, ``"auto"`` reads the cost store's
        measured ``pw.spec_tier`` prior for this backend, and a
        ``Drafter``/``SpecController`` instance (kvcache/speculative.py,
        e.g. a small draft model) is used directly.  Default (None):
        off."""
        sched = getattr(self, "_serve_executor", None)
        if sched is not None and not sched._closed:
            if paged is not None or max_batch_size is not None \
                    or tp is not None or chain_steps is not None \
                    or quantize is not None or speculative is not None:
                import logging

                logging.getLogger(__name__).warning(
                    "serving_executor(paged=%r, max_batch_size=%r,"
                    " tp=%r, chain_steps=%r, quantize=%r, speculative=%r) "
                    "ignored: the shared executor already exists; shut it "
                    "down first to rebuild with different settings",
                    paged, max_batch_size, tp, chain_steps, quantize,
                    speculative,
                )
            return sched
        from ..serve.scheduler import RequestScheduler

        kwargs.setdefault("name", "host_decoder")
        kwargs.setdefault("max_queue", 64)
        linger = kwargs.pop("batch_linger_ms", None)
        engine = None
        if paged is False and self._paged_engine is None:
            # explicit opt-out frees the f32 weight pin for good
            self._paged_engine = False
            self._jax_params = None
        if paged or paged is None:
            engine_kwargs = {}
            if max_batch_size is not None:
                engine_kwargs["max_batch_size"] = max_batch_size
            if tp is not None:
                engine_kwargs["tp"] = tp
            if chain_steps is not None:
                engine_kwargs["chain_steps"] = chain_steps
            if quantize is not None:
                engine_kwargs["quantize"] = quantize
            if speculative is not None:
                engine_kwargs["speculative"] = speculative
            engine = self.paged_engine(**engine_kwargs)
            if engine is None and paged:
                raise RuntimeError("paged=True but the KV engine is "
                                   "unavailable (see log)")
        if engine is not None:
            if paged is None:
                import logging

                logging.getLogger(__name__).info(
                    "serving_executor: decode tier auto-selected the paged "
                    "KV engine (batched f32 decode; pass paged=False for "
                    "the serialized int8 tier)"
                )
            self._serve_executor = sched = RequestScheduler(
                lambda reqs: engine.serve_batch(
                    reqs, scheduler=self._serve_executor
                ),
                max_batch_size=max_batch_size or engine.max_batch_size,
                batch_linger_ms=2.0 if linger is None else linger, **kwargs,
            )
        else:
            # payloads may carry a third (priority) element for the paged
            # tier; the serialized tier just ignores it
            self._serve_executor = sched = RequestScheduler(
                lambda reqs: [self.generate(r[0], r[1]) for r in reqs],
                max_batch_size=1,
                batch_linger_ms=0.0 if linger is None else linger, **kwargs,
            )
        return sched

    def generate_scheduled(self, prompt_ids, n_new: int,
                           **submit_kwargs) -> list[int]:
        """Generation routed through the shared serving executor.

        NOTE: with the default paged tier this decodes through the
        full-precision JAX weights, so near-tie tokens can differ from
        the int8 :meth:`generate` output on the same instance; build the
        executor with ``paged=False`` for int8 output parity.  A
        ``priority=`` submit kwarg also rides in the payload so the paged
        engine's preemption policy sees the class even for requests that
        enter at batch formation (not just poll_inflight arrivals)."""
        payload = (list(prompt_ids), int(n_new))
        if submit_kwargs.get("priority") is not None:
            from ..serve.admission import Priority

            # submit() accepts Priority | str | int — parse, don't int()
            payload = payload + (
                int(Priority.parse(submit_kwargs["priority"])),
            )
        return self.serving_executor().submit(payload, **submit_kwargs)
