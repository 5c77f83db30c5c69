"""Plain reference: GPT-2's forward pass in float32, no cache, no kernels.

Pre-LN blocks, learned positions, tanh GELU (``gelu_new``), tied head —
Radford et al. 2019 as published in openai-community/gpt2-large.  Imports
nothing of ``pathway_tpu``.  Run layer by layer so that it fits beside the
weights once the program's state is freed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


@functools.partial(jax.jit, static_argnames=("n_heads", "eps"))
def _block(x, lay, *, n_heads: int, eps: float):
    B, T, D = x.shape
    hd = D // n_heads
    h = _ln(x, lay["ln1_scale"], lay["ln1_bias"], eps)
    q = (h @ lay["wq"] + lay["bq"]).reshape(B, T, n_heads, hd)
    k = (h @ lay["wk"] + lay["bk"]).reshape(B, T, n_heads, hd)
    v = (h @ lay["wv"] + lay["bv"]).reshape(B, T, n_heads, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + a.reshape(B, T, D) @ lay["wo"] + lay["bo"]
    h = _ln(x, lay["ln2_scale"], lay["ln2_bias"], eps)
    ff = jax.nn.gelu(h @ lay["w_up"] + lay["b_up"], approximate=True)
    return x + ff @ lay["w_down"] + lay["b_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x_rows, params, *, eps: float):
    h = _ln(x_rows, params["ln_f_scale"], params["ln_f_bias"], eps)
    return h @ params["embed"].T


def logits_at(params: dict, shape: dict, tokens, rows, cols):
    """``tokens`` int32 [B, T] (right-padded; causal, so padding changes
    nothing before it).  Returns float32 logits [len(rows), vocab] at the
    positions ``(rows[i], cols[i])``."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = params["embed"][tokens] + params["pos_embed"][: tokens.shape[1]]
        for lay in params["layers"]:
            x = _block(x, lay, n_heads=shape["n_heads"], eps=shape["ln_eps"])
        picked = x[jnp.asarray(rows), jnp.asarray(cols)]
        return _head(picked, params, eps=shape["ln_eps"])


ROWS = 8                      # requests to a block: scores fit beside the weights
WIDTHS = (256, 512, 1024)     # padded lengths, so that few programs compile


def served_gaps(params: dict, shape: dict, requests: list):
    """For each ``(prompt, served)``: at every served position the gap by
    which the served token's reference logit lies below the reference's
    best, teacher-forced over prompt + served.  One list of gaps a request,
    and over all positions the reference's own margin (best minus second)
    and the standard deviation of its logits.  In blocks of ``ROWS``
    requests, shortest first."""
    order = sorted(range(len(requests)),
                   key=lambda r: len(requests[r][0]) + len(requests[r][1]))
    gaps: dict = {}
    margin, std = [], []
    for b in range(0, len(order), ROWS):
        block = [requests[r] for r in order[b: b + ROWS]]
        longest = max(len(p) + len(s) for p, s in block)
        T = min(next((w for w in WIDTHS if w >= longest), WIDTHS[-1]),
                shape["max_len"])
        toks = np.zeros((ROWS, T), np.int32)
        rows, cols, want = [], [], []
        for r, (p, s) in enumerate(block):
            seq = list(p) + list(s)
            toks[r, : len(seq)] = seq
            rows += [r] * len(s)
            cols += range(len(p) - 1, len(p) + len(s) - 1)
            want += list(s)
        n = len(want)
        pad = -(-n // 512) * 512 - n  # position 0 of row 0, dropped below
        logits = np.asarray(logits_at(
            params, shape, toks, np.asarray(rows + [0] * pad),
            np.asarray(cols + [0] * pad)), np.float32)[:n]
        top2 = -np.partition(-logits, 1, axis=-1)[:, :2]
        gap = top2[:, 0] - logits[np.arange(n), np.asarray(want)]
        margin.append(top2[:, 0] - top2[:, 1])
        std.append(logits.std(-1))
        i = 0
        for r, (_p, s) in zip(order[b: b + ROWS], block):
            gaps[r] = [float(g) for g in gap[i: i + len(s)]]
            i += len(s)
    stats = {"margin": np.concatenate(margin), "std": np.concatenate(std),
             "order": [r for r in order]}
    return [gaps[r] for r in range(len(requests))], stats
