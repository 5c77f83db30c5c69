"""Plain reference: the ``lfm2_moe`` forward pass in float32, no cache, no
kernels (LiquidAI/LFM2-8B-A1B's ``config.json`` keys).  Imports nothing of
``pathway_tpu``.

A layer: ``x += mixer(norm_op(x)); x += ffn(norm_ffn(x))``, RMSNorm, no
bias; after the last layer ``norm_out`` and the head.

- ``full_attention``: q (n_heads x hd), k, v (n_kv_heads x hd); q and k
  RMS-normalised per head (learned scale); rotary over the whole head,
  rotate-half; causal ``softmax(q k^T / sqrt(hd)) v``, query head ``i`` on
  K/V head ``i // (n_heads // n_kv_heads)``; ``Wo``.
- ``conv``: ``(B, C, X) = split3(x W_in)``, ``u = B * X``, ``c_t = w[:, 0]
  u_{t-2} + w[:, 1] u_{t-1} + w[:, 2] u_t`` (``u`` before the sequence is
  0), ``y = C * c``, ``W_out``.
- dense FFN (layers before ``n_dense_layers``): ``W2(silu(x W1) * x W3)``.
- expert FFN: ``s = sigmoid(x Wg)``; chosen = the ``top_k`` largest of ``s
  + b``; ``w = s[chosen] / (sum + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``sum_e w_e W2_e(silu(x W1_e) * x W3_e)``,
  every expert applied to the tokens this reference's own router gave it.

Departures from the published model, also under ``assumed`` in the
configuration file: the catalog's copy of the config has no tying key, so
the head is **tied** to the embedding as the LFM2 family publishes it; the
weights are random from the seed (benchmark/weights_lfm2.py), rounded to
bf16 once and handed in that form to program and reference alike.  Here
they are upcast one layer at a time, so that 9 GB of bf16 weights never
stand as 18 GB of f32.

Besides the logits it returns, per position, the smallest margin between
the ``top_k``-th and the next router selection score over the expert
layers: where that margin is tiny the program's router may choose the
other expert, and the position's logits then differ by far more than
rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ATTENTION = "full_attention"
F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, T, H, hd) at positions 0..T-1, rotate-half."""
    T, hd = x.shape[1], x.shape[3]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(x, lay, shape):
    B, T, _ = x.shape
    H, KV = shape["n_heads"], shape["n_kv_heads"]
    hd, eps = shape["d_model"] // H, shape["norm_eps"]
    q = _rope(_rms((x @ lay["wq"]).reshape(B, T, H, hd), lay["q_norm"], eps),
              shape["rope_theta"])
    k = _rope(_rms((x @ lay["wk"]).reshape(B, T, KV, hd), lay["k_norm"],
                   eps), shape["rope_theta"])
    v = (x @ lay["wv"]).reshape(B, T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return a.reshape(B, T, H * hd) @ lay["wo"]


def _conv(x, lay):
    gate_b, gate_c, xin = jnp.split(x @ lay["w_in"], 3, axis=-1)
    u = gate_b * xin
    u1 = jnp.pad(u, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    u2 = jnp.pad(u, ((0, 0), (2, 0), (0, 0)))[:, :-2]
    w = lay["conv_w"]
    c = w[:, 0] * u2 + w[:, 1] * u1 + w[:, 2] * u
    return (gate_c * c) @ lay["w_out"]


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _experts(x, lay, shape):
    """(output, margin between the top_k-th and the next selection score)."""
    k = shape["top_k"]
    s = jax.nn.sigmoid(x @ lay["wg"])                       # (B, T, E)
    sel = s + lay["expert_bias"] if "expert_bias" in lay else s
    top, idx = jax.lax.top_k(sel, k + 1)
    idx = idx[..., :k]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if shape["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    w = w * shape["routed_scaling_factor"]
    out = jnp.zeros_like(x)
    for e in range(lay["wg"].shape[1]):
        # the weight this expert has for each token (0 where not chosen):
        # the expert sees every token, only the chosen ones count
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        out = out + we * _swiglu(x, lay["w1"][e], lay["w3"][e], lay["w2"][e])
    return out, top[..., k - 1] - top[..., k]


@functools.partial(jax.jit, static_argnames=("kind", "dense", "shape_key"))
def _layer(x, margin, lay, *, kind: str, dense: bool, shape_key: tuple):
    shape = dict(shape_key)
    lay = jax.tree_util.tree_map(lambda a: a.astype(F32), lay)
    eps = shape["norm_eps"]
    h = _rms(x, lay["norm_op"], eps)
    x = x + (_attention(h, lay, shape) if kind == ATTENTION
             else _conv(h, lay))
    h = _rms(x, lay["norm_ffn"], eps)
    if dense:
        return x + _swiglu(h, lay["w1"], lay["w3"], lay["w2"]), margin
    y, m = _experts(h, lay, shape)
    return x + y, jnp.minimum(margin, m)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(rows, norm_out, embed, *, eps: float):
    return _rms(rows, norm_out.astype(F32), eps) @ embed.astype(F32).T


def logits_at(params: dict, shape: dict, tokens, rows, cols):
    """``tokens`` int32 [B, T] (right-padded; causal, so padding changes
    nothing before it).  Returns float32 logits [len(rows), vocab] at the
    positions ``(rows[i], cols[i])`` and, for the same positions, the
    smallest router margin over the expert layers."""
    key = tuple(sorted((k, v) for k, v in shape.items()
                       if not isinstance(v, (list, tuple))))
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = params["embed"][tokens].astype(F32)
        margin = jnp.full(tokens.shape, jnp.inf, F32)
        for li, (kind, lay) in enumerate(zip(shape["layer_types"],
                                             params["layers"])):
            x, margin = _layer(x, margin, lay, kind=kind,
                               dense=li < shape["n_dense_layers"],
                               shape_key=key)
        r, c = jnp.asarray(rows), jnp.asarray(cols)
        head = params.get("head")
        logits = _head(x[r, c], params["norm_out"],
                       params["embed"] if head is None else head.T,
                       eps=shape["norm_eps"])
        return logits, margin[r, c]


ROWS = 8                        # requests to a block
WIDTHS = (256, 512, 1024, 2048)  # padded lengths, so that few programs compile


def served_gaps(params: dict, shape: dict, requests: list):
    """For each ``(prompt, served)``: at every served position the gap by
    which the served token's reference logit lies below the reference's
    best, teacher-forced over prompt + served.  One list of gaps a request,
    and over all positions (in ``order``'s block order) the reference's own
    margin (best minus second), the standard deviation of its logits and
    its smallest router margin.  In blocks of ``ROWS`` requests, shortest
    first."""
    order = sorted(range(len(requests)),
                   key=lambda r: len(requests[r][0]) + len(requests[r][1]))
    gaps: dict = {}
    margin, std, router = [], [], []
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
        logits, rm = logits_at(params, shape, toks,
                               np.asarray(rows + [0] * pad),
                               np.asarray(cols + [0] * pad))
        logits = np.asarray(logits, np.float32)[:n]
        top2 = -np.partition(-logits, 1, axis=-1)[:, :2]
        gap = top2[:, 0] - logits[np.arange(n), np.asarray(want)]
        margin.append(top2[:, 0] - top2[:, 1])
        std.append(logits.std(-1))
        router.append(np.asarray(rm, np.float32)[:n])
        i = 0
        for r, (_p, s) in zip(order[b: b + ROWS], block):
            gaps[r] = [float(g) for g in gap[i: i + len(s)]]
            i += len(s)
    stats = {"margin": np.concatenate(margin), "std": np.concatenate(std),
             "router_margin": np.concatenate(router),
             "order": [r for r in order]}
    return [gaps[r] for r in range(len(requests))], stats
