"""Plain reference: the ``afmoe`` forward pass in float32, no cache, no
kernels, no batching (arcee-ai/Trinity-Mini's ``config.json`` keys).
Imports nothing of ``pathway_tpu``.

``x0 = embed[token] * sqrt(d_model)`` (``mup_enabled``); a layer is
``x += RMS(attention(RMS(x; norm_in)); norm_post_attn)`` then
``x += RMS(ffn(RMS(x; norm_pre_mlp)); norm_post_mlp)``, RMSNorm, no bias;
after the last layer ``norm_out`` and the untied ``head``.

- attention: q and a gate g (n_heads x hd), k and v (n_kv_heads x hd); q
  and k RMS-normalised over hd (one scale vector for all heads); a
  ``sliding_attention`` layer rotates q and k (rotate-half, the whole head)
  and lets query ``i`` see key ``j`` iff ``i - window < j <= i``, as a mask
  over the full score matrix; a ``full_attention`` layer has no rotary and
  ``j <= i``; ``softmax(q k^T / sqrt(hd)) v``, query head ``h`` on K/V head
  ``h // (n_heads // n_kv_heads)``; the output times ``sigmoid(g)``;
  ``Wo``.
- dense FFN (layers before ``n_dense_layers``): ``W2(silu(x W1) * x W3)``.
- expert FFN: ``s = sigmoid(x Wr)``; chosen = the ``top_k`` largest of ``s
  + b``; ``w = s[chosen] / (sum + 1e-20)`` (``route_norm``) times
  ``route_scale``; ``SwiGLU_shared(x) + sum_e w_e SwiGLU_e(x)``, every
  expert applied to every token, only the chosen ones counted.

What the published keys do not say and the ``afmoe`` modeling code does
stands under ``assumed`` in the configuration file.  The weights are random
from the seed (benchmark/weights_afmoe.py), rounded to bf16 once and handed
in that form to program and reference alike; here they are upcast a layer,
an expert and a slice of the vocabulary at a time, and attention runs in
blocks of query positions, so that 13.5 GB of bf16 weights and a context of
8,192 fit beside each other.

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

SLIDING = "sliding_attention"
F32 = jnp.float32
Q_BLOCK = 512      # query positions a block of attention
V_BLOCK = 32768    # vocabulary rows a slice of the head
PAD = 1024         # sequences are padded to a multiple (few programs)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (T, H, hd) at positions 0..T-1, rotate-half."""
    T, hd = x.shape[0], x.shape[2]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(x, lay, shape, sliding: bool):
    T = x.shape[0]
    H, KV, hd = shape["n_heads"], shape["n_kv_heads"], shape["head_dim"]
    eps, rep = shape["norm_eps"], shape["n_heads"] // shape["n_kv_heads"]
    q = _rms((x @ lay["wq"]).reshape(T, H, hd), lay["q_norm"], eps)
    k = _rms((x @ lay["wk"]).reshape(T, KV, hd), lay["k_norm"], eps)
    v = (x @ lay["wv"]).reshape(T, KV, hd)
    gate = jax.nn.sigmoid(x @ lay["wgate"])
    if sliding:
        q, k = _rope(q, shape["rope_theta"]), _rope(k, shape["rope_theta"])
    q = q.reshape(T, KV, rep, hd)
    keys = jnp.arange(T)[None, :]

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, min(Q_BLOCK, T), 0)
        pos = q0 + jnp.arange(qb.shape[0])[:, None]
        seen = keys <= pos
        if sliding:
            seen = seen & (keys > pos - shape["sliding_window"])
        s = jnp.einsum("qgrd,kgd->grqk", qb, k) / jnp.sqrt(F32(hd))
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, -1), v)

    a = jax.lax.map(block, jnp.arange(0, T, min(Q_BLOCK, T)))
    return (a.reshape(T, H * hd) * gate) @ lay["wo"]


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _experts(x, lay, shape):
    """(output, margin between the top_k-th and the next selection score).
    ``lay``'s expert matrices come in the dtype they were made in and are
    upcast an expert at a time."""
    k = shape["top_k"]
    s = jax.nn.sigmoid(x @ lay["wg"].astype(F32))             # (T, E)
    top, idx = jax.lax.top_k(s + lay["expert_bias"].astype(F32), k + 1)
    idx = idx[..., :k]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if shape["route_norm"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * shape["route_scale"]

    def one(out, ew):
        e, w1, w3, w2 = ew
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        return out + we * _swiglu(x, w1.astype(F32), w3.astype(F32),
                                  w2.astype(F32)), None

    shared = {n: m.astype(F32) for n, m in lay["shared"].items()}
    out, _ = jax.lax.scan(
        one, _swiglu(x, shared["w1"], shared["w3"], shared["w2"]),
        (jnp.arange(lay["wg"].shape[1]), lay["w1"], lay["w3"], lay["w2"]))
    return out, top[..., k - 1] - top[..., k]


@functools.partial(jax.jit, static_argnames=("sliding", "dense", "shape_key"))
def _layer(x, margin, lay, *, sliding: bool, dense: bool, shape_key: tuple):
    shape = dict(shape_key)
    eps = shape["norm_eps"]
    experts = {n: lay[n] for n in ("w1", "w3", "w2", "wg", "expert_bias",
                                   "shared") if not dense and n in lay}
    lay = jax.tree_util.tree_map(
        lambda a: a.astype(F32),
        {n: m for n, m in lay.items() if n not in experts})
    x = x + _rms(_attention(_rms(x, lay["norm_in"], eps), lay, shape, sliding),
                 lay["norm_post_attn"], eps)
    h = _rms(x, lay["norm_pre_mlp"], eps)
    if dense:
        y, m = _swiglu(h, lay["w1"], lay["w3"], lay["w2"]), margin
    else:
        y, m = _experts(h, experts, shape)
        m = jnp.minimum(margin, m)
    return x + _rms(y, lay["norm_post_mlp"], eps), m


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(rows, norm_out, head, *, eps: float):
    return _rms(rows, norm_out.astype(F32), eps) @ head.astype(F32)


def logits_at(params: dict, shape: dict, tokens, cols):
    """One sequence: ``tokens`` int32 [T] (causal and windowed masks: what
    follows a position changes nothing at it, so the sequence is padded on
    the right).  Returns float32 logits [len(cols), vocab] at the positions
    ``cols`` and, for the same positions, the smallest router margin over
    the expert layers."""
    key = tuple(sorted((k, v) for k, v in shape.items()
                       if not isinstance(v, (list, tuple))))
    with jax.default_matmul_precision("highest"):
        n = len(tokens)
        toks = np.zeros(n if n <= Q_BLOCK else -(-n // PAD) * PAD, np.int32)
        toks[:n] = np.asarray(tokens, np.int32)
        x = params["embed"][jnp.asarray(toks)].astype(F32)
        if shape["mup_enabled"]:
            x = x * F32(np.sqrt(shape["d_model"]))
        margin = jnp.full(toks.shape, jnp.inf, F32)
        for li, (kind, lay) in enumerate(zip(shape["layer_types"],
                                             params["layers"])):
            x, margin = _layer(x, margin, lay, sliding=kind == SLIDING,
                               dense=li < shape["n_dense_layers"],
                               shape_key=key)
        c = jnp.asarray(cols)
        head = params["head"]
        logits = jnp.concatenate([
            _head(x[c], params["norm_out"], head[:, v0: v0 + V_BLOCK],
                  eps=shape["norm_eps"])
            for v0 in range(0, head.shape[1], V_BLOCK)], axis=-1)
        return logits, margin[c]


def served_gaps(params: dict, shape: dict, requests: list):
    """For each ``(prompt, served)``: at every served position the gap by
    which the served token's reference logit lies below the reference's
    best, teacher-forced over prompt + served, one request at a time.  One
    list of gaps a request, and over all positions (in the requests' order)
    the reference's own margin (best minus second), the standard deviation
    of its logits, its smallest router margin and the position's context
    (tokens it attends with no window)."""
    gaps, margin, std, router, ctx = [], [], [], [], []
    for p, s in requests:
        cols = np.arange(len(p) - 1, len(p) + len(s) - 1)
        pad = -(-len(cols) // 64) * 64 - len(cols)  # few head programs
        logits, rm = logits_at(params, shape, list(p) + list(s),
                               np.concatenate([cols, np.zeros(pad, int)]))
        logits = np.asarray(logits, np.float32)[: len(cols)]
        top2 = -np.partition(-logits, 1, axis=-1)[:, :2]
        gaps.append([float(g) for g in
                     top2[:, 0] - logits[np.arange(len(cols)), np.asarray(s)]])
        margin.append(top2[:, 0] - top2[:, 1])
        std.append(logits.std(-1))
        router.append(np.asarray(rm, np.float32)[: len(cols)])
        ctx.append(cols + 1)
    stats = {"margin": np.concatenate(margin), "std": np.concatenate(std),
             "router_margin": np.concatenate(router),
             "context": np.concatenate(ctx),
             "order": list(range(len(requests)))}
    return gaps, stats
