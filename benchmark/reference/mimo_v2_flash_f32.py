"""Plain reference: the ``mimo_v2_flash`` forward pass in float32, no cache,
no kernels, no batching (XiaomiMiMo/MiMo-V2-Flash's ``config.json`` keys).
Imports nothing of ``pathway_tpu``.

``x0 = embed[token]``; a layer is ``x += attention(RMS(x; norm_in)) Wo`` then
``x += ffn(RMS(x; norm_pre_mlp))``, RMSNorm (eps under the root), no bias;
after the last layer ``norm_out`` and the untied ``head``.

- attention: ``q = h Wq`` (n_heads x head_dim), ``k = h Wk`` (n_kv x
  head_dim), ``v = (h Wv) * value_scale`` (n_kv x v_head_dim), with ``n_kv =
  n_kv_heads`` on a ``full_attention`` layer and ``window_kv_heads`` on a
  ``sliding_attention`` one, query head ``h`` on K/V head ``h // (n_heads //
  n_kv)``; rotate-half rotary on the LEADING ``rotary_dim`` of the
  ``head_dim`` of q and k (the others pass), theta ``rope_theta`` on a full
  layer and ``window_rope_theta`` on a sliding one; ``s_ij = q_i . k_j /
  sqrt(head_dim)``; query ``i`` sees key ``j`` iff ``j <= i`` (full) or ``i -
  window < j <= i`` (sliding), as a mask over the full score matrix; on a
  sliding layer ``p_ij = exp(s_ij) / (sum_j' exp(s_ij') + exp(sink_h))``,
  ``sink_h`` one learned number a query head that carries no value; on a
  full layer ``p = softmax(s)``; the heads' ``sum_j p_ij v_j`` side by side
  (n_heads x v_head_dim) through ``Wo``.
- dense FFN (layers before ``n_dense_layers``): ``W2(silu(x W1) * x W3)``.
- expert FFN: ``s = sigmoid(x Wg)`` (f32, all ``n_experts``); chosen = the
  ``top_k`` largest of ``s + b``; ``w = s[chosen] / (sum + 1e-20)`` times
  ``route_scale``; ``sum_e w_e SwiGLU_e(x)``, no shared expert.  Of an
  expert-parallel deployment the weights hold the share ``first_expert ..
  first_expert + n_held_experts`` only: every held expert is applied to
  every token, only the chosen ones counted, and what the experts held
  elsewhere would add is left out (the weights are still normalised over
  all ``top_k`` chosen).

Departures from the published modeling code, each ``assumed`` in the
configuration file: the value scale multiplies v (the code scales the
attention output before ``Wo``: the same number); the window counts the
query's own position.  The weights are random from the seed
(benchmark/weights_mimo_v2_flash.py), rounded to bf16 once and handed in
that form to program and reference alike; here they are upcast a layer, an
expert and a slice of the vocabulary at a time, and attention runs in
blocks of query positions, so that 13 GB of bf16 weights and a context of
8,192 fit beside each other.

Besides the logits it returns, per position, the smallest margin between
the ``top_k``-th and the next router selection score over the expert
layers: where that margin is tiny the program's router may choose the
other expert, and the position's logits then differ by far more than
rounding.  On request (``probe``) also, for every expert layer and
position asked for, what its router saw and what it made of it: the normed
stream ``RMS(x; norm_pre_mlp)``, the held experts' combine weights (0 for
one not chosen) and that layer's own margin - for a comparison of the
router alone, on the reference's own stream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SLIDING = "sliding_attention"
F32 = jnp.float32
Q_BLOCK = 256      # query positions a block of attention
V_BLOCK = 32768    # vocabulary rows a slice of the head
PAD = 1024         # sequences are padded to a multiple (few programs)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope_leading(x, theta, rot: int):
    """x (T, H, hd) at positions 0..T-1: rotate-half on the first ``rot``
    of the head, the others pass."""
    T = x.shape[0]
    r, rest = x[..., :rot], x[..., rot:]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    turned = jnp.concatenate([-r[..., rot // 2:], r[..., : rot // 2]], -1)
    return jnp.concatenate([r * jnp.cos(ang) + turned * jnp.sin(ang), rest],
                           -1)


def _attention(x, lay, shape, sliding: bool):
    T = x.shape[0]
    H, hd, hv = shape["n_heads"], shape["head_dim"], shape["v_head_dim"]
    KV = shape["window_kv_heads"] if sliding else shape["n_kv_heads"]
    theta = shape["window_rope_theta"] if sliding else shape["rope_theta"]
    rep = H // KV
    q = _rope_leading((x @ lay["wq"]).reshape(T, H, hd), theta,
                      shape["rotary_dim"]).reshape(T, KV, rep, hd)
    k = _rope_leading((x @ lay["wk"]).reshape(T, KV, hd), theta,
                      shape["rotary_dim"])
    v = (x @ lay["wv"]).reshape(T, KV, hv) * F32(shape["value_scale"])
    keys = jnp.arange(T)[None, :]

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, min(Q_BLOCK, T), 0)
        pos = q0 + jnp.arange(qb.shape[0])[:, None]
        seen = keys <= pos
        if sliding:
            seen = seen & (keys > pos - shape["sliding_window"])
        s = jnp.einsum("qgrd,kgd->grqk", qb, k) / jnp.sqrt(F32(hd))
        s = jnp.where(seen[None, None], s, -jnp.inf)
        if sliding:  # one more logit a query head; its column is dropped
            sink = jnp.broadcast_to(
                lay["sinks"].reshape(KV, rep, 1, 1), s.shape[:3] + (1,))
            p = jax.nn.softmax(jnp.concatenate([s, sink], -1), -1)[..., :T]
        else:
            p = jax.nn.softmax(s, -1)
        return jnp.einsum("grqk,kgd->qgrd", p, v)

    a = jax.lax.map(block, jnp.arange(0, T, min(Q_BLOCK, T)))
    return a.reshape(T, H * hv) @ lay["wo"]


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _experts(x, lay, shape):
    """(output, margin between the top_k-th and the next selection score,
    the held experts' combine weights (T, held)).  ``lay``'s expert matrices
    come in the dtype they were made in and are upcast an expert at a
    time."""
    k, held = shape["top_k"], lay["w1"].shape[0]
    first = shape["first_expert"] if shape["n_held_experts"] is not None \
        else 0
    s = jax.nn.sigmoid(x @ lay["wg"].astype(F32))             # (T, E)
    top, idx = jax.lax.top_k(s + lay["expert_bias"].astype(F32), k + 1)
    idx = idx[..., :k]
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * shape["route_scale"]

    def one(out, ew):
        e, w1, w3, w2 = ew
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        return out + we * _swiglu(x, w1.astype(F32), w3.astype(F32),
                                  w2.astype(F32)), we[:, 0]

    out, held_w = jax.lax.scan(
        one, jnp.zeros_like(x),
        (first + jnp.arange(held), lay["w1"], lay["w3"], lay["w2"]))
    return out, top[..., k - 1] - top[..., k], held_w.T


_EXPERT_LEAVES = ("w1", "w3", "w2", "wg", "expert_bias")


@functools.partial(jax.jit, static_argnames=("sliding", "dense", "shape_key"))
def _layer(x, margin, cols, lay, *, sliding: bool, dense: bool,
           shape_key: tuple):
    """``(x, margin, probe)``: ``probe`` at the positions ``cols`` what an
    expert layer's router saw, the held experts' combine weights and the
    layer's margin; None on a dense layer."""
    shape = dict(shape_key)
    eps = shape["norm_eps"]
    experts = {n: lay[n] for n in _EXPERT_LEAVES if not dense and n in lay}
    lay = jax.tree_util.tree_map(
        lambda a: a.astype(F32),
        {n: m for n, m in lay.items() if n not in experts})
    x = x + _attention(_rms(x, lay["norm_in"], eps), lay, shape, sliding)
    h = _rms(x, lay["norm_pre_mlp"], eps)
    if dense:
        return x + _swiglu(h, lay["w1"], lay["w3"], lay["w2"]), margin, None
    y, m, held_w = _experts(h, experts, shape)
    return x + y, jnp.minimum(margin, m), (h[cols], held_w[cols], m[cols])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(rows, norm_out, head, *, eps: float):
    return _rms(rows, norm_out.astype(F32), eps) @ head.astype(F32)


def logits_at(params: dict, shape: dict, tokens, cols, probe: bool = False):
    """One sequence: ``tokens`` int32 [T] (causal and windowed masks: what
    follows a position changes nothing at it, so the sequence is padded on
    the right).  Returns float32 logits [len(cols), vocab] at the positions
    ``cols`` and, for the same positions, the smallest router margin over
    the expert layers; with ``probe`` also ``{layer: (stream, held weights,
    margin)}`` at ``cols`` for every expert layer (:func:`_layer`)."""
    key = tuple(sorted((k, v) for k, v in shape.items()
                       if not isinstance(v, (list, tuple))))
    with jax.default_matmul_precision("highest"):
        n = len(tokens)
        toks = np.zeros(n if n <= Q_BLOCK else -(-n // PAD) * PAD, np.int32)
        toks[:n] = np.asarray(tokens, np.int32)
        x = params["embed"][jnp.asarray(toks)].astype(F32)
        margin = jnp.full(toks.shape, jnp.inf, F32)
        c = jnp.asarray(cols)
        probes = {}
        for li, (kind, lay) in enumerate(zip(shape["layer_types"],
                                             params["layers"])):
            x, margin, seen = _layer(
                x, margin, c, lay, sliding=kind == SLIDING,
                dense=li < shape["n_dense_layers"], shape_key=key)
            if probe and seen is not None:
                probes[li] = tuple(np.asarray(a, np.float32) for a in seen)
        head = params["head"]
        logits = jnp.concatenate([
            _head(x[c], params["norm_out"], head[:, v0: v0 + V_BLOCK],
                  eps=shape["norm_eps"])
            for v0 in range(0, head.shape[1], V_BLOCK)], axis=-1)
        return (logits, margin[c], probes) if probe else (logits, margin[c])


def served_gaps(params: dict, shape: dict, requests: list):
    """For each ``(prompt, served)``: at every served position the gap by
    which the served token's reference logit lies below the reference's
    best, teacher-forced over prompt + served, one request at a time.  One
    list of gaps a request, and over all positions (in the requests' order)
    the reference's own margin (best minus second), the standard deviation
    of its logits, its smallest router margin, the position's context
    (tokens a full layer attends there) and ``router_probe``: ``{expert
    layer: (stream (N, D), held experts' combine weights (N, held), the
    layer's router margin (N,))}`` (:func:`logits_at`)."""
    gaps, margin, std, router, ctx, probes = [], [], [], [], [], {}
    for p, s in requests:
        cols = np.arange(len(p) - 1, len(p) + len(s) - 1)
        pad = -(-len(cols) // 64) * 64 - len(cols)  # few head programs
        logits, rm, seen = logits_at(
            params, shape, list(p) + list(s),
            np.concatenate([cols, np.zeros(pad, int)]), probe=True)
        logits = np.asarray(logits, np.float32)[: len(cols)]
        top2 = -np.partition(-logits, 1, axis=-1)[:, :2]
        gaps.append([float(g) for g in
                     top2[:, 0] - logits[np.arange(len(cols)), np.asarray(s)]])
        margin.append(top2[:, 0] - top2[:, 1])
        std.append(logits.std(-1))
        router.append(np.asarray(rm, np.float32)[: len(cols)])
        ctx.append(cols + 1)
        for li, arrays in seen.items():
            probes.setdefault(li, []).append(
                tuple(a[: len(cols)] for a in arrays))
    stats = {"margin": np.concatenate(margin), "std": np.concatenate(std),
             "router_margin": np.concatenate(router),
             "context": np.concatenate(ctx),
             "order": list(range(len(requests))),
             "router_probe": {li: tuple(np.concatenate(a) for a in zip(*got))
                              for li, got in probes.items()}}
    return gaps, stats
