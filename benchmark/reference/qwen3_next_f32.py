"""Plain reference: the ``qwen3_next`` forward pass in float32, no cache, no
kernels, no batching (Qwen/Qwen3-Next-80B-A3B-Instruct's ``config.json``
keys; the mathematics of Gated Delta Networks, arXiv:2412.06464, and of the
gated attention and the router the model card describes).  Imports nothing
of ``pathway_tpu``.

A layer is ``x += mixer(N(x; norm_in)); x += moe(N(x; norm_ffn))`` with
``N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` (a zero-centred scale),
no bias; after the last layer ``N(x; norm_out)`` and the untied ``head``.

- ``full_attention`` (``H`` query heads over ``KV`` K/V heads of ``hd``,
  over the whole sequence): ``[q_h ; gate_h] = h W_q`` a head; ``k = h
  W_k``, ``v = h W_v``; ``q_h <- N(q_h; q_norm)``, ``k_j <- N(k_j;
  k_norm)``; rotate-half rotary on the first ``rotary_dim`` of a head (the
  rest passes); ``softmax(q k^T / sqrt(hd))`` causal, query head ``h`` on
  K/V head ``h // (H // KV)``; ``(P v * sigmoid(gate)) W_o``.
- ``linear_attention`` (gated DeltaNet; ``Hk`` key heads of ``dk``, ``Hv``
  value heads of ``dv``): ``[q ; k ; v ; z] = h W_qkvz``, ``[b ; a] = h
  W_ba``; ``[q ; k ; v]`` through one depthwise causal convolution of
  ``conv_kernel`` taps (zero before the sequence), then SiLU; ``q =
  l2norm(q) dk^-0.5``, ``k = l2norm(k)``; value head ``h`` takes key head
  ``h // (Hv // Hk)``; ``beta = sigmoid(b)``, ``alpha = exp(-exp(A_log[h])
  softplus(a + dt_bias[h]))``, ONE number a value head and token; then
  TOKEN BY TOKEN (``lax.scan``), a state ``S`` (dk x dv) a value head from
  zero: ``S <- alpha_t S; u_t = beta_t (v_t - S^T k_t); S <- S + k_t u_t^T;
  o_t = S^T q_t``; ``y = (N0(o; o_norm) * SiLU(z)) W_out``, ``N0`` a plain
  scale shared by all heads.
- experts (every layer): ``p = softmax(x W_g)`` over all ``n_experts``;
  chosen = the ``top_k`` largest; ``w = p[chosen] / sum of the chosen``;
  ``sigmoid(x w_sg) SwiGLU_shared(x) + sum_e w_e SwiGLU_e(x)`` over the
  experts HELD: the weights hold ``n_held_experts`` experts from
  ``first_expert`` on (one share of an expert-parallel deployment), every
  held expert is applied to every token and only the chosen ones counted,
  and what the chosen experts held elsewhere would add is left out, as the
  program leaves it out.

What the published keys do not say (each also under ``assumed`` in the
configuration file): the zero-centred scales and the plain one of the
DeltaNet output norm; the doubled ``W_q`` and its split a head; no bias;
``l2norm``'s eps 1e-6 and the query scale; rotate-half on the LEADING
``rotary_dim``; the f32 state; the router's f32 softmax; no multi-token
prediction head.

The weights are random from the seed (benchmark/weights_qwen3_next.py),
rounded to bf16 once and handed in that form to program and reference
alike; here they are upcast a layer, an expert and a slice of the
vocabulary at a time, and attention runs in blocks of query positions.

``final_states`` gives the state each gated-DeltaNet layer is left with
after a sequence's last token (what the program keeps in its arena).

Besides the logits it returns, per position, the smallest margin between
the ``top_k``-th and the next router LOGIT over the expert layers (of all
``n_experts``: a choice that moves between an expert held here and one held
elsewhere changes the result like any other).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GDN = "linear_attention"
F32 = jnp.float32
Q_BLOCK = 512      # query positions a block of attention
V_BLOCK = 32768    # vocabulary rows a slice of the head
PAD = 1024         # sequences are padded to a multiple (few programs)


def _norm(x, w, eps):
    """Zero-centred RMSNorm."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _plain_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _causal_conv(x, w):
    """x (T, W), w (W, taps): ``y_t = sum_j w[:, j] x_{t - (taps-1-j)}``,
    zero before the sequence."""
    T, taps = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(w[:, j] * padded[j: j + T] for j in range(taps))


def _rotary(x, theta, rot):
    """Rotate-half on the first ``rot`` values of a head; x (T, H, hd)."""
    T = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    r = x[..., :rot]
    turned = jnp.concatenate([-r[..., rot // 2:], r[..., : rot // 2]], -1)
    return jnp.concatenate([r * cos + turned * sin, x[..., rot:]], -1)


def _gdn(x, lay, shape):
    T = x.shape[0]
    Hk, Hv = shape["gdn_key_heads"], shape["gdn_value_heads"]
    dk, dv = shape["gdn_key_dim"], shape["gdn_value_dim"]
    kw, vw = Hk * dk, Hv * dv
    qkvz = x @ lay["wqkvz"]
    y = jax.nn.silu(_causal_conv(qkvz[:, :2 * kw + vw], lay["conv_w"]))
    z = qkvz[:, 2 * kw + vw:]
    q = _l2norm(y[:, :kw].reshape(T, Hk, dk)) * F32(dk ** -0.5)
    k = _l2norm(y[:, kw:2 * kw].reshape(T, Hk, dk))
    v = y[:, 2 * kw:].reshape(T, Hv, dv)
    own = jnp.arange(Hv) // (Hv // Hk)       # a value head's key head
    q, k = q[:, own], k[:, own]
    ba = x @ lay["wba"]
    beta = jax.nn.sigmoid(ba[:, :Hv])
    alpha = jnp.exp(-jnp.exp(lay["a_log"])[None, :]
                    * jax.nn.softplus(ba[:, Hv:] + lay["dt_bias"][None, :]))

    def token(s, xs):
        q1, k1, v1, a1, b1 = xs
        s = s * a1[:, None, None]
        u = b1[:, None] * (v1 - jnp.einsum("hkv,hk->hv", s, k1))
        s = s + k1[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q1)

    # unrolled sixteen tokens an iteration of the loop: the same token by
    # token arithmetic, a sixteenth of the loop's turns
    s, o = jax.lax.scan(token, jnp.zeros((Hv, dk, dv), F32),
                        (q, k, v, alpha, beta), unroll=16)
    o = _plain_norm(o, lay["o_norm"], shape["norm_eps"]).reshape(T, vw)
    return (o * jax.nn.silu(z)) @ lay["wo"], s


def _full(x, lay, shape):
    T, H, KV = x.shape[0], shape["n_heads"], shape["n_kv_heads"]
    hd, eps = shape["head_dim"], shape["norm_eps"]
    qg = (x @ lay["wq"]).reshape(T, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(T, H * hd)
    q = _norm(q, lay["q_norm"], eps)
    k = _norm((x @ lay["wk"]).reshape(T, KV, hd), lay["k_norm"], eps)
    v = (x @ lay["wv"]).reshape(T, KV, hd)
    q = _rotary(q, shape["rope_theta"], shape["rotary_dim"])
    k = _rotary(k, shape["rope_theta"], shape["rotary_dim"])
    own = jnp.arange(H) // (H // KV)         # a query head's K/V head
    k, v = k[:, own], v[:, own]
    keys = jnp.arange(T)[None, :]

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, min(Q_BLOCK, T), 0)
        pos = q0 + jnp.arange(qb.shape[0])[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(F32(hd))
        s = jnp.where((keys <= pos)[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    a = jax.lax.map(block, jnp.arange(0, T, min(Q_BLOCK, T)))
    return (a.reshape(T, H * hd) * jax.nn.sigmoid(gate)) @ lay["wo"]


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _experts(x, lay, shape):
    """(output, margin between the top_k-th and the next router logit).
    ``lay``'s expert matrices (the held ones) come in the dtype they were
    made in and are upcast an expert at a time."""
    k = shape["top_k"]
    held = lay["w1"].shape[0]
    first = shape["first_expert"] if shape["n_held_experts"] is not None \
        else 0
    logits = x @ lay["wg"].astype(F32)                        # (T, E)
    p = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(logits, k + 1)
    idx = idx[..., :k]
    w = jnp.take_along_axis(p, idx, axis=-1)
    w = w / jnp.sum(w, -1, keepdims=True)

    def one(out, ew):
        e, w1, w3, w2 = ew
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        return out + we * _swiglu(x, w1.astype(F32), w3.astype(F32),
                                  w2.astype(F32)), None

    shared = {n: m.astype(F32) for n, m in lay["shared"].items()}
    gate = jax.nn.sigmoid(x @ lay["w_sg"].astype(F32))        # (T, 1)
    out, _ = jax.lax.scan(
        one, gate * _swiglu(x, shared["w1"], shared["w3"], shared["w2"]),
        (first + jnp.arange(held), lay["w1"], lay["w3"], lay["w2"]))
    return out, top[..., k - 1] - top[..., k]


_EXPERT_LEAVES = ("w1", "w3", "w2", "wg", "w_sg", "shared")


@functools.partial(jax.jit, static_argnames=("gdn", "shape_key"))
def _layer(x, margin, lay, *, gdn: bool, shape_key: tuple):
    shape = dict(shape_key)
    eps = shape["norm_eps"]
    experts = {n: lay[n] for n in _EXPERT_LEAVES}
    lay = jax.tree_util.tree_map(
        lambda a: a.astype(F32),
        {n: m for n, m in lay.items() if n not in experts})
    h = _norm(x, lay["norm_in"], eps)
    mixed, state = _gdn(h, lay, shape) if gdn else (_full(h, lay, shape), None)
    x = x + mixed
    y, m = _experts(_norm(x, lay["norm_ffn"], eps), experts, shape)
    return x + y, jnp.minimum(margin, m), state


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(rows, norm_out, head, *, eps: float):
    return _norm(rows, norm_out.astype(F32), eps) @ head.astype(F32)


def _layers(params: dict, shape: dict, toks):
    """The layers over one sequence ``toks`` int32 [T]: the last layer's
    output, per position the smallest router margin over the expert
    layers, and the state each gated-DeltaNet layer is left with."""
    key = tuple(sorted((k, v) for k, v in shape.items()
                       if not isinstance(v, (list, tuple))))
    x = params["embed"][jnp.asarray(toks)].astype(F32)
    margin = jnp.full(toks.shape, jnp.inf, F32)
    states = []
    for kind, lay in zip(shape["layer_types"], params["layers"]):
        x, margin, state = _layer(x, margin, lay, gdn=kind == GDN,
                                  shape_key=key)
        states += [] if state is None else [state]
    return x, margin, states


def final_states(params: dict, shape: dict, tokens):
    """One sequence of at most ``Q_BLOCK`` tokens (none is padding: a
    state counts every token it was given): float32 [gated-DeltaNet layers,
    Hv, dk, dv], the state of each such layer after the last token."""
    assert len(tokens) <= Q_BLOCK, len(tokens)
    with jax.default_matmul_precision("highest"):
        return np.stack(_layers(params, shape,
                                np.asarray(tokens, np.int32))[2])


def logits_at(params: dict, shape: dict, tokens, cols):
    """One sequence: ``tokens`` int32 [T] (everything is causal: what
    follows a position changes nothing at it, so the sequence is padded on
    the right).  Returns float32 logits [len(cols), vocab] at the positions
    ``cols`` and, for the same positions, the smallest router margin over
    the expert layers."""
    with jax.default_matmul_precision("highest"):
        n = len(tokens)
        toks = np.zeros(n if n <= Q_BLOCK else -(-n // PAD) * PAD, np.int32)
        toks[:n] = np.asarray(tokens, np.int32)
        x, margin, _states = _layers(params, shape, toks)
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
    (tokens before and at it)."""
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
