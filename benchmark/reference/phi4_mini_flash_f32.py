"""Plain reference: the ``phi4_mini_flash`` forward pass in float32, no cache,
no kernels, no batching, and no skip (microsoft/Phi-4-mini-flash-reasoning's
``config.json`` keys, the SambaY layers of its modeling code).  Imports
nothing of ``pathway_tpu``.

``x0 = embed[token]``; a layer is ``h = x + mixer(LN(x; ln1))``, ``x' = h +
W2(silu(LN(h; ln2) W1) * LN(h; ln2) W3)``, LayerNorm with weight and bias;
after the last layer ``LN(x; ln_f)`` and the head tied to the embedding.
With ``L`` layers and ``half = L / 2``, layer ``i``'s mixer is:

- Mamba (``i < half + 2``, even): ``[u; z] = x W_in``; ``u' = silu(conv(u) +
  conv_b)``, causal depthwise over ``d_conv`` taps (zeros before the
  sequence); ``[delta; B; C] = u' W_x``; ``dt = softplus(delta W_dt +
  dt_bias)``; ``s_t = exp(dt_t * (-exp(A_log))) * s_{t-1} + (dt_t u'_t)
  B_t^T`` a token after another (``lax.scan``), ``y_t = C_t^T s_t + D u'_t``;
  the mixer is ``(y * silu(z)) W_out``, and at layer ``half`` ``y *
  silu(z)`` is the memory ``M``;
- differential attention (``i < half + 1`` odd: a window of
  ``sliding_window`` keys, the query's own included; ``i = half + 1``:
  every earlier key): q (H x hd), k (KV x hd), v (KV/2 x 2 hd), no
  positional encoding; ``q1`` = query heads ``0 .. H/2``, ``q2`` the rest,
  ``k1`` / ``k2`` the two halves of the key heads, pair ``p`` on key head
  ``p // (H / KV)`` of its half and value head ``p // (H / KV)``;
  ``softmax(q1 k1^T / sqrt(hd)) v - lam softmax(q2 k2^T / sqrt(hd)) v``,
  ``RMS(.; subln, subln_eps) * (1 - lam_init)``, through ``W_o``, with
  ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init`` and ``lam_init = 0.8
  - 0.6 exp(-0.3 i)``; masks over the full score matrix, in blocks of
  query positions;
- a gated memory unit (``i >= half + 2``, even): ``(silu(x W_gate) * M)
  W_mem``;
- cross-attention (``i >= half + 2``, odd): the same differential attention
  with the layer's own ``W_q`` / ``W_o`` over layer ``half + 1``'s k and v,
  every earlier key.

EVERY token runs all ``L`` layers here: the program's skip of the
cross-decoder for the tokens that emit nothing is what this holds it to.

The weights are random from the seed (benchmark/weights_phi4_mini_flash.py),
rounded to bf16 once and handed in that form to program and reference
alike; here they are upcast a layer and a slice of the vocabulary at a
time, so that 7.7 GB of bf16 weights and a context of 8,192 fit beside each
other (an upcast of a bf16 value is exact).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 256      # query positions a block of attention
V_BLOCK = 32768    # vocabulary rows a slice of the head
PAD = 2048         # sequences are padded to a multiple (few programs)


def layer_types(shape: dict) -> list:
    L = shape["n_layers"]
    half, out = L // 2, []
    for i in range(L):
        even = i % 2 == 0
        if i < half + 2:
            out.append("mamba" if even else
                       ("full" if i == half + 1 else "sliding"))
        else:
            out.append("gmu" if even else "cross")
    return out


def _ln(x, lay, prefix, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    c = x - mu
    return c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + eps) \
        * lay[prefix + "_w"] + lay[prefix + "_b"]


def _mamba(x, lay, shape, last):
    """(mixer output, y * silu(z), the state after the token at ``last``
    (N, E))."""
    T = x.shape[0]
    E = shape["expand"] * shape["d_model"]
    N, R, K = shape["d_state"], shape["dt_rank"], shape["d_conv"]
    uz = x @ lay["w_in"]
    u, z = uz[:, :E], uz[:, E:]
    up = jnp.concatenate([jnp.zeros((K - 1, E), F32), u], 0)
    y = lay["conv_b"] + sum(lay["conv_w"][:, k] * up[k:k + T]
                            for k in range(K))
    u1 = jax.nn.silu(y)
    xdbc = u1 @ lay["w_x"]
    dt = jax.nn.softplus(xdbc[:, :R] @ lay["w_dt"] + lay["dt_bias"])
    b, c = xdbc[:, R:R + N], xdbc[:, R + N:]
    a = -jnp.exp(lay["A_log"])                                 # (N, E)

    def step(s, xs):
        dt1, u1_, b1, c1 = xs
        s = jnp.exp(dt1[None] * a) * s + b1[:, None] * (dt1 * u1_)[None]
        return s, c1 @ s

    def kept(carry, xs):
        s, at = carry
        s, y = step(s, xs[1:])
        return (s, jnp.where(xs[0] == last, s, at)), y

    zero = jnp.zeros((N, E), F32)
    (_s, state), ys = jax.lax.scan(kept, (zero, zero),
                                   (jnp.arange(T), dt, u1, b, c))
    g = (ys + lay["D"] * u1) * jax.nn.silu(z)
    return g @ lay["w_out"], g, state


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * float(np.exp(-0.3 * layer))


def _differential(q, k, v, lay, shape, lam0, window):
    """q (T, H, hd), k (T, KV, hd), v (T, KV/2, 2 hd) -> (T, H * hd);
    ``lam0`` the layer's ``lam_init``."""
    T, H, hd = q.shape
    KV = k.shape[1]
    rep = H // KV
    lam = jnp.exp(lay["lq1"] @ lay["lk1"]) - jnp.exp(lay["lq2"] @ lay["lk2"]) \
        + lam0
    keys = jnp.arange(T)[None, :]

    def half(qh, kh):
        """softmax(qh kh^T / sqrt(hd)) v over the pairs: (T, H/2, 2 hd)."""
        qg = qh.reshape(T, KV // 2, rep, hd)

        def block(q0):
            qb = jax.lax.dynamic_slice_in_dim(qg, q0, min(Q_BLOCK, T), 0)
            pos = q0 + jnp.arange(qb.shape[0])[:, None]
            seen = keys <= pos
            if window is not None:
                seen = seen & (keys > pos - window)
            s = jnp.einsum("qgrd,kgd->grqk", qb, kh) / jnp.sqrt(F32(hd))
            p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
            return jnp.einsum("grqk,kgd->qgrd", p, v)

        o = jax.lax.map(block, jnp.arange(0, T, min(Q_BLOCK, T)))
        return o.reshape(T, H // 2, 2 * hd)

    o = half(q[:, :H // 2], k[:, :KV // 2]) \
        - lam * half(q[:, H // 2:], k[:, KV // 2:])
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + shape["subln_eps"]) * lay["subln"]
    return (o * (1.0 - lam0)).reshape(T, -1)


@functools.partial(jax.jit, static_argnames=("kind", "keeps_memory",
                                             "shape_key"))
def _layer(x, memory, kv, last, lam0, lay, *, kind: str, keeps_memory: bool,
           shape_key: tuple):
    """``(x, memory, kv, state)`` after one layer: ``memory`` the memory
    layer's ``y * silu(z)`` (``keeps_memory``), ``kv`` the full layer's (k,
    v), ``state`` a Mamba layer's scan state after the token at ``last``
    (None elsewhere); ``lam0`` a differential layer's ``lam_init``.  One
    program a layer kind and length, not a layer."""
    shape = dict(shape_key)
    lay = jax.tree_util.tree_map(lambda a: a.astype(F32), lay)
    T = x.shape[0]
    hd, eps = shape["head_dim"], shape["norm_eps"]
    h = _ln(x, lay, "ln1", eps)
    state = None
    if kind == "mamba":
        out, g, state = _mamba(h, lay, shape, last)
        if keeps_memory:
            memory = g
    elif kind == "gmu":
        out = (jax.nn.silu(h @ lay["w_gate"]) * memory) @ lay["w_mem"]
    else:
        q = (h @ lay["wq"]).reshape(T, -1, hd)
        if kind != "cross":
            k = (h @ lay["wk"]).reshape(T, -1, hd)
            v = (h @ lay["wv"]).reshape(T, k.shape[1] // 2, 2 * hd)
            if kind == "full":
                kv = (k, v)
        else:
            k, v = kv
        window = shape["sliding_window"] if kind == "sliding" else None
        out = _differential(q, k, v, lay, shape, lam0, window) @ lay["wo"]
    x = x + out
    h = _ln(x, lay, "ln2", eps)
    x = x + (jax.nn.silu(h @ lay["w1"]) * (h @ lay["w3"])) @ lay["w2"]
    return x, memory, kv, state


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(rows, ln, embed, *, eps: float):
    ln = jax.tree_util.tree_map(lambda a: a.astype(F32), ln)
    return _ln(rows, ln, "ln_f", eps) @ embed.astype(F32).T


def _stream(params: dict, shape: dict, tokens):
    """Every layer over one sequence: ``(x (T', D), the Mamba layers'
    states after its last token (layers, 1, N, E))``, the sequence padded
    on the right to ``T'`` (every mask and the scan are causal: what
    follows a position changes nothing at it)."""
    key = tuple(sorted((k, v) for k, v in shape.items()
                       if not isinstance(v, (list, tuple, dict))))
    n = len(tokens)
    toks = np.zeros(n if n <= Q_BLOCK else -(-n // PAD) * PAD, np.int32)
    toks[:n] = np.asarray(tokens, np.int32)
    x = params["embed"][jnp.asarray(toks)].astype(F32)
    E = shape["expand"] * shape["d_model"]
    memory = jnp.zeros((len(toks), E), F32)
    H, hd = shape["n_kv_heads"], shape["head_dim"]
    kv = (jnp.zeros((len(toks), H, hd), F32),
          jnp.zeros((len(toks), H // 2, 2 * hd), F32))
    states = []
    for li, kind in enumerate(layer_types(shape)):
        x, memory, kv, state = _layer(
            x, memory, kv, jnp.int32(n - 1), F32(lambda_init(li)),
            params["layers"][li], kind=kind,
            keeps_memory=li == shape["n_layers"] // 2, shape_key=key)
        if state is not None:
            states.append(state[None])
    return x, jnp.stack(states)


def final_states(params: dict, shape: dict, tokens):
    """The Mamba layers' f32 scan states after the last of ``tokens``, one
    sequence from an empty state: float32 [layers, 1, d_state, E], as the
    program's arena holds a slot's."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_stream(params, shape, tokens)[1], np.float32)


def logits_at(params: dict, shape: dict, tokens, cols):
    """One sequence: ``tokens`` int32 [T].  Returns float32 logits
    [len(cols), vocab] at the positions ``cols``."""
    with jax.default_matmul_precision("highest"):
        x, _states = _stream(params, shape, tokens)
        rows = x[jnp.asarray(cols)]
        ln = {k: params[k] for k in ("ln_f_w", "ln_f_b")}
        embed = params["embed"]
        return jnp.concatenate([
            _head(rows, ln, embed[v0: v0 + V_BLOCK], eps=shape["norm_eps"])
            for v0 in range(0, embed.shape[0], V_BLOCK)], axis=-1)


def served_gaps(params: dict, shape: dict, requests: list):
    """For each ``(prompt, served)``: at every served position the gap by
    which the served token's reference logit lies below the reference's
    best, teacher-forced over prompt + served, one request at a time.  One
    list of gaps a request, and over all positions (in the requests' order)
    the reference's own margin (best minus second), the standard deviation
    of its logits, ``router_margin`` (infinite: there is no router, so no
    position is a router near-tie) and the position's context."""
    gaps, margin, std, ctx = [], [], [], []
    for p, s in requests:
        cols = np.arange(len(p) - 1, len(p) + len(s) - 1)
        pad = -(-len(cols) // 64) * 64 - len(cols)  # few head programs
        logits = np.asarray(logits_at(
            params, shape, list(p) + list(s),
            np.concatenate([cols, np.zeros(pad, int)])), np.float32)
        logits = logits[: len(cols)]
        top2 = -np.partition(-logits, 1, axis=-1)[:, :2]
        gaps.append([float(g) for g in
                     top2[:, 0] - logits[np.arange(len(cols)), np.asarray(s)]])
        margin.append(top2[:, 0] - top2[:, 1])
        std.append(logits.std(-1))
        ctx.append(cols + 1)
    margin = np.concatenate(margin)
    return gaps, {"margin": margin, "std": np.concatenate(std),
                  "router_margin": np.full(margin.shape, np.inf, np.float32),
                  "context": np.concatenate(ctx),
                  "order": list(range(len(requests)))}
