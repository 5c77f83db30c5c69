"""Plain reference: the ``kimi_linear`` forward pass in float32, no cache, no
kernels, no batching (moonshotai/Kimi-Linear-48B-A3B-Instruct's
``config.json`` keys; the mathematics of the Kimi Linear report,
arXiv:2510.26692, and DeepSeek-V3's forms of latent attention and of the
router).  Imports nothing of ``pathway_tpu``.

A layer is ``x += mixer(RMS(x; norm_in)); x += ffn(RMS(x; norm_ffn))``,
RMSNorm, no bias, no positional encoding anywhere; after the last layer
``norm_out`` and the untied ``head``.

- ``kda`` (Kimi Delta Attention; h the normed input, ``H`` heads of ``dk =
  dv``): ``[q~ ; k~ ; v~] = h W_qkv``; each of the three through its own
  depthwise causal convolution of ``conv_kernel`` taps (zero before the
  sequence), then SiLU; ``q = l2norm(q) dk^-0.5``, ``k = l2norm(k)``;
  ``alpha = exp(-exp(A_log[head]) softplus(W_fb (W_fa h) + dt_bias))`` a
  channel; ``beta = sigmoid(h W_b)`` a head; then TOKEN BY TOKEN
  (``lax.scan``), a state ``S`` (dk x dv) a head from zero:
  ``S <- diag(alpha_t) S; u_t = beta_t (v_t - S^T k_t); S <- S + k_t u_t^T;
  o_t = S^T q_t``; ``y = (RMS_dv(o) * sigmoid(W_gb (W_ga h))) W_o``.
- ``mla`` (latent attention, EXPANDED a head over the whole sequence):
  ``q = h W_q`` (heads of ``qk_nope + qk_rope``); ``[c~ ; k_r] = h W_kv_a``;
  ``c = RMS(c~)``; ``[k_nope ; v] = c W_kv_b`` a head; ``k = [k_nope ;
  k_r]``, ``k_r`` the same for every head, no rotary on either part;
  ``softmax(q k^T / sqrt(qk_nope + qk_rope))`` causal, ``(P v) W_o``.
- dense FFN (layers before ``n_dense_layers``): ``W2(silu(x W1) * x W3)``.
- expert FFN: ``s = sigmoid(x Wr)`` (``n_experts`` wide); chosen = the
  ``top_k`` largest of ``s + b``; ``w = s[chosen] / (sum + 1e-20)``
  (``route_norm``) times ``route_scale``; ``SwiGLU_shared(x) + sum_e w_e
  SwiGLU_e(x)`` over the experts HELD: the weights hold ``n_held_experts``
  experts from ``first_expert`` on (one share of an expert-parallel
  deployment), every held expert is applied to every token and only the
  chosen ones counted, and what the chosen experts held elsewhere would add
  is left out, as the program leaves it out.

Departures from the published description, and what it does not say (each
also under ``assumed`` in the configuration file): the query scale
``dk^-0.5`` after the l2 norm; the decay's form (``A_log`` a head,
``dt_bias`` a channel, softplus); no bias in any projection; the output
norm's scale shared by all heads; the conv without bias, SiLU after it;
``sqrt(qk_nope + qk_rope)`` in the latent scores; the router's 1e-20.

The weights are random from the seed (benchmark/weights_kimi_linear.py),
rounded to bf16 once and handed in that form to program and reference
alike; here they are upcast a layer, an expert and a slice of the
vocabulary at a time, and attention runs in blocks of query positions.

Besides the logits it returns, per position, the smallest margin between
the ``top_k``-th and the next router selection score over the expert
layers (of all ``n_experts``: a choice that moves between an expert held
here and one held elsewhere changes the result like any other).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

KDA = "kda"
F32 = jnp.float32
Q_BLOCK = 512      # query positions a block of attention
V_BLOCK = 32768    # vocabulary rows a slice of the head
PAD = 1024         # sequences are padded to a multiple (few programs)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _causal_conv(x, w):
    """x (T, W), w (W, taps): ``y_t = sum_j w[:, j] x_{t - (taps-1-j)}``,
    zero before the sequence."""
    T, taps = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(w[:, j] * padded[j: j + T] for j in range(taps))


def _kda(x, lay, shape):
    T, H, dk = x.shape[0], shape["n_heads"], shape["kda_head_dim"]
    y = jax.nn.silu(_causal_conv(x @ lay["wqkv"], lay["conv_w"]))
    q, k, v = (a.reshape(T, H, dk) for a in jnp.split(y, 3, axis=-1))
    q = _l2norm(q) * F32(dk ** -0.5)
    k = _l2norm(k)
    rate = jax.nn.softplus((x @ lay["w_fa"]) @ lay["w_fb"] + lay["dt_bias"])
    alpha = jnp.exp(-jnp.exp(lay["a_log"])[None, :, None]
                    * rate.reshape(T, H, dk))
    beta = jax.nn.sigmoid(x @ lay["wb"])                       # (T, H)

    def token(s, xs):
        q1, k1, v1, a1, b1 = xs
        s = s * a1[:, :, None]
        u = b1[:, None] * (v1 - jnp.einsum("hkv,hk->hv", s, k1))
        s = s + k1[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q1)

    # unrolled sixteen tokens an iteration of the loop: the same token by
    # token arithmetic, a sixteenth of the loop's turns
    _s, o = jax.lax.scan(token, jnp.zeros((H, dk, dk), F32),
                         (q, k, v, alpha, beta), unroll=16)
    gate = jax.nn.sigmoid((x @ lay["w_ga"]) @ lay["w_gb"])
    o = _rms(o, lay["o_norm"], shape["norm_eps"]).reshape(T, H * dk)
    return (o * gate) @ lay["wo"]


def _mla(x, lay, shape):
    T, H = x.shape[0], shape["n_heads"]
    nope, rope = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"]
    r, dv = shape["kv_lora_rank"], shape["v_head_dim"]
    q = (x @ lay["wq"]).reshape(T, H, nope + rope)
    kv = x @ lay["wkv_a"]
    c = _rms(kv[:, :r], lay["kv_norm"], shape["norm_eps"])
    k_r = kv[:, r:]
    kvb = (c @ lay["wkv_b"]).reshape(T, H, nope + dv)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_r[:, None, :], (T, H, rope))], -1)
    v = kvb[..., nope:]
    keys = jnp.arange(T)[None, :]

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, min(Q_BLOCK, T), 0)
        pos = q0 + jnp.arange(qb.shape[0])[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(F32(nope + rope))
        s = jnp.where((keys <= pos)[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    a = jax.lax.map(block, jnp.arange(0, T, min(Q_BLOCK, T)))
    return a.reshape(T, H * dv) @ lay["wo"]


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _experts(x, lay, shape):
    """(output, margin between the top_k-th and the next selection score).
    ``lay``'s expert matrices (the held ones) come in the dtype they were
    made in and are upcast an expert at a time."""
    k = shape["top_k"]
    held = lay["w1"].shape[0]
    first = shape["first_expert"] if shape["n_held_experts"] is not None \
        else 0
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
        (first + jnp.arange(held), lay["w1"], lay["w3"], lay["w2"]))
    return out, top[..., k - 1] - top[..., k]


@functools.partial(jax.jit, static_argnames=("kda", "dense", "shape_key"))
def _layer(x, margin, lay, *, kda: bool, dense: bool, shape_key: tuple):
    shape = dict(shape_key)
    eps = shape["norm_eps"]
    experts = {n: lay[n] for n in ("w1", "w3", "w2", "wg", "expert_bias",
                                   "shared") if not dense and n in lay}
    lay = jax.tree_util.tree_map(
        lambda a: a.astype(F32),
        {n: m for n, m in lay.items() if n not in experts})
    h = _rms(x, lay["norm_in"], eps)
    x = x + (_kda(h, lay, shape) if kda else _mla(h, lay, shape))
    h = _rms(x, lay["norm_ffn"], eps)
    if dense:
        y, m = _swiglu(h, lay["w1"], lay["w3"], lay["w2"]), margin
    else:
        y, m = _experts(h, experts, shape)
        m = jnp.minimum(margin, m)
    return x + y, m


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(rows, norm_out, head, *, eps: float):
    return _rms(rows, norm_out.astype(F32), eps) @ head.astype(F32)


def logits_at(params: dict, shape: dict, tokens, cols):
    """One sequence: ``tokens`` int32 [T] (everything is causal: what
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
        margin = jnp.full(toks.shape, jnp.inf, F32)
        for li, (kind, lay) in enumerate(zip(shape["layer_types"],
                                             params["layers"])):
            x, margin = _layer(x, margin, lay, kda=kind == KDA,
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
