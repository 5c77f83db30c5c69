"""Seeded ``lfm2_moe`` weights, made on the device a layer at a time.

The parameter pytree of ``pathway_tpu.models.lfm2`` (no biases), in the
configuration's dtype: each leaf is drawn in f32 and rounded once inside
the jitted call that makes its layer, so the f32 form of the model (18 GB
at the cell's size) never exists; program and reference get the same
rounded arrays.  Scales (``assumed`` in the configuration file): matrices
N(0, 1/fan_in), embeddings 0.02, norm scales 1 +- 0.1, expert bias
N(0, 0.02^2) kept f32, conv taps N(0, 1/3); the output projections of
every layer after the first scaled down (:func:`out_scale`), so that a
rounding error does not grow with the depth.

Why that scale.  Under a tied head the embedding has to stay a small part
of the stream (at a twentieth of its size a token's own logit already
stands 2 standard deviations out), so the stream is built by the first
layer's branches.  With every later branch as large again, the stream's
variance after ``n`` branches is ``n`` and a relative error grows by
``(n + g^2) / (n + 1)`` a branch, ``g`` the branch's own gain (2 for
SwiGLU, 3 for the gated conv, both products of projections): ~70-fold over
13 layers, so that a bf16 program's greedy token was the f32 reference's
best at 3 of 4 positions and ``correct`` could only be statistical
(PERF.md, PR 27).  With the later ``2 (L - 1)`` branches together as large
as one of the first two, the error grows about 2.5-fold, as it does in a
trained checkpoint, whose branches are small beside its stream.
"""

from __future__ import annotations

import functools

from benchmark.weights import seed_key

ATTENTION = "full_attention"


def out_scale(layer: int, n_layers: int) -> float:
    """The factor on a layer's output projections (conv ``W_out``,
    attention ``Wo``, the feed-forward's and every expert's ``W2``): 1 in
    the first layer, ``1 / sqrt(2 (L - 1))`` after it."""
    return 1.0 if layer == 0 else 1.0 / (2.0 * (n_layers - 1)) ** 0.5


def lfm2_params(shape: dict, seed: int, dtype, rounding: str | None = None):
    """``shape``: vocab_size, d_model, n_heads, n_kv_heads, d_ff,
    d_ff_expert, n_experts, n_dense_layers, layer_types.  ``rounding``
    ``"int8"``: the same draws with every matrix of the mixers, the
    feed-forwards and the experts (not the router, the embedding, norms or
    conv taps) rounded to 8 bits a weight, symmetric, one scale an output
    channel, before it is rounded to ``dtype``: what an int8 plan of the
    weights would compute with (``correct``'s low-precision control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    D, V = shape["d_model"], shape["vocab_size"]
    hd = D // shape["n_heads"]
    KV = shape["n_kv_heads"] * hd
    E, F, FE = shape["n_experts"], shape["d_ff"], shape["d_ff_expert"]

    def draw(ks, dims, scale, keep_f32=False):
        x = jax.random.normal(next(ks), dims, jnp.float32) * scale
        return x if keep_f32 else x.astype(dtype)

    def mat(ks, *dims, scale=1.0):
        x = jax.random.normal(next(ks), dims, jnp.float32) \
            * (scale / np.sqrt(dims[-2]))
        if rounding == "int8":
            step = jnp.max(jnp.abs(x), axis=-2, keepdims=True) / 127.0
            x = jnp.round(x / step) * step
        return x.astype(dtype)

    def norm(ks, width):
        return (1.0 + jax.random.normal(next(ks), (width,), jnp.float32)
                * 0.1).astype(dtype)

    @functools.partial(jax.jit, static_argnames=("kind", "dense", "out"))
    def layer(key, *, kind: str, dense: bool, out: float):
        ks = iter(jax.random.split(key, 16))
        lay = {"norm_op": norm(ks, D), "norm_ffn": norm(ks, D)}
        if kind == ATTENTION:
            lay.update(wq=mat(ks, D, D), wk=mat(ks, D, KV), wv=mat(ks, D, KV),
                       wo=mat(ks, D, D, scale=out), q_norm=norm(ks, hd),
                       k_norm=norm(ks, hd))
        else:
            lay.update(w_in=mat(ks, D, 3 * D),
                       conv_w=draw(ks, (D, 3), 1.0 / np.sqrt(3.0)),
                       w_out=mat(ks, D, D, scale=out))
        if dense:
            lay.update(w1=mat(ks, D, F), w3=mat(ks, D, F),
                       w2=mat(ks, F, D, scale=out))
        else:
            lay.update(wg=draw(ks, (D, E), 1.0 / np.sqrt(D)), w1=mat(ks, E, D, FE),
                       w3=mat(ks, E, D, FE),
                       w2=mat(ks, E, FE, D, scale=out),
                       expert_bias=draw(ks, (E,), 0.02, keep_f32=True))
        return lay

    @jax.jit
    def ends(key):
        ks = iter(jax.random.split(key, 2))
        return {"embed": draw(ks, (V, D), 0.02), "norm_out": norm(ks, D)}

    keys = jax.random.split(seed_key(seed), len(shape["layer_types"]) + 1)
    params = ends(keys[0])
    params["layers"] = [
        layer(k, kind=kind, dense=i < shape["n_dense_layers"],
              out=out_scale(i, len(shape["layer_types"])))
        for i, (k, kind) in enumerate(zip(keys[1:], shape["layer_types"]))]
    return params
