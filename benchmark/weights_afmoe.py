"""Seeded ``afmoe`` weights, made on the device a layer at a time.

The parameter pytree of ``pathway_tpu.models.afmoe`` (no biases), in the
configuration's dtype: each leaf is drawn in f32 and rounded once inside
the jitted call that makes its layer, the experts' matrices sixteen experts
at a time (a whole ``(128, 2048, 1024)`` leaf in f32 is 3.2 GB, and three
of them do not fit beside the 13.5 GB the cell holds), so the f32 form of
the model never exists; program and reference get the same rounded arrays.

Scales (``assumed`` in the configuration file): matrices N(0, 1/fan_in),
the router's too; embeddings N(0, 0.02^2) (times ``sqrt(d_model)`` in the
model: a stream of 0.9); norm scales 1 +- 0.1; expert bias N(0, 0.02^2)
kept f32; the head N(0, 1/d_model), untied.  One departure, so that
``correct`` gates: every branch of this family leaves through an RMS norm,
so a branch's size is its post-norm's scale whatever its matrices hold, and
the two post-norms of every layer after the first are scaled by
:func:`out_scale` = ``1 / sqrt(2 (L - 1))``: the first layer's branches
build the stream and the other ``2 (L - 1)`` together add as much variance
as one of them, so that a rounding error does not grow with the depth (the
lesson of ``weights_lfm2``; PERF.md, PR 27).

The q and k norm scales stay at 1 +- 0.1 like every other norm (scores of
unit deviation).  Sharper attention was tried and taken out (PERF.md, PR
31): at q/k scales of 1.5 a few dozen keys carry a query's attention, and
with a router that picks 8 of 128 experts by margins of ~0.007 the bf16
program's rounding then moves three times as many choices of the eighth
expert as at scale 1; sound runs read three times higher in every
comparison of ``correct`` while the planted faults read the same, so the
weakest faults could not be told from rounding.
"""

from __future__ import annotations

import functools

EXPERT_CHUNK = 16  # experts drawn at a time


def seed_key(seed: int):
    """A key of the ``rbg`` generator from any whole number (seeds run past
    2**31): the chip's own bit generator draws the 6.8 billion weights in
    a few seconds where the default counter-based one takes most of a
    minute; the same seed gives the same weights on the same device."""
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def out_scale(layer: int, n_layers: int) -> float:
    """The factor on a layer's two post-norm scales: 1 in the first layer,
    ``1 / sqrt(2 (L - 1))`` after it."""
    return 1.0 if layer == 0 else 1.0 / (2.0 * (n_layers - 1)) ** 0.5


def afmoe_params(shape: dict, seed: int, dtype, rounding: str | None = None):
    """``shape``: vocab_size, d_model, n_heads, n_kv_heads, head_dim, d_ff,
    d_ff_expert, n_experts, n_dense_layers, layer_types.  ``rounding``
    ``"int8"``: the same draws with every matrix of the attention, the
    feed-forwards and the experts (not the router, the embedding, the head
    or the norms) rounded to 8 bits a weight, symmetric, one scale an
    output channel, before it is rounded to ``dtype``: what an int8 plan of
    the weights would compute with (``correct``'s low-precision control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    D, V, hd = shape["d_model"], shape["vocab_size"], shape["head_dim"]
    Q, KV = shape["n_heads"] * hd, shape["n_kv_heads"] * hd
    E, F, FE = shape["n_experts"], shape["d_ff"], shape["d_ff_expert"]
    L = len(shape["layer_types"])

    def draw(ks, dims, scale, keep_f32=False):
        x = jax.random.normal(next(ks), dims, jnp.float32) * scale
        return x if keep_f32 else x.astype(dtype)

    def mat(key, *dims):
        x = jax.random.normal(key, dims, jnp.float32) / np.sqrt(dims[-2])
        if rounding == "int8":
            step = jnp.max(jnp.abs(x), axis=-2, keepdims=True) / 127.0
            x = jnp.round(x / step) * step
        return x.astype(dtype)

    def experts(key, rows, cols):
        """(E, rows, cols), EXPERT_CHUNK experts at a time."""
        chunk = min(EXPERT_CHUNK, E)
        if E % chunk:
            chunk = E
        keys = jax.random.split(key, E // chunk)
        out = jax.lax.map(lambda k: mat(k, chunk, rows, cols), keys)
        return out.reshape(E, rows, cols)

    def norm(ks, width, scale=1.0):
        return ((1.0 + jax.random.normal(next(ks), (width,), jnp.float32)
                 * 0.1) * scale).astype(dtype)

    @functools.partial(jax.jit, static_argnames=("dense", "out"))
    def layer(key, *, dense: bool, out: float):
        ks = iter(jax.random.split(key, 24))
        lay = {"norm_in": norm(ks, D), "norm_post_attn": norm(ks, D, out),
               "norm_pre_mlp": norm(ks, D), "norm_post_mlp": norm(ks, D, out),
               "wq": mat(next(ks), D, Q), "wk": mat(next(ks), D, KV),
               "wv": mat(next(ks), D, KV), "wgate": mat(next(ks), D, Q),
               "wo": mat(next(ks), Q, D),
               "q_norm": norm(ks, hd), "k_norm": norm(ks, hd)}
        if dense:
            lay.update(w1=mat(next(ks), D, F), w3=mat(next(ks), D, F),
                       w2=mat(next(ks), F, D))
        else:
            lay.update(
                wg=draw(ks, (D, E), 1.0 / np.sqrt(D)),
                expert_bias=draw(ks, (E,), 0.02, keep_f32=True),
                w1=experts(next(ks), D, FE), w3=experts(next(ks), D, FE),
                w2=experts(next(ks), FE, D),
                shared={"w1": mat(next(ks), D, FE), "w3": mat(next(ks), D, FE),
                        "w2": mat(next(ks), FE, D)})
        return lay

    @jax.jit
    def ends(key):
        ks = iter(jax.random.split(key, 3))
        return {"embed": draw(ks, (V, D), 0.02), "norm_out": norm(ks, D),
                "head": draw(ks, (D, V), 1.0 / np.sqrt(D))}

    keys = jax.random.split(seed_key(seed), L + 1)
    params = ends(keys[0])
    params["layers"] = [
        layer(k, dense=i < shape["n_dense_layers"], out=out_scale(i, L))
        for i, k in enumerate(keys[1:])]
    return params
