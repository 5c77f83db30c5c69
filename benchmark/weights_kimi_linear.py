"""Seeded ``kimi_linear`` weights, made on the device a layer at a time.

The parameter pytree of ``pathway_tpu.models.kimi_linear`` (no biases), in
the configuration's dtype: each leaf is drawn in f32 and rounded once inside
the jitted call that makes its layer, the experts' matrices sixteen experts
at a time, so the f32 form of the model never exists; program and reference
get the same rounded arrays.  Only the experts HELD are drawn
(``n_held_experts`` of them: the chip's share); the router is as wide as
published.

Scales (``assumed`` in the configuration file): matrices N(0, 1/fan_in),
the router's too; embeddings N(0, 0.02^2); norm scales 1 +- 0.1 (the latent
norm's and the KDA output norm's too); expert bias N(0, 0.02^2) kept f32;
conv taps N(0, 1/taps); the head N(0, 1/d_model), untied.  The decay:
``A_log = 0`` a head and ``dt_bias`` a channel the inverse softplus of a
rate drawn so that ``exp(-rate)`` is log-uniform in its distance from 1
between 0.955 and 0.9998, with ``W_fb`` (the second matrix of the decay's
low-rank projection) at a fifth of its fan-in scale: the projection then
adds N(0, 0.2^2) inside the softplus, which moves a channel's rate by a
factor of at most 2.2 at four deviations, so that every decay a token lies
between 0.9 and 0.9999 (both kept in f32).  One departure, so that
``correct`` gates (PERF.md, PR 27): the matrices through which a branch
leaves (``wo`` of the KDA layers, ``w2`` of the dense layer, of the experts
and of the shared expert) are scaled by :func:`out_scale` = ``1 / sqrt(2 (L - 1))`` in every
layer after the first, so that the first layer's branches build the stream,
the other ``2 (L - 1)`` together add as much variance as one of them, and a
rounding error does not grow with the depth.  A second, for the latent
layers (PERF.md, PR 33): with every matrix at its fan-in scale the latent
scores have deviation 1 over 800 to 6,800 keys, the softmax is nearly flat,
the mix is the mean of thousands of random values (a fiftieth of a unit a
lane) and the latent branch adds nothing a comparison can see: ``k_r`` left
out of the scores read as a sound run.  ``W_q`` of the latent layers is
therefore drawn at :data:`Q_SHARP` = 2 times its fan-in scale: scores of
deviation 2, a few dozen keys carry a query's attention (as in a trained
model) and the mix arrives at about a seventh of a unit; and the latent
layers' ``wo`` is NOT scaled by :func:`out_scale` (with it the branch would
be 3% of the stream, and ``k_r`` left out still read 0.034 beside sound runs
of 0.016-0.025): at its fan-in scale the latent branch is as large as a KDA
layer's scaled one, a part of the stream that ``correct`` holds.
"""

from __future__ import annotations

import functools

from benchmark.weights_afmoe import seed_key

EXPERT_CHUNK = 16  # experts drawn at a time
KDA = "kda"
# the latent layers' W_q over its fan-in scale: latent scores of deviation 2
Q_SHARP = 2.0


def out_scale(layer: int, n_layers: int) -> float:
    """The factor on a layer's output projections: 1 in the first layer,
    ``1 / sqrt(2 (L - 1))`` after it."""
    return 1.0 if layer == 0 else 1.0 / (2.0 * (n_layers - 1)) ** 0.5


def kimi_linear_params(shape: dict, seed: int, dtype,
                       rounding: str | None = None):
    """``shape``: the fields of ``KimiLinearConfig``.  ``rounding``
    ``"int8"``: the same draws with every matrix of the mixers, the
    feed-forwards and the held experts (not the router, the embedding, the
    head, the norms, the conv taps or the decay's parameters) rounded to 8
    bits a weight, symmetric, one scale an output channel, before it is
    rounded to ``dtype``: what an int8 plan of the weights would compute
    with (``correct``'s low-precision control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    D, V, H = shape["d_model"], shape["vocab_size"], shape["n_heads"]
    W, lo, taps = H * shape["kda_head_dim"], shape["kda_head_dim"], \
        shape["conv_kernel"]
    nope, rope = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"]
    r, dv = shape["kv_lora_rank"], shape["v_head_dim"]
    E, F, FE = shape["n_experts"], shape["d_ff"], shape["d_ff_expert"]
    held = E if shape["n_held_experts"] is None else shape["n_held_experts"]
    L = len(shape["layer_types"])

    def draw(ks, dims, scale, keep_f32=False):
        x = jax.random.normal(next(ks), dims, jnp.float32) * scale
        return x if keep_f32 else x.astype(dtype)

    def mat(key, *dims, scale=1.0):
        x = jax.random.normal(key, dims, jnp.float32) \
            * (scale / np.sqrt(dims[-2]))
        if rounding == "int8":
            step = jnp.max(jnp.abs(x), axis=-2, keepdims=True) / 127.0
            x = jnp.round(x / step) * step
        return x.astype(dtype)

    def experts(key, rows, cols, scale=1.0):
        """(held, rows, cols), EXPERT_CHUNK experts at a time."""
        chunk = min(EXPERT_CHUNK, held)
        if held % chunk:
            chunk = held
        keys = jax.random.split(key, held // chunk)
        out = jax.lax.map(lambda k: mat(k, chunk, rows, cols, scale=scale),
                          keys)
        return out.reshape(held, rows, cols)

    def norm(ks, width):
        return (1.0 + jax.random.normal(next(ks), (width,), jnp.float32)
                * 0.1).astype(dtype)

    def decay(ks):
        u = jax.random.uniform(next(ks), (W,), jnp.float32)
        lo1, hi1 = np.log(1.0 - 0.955), np.log(1.0 - 0.9998)
        rate = -jnp.log(1.0 - jnp.exp(lo1 + u * (hi1 - lo1)))
        return jnp.zeros((H,), jnp.float32), jnp.log(jnp.expm1(rate))

    @functools.partial(jax.jit, static_argnames=("kda", "dense", "out"))
    def layer(key, *, kda: bool, dense: bool, out: float):
        ks = iter(jax.random.split(key, 32))
        lay = {"norm_in": norm(ks, D), "norm_ffn": norm(ks, D)}
        if kda:
            a_log, dt_bias = decay(ks)
            lay.update(
                wqkv=mat(next(ks), D, 3 * W),
                conv_w=draw(ks, (3 * W, taps), 1.0 / np.sqrt(taps)),
                w_fa=mat(next(ks), D, lo),
                w_fb=mat(next(ks), lo, W, scale=0.2), a_log=a_log,
                dt_bias=dt_bias, wb=mat(next(ks), D, H),
                w_ga=mat(next(ks), D, lo), w_gb=mat(next(ks), lo, W),
                o_norm=norm(ks, lo), wo=mat(next(ks), W, D, scale=out))
        else:
            lay.update(
                wq=mat(next(ks), D, H * (nope + rope), scale=Q_SHARP),
                wkv_a=mat(next(ks), D, r + rope), kv_norm=norm(ks, r),
                wkv_b=mat(next(ks), r, H * (nope + dv)),
                wo=mat(next(ks), H * dv, D))
        if dense:
            lay.update(w1=mat(next(ks), D, F), w3=mat(next(ks), D, F),
                       w2=mat(next(ks), F, D, scale=out))
        else:
            lay.update(
                wg=draw(ks, (D, E), 1.0 / np.sqrt(D)),
                expert_bias=draw(ks, (E,), 0.02, keep_f32=True),
                w1=experts(next(ks), D, FE), w3=experts(next(ks), D, FE),
                w2=experts(next(ks), FE, D, scale=out),
                shared={"w1": mat(next(ks), D, FE), "w3": mat(next(ks), D, FE),
                        "w2": mat(next(ks), FE, D, scale=out)})
        return lay

    @jax.jit
    def ends(key):
        ks = iter(jax.random.split(key, 3))
        return {"embed": draw(ks, (V, D), 0.02), "norm_out": norm(ks, D),
                "head": draw(ks, (D, V), 1.0 / np.sqrt(D))}

    keys = jax.random.split(seed_key(seed), L + 1)
    params = ends(keys[0])
    params["layers"] = [
        layer(k, kda=shape["layer_types"][i] == KDA,
              dense=i < shape["n_dense_layers"], out=out_scale(i, L))
        for i, k in enumerate(keys[1:])]
    return params
