"""Seeded ``qwen3_next`` weights, made on the device a layer at a time.

The parameter pytree of ``pathway_tpu.models.qwen3_next`` (no biases), in
the configuration's dtype: each leaf is drawn in f32 and rounded once inside
the jitted call that makes its layer, the experts' matrices sixteen experts
at a time, so the f32 form of the model never exists; program and reference
get the same rounded arrays.  Only the experts HELD are drawn
(``n_held_experts`` of them: the chip's share); the router is as wide as
published.

Scales (``assumed`` in the configuration file): matrices N(0, 1/fan_in),
the router's and the shared expert's gate too; embeddings N(0, 0.02^2); the
zero-centred norm scales N(0, 0.1^2) (they multiply as ``1 + w``: 1 +- 0.1)
and the plain one of the DeltaNet output norm 1 +- 0.1; conv taps N(0,
1/taps); the head N(0, 1/d_model), untied.  The decay: ``A_log = 0`` a value
head and ``dt_bias`` a value head the inverse softplus of a rate drawn so
that ``exp(-rate)`` is log-uniform in its distance from 1 between 0.955 and
0.9998, with the ``a`` half of ``W_ba`` at a fifth of its fan-in scale: the
projection then adds N(0, 0.2^2) inside the softplus, which moves a head's
rate by a factor of at most 2.2 at four deviations, so that every decay a
token lies between 0.9 and 0.9999 (both kept in f32;
``kimi_linear.decay_parameters``' rule, one number a head here).

Departures, so that ``correct`` gates (PERF.md, PRs 27 and 33): the matrices
through which a branch leaves (``wo`` of the DeltaNet layers, ``w2`` of the
experts and of the shared expert) are scaled by :func:`out_scale` = ``1 /
sqrt(2 (L - 1))`` in every layer after the first, so that the first layer's
branches build the stream, the others together add as much variance as one
of them, and a rounding error does not grow with the depth.  And PR 33's
lesson for the three full-attention layers, applied before the first chip
run: with q and k normalised a head and scales of 1 +- 0.1 the scores have
deviation 1 over 800 to 6,800 keys, the softmax is nearly flat, the mix is
the mean of thousands of random values and the branch adds nothing a
comparison can see.  ``q_norm`` is therefore drawn around :data:`Q_SHARP` -
1 (it multiplies as ``1 + w``: a query :data:`Q_SHARP` = 2 times as long,
scores of deviation 2, a few dozen keys carry a query's attention as in a
trained model, and the mix arrives at a tenth to a quarter of a unit before
its gate), and the full layers' ``wo`` is NOT scaled by :func:`out_scale`:
at its fan-in scale the gated attention branch is about as large as a
DeltaNet layer's scaled one, a part of the stream that ``correct`` holds.
"""

from __future__ import annotations

import functools

from benchmark.weights_afmoe import seed_key
from benchmark.weights_kimi_linear import out_scale

EXPERT_CHUNK = 16  # experts drawn at a time
GDN = "linear_attention"
# the full layers' query scale over the plain 1: scores of deviation 2
Q_SHARP = 2.0


def qwen3_next_params(shape: dict, seed: int, dtype,
                      rounding: str | None = None):
    """``shape``: the fields of ``Qwen3NextConfig``.  ``rounding``
    ``"int8"``: the same draws with every matrix of the mixers, the held
    experts and the shared expert (not the router, the shared expert's gate,
    the embedding, the head, the norms, the conv taps or the decay's
    parameters) rounded to 8 bits a weight, symmetric, one scale an output
    channel, before it is rounded to ``dtype``: what an int8 plan of the
    weights would compute with (``correct``'s low-precision control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    D, V = shape["d_model"], shape["vocab_size"]
    H, KV, hd = shape["n_heads"], shape["n_kv_heads"], shape["head_dim"]
    Hk, Hv = shape["gdn_key_heads"], shape["gdn_value_heads"]
    dk, dv, taps = shape["gdn_key_dim"], shape["gdn_value_dim"], \
        shape["conv_kernel"]
    conv_w, vw = 2 * Hk * dk + Hv * dv, Hv * dv
    E, FE, FS = shape["n_experts"], shape["d_ff_expert"], shape["d_ff_shared"]
    held = E if shape["n_held_experts"] is None else shape["n_held_experts"]
    L = len(shape["layer_types"])

    def draw(ks, dims, scale, mean=0.0, keep_f32=False):
        x = mean + jax.random.normal(next(ks), dims, jnp.float32) * scale
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

    def decay(ks):
        u = jax.random.uniform(next(ks), (Hv,), jnp.float32)
        lo1, hi1 = np.log(1.0 - 0.955), np.log(1.0 - 0.9998)
        rate = -jnp.log(1.0 - jnp.exp(lo1 + u * (hi1 - lo1)))
        return jnp.zeros((Hv,), jnp.float32), jnp.log(jnp.expm1(rate))

    @functools.partial(jax.jit, static_argnames=("gdn", "out"))
    def layer(key, *, gdn: bool, out: float):
        ks = iter(jax.random.split(key, 32))
        lay = {"norm_in": draw(ks, (D,), 0.1), "norm_ffn": draw(ks, (D,), 0.1)}
        if gdn:
            a_log, dt_bias = decay(ks)
            lay.update(
                wqkvz=mat(next(ks), D, conv_w + vw),
                wba=jnp.concatenate([mat(next(ks), D, Hv),
                                     mat(next(ks), D, Hv, scale=0.2)], 1),
                conv_w=draw(ks, (conv_w, taps), 1.0 / np.sqrt(taps)),
                a_log=a_log, dt_bias=dt_bias,
                o_norm=draw(ks, (dv,), 0.1, mean=1.0),
                wo=mat(next(ks), vw, D, scale=out))
        else:
            lay.update(
                wq=mat(next(ks), D, H * 2 * hd), wk=mat(next(ks), D, KV * hd),
                wv=mat(next(ks), D, KV * hd),
                q_norm=draw(ks, (hd,), 0.1, mean=Q_SHARP - 1.0),
                k_norm=draw(ks, (hd,), 0.1), wo=mat(next(ks), H * hd, D))
        lay.update(
            wg=draw(ks, (D, E), 1.0 / np.sqrt(D)),
            w_sg=draw(ks, (D, 1), 1.0 / np.sqrt(D)),
            w1=experts(next(ks), D, FE), w3=experts(next(ks), D, FE),
            w2=experts(next(ks), FE, D, scale=out),
            shared={"w1": mat(next(ks), D, FS), "w3": mat(next(ks), D, FS),
                    "w2": mat(next(ks), FS, D, scale=out)})
        return lay

    @jax.jit
    def ends(key):
        ks = iter(jax.random.split(key, 3))
        return {"embed": draw(ks, (V, D), 0.02),
                "norm_out": draw(ks, (D,), 0.1),
                "head": draw(ks, (D, V), 1.0 / np.sqrt(D))}

    keys = jax.random.split(seed_key(seed), L + 1)
    params = ends(keys[0])
    params["layers"] = [
        layer(k, gdn=shape["layer_types"][i] == GDN, out=out_scale(i, L))
        for i, k in enumerate(keys[1:])]
    return params
