"""Seeded ``mimo_v2_flash`` weights, made on the device a layer at a time.

The parameter pytree of ``pathway_tpu.models.mimo_v2_flash`` (no biases),
in the configuration's dtype: each leaf is drawn in f32 and rounded once
inside the jitted call that makes its layer, the experts' matrices sixteen
experts at a time, so the f32 form of the model never exists; program and
reference get the same rounded arrays.  Only the experts HELD are drawn
(``n_held_experts`` of them: the chip's share); the router is as wide as
published.

Scales (``assumed`` in the configuration file): matrices N(0, 1/fan_in),
the router's too; the router's bias N(0, 0.02^2) in f32; embeddings N(0,
0.02^2); norm scales 1 +- 0.1; the head N(0, 1/d_model), untied.

Departures, so that ``correct`` gates (PERF.md, PRs 27 and 33), applied
before the first chip run but for the one the first run threw out:

- the feed-forwards' ``w2`` (the dense one's and the held experts') is
  scaled by ``out_scale`` = ``1 / sqrt(2 (L - 1))`` in every layer after the
  first, so that the first layer's branches build the stream, the others
  together add as much variance as one of them, and a rounding error does
  not grow with the depth.  (The first chip run also scaled the held
  experts' ``w2`` by ``sqrt(router experts / held)`` = 4, so that the
  sixteenth of a token's experts that is here would add a whole layer's
  variance: sound runs then read 0.055 where the other long cells read
  0.004-0.02, because a router near-tie that the bf16 stream flips moved
  four times as much; without it 0.0083 on the same seed: PERF.md, PR 40);
- ``wq`` is drawn at :data:`Q_SHARP` = 2 times its fan-in scale: q . k /
  sqrt(192) then has deviation 2, a few dozen keys carry a query's
  attention as in a trained model, and the mix arrives at a tenth to a
  quarter of a unit; the attention's ``wo`` is NOT scaled by ``out_scale``
  (with it sound runs read 0.0031, and the attention branch, which every
  fault this configuration is new for lives in, is 4.5 times smaller);
- the sinks are N(``log(window) + 0.1``, 1) in f32: at scores of deviation 2
  the keys of a full window weigh ``window x e^2``, and a sink of that mean
  holds about 15% of a row's mass (5 to 30% at one deviation).  A sink of
  N(1, 1) would hold 0.5% of it at a window of 128, and its own planted
  faults would be invisible.
"""

from __future__ import annotations

import functools

from benchmark.weights_afmoe import seed_key
from benchmark.weights_kimi_linear import out_scale

EXPERT_CHUNK = 16  # experts drawn at a time
SLIDING = "sliding_attention"
Q_SHARP = 2.0      # the query scale over the plain 1: scores of deviation 2


def mimo_v2_flash_params(shape: dict, seed: int, dtype,
                         rounding: str | None = None):
    """``shape``: the fields of ``MimoV2FlashConfig``.  ``rounding``
    ``"int8"``: the same draws with every matrix of the attention, the dense
    feed-forward and the held experts (not the router, its bias, the sinks,
    the embedding, the head or the norms) rounded to 8 bits a weight,
    symmetric, one scale an output channel, before it is rounded to
    ``dtype``: what an int8 plan of the weights would compute with
    (``correct``'s low-precision control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    D, V, H = shape["d_model"], shape["vocab_size"], shape["n_heads"]
    hd, hv = shape["head_dim"], shape["v_head_dim"]
    E, FE, FF = shape["n_experts"], shape["d_ff_expert"], shape["d_ff"]
    held = E if shape["n_held_experts"] is None else shape["n_held_experts"]
    L = len(shape["layer_types"])
    sink_mean = float(np.log(shape["sliding_window"]) + 0.1)

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

    @functools.partial(jax.jit, static_argnames=("sliding", "dense", "out"))
    def layer(key, *, sliding: bool, dense: bool, out: float):
        ks = iter(jax.random.split(key, 16))
        KV = shape["window_kv_heads"] if sliding else shape["n_kv_heads"]
        lay = {"norm_in": draw(ks, (D,), 0.1, mean=1.0),
               "norm_pre_mlp": draw(ks, (D,), 0.1, mean=1.0),
               "wq": mat(next(ks), D, H * hd, scale=Q_SHARP),
               "wk": mat(next(ks), D, KV * hd),
               "wv": mat(next(ks), D, KV * hv),
               "wo": mat(next(ks), H * hv, D)}
        if sliding:
            lay["sinks"] = draw(ks, (H,), 1.0, mean=sink_mean, keep_f32=True)
        if dense:
            lay.update(w1=mat(next(ks), D, FF), w3=mat(next(ks), D, FF),
                       w2=mat(next(ks), FF, D, scale=out))
        else:
            lay.update(
                wg=draw(ks, (D, E), 1.0 / np.sqrt(D)),
                expert_bias=draw(ks, (E,), 0.02, keep_f32=True),
                w1=experts(next(ks), D, FE), w3=experts(next(ks), D, FE),
                w2=experts(next(ks), FE, D, scale=out))
        return lay

    @jax.jit
    def ends(key):
        ks = iter(jax.random.split(key, 3))
        return {"embed": draw(ks, (V, D), 0.02),
                "norm_out": draw(ks, (D,), 0.1, mean=1.0),
                "head": draw(ks, (D, V), 1.0 / np.sqrt(D))}

    keys = jax.random.split(seed_key(seed), L + 1)
    params = ends(keys[0])
    params["layers"] = [
        layer(k, sliding=shape["layer_types"][i] == SLIDING,
              dense=i < shape["n_dense_layers"], out=out_scale(i, L))
        for i, k in enumerate(keys[1:])]
    return params
