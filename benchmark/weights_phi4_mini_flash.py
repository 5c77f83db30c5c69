"""Seeded ``phi4_mini_flash`` weights, made on the device a layer at a time.

The parameter pytree of ``pathway_tpu.models.phi4_mini_flash`` in the
configuration's dtype: each leaf is drawn in f32 and rounded once inside the
jitted call that makes its layer, so the f32 form of the model never exists;
program and reference get the same rounded arrays.  The scan's own
parameters, the conv bias and the differential lambdas stay f32.

Scales (``assumed`` in the configuration file): matrices N(0, 1/fan_in);
embeddings N(0, 0.02^2), tied to the head; LayerNorm weights 1 +- 0.1 and
biases N(0, 0.02^2); the conv taps N(0, 1/4), their bias N(0, 0.02^2); the
lambdas N(0, 0.1^2) (the Diff Transformer's); subln 1 +- 0.1; the scan as
published (:func:`scan_init`, written here from the Mamba paper's
initialisation and not taken from the program).

Departures, made before the first chip run, so that ``correct`` gates (the
reasoning of ``weights_mimo_v2_flash``):

- the output projections (``w_out``, ``wo``, ``w_mem``, ``w2``) of every
  layer after the first are scaled by ``1 / sqrt(2 (L - 1))``: the first
  layer's branches build the stream, the others together add as much as
  one, and a rounding error does not grow with the depth;
- ``wq`` is drawn at :data:`Q_SHARP` = 2 times its fan-in scale: a score
  ``q . k / 8`` has deviation 2 and a few dozen keys carry a query's map,
  as in a trained model; at 1 both maps of a head would be near uniform
  and their difference near zero, and the window and the second map would
  be faults no reading could see.
"""

from __future__ import annotations

import functools

from benchmark.weights_afmoe import seed_key
from benchmark.weights_kimi_linear import out_scale

Q_SHARP = 2.0      # the query scale over the plain 1: scores of deviation 2


def scan_init(key, E: int, N: int) -> dict:
    """A Mamba layer's published scan initialisation over ``E`` channels of
    ``N`` states, f32: ``A_log = log(1 .. N)`` for every channel, laid out
    (N, E); ``D = 1``; ``dt_bias = dt + log(1 - exp(-dt))`` (the inverse of
    softplus) of a step ``dt`` drawn log-uniform in [1e-3, 1e-1] a
    channel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    u = jax.random.uniform(key, (E,), jnp.float32)
    dt = jnp.exp(np.log(1e-3) + u * (np.log(1e-1) - np.log(1e-3)))
    a_log = jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))
    return {"A_log": jnp.tile(a_log[:, None], (1, E)),
            "D": jnp.ones((E,), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}


def phi4_mini_flash_params(shape: dict, seed: int, dtype,
                           rounding: str | None = None):
    """``shape``: the fields of ``Phi4MiniFlashConfig`` and its
    ``layer_types``.  ``rounding`` ``"int8"``: the same draws with every
    matrix of the mixers and the feed-forwards (not the embedding, the
    norms, the conv or the scan's and the lambdas' vectors) rounded to 8
    bits a weight, symmetric, one scale an output channel, before it is
    rounded to ``dtype``: what an int8 plan of the weights would compute
    with (``correct``'s low-precision control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    D, V, F = shape["d_model"], shape["vocab_size"], shape["d_ff"]
    H, KV, hd = shape["n_heads"], shape["n_kv_heads"], shape["head_dim"]
    E = shape["expand"] * D
    N, R, K = shape["d_state"], shape["dt_rank"], shape["d_conv"]
    kinds = list(shape["layer_types"])
    L = len(kinds)

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

    def norm(ks, prefix):
        return {f"{prefix}_w": draw(ks, (D,), 0.1, mean=1.0),
                f"{prefix}_b": draw(ks, (D,), 0.02)}

    @functools.partial(jax.jit, static_argnames=("kind", "out"))
    def layer(key, *, kind: str, out: float):
        ks = iter(jax.random.split(key, 24))
        lay = {**norm(ks, "ln1"), **norm(ks, "ln2"),
               "w1": mat(next(ks), D, F), "w3": mat(next(ks), D, F),
               "w2": mat(next(ks), F, D, scale=out)}
        if kind == "mamba":
            lay.update(w_in=mat(next(ks), D, 2 * E),
                       conv_w=draw(ks, (E, K), K ** -0.5),
                       conv_b=draw(ks, (E,), 0.02, keep_f32=True),
                       w_x=mat(next(ks), E, R + 2 * N),
                       w_dt=mat(next(ks), R, E),
                       w_out=mat(next(ks), E, D, scale=out),
                       **scan_init(next(ks), E, N))
        elif kind == "gmu":
            lay.update(w_gate=mat(next(ks), D, E),
                       w_mem=mat(next(ks), E, D, scale=out))
        else:
            lay.update(wq=mat(next(ks), D, H * hd, scale=Q_SHARP),
                       wo=mat(next(ks), H * hd, D, scale=out),
                       subln=draw(ks, (2 * hd,), 0.1, mean=1.0),
                       **{k: draw(ks, (hd,), 0.1, keep_f32=True)
                          for k in ("lq1", "lk1", "lq2", "lk2")})
            if kind != "cross":
                lay.update(wk=mat(next(ks), D, KV * hd),
                           wv=mat(next(ks), D, KV * hd))
        return lay

    @jax.jit
    def ends(key):
        ks = iter(jax.random.split(key, 3))
        return {"embed": draw(ks, (V, D), 0.02), **norm(ks, "ln_f")}

    keys = jax.random.split(seed_key(seed), L + 1)
    params = ends(keys[0])
    params["layers"] = [layer(k, kind=kinds[i], out=out_scale(i, L))
                        for i, k in enumerate(keys[1:])]
    return params
