"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights and hands the same arrays to the program
and to the plain reference.  Layout: the parameter pytree of
``pathway_tpu.models`` (what ``params_from_gpt2_state_dict`` /
``params_from_bert_state_dict`` produce, biases included), float32.
Scales (listed as ``assumed`` in the configuration files): matrices
1/sqrt(fan_in), embeddings and biases 0.02, layer-norm scale 1 +- 0.1.
"""

from __future__ import annotations


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds run past 2**31)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def transformer_params(shape: dict, seed: int, *, embed_ln: bool = False):
    """``shape``: vocab_size, d_model, n_layers, d_ff, max_len.
    ``embed_ln``: BERT's layer norm after the embeddings."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    V, D, L = shape["vocab_size"], shape["d_model"], shape["n_layers"]
    F, T = shape["d_ff"], shape["max_len"]

    def make(key):
        ks = iter(jax.random.split(key, 16 * L + 8))

        def n(shape_, scale):
            return jax.random.normal(next(ks), shape_, jnp.float32) * scale

        def ln():
            return 1.0 + n((D,), 0.1), n((D,), 0.02)

        p = {"embed": n((V, D), 0.02), "pos_embed": n((T, D), 0.02),
             "layers": []}
        p["ln_f_scale"], p["ln_f_bias"] = ln()
        if embed_ln:
            p["ln_e_scale"], p["ln_e_bias"] = ln()
        for _ in range(L):
            lay = {}
            for w, b, (i, o) in (("wq", "bq", (D, D)), ("wk", "bk", (D, D)),
                                 ("wv", "bv", (D, D)), ("wo", "bo", (D, D)),
                                 ("w_up", "b_up", (D, F)),
                                 ("w_down", "b_down", (F, D))):
                lay[w] = n((i, o), 1.0 / np.sqrt(i))
                lay[b] = n((o,), 0.02)
            lay["ln1_scale"], lay["ln1_bias"] = ln()
            lay["ln2_scale"], lay["ln2_bias"] = ln()
            p["layers"].append(lay)
        return p

    return jax.jit(make)(seed_key(seed))
