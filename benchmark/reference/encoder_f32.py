"""Plain reference: a BERT-family sentence encoder in float32.

Post-LN blocks, learned positions, layer norm after the embeddings, exact
(erf) GELU, masked mean pooling and L2 normalisation — the forward pass of
sentence-transformers/all-MiniLM-L6-v2 (token types folded into segment 0).
Imports nothing of ``pathway_tpu``.  Runs in blocks of rows so that it fits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


@functools.partial(jax.jit, static_argnames=("n_heads", "eps", "dtype"))
def _encode(params, ids, mask, *, n_heads: int, eps: float, dtype):
    """``dtype``: float32 for the reference; a lower one only for the
    control of ``correct`` (the reference put in the program's place)."""
    B, T = ids.shape
    c = lambda a: a.astype(dtype)  # noqa: E731
    x = c(params["embed"])[ids] + c(params["pos_embed"])[:T]
    x = _ln(x, c(params["ln_e_scale"]), c(params["ln_e_bias"]), eps)
    D = x.shape[-1]
    hd = D // n_heads
    for lay in params["layers"]:
        q = (x @ c(lay["wq"]) + c(lay["bq"])).reshape(B, T, n_heads, hd)
        k = (x @ c(lay["wk"]) + c(lay["bk"])).reshape(B, T, n_heads, hd)
        v = (x @ c(lay["wv"]) + c(lay["bv"])).reshape(B, T, n_heads, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd)).astype(dtype)
        s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = _ln(x + a.reshape(B, T, D) @ c(lay["wo"]) + c(lay["bo"]),
                c(lay["ln1_scale"]), c(lay["ln1_bias"]), eps)
        ff = jax.nn.gelu(x @ c(lay["w_up"]) + c(lay["b_up"]),
                         approximate=False)
        x = _ln(x + ff @ c(lay["w_down"]) + c(lay["b_down"]),
                c(lay["ln2_scale"]), c(lay["ln2_bias"]), eps)
    m = mask[:, :, None].astype(jnp.float32)
    pooled = jnp.sum(x.astype(jnp.float32) * m, 1) / jnp.maximum(
        jnp.sum(m, 1), 1.0)
    return pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-12)


def embed(params: dict, shape: dict, token_lists: list, block: int = 512,
          dtype=jnp.float32) -> np.ndarray:
    """L2-normed float32 [n, d_model] embeddings of tokenised texts."""
    T = max(1, max(len(t) for t in token_lists))
    T = min(-(-T // 32) * 32, shape["max_len"])
    out = []
    with jax.default_matmul_precision("highest"):
        for s in range(0, len(token_lists), block):
            chunk = token_lists[s: s + block]
            ids = np.zeros((block, T), np.int32)
            mask = np.zeros((block, T), bool)
            mask[len(chunk):, 0] = True  # padding rows: one token, unused
            for r, t in enumerate(chunk):
                t = t[:T]
                ids[r, : len(t)] = t
                mask[r, : len(t)] = True
            e = _encode(params, jnp.asarray(ids), jnp.asarray(mask),
                        n_heads=shape["n_heads"], eps=shape["ln_eps"],
                        dtype=dtype)
            out.append(np.asarray(e, np.float32)[: len(chunk)])
    return np.concatenate(out, 0)
