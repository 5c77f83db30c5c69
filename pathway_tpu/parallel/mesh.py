"""Device mesh + sharding helpers.

The reference scales via timely workers over TCP
(external/timely-dataflow/communication, src/engine/dataflow/config.rs);
the TPU build scales via jax.sharding over ICI/DCN: pick a mesh, annotate
shardings, let XLA insert collectives.

Axes: dp (data/batch), tp (tensor/model), sp (sequence).  Single-chip runs
use a trivial 1-device mesh so the same pjit'd code paths run everywhere.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    n_devices: int | None = None,
    *,
    dp: int | None = None,
    tp: int | None = None,
    axis_names: Sequence[str] = ("dp", "tp"),
) -> Mesh:
    devices = jax.devices()
    n = n_devices or len(devices)
    devices = devices[:n]
    if dp is None and tp is None:
        # favor tensor parallelism within a host: ICI all-reduces are cheap
        tp = _largest_pow2_divisor(n, cap=8)
        dp = n // tp
    elif dp is None:
        dp = n // tp
    elif tp is None:
        tp = n // dp
    assert dp * tp == n, f"dp({dp}) * tp({tp}) != n_devices({n})"
    arr = np.asarray(devices).reshape(dp, tp)
    return Mesh(arr, axis_names=tuple(axis_names))


def _largest_pow2_divisor(n: int, cap: int) -> int:
    p = 1
    while p * 2 <= cap and n % (p * 2) == 0:
        p *= 2
    return p


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("dp"))


def param_sharding_rules(path: tuple[str, ...], leaf_shape: tuple[int, ...]) -> P:
    """Megatron-style tensor-parallel layout for transformer params:
    - attention qkv / ffn up: shard output dim over tp (column parallel)
    - attention out / ffn down: shard input dim over tp (row parallel)
    - embeddings: shard vocab over tp
    - everything else replicated
    """
    name = "/".join(path)
    if len(leaf_shape) < 2:
        return P()
    if any(k in name for k in ("wq", "wk", "wv", "w_up", "w_gate")):
        return P(None, "tp")
    if any(k in name for k in ("wo", "w_down")):
        return P("tp", None)
    if "embed" in name:
        return P("tp", None)
    return P()


def shard_params(params, mesh: Mesh):
    """Apply the tensor-parallel layout to a param pytree."""

    def place(path, leaf):
        spec = param_sharding_rules(_path_names(path), leaf.shape)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, params)


# -- decoder / paged-KV tensor parallelism (Round-9) ------------------------
#
# The serving path shards over a (dp=1, tp=N) mesh: K/V pool arrays split
# on the head axis, decoder params follow Megatron column/row rules with
# ONE psum per row-parallel projection, and the vocab axis of the tied
# embedding is sharded so logits are all-gathered before the in-jit
# argmax.  Unlike the encoder rules above, the decoder keeps ``pos_embed``
# replicated (positions are gathered per token inside shard_map) and
# shards the column-parallel BIASES alongside their weights.

# [n_layers, num_blocks, block_size, n_kv_heads * head_dim]: heads over tp
# (the fused axis is head-major: a shard's slice is its own heads, whole)
KV_POOL_PSPEC = P(None, None, None, "tp")


def kv_pool_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, KV_POOL_PSPEC)


def tp_mesh(tp: int) -> Mesh:
    """A (dp=1, tp=tp) mesh over the first ``tp`` local devices."""
    return make_mesh(n_devices=tp, dp=1, tp=tp)


def legal_tp_values(n_kv_heads: int, vocab_size: int,
                    n_devices: int | None = None,
                    d_ff: int | None = None) -> list[int]:
    cap = min(n_kv_heads, n_devices) if n_devices else n_kv_heads
    return [
        t for t in range(1, cap + 1)
        if n_kv_heads % t == 0 and vocab_size % t == 0
        and (d_ff is None or d_ff % t == 0)
    ]


def validate_decoder_tp(n_kv_heads: int, vocab_size: int, tp: int,
                        n_devices: int | None = None,
                        d_ff: int | None = None) -> None:
    """Fail loudly — naming the offending dims and the legal tp values —
    when a requested tensor-parallel degree cannot shard the decoder.
    Every tp-split dimension is checked: the KV heads (attention shard +
    d_model, which is n_heads*head_dim), the vocab (tied embedding), and
    d_ff (column-parallel FFN-up / row-parallel FFN-down)."""
    problems = []
    if tp < 1:
        problems.append(f"tp={tp} must be >= 1")
    else:
        if n_kv_heads % tp:
            problems.append(f"n_kv_heads={n_kv_heads} % tp={tp} != 0")
        if vocab_size % tp:
            problems.append(f"vocab_size={vocab_size} % tp={tp} != 0")
        if d_ff is not None and d_ff % tp:
            problems.append(f"d_ff={d_ff} % tp={tp} != 0")
        if n_devices is not None and tp > n_devices:
            problems.append(f"tp={tp} > {n_devices} local devices")
    if problems:
        legal = legal_tp_values(n_kv_heads, vocab_size, n_devices, d_ff)
        raise ValueError(
            "cannot shard the paged decode path: "
            + "; ".join(problems)
            + f". Legal tp values for this model/host: {legal}"
        )


def decoder_param_sharding_rules(path: tuple[str, ...],
                                 leaf_shape: tuple[int, ...]) -> P:
    """Tensor-parallel layout for the DECODER param pytree (models/decoder):
    - wq/wk/wv/w_up: shard the output dim (column parallel), their biases
      shard with them;
    - wo/w_down: shard the input dim (row parallel; one psum after, so the
      replicated bo/b_down is added ONCE, post-reduction);
    - embed: shard the vocab dim (tied input lookup + output head);
    - pos_embed / layer norms / everything else: replicated.
    """
    name = path[-1] if path else ""
    # Round-17 decode-plan leaves: int8 ``{w}_q`` weights shard exactly
    # like their f32 base; the per-output-channel ``{w}_s`` scales shard
    # WITH the output axis — split for column-parallel bases (each shard
    # scales its own output columns), replicated for row-parallel ones
    # (every shard applies the full-channel scale to its partial product
    # before the psum; the scale distributes over the sum)
    if name.endswith("_q") and name[:-2] in (
            "wqkv", "wo", "w_up", "w_down", "embed_t"):
        name = name[:-2]
    if name.endswith("_s") and name[:-2] in ("wqkv", "w_up", "embed_t"):
        return P("tp")
    if name.endswith("_s") and name[:-2] in ("wo", "w_down"):
        return P()
    # wqkv/bqkv: the fused QKV gemm (Round-17) — columns laid out per
    # shard ([q_s | k_s | v_s], decoder.plan_decode_params), so the
    # plain column-parallel split hands each shard its unfused slices;
    # embed_t: the pre-transposed [D, V] vocab head, vocab over tp
    if name in ("wqkv", "embed_t"):
        return P(None, "tp")
    if name == "bqkv":
        return P("tp")
    if name in ("wq", "wk", "wv", "w_up", "w_gate"):
        return P(None, "tp")
    if name in ("bq", "bk", "bv", "b_up", "b_gate"):
        return P("tp")
    if name in ("wo", "w_down"):
        return P("tp", None)
    if name == "embed":
        return P("tp", None)
    return P()


def decoder_param_specs(params):
    def spec(path, leaf):
        return decoder_param_sharding_rules(_path_names(path), leaf.shape)

    return jax.tree_util.tree_map_with_path(spec, params)


def shard_decoder_params(params, mesh: Mesh):
    """Place a decoder param pytree per :func:`decoder_param_sharding_rules`."""

    def place(path, leaf):
        spec = decoder_param_sharding_rules(_path_names(path), leaf.shape)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, params)


def _path_names(path) -> tuple[str, ...]:
    out = []
    for p in path:
        k = getattr(p, "key", None)
        if k is None:
            k = getattr(p, "idx", None)
        if k is None:
            k = getattr(p, "name", p)
        out.append(str(k))
    return tuple(out)


def param_specs(params):
    def spec(path, leaf):
        return param_sharding_rules(_path_names(path), leaf.shape)

    return jax.tree_util.tree_map_with_path(spec, params)
