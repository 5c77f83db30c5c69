"""The main path's kernels, compiled for a described v5e at the shapes
chip_smoke.py runs them at — no chip needed, about two seconds each.

The TPU compiler is installed here and compiles for a chip that is
described, not attached; it refuses what the chip's compiler would refuse
(tiling, VMEM, HBM).  A compile that passes is not a chip run.

Everything that touches the topology lives in fixtures of THIS file: only
the worker that is given the file loads the TPU library.
"""

import importlib

import pytest

# GPT-2-large through the engine's own geometry on one 16 GB chip
# (chip_smoke.py prints it): 36 layers, 20 heads of 64, 16 rows, 1025
# blocks of 16.  The kernels take the stacked pool of all layers.
L, H, HD, B, BS, NBLK, NB = 36, 20, 64, 16, 16, 1025, 64
CHUNK = 2 * BS  # the mixed step's prefill chunk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler, no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def shape(one_chip, no_compile_cache):
    import jax

    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _paged():
    return importlib.import_module("pathway_tpu.kvcache.paged_attention")


def _compiled_kernel(fn, *args, **jit_kwargs):
    import jax

    compiled = jax.jit(fn, **jit_kwargs).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("C", [1, CHUNK], ids=["decode", "prefill_chunk"])
@pytest.mark.parametrize("heads", [H, H // 4], ids=["tp1", "tp4_shard"])
def test_paged_ragged_kernel_compiles_at_gpt2_large(shape, C, heads):
    import jax.numpy as jnp

    pa = _paged()
    i32 = jnp.int32
    _compiled_kernel(
        lambda q, k, v, li, bt, c0, cl: pa._paged_ragged_fn(
            q, k, v, li, bt, c0, cl, d_true=HD),
        shape((B, C, heads, HD), jnp.bfloat16),
        shape((L, NBLK, BS, heads, HD), jnp.bfloat16),
        shape((L, NBLK, BS, heads, HD), jnp.bfloat16),
        shape((1,), i32), shape((B, NB), i32), shape((B,), i32),
        shape((B,), i32),
    )


@pytest.mark.parametrize("heads", [H, H // 4], ids=["tp1", "tp4_shard"])
def test_paged_append_kernel_compiles_at_gpt2_large(shape, heads):
    import jax.numpy as jnp

    pa = _paged()
    i32 = jnp.int32
    pool = shape((L, NBLK, BS, heads, HD), jnp.bfloat16)
    _compiled_kernel(
        lambda q, k1, v1, k, v, li, bt, c0, cl, so: pa._paged_append_fn(
            q, k1, v1, k, v, li, bt, c0, cl, so, d_true=HD),
        shape((B, 1, heads, HD), jnp.bfloat16),
        shape((B, heads, HD), jnp.bfloat16),
        shape((B, heads, HD), jnp.bfloat16),
        pool, pool,
        shape((1,), i32), shape((B, NB), i32), shape((B,), i32),
        shape((B,), i32), shape((B,), i32),
        donate_argnums=(3, 4),
    )


@pytest.mark.parametrize("rows", [16384, 131072])
def test_knn_scores_kernel_compiles_at_the_index(shape, rows):
    import jax.numpy as jnp

    from pathway_tpu.ops.knn_pallas import pallas_scores

    # 8 queries against the all-MiniLM-L6-v2 index (384 wide)
    compiled = pallas_scores.lower(
        shape((8, 384), jnp.float32), shape((rows, 384), jnp.float32)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "bh,t,dtype",
    [(8 * 12, 512, "float32"), (20, 1024, "bfloat16")],
    ids=["minilm_encoder", "gpt2_large_prefill"],
)
def test_flash_attention_kernel_compiles(shape, bh, t, dtype):
    import jax.numpy as jnp

    from pathway_tpu.ops.attention_pallas import _flash_bhtd

    # head_dim lane-padded to 128 by flash_attention before the kernel
    x = shape((bh, t, 128), jnp.dtype(dtype))
    compiled = _flash_bhtd.lower(
        x, x, x, causal=True, t_valid=t, d_true=64
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
