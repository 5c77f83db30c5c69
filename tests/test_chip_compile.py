"""The main path's kernels, compiled for a described v5e at the shapes
chip_smoke.py runs them at — no chip needed, about two seconds each.

The TPU compiler is installed here and compiles for a chip that is
described, not attached; it refuses what the chip's compiler would refuse
(tiling, VMEM, HBM).  A compile that passes is not a chip run.

Everything that touches the topology lives in fixtures of THIS file: only
the worker that is given the file loads the TPU library.
"""

import importlib
import math

import pytest

# GPT-2-large through the engine's own geometry on one 16 GB chip
# (chip_smoke.py prints it): 36 layers, 20 heads of 64, 16 rows, 1025
# blocks of 16.  The kernels take the stacked pool of all layers, in
# BlockPool's shape: (L, NBLK, BS, heads * HD).
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


def _pool_copies(compiled, pool_dims):
    """The compiled program's ``copy`` ops whose result is pool-sized: a
    pool that XLA keeps in another layout than the kernel reads shows
    here, twice on the way in and twice on the way out."""
    dims = ",".join(str(d) for d in pool_dims)
    return [ln.strip()[:120] for ln in compiled.as_text().splitlines()
            if " copy(" in ln and f"[{dims}]" in ln.split(" copy(")[0]]


def _args(shape, heads, C):
    """q rows and the stacked pool of ``heads`` heads, with the index
    arrays both kernels take."""
    import jax.numpy as jnp

    i32 = jnp.int32
    pool = shape((L, NBLK, BS, heads * HD), jnp.bfloat16)
    q = shape((B, C, heads, HD), jnp.bfloat16)
    idx = (shape((1,), i32), shape((B, NB), i32), shape((B,), i32),
           shape((B,), i32))
    return q, pool, idx


@pytest.mark.parametrize("C", [1, CHUNK], ids=["decode", "prefill_chunk"])
@pytest.mark.parametrize("heads", [H, H // 4], ids=["tp1", "tp4_shard"])
def test_paged_ragged_kernel_compiles_at_gpt2_large(shape, C, heads):
    pa = _paged()
    q, pool, idx = _args(shape, heads, C)
    compiled = _compiled_kernel(
        lambda q, k, v, li, bt, c0, cl: pa._paged_ragged_fn(
            q, k, v, li, bt, c0, cl, d_true=HD),
        q, pool, pool, *idx,
    )
    # tp = 4: 5 x 64 = 320 lanes a shard do not fill whole tiles and the
    # copies come back (PERF.md, open questions); printed, not asserted
    print(f"heads={heads} C={C}: pool-sized copies "
          f"{len(_pool_copies(compiled, pool.shape))}")


@pytest.mark.parametrize("heads", [H, H // 4], ids=["tp1", "tp4_shard"])
def test_paged_append_kernel_compiles_at_gpt2_large(shape, heads):
    import jax.numpy as jnp

    pa = _paged()
    q, pool, idx = _args(shape, heads, 1)
    new = shape((B, heads, HD), jnp.bfloat16)
    compiled = _compiled_kernel(
        lambda q, k1, v1, k, v, li, bt, c0, cl, so: pa._paged_append_fn(
            q, k1, v1, k, v, li, bt, c0, cl, so, d_true=HD),
        q, new, new, pool, pool, *idx, idx[-1],
        donate_argnums=(3, 4),
    )
    print(f"heads={heads}: pool-sized copies "
          f"{len(_pool_copies(compiled, pool.shape))}")


def test_paged_kernels_compile_at_lfm2_spans(shape):
    """Both paged kernels at LFM2-8B-A1B's geometry (32 query heads over 8
    K/V heads of 64, tables of 128 blocks, three attention layers): a grid
    step attends a span of eight blocks that the kernel gathers itself
    from the pool in HBM, the query heads of a K/V head folded into the
    query rows (128 a chunk of 32, 4 a decode row)."""
    import jax.numpy as jnp

    pa = _paged()
    kv, rep, tables, blocks, i32 = 8, 4, 128, 2049, jnp.int32
    assert pa.span_blocks(BS, tables, kv * HD) == 128 // BS
    pool = shape((3, blocks, BS, kv * HD), jnp.bfloat16)
    idx = (shape((1,), i32), shape((B, tables), i32), shape((B,), i32),
           shape((B,), i32))
    ragged = _compiled_kernel(
        lambda q, k, v, li, bt, c0, cl: pa._paged_ragged_fn(
            q, k, v, li, bt, c0, cl, d_true=HD),
        shape((B, CHUNK, kv * rep, HD), jnp.bfloat16), pool, pool, *idx,
    )
    new = shape((B, kv, HD), jnp.bfloat16)
    append = _compiled_kernel(
        lambda q, k1, v1, k, v, li, bt, c0, cl, so: pa._paged_append_fn(
            q, k1, v1, k, v, li, bt, c0, cl, so, d_true=HD),
        shape((B, 1, kv * rep, HD), jnp.bfloat16), new, new, pool, pool,
        *idx, idx[-1], donate_argnums=(3, 4),
    )
    for compiled in (ragged, append):
        assert _pool_copies(compiled, pool.shape) == []


@pytest.mark.parametrize(
    "layers,blocks,heads",
    [(L, NBLK, H), (3, 2049, 8), (L, NBLK, H // 4)],
    ids=["gpt2_large", "lfm2", "tp4_shard"])
def test_paged_write_kernel_compiles(shape, layers, blocks, heads):
    """The mixed step's K/V writer alone: 48 packed tokens' rows into the
    stacked pool of GPT-2-large (1280 lanes), of LFM2-8B-A1B's three
    attention layers (512) and of a tp=4 shard (320: no whole tiles, the
    block spec spans the dims), both pools aliased in place."""
    import jax.numpy as jnp

    pa = _paged()
    T, i32 = B + CHUNK, jnp.int32
    pool = shape((layers, blocks, BS, heads * HD), jnp.bfloat16)
    rows = shape((T, heads * HD), jnp.bfloat16)
    compiled = _compiled_kernel(
        pa._paged_write_fn, rows, rows, pool, pool, shape((1,), i32),
        shape((T,), i32), shape((T,), i32), donate_argnums=(2, 3))
    copies = _pool_copies(compiled, pool.shape)
    print(f"heads={heads}: pool-sized copies {len(copies)}")
    if heads * HD % 128 == 0:
        assert copies == []
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _mixed_like(q, k1, v1, kp, vp, sb, so, bt, c0, cl):
    """What a mixed step does to the pool, over two layers: every packed
    token's row written block by block by the writer kernel, then the
    ragged kernel over the stacked pool."""
    import jax.numpy as jnp

    pa = _paged()
    out = []
    for li in range(2):
        kp, vp = pa.paged_write_rows(kp, vp, sb, so, k1, v1, layer=li,
                                     use_pallas=True, interpret=False)
        out.append(pa._paged_ragged_fn(
            q, kp, vp, jnp.full((1,), li, jnp.int32), bt, c0, cl,
            d_true=HD))
    return out[0] + out[1], kp, vp


def _chain_like(q, k1, v1, kp, vp, bt, c0, cl, so):
    """What a chained decode does to the pool: the append kernel over
    two layers inside a two-step ``lax.scan`` that carries the pools."""
    import jax
    import jax.numpy as jnp

    pa = _paged()

    def body(carry, _):
        kp, vp, acc = carry
        for li in range(2):
            a, kp, vp = pa._paged_append_fn(
                q, k1, v1, kp, vp, jnp.full((1,), li, jnp.int32), bt, c0,
                cl, so, d_true=HD)
            acc = acc + a
        return (kp, vp, acc), None

    (kp, vp, acc), _ = jax.lax.scan(
        body, (kp, vp, jnp.zeros_like(q)), None, length=2)
    return acc, kp, vp


@pytest.mark.parametrize("program", ["mixed", "chained", "cow_copy"])
def test_step_programs_keep_the_pool_where_it_is(shape, program):
    """The pool in BlockPool's shape goes through a step-shaped program
    without a change of layout: its entry layout is row-major (what the
    kernels read), no pool-sized copy is compiled, and the program's
    temporaries stay small.  With heads and head_dim as separate minor
    axes each such program held four pool-sized copies and 7.26 GB of
    temporaries (PERF.md, PR 26)."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.kvcache.block_pool import _cow_copy_fn

    i32 = jnp.int32
    T = B + CHUNK
    q, pool, (_li, bt, c0, cl) = _args(
        shape, H, CHUNK if program == "mixed" else 1)
    if program == "mixed":
        rows = shape((T, H, HD), jnp.bfloat16)
        fn, pools_at = _mixed_like, (3, 4)
        args = (q, rows, rows, pool, pool, shape((T,), i32),
                shape((T,), i32), bt, c0, cl)
    elif program == "chained":
        new = shape((B, H, HD), jnp.bfloat16)
        fn, pools_at = _chain_like, (3, 4)
        args = (q, new, new, pool, pool, bt, c0, cl, c0)
    else:
        fn, pools_at = _cow_copy_fn, (0,)
        args = (pool, shape((), i32), shape((), i32))
    compiled = jax.jit(fn, donate_argnums=pools_at).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (program != "cow_copy")
    layouts = compiled.input_formats[0]
    for i in pools_at:
        assert layouts[i].layout.major_to_minor == (0, 1, 2, 3), layouts[i]
    assert _pool_copies(compiled, pool.shape) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


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


# -- the lfm2_moe block family (LFM2-8B-A1B's published widths) ---------------
# 2048 wide, 32 query heads over 8 K/V heads of 64, 32 experts of 1792
# top-4, conv 3, vocab 65,536; the engine's geometry for the benchmark's
# cut: 16 rows, 2,049 blocks of 16, tables of 128, chains of 16.


def _lfm2_programs(shape, depth):
    """(cfg, parameter shapes, pool, arena, the three step programs with
    their index arguments) at ``depth`` layers of the published pattern
    after one leading dense layer."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import lfm2

    pattern = ("conv", "full_attention", "conv", "conv", "conv") \
        + ("full_attention", "conv", "conv", "conv") * 2
    cfg = lfm2.Lfm2Config(n_dense_layers=1, layer_types=pattern[:depth],
                          max_len=2048, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda: lfm2.init_lfm2_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda s: shape(s.shape, s.dtype), shapes)
    rows, chunk, tables, blocks, chain = 16, 32, 128, 2049, 16
    T, i32 = rows + chunk, jnp.int32
    pool = shape((len(cfg.attn_layers), blocks, BS,
                  cfg.n_kv_heads * cfg.head_dim), jnp.bfloat16)
    arena = shape((len(cfg.conv_layers), rows + 1, 2, cfg.d_model),
                  jnp.bfloat16)

    def vec(*dims):
        return shape(dims, i32)

    step = (vec(rows), vec(rows), vec(rows, tables))
    programs = {
        "mixed": (lfm2.hybrid_mixed_step, (
            vec(T), vec(T), vec(rows, tables), vec(rows), vec(rows),
            vec(rows, chunk), vec(T), vec(T), vec(T), vec(T), vec(rows),
            vec(rows))),
        "decode": (lfm2.hybrid_decode_step,
                   step + (vec(rows), vec(rows), vec(rows))),
        "chained": (lfm2.hybrid_chained_decode,
                    step + (vec(rows, chain), vec(rows, chain), vec(rows))),
    }
    return cfg, params, pool, arena, programs


@pytest.mark.parametrize("program", ["mixed", "decode", "chained"])
def test_lfm2_step_programs_keep_the_pool_where_it_is(shape, program,
                                                      monkeypatch):
    """The hybrid family's three step programs at the published widths,
    two periods of the layer pattern deep: the kernels are in the compiled
    text (three attention calls, two grouped matmuls an expert layer), the
    K/V pool enters row-major and is never copied whole, the temporaries
    are a few MB.  The step functions ask the backend whether to
    interpret their kernels; here they are told it is the chip."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params, pool, arena, programs = _lfm2_programs(shape, depth=9)
    fn, args = programs[program]
    compiled = jax.jit(
        lambda p, k, v, c, *a: fn(p, cfg, k, v, c, *a, attn="pallas"),
        donate_argnums=(1, 2, 3),
    ).lower(params, pool, pool, arena, *args).compile()
    n_moe = cfg.n_layers - cfg.n_dense_layers
    # a mixed step's attention layer is two calls: the writer, the kernel
    n_attn = len(cfg.attn_layers) * (2 if program == "mixed" else 1)
    assert compiled.as_text().count("tpu_custom_call") == n_attn + 2 * n_moe
    layouts = compiled.input_formats[0]
    for i in (1, 2):
        assert layouts[i].layout.major_to_minor == (0, 1, 2, 3), layouts[i]
    assert _pool_copies(compiled, pool.shape) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _compiled_gmm(shape, E, D, F, tiles, tm):
    """``_moe_gmm_fn`` over ``tiles`` row tiles of ``tm`` rows on ``E``
    experts of 3 x D x F in bf16: its two kernels are in the text."""
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    bf16 = jnp.bfloat16
    compiled = _compiled_kernel(
        lambda *a: moe._moe_gmm_fn(*a, tm=tm), shape((tiles * tm, D), bf16),
        shape((E, D, F), bf16), shape((E, D, F), bf16),
        shape((E, F, D), bf16), shape((tiles,), jnp.int32),
        shape((1,), jnp.int32))
    assert compiled.as_text().count("tpu_custom_call") == 2
    return compiled


@pytest.mark.parametrize("pairs", [64, 192], ids=["decode", "mixed"])
def test_grouped_matmul_kernel_compiles_at_the_published_experts(shape,
                                                                 pairs):
    """32 experts of 3 x 2048 x 1792 in bf16, the routed pairs of a decode
    step (16 x 4) and of a mixed step (48 x 4), in whole tiles of 16."""
    from pathway_tpu.ops import moe

    E, D, F = 32, 2048, 1792
    tiles = moe.n_tiles(pairs, E)
    compiled = _compiled_gmm(shape, E, D, F, tiles, moe.TM)
    assert "_moe_gmm_fn" in compiled.as_text()


@pytest.mark.parametrize("tm", ["rule", 64])
def test_grouped_matmul_kernel_compiles_at_tall_row_tiles(shape, tm):
    """The same 32 experts at the routed pairs of a mixed step at the chosen
    chunk (528 x 4: 66 rows an expert), in the row tile the rule gives, the
    tallest (128 x 2,048 rows beside two 2,048 x 256 panels,
    double-buffered: ~5 MB of VMEM), and in the one under it.  The kernel
    asks for no VMEM limit: the compiler holds it to its own 16 MiB."""
    from pathway_tpu.ops import moe

    E, D, F, T, k = 32, 2048, 1792, 528, 4
    if tm == "rule":
        tm = moe.row_tile(T, k, E)
        assert tm == 128
    tiles = moe.n_tiles(T * k, E, tm)
    compiled = _compiled_gmm(shape, E, D, F, tiles, tm)
    assert "vmem_limit" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


# -- the afmoe block family (Trinity-Mini's published widths) -----------------
# 2048 wide, 32 query heads over 4 K/V heads of 128, 128 experts of 1024
# top-8 beside a shared one, a sliding window of 2,048, vocab 200,192; the
# engine's geometry for the benchmark's cut: 16 rows, a chunk of 256, tables
# of 512, 8,193 full blocks and 16 x 146 + 1 window blocks of 16.


@pytest.mark.parametrize("window", [None, 2048], ids=["full", "window"])
def test_paged_kernels_compile_at_trinity_spans(shape, window):
    """Both paged kernels at Trinity-Mini's geometry: a head is a whole
    lane tile, the eight query heads of a K/V head fold into 2,048 query
    rows a chunk of 256 (in column tiles of 256 rows, under a VMEM limit the
    call asks for), and with a window the spans behind it are dead grid steps."""
    import jax.numpy as jnp

    pa = _paged()
    kv, rep, hd, chunk, tables, i32 = 4, 8, 128, 256, 512, jnp.int32
    blocks = 8193 if window is None else 16 * 146 + 1
    assert pa.span_blocks(BS, tables, kv * hd) == 128 // BS
    # one K/V head a group: a first tile of two query columns' folded heads
    # (a sublane tile of bf16), wide ones of 256 rows, run in one loop
    assert pa._heads_per_group(kv, hd, chunk * rep) == 1
    assert pa._col_tiles(1, chunk * rep, rep, jnp.bfloat16) == (16, 256)
    pool = shape((2 if window is None else 6, blocks, BS, kv * hd),
                 jnp.bfloat16)
    idx = (shape((1,), i32), shape((B, tables), i32), shape((B,), i32),
           shape((B,), i32))
    ragged = _compiled_kernel(
        lambda q, k, v, li, bt, c0, cl: pa._paged_ragged_fn(
            q, k, v, li, bt, c0, cl, d_true=hd, window=window),
        shape((B, chunk, kv * rep, hd), jnp.bfloat16), pool, pool, *idx,
    )
    new = shape((B, kv, hd), jnp.bfloat16)
    append = _compiled_kernel(
        lambda q, k1, v1, k, v, li, bt, c0, cl, so: pa._paged_append_fn(
            q, k1, v1, k, v, li, bt, c0, cl, so, d_true=hd, window=window),
        shape((B, 1, kv * rep, hd), jnp.bfloat16), new, new, pool, pool,
        *idx, idx[-1], donate_argnums=(3, 4),
    )
    for compiled in (ragged, append):
        assert _pool_copies(compiled, pool.shape) == []


def test_afmoe_mixed_step_compiles_at_the_published_widths(shape,
                                                           monkeypatch):
    """The windowed family's mixed step at the published widths, three
    layers deep (sliding dense, sliding experts, full experts): the kernels
    are in the compiled text (a writer and an attention call a layer, two
    grouped matmuls an expert layer), both pool pairs enter row-major and
    neither is copied whole, the temporaries stay under 128 MB."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.kvcache.windowed import window_pool_blocks
    from pathway_tpu.models import afmoe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s, f = afmoe.SLIDING, afmoe.FULL
    cfg = afmoe.AfmoeConfig(n_dense_layers=1, layer_types=(s, s, f),
                            max_len=8192, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda: afmoe.init_afmoe_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype), shapes)
    rows, chunk, tables, i32 = 16, 256, 512, jnp.int32
    T, D = rows + chunk, cfg.n_kv_heads * cfg.head_dim
    pool = shape((1, 8193, BS, D), jnp.bfloat16)
    wpool = shape((2, window_pool_blocks(cfg.sliding_window, chunk, BS, rows),
                   BS, D), jnp.bfloat16)
    assert wpool.shape[1] == 16 * 146 + 1

    def vec(*dims):
        return shape(dims, i32)

    args = (vec(T), vec(T), vec(rows, tables), vec(rows), vec(rows),
            vec(rows, chunk), vec(T), vec(T), vec(T), vec(T), vec(rows),
            vec(rows, tables))
    compiled = jax.jit(
        lambda p, k, v, kw, vw, *a: afmoe.windowed_mixed_step(
            p, cfg, k, v, kw, vw, *a, attn="pallas"),
        donate_argnums=(1, 2, 3, 4),
    ).lower(params, pool, pool, wpool, wpool, *args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2 * 3 + 2 * 2
    layouts = compiled.input_formats[0]
    for i in (1, 2, 3, 4):
        assert layouts[i].layout.major_to_minor == (0, 1, 2, 3), layouts[i]
    assert _pool_copies(compiled, pool.shape) == []
    assert _pool_copies(compiled, wpool.shape) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 128 << 20


@pytest.mark.parametrize("tm,tiles", [(16, 264), ("rule", 196)])
def test_grouped_matmul_kernel_compiles_at_128_experts(shape, tm, tiles):
    """128 experts of 3 x 2048 x 1024 in bf16 and the routed pairs of a
    mixed step of 272 tokens top-8 (17 rows an expert), in whole tiles of
    16 and of the rule's 32."""
    from pathway_tpu.ops import moe

    E, D, F, T, k = 128, 2048, 1024, 272, 8
    if tm == "rule":
        tm = moe.row_tile(T, k, E)
        assert tm == 32
    assert moe.n_tiles(T * k, E, tm) == tiles
    _compiled_gmm(shape, E, D, F, tiles, tm)


# -- the step programs behind their packed operand (PR 32) --------------------
# The engine hands a jitted step program (params, *cache arrays, packed):
# the round's index arrays in one int32 buffer (kvcache/packing.py).  Here
# each family's own table of programs, wrapped as the engine wraps it, at
# the published widths and a few layers deep.


def _family_case(shape, family, chunk=None):
    """(programs table, parameter shapes, cache arrays, the mixed and the
    chained program's index arrays, custom calls of a mixed step);
    ``chunk``: the decoder's or the lfm2 family's prefill chunk (two blocks
    unless given)."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import families

    i32, rows, chain = jnp.int32, B, 16

    def vec(*dims):
        return shape(dims, i32)

    def index_arrays(T, chunk, tables, extras):
        return {
            "mixed": (vec(T), vec(T), vec(rows, tables), vec(rows),
                      vec(rows), vec(rows, chunk), vec(T), vec(T), vec(T),
                      vec(T), vec(rows)) + extras,
            "chained": (vec(rows), vec(rows), vec(rows, tables),
                        vec(rows, chain), vec(rows, chain)) + extras}

    def planned(fam, cfg, init):
        shapes = jax.eval_shape(lambda: fam.plan(
            cfg, init(cfg, jax.random.PRNGKey(0)), tp=1, quantize=None))
        return jax.tree_util.tree_map(lambda s: shape(s.shape, s.dtype),
                                      shapes)

    if family == "decoder":
        from pathway_tpu.models import decoder

        fam = families.DecoderFamily
        cfg = decoder.DecoderConfig(vocab_size=50257, d_model=H * HD,
                                    n_layers=2, n_heads=H, d_ff=4 * H * HD,
                                    max_len=1024, dtype=jnp.bfloat16)
        params = planned(fam, cfg, lambda c, k: decoder.init_decoder_params(
            c, k))
        pool = shape((2, NBLK, BS, H * HD), jnp.bfloat16)
        chunk = chunk or CHUNK
        return (fam.programs(cfg, "pallas", None), params, (pool, pool),
                index_arrays(B + chunk, chunk, NB, ()), {"mixed": 2 * 2,
                                                         "chained": 2})
    if family == "lfm2":
        cfg, _raw, pool, arena, _p = _lfm2_programs(shape, depth=5)
        from pathway_tpu.models import lfm2

        fam = families.Lfm2Family
        params = planned(fam, cfg, lfm2.init_lfm2_params)
        n_moe = cfg.n_layers - cfg.n_dense_layers
        n_attn = len(cfg.attn_layers)
        return (fam.programs(cfg, "pallas", None), params,
                (pool, pool, arena),
                index_arrays(rows + (chunk or 32), chunk or 32, 128,
                             (vec(rows),)),
                {"mixed": 2 * n_attn + 2 * n_moe,
                 "chained": n_attn + 2 * n_moe})
    from pathway_tpu.kvcache.windowed import window_pool_blocks
    from pathway_tpu.models import afmoe

    fam = families.AfmoeFamily
    s, f = afmoe.SLIDING, afmoe.FULL
    cfg = afmoe.AfmoeConfig(n_dense_layers=1, layer_types=(s, s, f),
                            max_len=8192, dtype=jnp.bfloat16)
    params = planned(fam, cfg, afmoe.init_afmoe_params)
    chunk, tables, D = 256, 512, cfg.n_kv_heads * cfg.head_dim
    pool = shape((1, 8193, BS, D), jnp.bfloat16)
    wpool = shape((2, window_pool_blocks(cfg.sliding_window, chunk, BS, rows),
                   BS, D), jnp.bfloat16)
    return (fam.programs(cfg, "pallas", None), params,
            (pool, pool, wpool, wpool),
            index_arrays(rows + chunk, chunk, tables, (vec(rows, tables),)),
            {"mixed": 2 * 3 + 2 * 2, "chained": 3 + 2 * 2})


@pytest.mark.parametrize("program", ["mixed", "chained"])
@pytest.mark.parametrize("family", ["decoder", "lfm2", "afmoe"])
def test_packed_step_programs_lower_under_their_names(shape, monkeypatch,
                                                      family, program):
    """A family's mixed and chained programs as the engine jits them, on
    one packed index operand: the module is still ``jit__mixed_fn`` /
    ``jit__chained_fn`` and the kernels' functions ``_paged_*_fn`` (the
    names the benchmark's readers search the device trace for), every
    kernel is in the compiled text, the cache's arrays are donated and
    enter row-major, and no pool is copied whole."""
    import math
    import re

    import jax
    import jax.numpy as jnp

    from pathway_tpu.kvcache.packing import RoundLayout

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    table, params, state, host, calls = _family_case(shape, family)
    fn, donated = table[program]
    assert tuple(donated) == tuple(range(1, len(state) + 1))
    layout = RoundLayout(host[program])
    packed = shape((layout.size,), jnp.int32)
    assert layout.size == sum(math.prod(a.shape) for a in host[program])
    lowered = jax.jit(layout.program(fn), donate_argnums=donated).lower(
        params, *state, packed)
    text = lowered.as_text()
    assert re.search(r"module @(\w+)", text).group(1) == f"jit__{program}_fn"
    kernels = {re.sub(r"_\d+$", "", f) for f in re.findall(
        r"func\.func \w+ @(_paged_\w+)\(", text)}
    assert kernels == ({"_paged_ragged_fn", "_paged_write_fn"}
                       if program == "mixed" else {"_paged_append_fn"})
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == calls[program]
    layouts = compiled.input_formats[0]
    for i in donated:
        assert layouts[i].layout.major_to_minor == (0, 1, 2, 3), layouts[i]
    for pool in {s.shape for s in state}:
        assert _pool_copies(compiled, pool) == []


# -- the chunk the engine chooses for itself (PR 34) --------------------------
# obs/memory.choose_engine_config sizes the prefill chunk from the plan's
# bytes, the ledger and the chip's roof; here the rung it gives each of the
# two geometries whose cells leave the chunk to it, under the described
# v5e's published roof and budget, through the ragged kernel and the mixed
# program.


def _chosen_chunk(family):
    """(the rung of the rule for the family's whole model, K/V heads,
    query heads a K/V head, blocks a table, pool blocks, attention
    layers)."""
    from pathway_tpu.obs import memory

    from .utils import described_decode_plan

    cfg, plan, dtype, kw = described_decode_plan(
        "gpt2_large_f32" if family == "decoder" else "lfm2")
    res = memory.choose_engine_config(
        cfg, params=plan, dtype=dtype, budget_bytes=int(15.02 * 2 ** 30),
        reference_attn=False, roof={"peak": 197e12, "membw": 819e9},
        seq_buckets=(64, 256, 1024), **kw)
    assert "prefill_chunk" in res["chosen"]
    assert res["chunk_source"].startswith("ridge"), res["chunk_source"]
    assert res["max_batch_size"] == B
    geometry = (H, 1, NB, NBLK, L) if family == "decoder" else (
        cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, 128, 2049,
        len(cfg.attn_layers))
    return (res["prefill_chunk"],) + geometry


@pytest.mark.parametrize("family", ["decoder", "lfm2"])
def test_ragged_kernel_compiles_at_the_chosen_chunk(shape, family):
    """``_paged_ragged_fn`` at the rung the rule gives GPT-2-large (20
    heads of 64) and LFM2-8B-A1B (32 query heads over 8 K/V heads of 64:
    four query heads folded on a K/V head's rows).  The compiler holds the
    kernel to the VMEM its call asks for (:func:`_vmem_limit`), which has
    to cover the scratch the kernel declares and stay under the chip's
    128 MiB."""
    import jax.numpy as jnp

    pa = _paged()
    chunk, kv, rep, tables, blocks, layers = _chosen_chunk(family)
    i32 = jnp.int32
    pool = shape((layers, blocks, BS, kv * HD), jnp.bfloat16)
    idx = (shape((1,), i32), shape((B, tables), i32), shape((B,), i32),
           shape((B,), i32))
    compiled = _compiled_kernel(
        lambda q, k, v, li, bt, c0, cl: pa._paged_ragged_fn(
            q, k, v, li, bt, c0, cl, d_true=HD),
        shape((B, chunk, kv * rep, HD), jnp.bfloat16), pool, pool, *idx,
    )
    assert _pool_copies(compiled, pool.shape) == []
    C = chunk * rep
    G = pa._heads_per_group(kv, HD, C)
    K = pa.span_blocks(BS, tables, kv * HD)
    # two heads of 64 a group: a first tile of 8 folded columns (16 rows: a
    # sublane tile of bf16) and wide ones of 128 (256 rows), whatever the
    # chunk: the loop over a row's live tiles is 2 or 16 tiles long
    assert G == 2 and pa._col_tiles(G, C, rep, jnp.bfloat16) == (8, 128)
    assert C // 128 == {"decoder": 2, "lfm2": 16}[family]
    scratch = sum(
        math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
        for s in pa._scratch(K, BS, kv * HD, jnp.bfloat16, kv, C, HD, G,
                             jnp.bfloat16) if hasattr(s, "dtype")
        and len(s.shape) > 1)
    asked = pa._vmem_limit(K, BS, kv * HD, jnp.bfloat16, kv, C, HD, G,
                           jnp.bfloat16)
    limit = asked["compiler_params"].vmem_limit_bytes if asked \
        else 16 * 2 ** 20  # the compiler's own scoped limit
    print(f"{family}: chunk {chunk}, {C * G} rows a group, scratch "
          f"{scratch / 2 ** 20:.1f} MiB, limit {limit / 2 ** 20:.0f} MiB")
    assert scratch < limit <= 100 * 2 ** 20


@pytest.mark.parametrize(
    "kv,rep,hd,window,k",
    [(20, 1, 64, None, 4), (20, 1, 64, None, 23), (8, 4, 64, None, 4),
     (4, 8, 128, 2048, 4), (4, 8, 128, None, 4)],
    ids=["gpt2_k4", "gpt2_k23", "lfm2_k4", "trinity_window_k4",
         "trinity_k4"])
def test_ragged_kernel_compiles_at_verify_widths(shape, kv, rep, hd, window,
                                                 k):
    """A verify round of speculative decoding is the ragged kernel at
    ``k + 1`` query columns: folded widths (5, 24, 20, 40) that are no whole
    sublane tiles of bf16.  A tile's queries come in whole sublane tiles, so
    such a row has to be one tile (``_col_tiles``: None): the compiler
    refuses a read past the block."""
    import jax.numpy as jnp

    pa = _paged()
    C, tables, i32 = (k + 1) * rep, 64, jnp.int32
    assert pa._col_tiles(pa._heads_per_group(kv, hd, C), C, rep,
                         jnp.bfloat16) is None
    pool = shape((2, NBLK, BS, kv * hd), jnp.bfloat16)
    idx = (shape((1,), i32), shape((B, tables), i32), shape((B,), i32),
           shape((B,), i32))
    kw = {} if window is None else {"window": window}
    compiled = _compiled_kernel(
        lambda q, kp, vp, li, bt, c0, cl: pa._paged_ragged_fn(
            q, kp, vp, li, bt, c0, cl, d_true=hd, **kw),
        shape((B, k + 1, kv * rep, hd), jnp.bfloat16), pool, pool, *idx,
    )
    assert _pool_copies(compiled, pool.shape) == []


@pytest.mark.parametrize("family", ["decoder", "lfm2"])
def test_mixed_program_compiles_at_the_chosen_chunk(shape, monkeypatch,
                                                    family):
    """The family's mixed program as the engine jits it, ``max_batch_size
    + chunk`` packed tokens wide at the rung the rule gives the whole
    model, a few layers deep: every kernel is in the compiled text, no
    pool is copied whole, and the temporaries stay a small part of what
    the ledger bills a step of that width."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.kvcache.packing import RoundLayout

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chunk = _chosen_chunk(family)[0]
    if family == "lfm2":  # 528 tokens x 4 on 32 experts: the tall row tile
        from pathway_tpu.ops import moe

        assert moe.row_tile(B + chunk, 4, 32) == 128
    table, params, state, host, calls = _family_case(shape, family, chunk)
    fn, donated = table["mixed"]
    layout = RoundLayout(host["mixed"])
    compiled = jax.jit(layout.program(fn), donate_argnums=donated).lower(
        params, *state, shape((layout.size,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == calls["mixed"]
    for pool in {s.shape for s in state}:
        assert _pool_copies(compiled, pool) == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"{family}: chunk {chunk}, temporaries {temp / 2 ** 20:.1f} MiB")
    assert temp < 512 << 20


# -- the kimi_linear block family (Kimi-Linear-48B-A3B's published widths) ----


def _kimi_arena(shape, layers=10, slots=17):
    import jax.numpy as jnp

    return shape((layers, slots, 32, 128, 128), jnp.float32)


def test_kda_kernels_compile_at_the_published_widths(shape):
    """The chunk kernel (20 work items of 128 tokens: a mixed step of 528
    tokens in 16 rows; 32 heads of 128, eight a grid step; products in f32
    at the highest precision, one with its left operand transposed) and the
    step kernel (16 rows, a slot of 32 x 128 x 128 f32 a grid step), the
    arena aliased in place: nothing arena-sized is a temporary."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import kda

    bf, i32 = jnp.bfloat16, jnp.int32
    NW, n, W = kda.n_items(528, 16), kda.CHUNK, 32 * 128
    assert (NW, n) == (20, 128)
    tok = shape((NW, n, W), bf)
    chunk = _compiled_kernel(
        kda._kda_chunk_fn, tok, tok, tok, tok, shape((NW, n, W), jnp.float32),
        _kimi_arena(shape), shape((1,), i32), shape((NW,), i32),
        shape((NW,), i32), donate_argnums=(5,))
    col = shape((16, 128, 32), jnp.float32)
    step = _compiled_kernel(
        kda._kda_step_fn, col, col, col, col, shape((16, 32, 128), bf),
        _kimi_arena(shape), shape((1,), i32), shape((16,), i32),
        shape((16,), i32), donate_argnums=(5,))
    for compiled in (chunk, step):
        assert compiled.as_text().count("tpu_custom_call") == 1
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_latent_kernels_compile_at_the_published_widths(shape):
    """The latent pool's three kernels at 640 lanes a stored row (576 and
    64 of padding), 32 query heads folded on it: the ragged call of a mixed
    step of 528 packed tokens in the layout its rule gives (24 kernel rows
    of 64 query columns, 2,048 folded rows each, as Trinity's eight heads at
    a chunk of 256), the queries' gather into the pieces and the output's
    back to the stream included; the fused append at one column; the
    writer.  The pool is one operand and is never copied, and the packed
    call's temporaries are under what the rows at the chunk's width hold
    (the sixteen rows' 335 MB of gathered queries)."""
    import functools

    import jax.numpy as jnp

    pa = _paged()
    bf, i32 = jnp.bfloat16, jnp.int32
    pool = shape((3, 8193, BS, 640), bf)
    rows, chunk, tables = 16, 512, 512
    T = rows + chunk
    assert pa.query_layout(T, rows, chunk, 32, 640, 640, bf, Dv=0,
                           keys=tables * BS) == (64, 24)
    # a piece's 64 x 32 folded rows: a first tile of one query column's 32
    # heads, wide ones of 256 rows (eight query columns)
    assert pa._col_tiles(1, 64 * 32, 32, bf) == (32, 256)
    kw = dict(scale=192 ** -0.5, layer=1, use_pallas=True, interpret=False)

    def packed(q, p, bt, sp, nv, idx, tr, tc):
        return pa.latent_attention(q, p, bt, start_pos=sp, n_valid=nv,
                                   packed=(idx, tr, tc), **kw)

    def the_rows(q, p, bt, sp, nv, idx, tr, tc):
        return pa.latent_attention(q[idx], p, bt, start_pos=sp, n_valid=nv,
                                   **kw)[tr, tc]

    args = (shape((T, 32, 640), bf), pool, shape((rows, tables), i32),
            shape((rows,), i32), shape((rows,), i32),
            shape((rows, chunk), i32), shape((T,), i32), shape((T,), i32))
    ragged = _compiled_kernel(packed, *args)
    assert "bf16[24,2048,640]" in ragged.as_text()
    temp, rows_temp = (c.memory_analysis().temp_size_in_bytes for c in (
        ragged, _compiled_kernel(the_rows, *args)))
    print(f"latent call temporaries: {temp} packed, {rows_temp} the rows")
    assert temp < rows_temp
    scaled = functools.partial
    append = _compiled_kernel(
        scaled(pa._paged_latent_append_fn, scale=192 ** -0.5),
        shape((16, 1, 32, 640), bf),
        shape((16, 640), bf), pool, shape((1,), i32), shape((16, 512), i32),
        shape((16,), i32), shape((16,), i32), shape((16,), i32),
        donate_argnums=(2,))
    write = _compiled_kernel(
        pa._paged_latent_write_fn, shape((528, 640), bf), pool,
        shape((1,), i32), shape((528,), i32), shape((528,), i32),
        donate_argnums=(1,))
    for compiled in (ragged, append, write):
        assert compiled.as_text().count("tpu_custom_call") == 1
        assert _pool_copies(compiled, pool.shape) == []


def _kimi_case(shape):
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import families, kimi_linear as m

    fam = families.KimiLinearFamily
    cfg = m.KimiLinearConfig(n_held_experts=64, max_len=8192,
                             layer_types=(m.KDA, m.KDA, m.MLA),
                             dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: fam.plan(
        cfg, m.init_kimi_linear_params(cfg, jax.random.PRNGKey(0)), tp=1,
        quantize=None))
    params = jax.tree_util.tree_map(lambda s: shape(s.shape, s.dtype), shapes)
    assert params["layers"][1]["w1"].shape == (64, 2304, 1024)
    assert params["layers"][1]["wg"].shape == (2304, 256)
    assert params["layers"][2]["w_kb"].shape == (32, 128, 512)
    state = (shape((1, 8193, BS, cfg.latent_lanes), jnp.bfloat16),
             shape((2, 17, 3, 3 * cfg.kda_width), jnp.bfloat16),
             _kimi_arena(shape, layers=2))
    return fam, cfg, params, state


@pytest.mark.parametrize("program", ["mixed", "chained"])
def test_kimi_packed_step_programs_lower_under_their_names(shape,
                                                           monkeypatch,
                                                           program):
    """The family's mixed and chained programs as the engine jits them at
    the published widths, three layers deep (kda dense, kda experts, latent
    experts; 64 of 256 experts held): the module is ``jit__mixed_fn`` /
    ``jit__chained_fn``, the kernels' functions carry the names the
    benchmark's readers search the device trace for, every kernel is in the
    compiled text (a mixed step: step and chunk kernels a KDA layer, writer
    and attention a latent layer, two grouped matmuls an expert layer), the
    three cache arrays are donated, the latent pool and the state arena
    enter row-major and are not copied (the conv inputs, 1.3 MB a layer, are
    XLA's to lay out)."""
    import re

    import jax
    import jax.numpy as jnp

    from pathway_tpu.kvcache.packing import RoundLayout

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fam, cfg, params, state = _kimi_case(shape)
    i32, rows, chunk, tables = jnp.int32, 16, 512, 512

    def vec(*dims):
        return shape(dims, i32)

    T = rows + chunk
    host = {"mixed": (vec(T), vec(T), vec(rows, tables), vec(rows),
                      vec(rows), vec(rows, chunk), vec(T), vec(T), vec(T),
                      vec(T), vec(rows), vec(rows)),
            "chained": (vec(rows), vec(rows), vec(rows, tables),
                        vec(rows, 16), vec(rows, 16), vec(rows))}[program]
    fn, donated = fam.programs(cfg, "pallas", None)[program]
    assert tuple(donated) == (1, 2, 3)
    layout = RoundLayout(host)
    lowered = jax.jit(layout.program(fn), donate_argnums=donated).lower(
        params, *state, shape((layout.size,), i32))
    text = lowered.as_text()
    assert re.search(r"module @(\w+)", text).group(1) == f"jit__{program}_fn"
    kernels = {re.sub(r"_\d+$", "", f) for f in re.findall(
        r"func\.func \w+ @(_(?:paged|kda|moe)_\w+)\(", text)}
    assert kernels == ({"_paged_latent_fn", "_paged_latent_write_fn",
                        "_kda_chunk_fn", "_kda_step_fn", "_moe_gmm_fn"}
                       if program == "mixed" else
                       {"_paged_latent_append_fn", "_kda_step_fn",
                        "_moe_gmm_fn"})
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == (
        2 * 2 + 2 + 2 * 2 if program == "mixed" else 2 + 1 + 2 * 2)
    layouts = compiled.input_formats[0]
    for i, rank in ((1, 4), (3, 5)):
        assert layouts[i].layout.major_to_minor == tuple(range(rank)), \
            layouts[i]
    for pool in (state[0].shape, state[2].shape):
        assert _pool_copies(compiled, pool) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 800 << 20


# -- the qwen3_next block family (Qwen3-Next-80B-A3B's published widths) ------


@pytest.mark.parametrize("chunk", [512, 1024], ids=["chosen", "cell"])
def test_paged_kernels_compile_at_two_heads_of_256(shape, chunk):
    """The three paged kernels at a head of 256 lanes (two lane tiles a
    head, one head a group), eight query heads folded on each of 2 K/V
    heads, a pool row of 512 lanes, at the chunk of 512 the engine's rule
    chooses and at the 1,024 the cell runs (PERF.md, PR 38's sweep): 4,096
    or 8,192 folded columns a K/V head, in column tiles of 256 rows under
    the VMEM limit the call asks for; no kernel rule changed for it."""
    import jax.numpy as jnp

    pa = _paged()
    kv, rep, hd, tables, i32 = 2, 8, 256, 512, jnp.int32
    bf = jnp.bfloat16
    assert pa.span_blocks(BS, tables, kv * hd) == 128 // BS
    assert pa._heads_per_group(kv, hd, chunk * rep) == 1
    assert pa._col_tiles(1, chunk * rep, rep, bf) == (16, 256)
    pool = shape((3, 8193, BS, kv * hd), bf)
    idx = (shape((1,), i32), shape((B, tables), i32), shape((B,), i32),
           shape((B,), i32))
    ragged = _compiled_kernel(
        lambda q, k, v, li, bt, c0, cl: pa._paged_ragged_fn(
            q, k, v, li, bt, c0, cl, d_true=hd),
        shape((B, chunk, kv * rep, hd), bf), pool, pool, *idx)
    new = shape((B, kv, hd), bf)
    append = _compiled_kernel(
        lambda q, k1, v1, k, v, li, bt, c0, cl, so: pa._paged_append_fn(
            q, k1, v1, k, v, li, bt, c0, cl, so, d_true=hd),
        shape((B, 1, kv * rep, hd), bf), new, new, pool, pool,
        *idx, idx[-1], donate_argnums=(3, 4))
    T = B + chunk
    rows = shape((T, kv * hd), bf)
    write = _compiled_kernel(
        pa._paged_write_fn, rows, rows, pool, pool, shape((1,), i32),
        shape((T,), i32), shape((T,), i32), donate_argnums=(2, 3))
    for name, compiled in (("ragged", ragged), ("append", append),
                           ("write", write)):
        assert _pool_copies(compiled, pool.shape) == []
        temp = compiled.memory_analysis().temp_size_in_bytes
        print(f"head 256 {name}: temporaries {temp} bytes")
        assert temp < 1 << 20


def test_kda_kernels_compile_with_one_decay_a_head(shape):
    """The two delta-rule kernels told that the decay is one number a head:
    the chunk kernel takes ``g`` as (items, 128, 32) f32 (every head's decay
    of an item in one block, a head's column picked in VMEM), the step
    kernel as (rows, 1, 32); 32 value heads of 128 x 128, nine layers'
    arena aliased in place."""
    import jax.numpy as jnp

    from pathway_tpu.ops import kda

    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    NW, n, W = kda.n_items(528, 16), kda.CHUNK, 32 * 128
    tok = shape((NW, n, W), bf)
    arena = _kimi_arena(shape, layers=9)
    chunk = _compiled_kernel(
        kda._kda_chunk_fn, tok, tok, tok, tok, shape((NW, n, 32), f32),
        arena, shape((1,), i32), shape((NW,), i32), shape((NW,), i32),
        donate_argnums=(5,))
    col = shape((16, 128, 32), f32)
    step = _compiled_kernel(
        kda._kda_step_fn, shape((16, 1, 32), f32), col, col, col,
        shape((16, 32, 128), bf), arena, shape((1,), i32),
        shape((16,), i32), shape((16,), i32), donate_argnums=(5,))
    for compiled in (chunk, step):
        assert compiled.as_text().count("tpu_custom_call") == 1
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("T,tm,tiles", [(528, 16, 458), (1040, 32, 453)])
def test_grouped_matmul_kernel_compiles_at_128_of_512_experts(shape, T, tm,
                                                              tiles):
    """128 held experts of 3 x 2048 x 512 in bf16 and the routed pairs of a
    mixed step of 528 or 1,040 tokens top-10 (all of them, were they to
    fall here), in the rule's tiles: 16 rows at 10.3 rows an expert of the
    router's 512, 32 at 20.3."""
    from pathway_tpu.ops import moe

    E, D, Fe = 128, 2048, 512
    assert moe.row_tile(T, 10, 512) == tm
    assert moe.n_tiles(T * 10, E, tm) == tiles
    _compiled_gmm(shape, E, D, Fe, tiles, tm)


def _qwen3_next_case(shape):
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import families, qwen3_next as m

    fam = families.Qwen3NextFamily
    cfg = m.Qwen3NextConfig(n_held_experts=128, max_len=8192,
                            layer_types=(m.GDN, m.GDN, m.FULL),
                            dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: fam.plan(
        cfg, m.init_qwen3_next_params(cfg, jax.random.PRNGKey(0)), tp=1,
        quantize=None))
    params = jax.tree_util.tree_map(lambda s: shape(s.shape, s.dtype), shapes)
    assert params["layers"][0]["wqkvz"].shape == (2048, 12288)
    assert params["layers"][0]["conv_w"].shape == (8192, 4)
    assert params["layers"][2]["wq"].shape == (2048, 16 * 512)
    assert params["layers"][1]["w1"].shape == (128, 2048, 512)
    assert params["layers"][1]["wg"].shape == (2048, 512)
    pool = shape((1, 8193, BS, 2 * 256), jnp.bfloat16)
    state = (pool, pool, shape((2, 17, 3, 8192), jnp.bfloat16),
             _kimi_arena(shape, layers=2))
    return fam, cfg, params, state


@pytest.mark.parametrize("program", ["mixed", "chained"])
def test_qwen3_next_packed_step_programs_lower_under_their_names(
        shape, monkeypatch, program):
    """The family's mixed and chained programs as the engine jits them at
    the published widths, three layers deep (two gated DeltaNet, one full;
    128 of 512 experts held): the module is ``jit__mixed_fn`` /
    ``jit__chained_fn``, the kernels' functions carry the names the
    benchmark's readers search the device trace for, every kernel is in the
    compiled text (a mixed step: step and chunk kernels a DeltaNet layer,
    writer and attention a full layer, two grouped matmuls a layer), the
    four cache arrays are donated, the K/V pools and the state arena enter
    row-major and are not copied."""
    import re

    import jax
    import jax.numpy as jnp

    from pathway_tpu.kvcache.packing import RoundLayout

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fam, cfg, params, state = _qwen3_next_case(shape)
    i32, rows, chunk, tables = jnp.int32, 16, 512, 512

    def vec(*dims):
        return shape(dims, i32)

    T = rows + chunk
    host = {"mixed": (vec(T), vec(T), vec(rows, tables), vec(rows),
                      vec(rows), vec(rows, chunk), vec(T), vec(T), vec(T),
                      vec(T), vec(rows), vec(rows)),
            "chained": (vec(rows), vec(rows), vec(rows, tables),
                        vec(rows, 16), vec(rows, 16), vec(rows))}[program]
    fn, donated = fam.programs(cfg, "pallas", None)[program]
    assert tuple(donated) == (1, 2, 3, 4)
    layout = RoundLayout(host)
    lowered = jax.jit(layout.program(fn), donate_argnums=donated).lower(
        params, *state, shape((layout.size,), i32))
    text = lowered.as_text()
    assert re.search(r"module @(\w+)", text).group(1) == f"jit__{program}_fn"
    kernels = {re.sub(r"_\d+$", "", f) for f in re.findall(
        r"func\.func \w+ @(_(?:paged|kda|moe)_\w+)\(", text)}
    assert kernels == ({"_paged_ragged_fn", "_paged_write_fn",
                        "_kda_chunk_fn", "_kda_step_fn", "_moe_gmm_fn"}
                       if program == "mixed" else
                       {"_paged_append_fn", "_kda_step_fn", "_moe_gmm_fn"})
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == (
        2 * 2 + 2 + 2 * 3 if program == "mixed" else 2 + 1 + 2 * 3)
    layouts = compiled.input_formats[0]
    for i, rank in ((1, 4), (2, 4), (4, 5)):
        assert layouts[i].layout.major_to_minor == tuple(range(rank)), \
            layouts[i]
    for pool in (state[0].shape, state[3].shape):
        assert _pool_copies(compiled, pool) == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"qwen3_next {program}: temporaries {temp / 2**20:.1f} MiB")
    assert temp < 800 << 20


# -- the mimo_v2_flash block family (MiMo-V2-Flash's published widths) --------

# 64 query heads of 192 over K/V heads of 192 (keys) and 128 (values): 4 on a
# full layer (16 query heads folded on each), 8 on a sliding one (8 each, a
# window of 128, a learned sink a query head).  The engine's geometry for the
# benchmark's cut: 16 rows, tables of 512, 8,193 full blocks of 16 in 2
# layers, 16 x 42 + 1 window blocks in 9.


@pytest.mark.parametrize("C", [1, 64, 5], ids=["decode", "piece", "verify"])
@pytest.mark.parametrize("kind", ["full", "sliding"])
def test_paged_kernels_compile_at_keys_of_192_beside_values_of_128(shape,
                                                                   kind, C):
    """The three paged kernels where a K head is one and a half lane tiles
    and a V head one: two heads a group (384 K lanes, 256 V lanes, both
    whole tiles), ``acc`` and the output at V's width, the pool rows 768 /
    512 (full) and 1,536 / 1,024 (sliding) lanes, never padded; on the
    sliding layers a window of 128 and the sinks as the softmax's starting
    state.  At one column (the fused append too), at the 64 columns of the
    pieces :func:`query_layout` cuts a mixed step's rows into (24 kernel rows
    for a step of 528 tokens in sixteen rows of 512), and at a verify width
    of 5."""
    import jax.numpy as jnp

    pa = _paged()
    H, hd, hd_v, tables, i32, bf = 64, 192, 128, 512, jnp.int32, jnp.bfloat16
    kv, layers, blocks, window = (4, 2, 8193, None) if kind == "full" \
        else (8, 9, 16 * 42 + 1, 128)
    rep = H // kv
    P, N = pa.query_layout(B + 512, B, 512, H, hd, kv * hd, bf,
                           Dv=kv * hd_v, keys=tables * BS)
    assert (P, N) == (64, 24)
    assert pa.span_blocks(BS, tables, math.gcd(kv * hd, kv * hd_v)) == 128 // BS
    assert pa._heads_per_group(kv, hd, C * rep, hd_v) == 2
    kpool = shape((layers, blocks, BS, kv * hd), bf)
    vpool = shape((layers, blocks, BS, kv * hd_v), bf)
    rows = N if C == P else B
    idx = (shape((1,), i32), shape((rows, tables), i32), shape((rows,), i32),
           shape((rows,), i32))
    sinks = () if window is None else (shape((H,), jnp.float32),)
    kw = {"d_true": hd, **({} if window is None else {"window": window})}
    ragged = _compiled_kernel(
        lambda *a: pa._paged_ragged_fn(*a, **kw),
        shape((rows, C, H, hd), bf), kpool, vpool, *idx, *sinks)
    compiled = [("ragged", ragged)]
    if C == 1:
        compiled.append(("append", _compiled_kernel(
            lambda *a: pa._paged_append_fn(*a, **kw),
            shape((B, 1, H, hd), bf), shape((B, kv, hd), bf),
            shape((B, kv, hd_v), bf), kpool, vpool, *idx, idx[-1], *sinks,
            donate_argnums=(3, 4))))
        T = B + 512
        compiled.append(("write", _compiled_kernel(
            pa._paged_write_fn, shape((T, kv * hd), bf),
            shape((T, kv * hd_v), bf), kpool, vpool, shape((1,), i32),
            shape((T,), i32), shape((T,), i32), donate_argnums=(2, 3))))
    for name, c in compiled:
        assert _pool_copies(c, kpool.shape) == []
        assert _pool_copies(c, vpool.shape) == []
        temp = c.memory_analysis().temp_size_in_bytes
        print(f"mimo {kind} C={C} {name}: temporaries {temp} bytes, "
              f"VMEM asked {pa._vmem_limit(128 // BS, BS, kv * hd, bf, kv, C * rep, hd, 2, bf, kv * hd_v, hd_v) or 'default'}")
        # in HBM: nothing at a column, at five, or at the pieces (two pieces
        # of 256 a row held 470 MB of folded queries and outputs)
        assert temp < 1 << 20


@pytest.mark.parametrize(
    "H,kv,hd,hd_v,C,tables,window",
    [(32, 8, 64, 64, 512, 128, None), (16, 2, 256, 256, 1024, 512, None),
     (64, 4, 192, 128, 512, 512, None), (64, 8, 192, 128, 512, 512, 128)],
    ids=["lfm2", "qwen3next", "mimo_full", "mimo_window"])
def test_ragged_kernel_compiles_at_the_cells_pieces(shape, H, kv, hd, hd_v, C,
                                                    tables, window):
    """``_paged_ragged_fn`` at the layout :func:`query_layout` gives each cell
    whose rows it cuts (sixteen rows, a step of 16 + C tokens): N kernel rows
    of P query columns, each over a table of the cell's width.  The VMEM the
    call asks for covers the scratch the kernel declares, and the queries
    and the output move no HBM temporaries of their own."""
    import jax.numpy as jnp

    pa = _paged()
    bf, i32 = jnp.bfloat16, jnp.int32
    P, N = pa.query_layout(B + C, B, C, H, hd, kv * hd, bf, Dv=kv * hd_v,
                           keys=tables * BS)
    assert P < C and N == (B + C) // P + B
    kpool = shape((2, 2049, BS, kv * hd), bf)
    vpool = shape((2, 2049, BS, kv * hd_v), bf)
    idx = (shape((1,), i32), shape((N, tables), i32), shape((N,), i32),
           shape((N,), i32))
    sinks = () if window is None else (shape((H,), jnp.float32),)
    kw = {"d_true": hd, **({} if window is None else {"window": window})}
    compiled = _compiled_kernel(
        lambda *a: pa._paged_ragged_fn(*a, **kw),
        shape((N, P, H, hd), bf), kpool, vpool, *idx, *sinks)
    assert _pool_copies(compiled, kpool.shape) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    rep, R = H // kv, P * H // kv
    hv = None if hd_v == hd else hd_v
    G = pa._heads_per_group(kv, hd, R, hv)
    K = pa.span_blocks(BS, tables, math.gcd(kv * hd, kv * hd_v))
    scratch = sum(
        math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
        for s in pa._scratch(K, BS, kv * hd, bf, kv, R, hd, G, bf,
                             kv * hd_v, hd_v) if hasattr(s, "dtype")
        and len(s.shape) > 1)
    asked = pa._vmem_limit(K, BS, kv * hd, bf, kv, R, hd, G, bf, kv * hd_v,
                           hd_v)
    limit = asked["compiler_params"].vmem_limit_bytes if asked \
        else 16 * 2 ** 20  # the compiler's own scoped limit
    print(f"{H}x{hd} on {kv}: P {P}, N {N}, rep {rep}, scratch "
          f"{scratch / 2 ** 20:.1f} MiB, limit {limit / 2 ** 20:.0f} MiB")
    assert scratch < limit <= 100 * 2 ** 20


def _mimo_case(shape):
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import families, mimo_v2_flash as m

    fam = families.MimoV2FlashFamily
    cfg = m.MimoV2FlashConfig(n_held_experts=16, max_len=8192,
                              layer_types=(m.FULL, m.SLIDING, m.SLIDING),
                              dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: fam.plan(
        cfg, m.init_mimo_v2_flash_params(cfg, jax.random.PRNGKey(0)), tp=1,
        quantize=None))
    params = jax.tree_util.tree_map(lambda s: shape(s.shape, s.dtype), shapes)
    assert params["layers"][0]["wq"].shape == (4096, 64 * 192)
    assert params["layers"][0]["wk"].shape == (4096, 4 * 192)
    assert params["layers"][1]["wv"].shape == (4096, 8 * 128)
    assert params["layers"][1]["wo"].shape == (64 * 128, 4096)
    assert params["layers"][1]["sinks"].dtype == jnp.float32
    assert params["layers"][1]["w1"].shape == (16, 4096, 2048)
    assert params["layers"][1]["wg"].shape == (4096, 256)
    assert "shared" not in params["layers"][1]
    kw = fam.cache_kwargs(cfg, 16, 512)
    assert (kw["n_heads"], kw["window_heads"], kw["head_dim"],
            kw["v_head_dim"]) == (4, 8, 192, 128)
    bf = jnp.bfloat16
    state = (shape((1, 8193, BS, 768), bf), shape((1, 8193, BS, 512), bf),
             shape((2, 673, BS, 1536), bf), shape((2, 673, BS, 1024), bf))
    return fam, cfg, params, state


@pytest.mark.parametrize("program", ["mixed", "chained"])
def test_mimo_packed_step_programs_lower_under_their_names(
        shape, monkeypatch, program):
    """The family's mixed and chained programs as the engine jits them at
    the published widths and a chunk of 512, three layers deep (one full
    with the dense feed-forward, two sliding with 16 of 256 experts held):
    the module is ``jit__mixed_fn`` / ``jit__chained_fn``, the kernels carry
    the names the benchmark's readers search the device trace for (no new
    one), the four pools of four widths are donated, enter row-major and
    are not copied."""
    import re

    import jax
    import jax.numpy as jnp

    from pathway_tpu.kvcache.packing import RoundLayout

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fam, cfg, params, state = _mimo_case(shape)
    i32, rows, chunk, tables = jnp.int32, 16, 512, 512

    def vec(*dims):
        return shape(dims, i32)

    T = rows + chunk
    host = {"mixed": (vec(T), vec(T), vec(rows, tables), vec(rows),
                      vec(rows), vec(rows, chunk), vec(T), vec(T), vec(T),
                      vec(T), vec(rows), vec(rows, tables)),
            "chained": (vec(rows), vec(rows), vec(rows, tables),
                        vec(rows, 16), vec(rows, 16),
                        vec(rows, tables))}[program]
    fn, donated = fam.programs(cfg, "pallas", None)[program]
    assert tuple(donated) == (1, 2, 3, 4)
    layout = RoundLayout(host)
    lowered = jax.jit(layout.program(fn), donate_argnums=donated).lower(
        params, *state, shape((layout.size,), i32))
    text = lowered.as_text()
    assert re.search(r"module @(\w+)", text).group(1) == f"jit__{program}_fn"
    kernels = {re.sub(r"_\d+$", "", f) for f in re.findall(
        r"func\.func \w+ @(_(?:paged|kda|moe)_\w+)\(", text)}
    assert kernels == ({"_paged_ragged_fn", "_paged_write_fn", "_moe_gmm_fn"}
                       if program == "mixed" else
                       {"_paged_append_fn", "_moe_gmm_fn"})
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == (
        2 * 3 + 2 * 2 if program == "mixed" else 3 + 2 * 2)
    layouts = compiled.input_formats[0]
    for i in (1, 2, 3, 4):
        assert layouts[i].layout.major_to_minor == (0, 1, 2, 3), layouts[i]
    for pool in state:
        assert _pool_copies(compiled, pool.shape) == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"mimo {program}: temporaries {temp / 2**20:.1f} MiB")
    assert temp < 1200 << 20


# -- the phi4_mini_flash block family (Phi-4-mini-flash-reasoning's widths) --


def test_mamba_kernels_compile_at_the_published_widths(shape):
    """The selective scan's two kernels at 5,120 channels of 16 states, the
    arena of 9 Mamba layers and 16 rows, and the items of a step of 16 rows
    + the chunk of 256 the rule gives the whole model on one chip
    (``obs.memory.choose_engine_config``, the cross-decoder billed on the
    rows): the temporaries each call asks of HBM beside its operands are
    reported."""
    import jax.numpy as jnp

    from pathway_tpu.ops import ssm

    f32, bf, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    NW, n, E, N = ssm.n_items(16 + 256, 16), ssm.CHUNK, 5120, 16
    arena = shape((9, 17, 1, N, E), f32)
    tok = shape((NW, n, E), f32)
    chunk = _compiled_kernel(
        ssm._mamba_chunk_fn, tok, tok, shape((NW, n, N), f32),
        shape((NW, n, N), f32), shape((N, E), f32), arena,
        shape((1,), i32), shape((NW,), i32), shape((NW,), i32),
        donate_argnums=(5,))
    lane, col = shape((16, 1, E), f32), shape((16, N, 1), f32)
    step = _compiled_kernel(
        ssm._mamba_step_fn, lane, lane, col, col, shape((N, E), f32), arena,
        shape((1,), i32), shape((16,), i32), shape((16,), i32),
        donate_argnums=(5,))
    for name, c in (("chunk", chunk), ("step", step)):
        mem = c.memory_analysis()
        print(f"_mamba_{name}_fn: {NW if name == 'chunk' else 16} rows, "
              f"temporaries {mem.temp_size_in_bytes / 2 ** 20:.1f} MiB, "
              f"operands {mem.argument_size_in_bytes / 2 ** 20:.1f} MiB")
        assert mem.temp_size_in_bytes < 64 << 20
        assert _pool_copies(c, arena.shape) == []
    assert bf  # the chunk's inputs are f32: the kernel reads no bf16


@pytest.mark.parametrize("C", [1, 16], ids=["decode", "piece"])
def test_differential_kernels_compile_at_the_published_widths(shape, C):
    """The ragged and append kernels with ``v_heads``: 40 query heads of 64
    on 20 key heads, 10 value heads of 128 shared by both key halves, the
    full layer's pool of one layer; a cross-attention row (one column) and a
    piece of 16 columns."""
    import jax.numpy as jnp

    pa = _paged()
    bf, i32 = jnp.bfloat16, jnp.int32
    pool = shape((1, 8193, BS, 1280), bf)
    idx = (shape((1,), i32), shape((B, 512), i32), shape((B,), i32),
           shape((B,), i32))
    q = shape((B, C, 40, 64), bf)
    compiled = _compiled_kernel(
        lambda q, kp, vp, li, bt, c0, cl: pa._paged_ragged_fn(
            q, kp, vp, li, bt, c0, cl, d_true=64, v_heads=10),
        q, pool, pool, *idx)
    assert _pool_copies(compiled, pool.shape) == []
    if C == 1:
        new = (shape((B, 20, 64), bf), shape((B, 10, 128), bf))
        compiled = _compiled_kernel(
            lambda q, kn, vn, kp, vp, li, bt, c0, cl, so: pa._paged_append_fn(
                q, kn, vn, kp, vp, li, bt, c0, cl, so, d_true=64, v_heads=10),
            q, *new, pool, pool, *idx, shape((B,), i32),
            donate_argnums=(3, 4))
        assert _pool_copies(compiled, pool.shape) == []


@pytest.mark.parametrize("program", ["mixed", "chained"])
def test_phi4_packed_step_programs_lower_under_their_names(
        shape, monkeypatch, program):
    """The family's mixed and chained programs as the engine jits them at
    the published widths and the chosen chunk of 256, eight layers deep (the
    stack's pattern: Mamba, window, Mamba, window, the memory Mamba, the
    full layer, a memory unit, a cross layer): the kernels carry the names
    the benchmark's readers search the device trace for, the six cache
    arrays are donated and not copied, and the temporaries are reported."""
    import re

    import jax
    import jax.numpy as jnp

    from pathway_tpu.kvcache.packing import RoundLayout
    from pathway_tpu.models import families, phi4_mini_flash as m

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fam = families.Phi4MiniFlashFamily
    cfg = m.Phi4MiniFlashConfig(n_layers=8, max_len=8192,
                                dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: fam.plan(
        cfg, m.init_phi4_mini_flash_params(cfg, jax.random.PRNGKey(0)),
        tp=1, quantize=None))
    params = jax.tree_util.tree_map(lambda s: shape(s.shape, s.dtype), shapes)
    kw = fam.cache_kwargs(cfg, 16, 256)
    assert (kw["conv_layers"], kw["window_layers"], kw["n_layers"]) \
        == (3, 2, 1)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    state = (shape((1, 8193, BS, 1280), bf), shape((1, 8193, BS, 1280), bf),
             shape((2, 801, BS, 1280), bf), shape((2, 801, BS, 1280), bf),
             shape((3, 17, 3, 5120), bf), shape((3, 17, 1, 16, 5120), f32))
    rows, chunk, tables = 16, 256, 512

    def vec(*dims):
        return shape(dims, i32)

    T = rows + chunk
    host = {"mixed": (vec(T), vec(T), vec(rows, tables), vec(rows),
                      vec(rows), vec(rows, chunk), vec(T), vec(T), vec(T),
                      vec(T), vec(rows), vec(rows, tables), vec(rows)),
            "chained": (vec(rows), vec(rows), vec(rows, tables),
                        vec(rows, 16), vec(rows, 16), vec(rows, tables),
                        vec(rows))}[program]
    fn, donated = fam.programs(cfg, "pallas", None)[program]
    assert tuple(donated) == (1, 2, 3, 4, 5, 6)
    layout = RoundLayout(host)
    lowered = jax.jit(layout.program(fn), donate_argnums=donated).lower(
        params, *state, shape((layout.size,), i32))
    text = lowered.as_text()
    assert re.search(r"module @(\w+)", text).group(1) == f"jit__{program}_fn"
    kernels = {re.sub(r"_\d+$", "", f) for f in re.findall(
        r"func\.func \w+ @(_(?:paged|mamba)_\w+)\(", text)}
    assert kernels == ({"_paged_ragged_fn", "_paged_write_fn",
                        "_mamba_chunk_fn", "_mamba_step_fn"}
                       if program == "mixed" else
                       {"_paged_append_fn", "_paged_ragged_fn",
                        "_mamba_step_fn"})
    compiled = lowered.compile()
    for pool in state:
        assert _pool_copies(compiled, pool.shape) == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"phi4 {program}: temporaries {temp / 2**20:.1f} MiB")
    assert temp < 1200 << 20
