"""Ragged paged attention: multi-query attention through a block table.

Two tiers with one contract:

- :func:`paged_attention_reference` — pure-JAX gather path (tier-1,
  ``JAX_PLATFORMS=cpu``).  It mirrors ``models/decoder.decode_step``'s
  einsum strings and masking EXACTLY, so when the gathered context length
  (``num_table_blocks * block_size``) equals the dense path's cache
  length, the logits are bit-identical to the dense batch-1 decode — the
  token-identity guarantee tests/test_kvcache.py pins.
- a Pallas TPU kernel (Ragged-Paged-Attention shape, arxiv 2604.15464):
  the block table rides in scalar-prefetch SMEM so each grid step DMAs
  one physical KV block straight into VMEM — the (B, L, H, D) gathered
  copy the reference path materializes in HBM never exists.  Online
  softmax is carried in VMEM scratch across the (sequential, innermost)
  block dimension, same (m, l, acc) recurrence as ops/attention_pallas.py.

Round-8 raggedness (the fused mixed decode/prefill step):

- every row carries ``C >= 1`` query tokens at CONSECUTIVE positions —
  decode rows use C=1, prefill-chunk rows up to the chunk width.  Query
  column ``c`` of row ``b`` attends to ``start_pos[b] + c + 1`` tokens
  (its own position included), clamped at the row's true context
  ``start_pos[b] + n_valid[b]`` for padding columns past ``n_valid``.
- the grid is length-aware: blocks past a row's context are neither
  DMA'd (the scalar-prefetched index map clamps to the row's last valid
  block, and Pallas elides the copy when the block index repeats) nor
  computed (``@pl.when`` guards), and the output is written at the
  row's LAST VALID block instead of the grid edge — a 1-block row in a
  64-block table costs one block of work, not 64.

Contract: every row must attend to AT LEAST one token
(``context_lens >= C`` in the consecutive form, ``start_pos >= 0`` and
``n_valid >= 1`` in the ragged form).  A zero-length row would produce
an all-masked softmax — NaNs from ``0/0`` in the reference path — so
both entry points fail loudly on concrete (non-traced) violations
instead of letting NaNs propagate; idle batch rows must be padded to
context 1 against the null block (the engine does).

Pool layout: ``(num_blocks, block_size, n_heads * head_dim)`` per layer
(the per-layer slice of BlockPool's stacked arrays): a token's heads lie
side by side on one minor axis, head-major.  With ``n_heads * head_dim``
a multiple of 128 a ``(block_size, n_heads * head_dim)`` block is a whole
number of the chip's tiles, so the layout XLA keeps the pool in is the
one the kernels read: no step program converts the pool on the way in
or out (PERF.md, PR 26; with heads and head_dim as separate minor axes
of 20 and 64 it did, four pool-sized copies a dispatch).  The kernels
split heads in VMEM, in groups of whole 128-lane tiles
(:func:`_heads_per_group`); queries and outputs keep their
``(..., H, hd)`` form at this module's boundary.

Round-9 tensor parallelism: heads are fully independent here, so the op
needs NO collectives and no tp-specific code — inside a shard_map over
the (dp=1, tp=N) mesh each shard simply passes its
``n_kv_heads/tp``-head pool slice and query slice (the fused axis is just
narrower, the kernel grid is unchanged).  The psum/all-gather points live
in the projections around the op (models/decoder.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e9


def _query_context(C: int, context_lens, start_pos, n_valid):
    """Resolve the two calling conventions to per-row ``(c0, cl_last)``:
    column ``c`` attends to ``min(c0 + c, cl_last)`` tokens.

    - consecutive form: ``context_lens`` (B,) is the LAST column's context
      (the decode case at C=1 — unchanged from round 7);
    - ragged form: ``start_pos``/``n_valid`` (B,) — chunk rows whose
      valid queries stop at ``n_valid`` (padding columns clamp).
    """
    if context_lens is not None:
        cl_last = jnp.asarray(context_lens, jnp.int32)
        c0 = cl_last - (C - 1)
    else:
        sp = jnp.asarray(start_pos, jnp.int32)
        cl_last = sp + jnp.asarray(n_valid, jnp.int32)
        c0 = sp + 1
    return c0, cl_last


def _require_positive_context(C: int, context_lens, start_pos, n_valid):
    """Fail-loud ``context >= 1`` contract on CONCRETE inputs (inside a
    jit the values are tracers and the check is skipped — the engine
    satisfies the contract by construction, padding idle rows to context
    1 against the null block)."""
    def _concrete_min(x):
        if x is None or isinstance(x, jax.core.Tracer):
            return None
        arr = np.asarray(x)
        return int(arr.min()) if arr.size else None

    cl = _concrete_min(context_lens)
    if cl is not None and cl < C:
        raise ValueError(
            f"paged attention requires context_lens >= n_queries ({C}); "
            f"got min {cl}. A zero-length row is an all-masked softmax "
            "(0/0 -> NaN in the reference path) — pad idle rows to "
            "context 1 against the null block instead."
        )
    nv = _concrete_min(n_valid)
    if nv is not None and nv < 1:
        raise ValueError(
            f"paged attention requires n_valid >= 1 per row; got min {nv}."
            " A zero-length row is an all-masked softmax (0/0 -> NaN in"
            " the reference path) — pad idle rows to one null-block token."
        )
    sp = _concrete_min(start_pos)
    if sp is not None and sp < 0:
        raise ValueError(
            f"paged attention requires start_pos >= 0; got min {sp}."
        )


def paged_attention_reference(q, k_pool, v_pool, block_tables,
                              context_lens=None, *, start_pos=None,
                              n_valid=None):
    """Gather-based ragged paged attention.

    q: (B, C, H, hd) — C consecutive query tokens per row (C=1 decode);
    k_pool/v_pool: (num_blocks, block_size, Hkv * hd), H a multiple of
    Hkv: query head ``i`` reads K/V head ``i // (H // Hkv)``;
    block_tables: (B, NB) int32, padded with the null block;
    context_lens: (B,) int32 — the LAST query column's context (position
    of the last query + 1); earlier columns attend to one token less
    each.  Alternatively pass ``start_pos``/``n_valid`` (B,) for ragged
    rows: column ``c`` attends to ``start_pos + min(c, n_valid-1) + 1``
    tokens (padding columns past ``n_valid`` clamp to the last valid
    query's context — their output is garbage the caller masks).
    Returns (B, C, H, hd).
    """
    B, C = q.shape[:2]
    _require_positive_context(C, context_lens, start_pos, n_valid)
    NB = block_tables.shape[1]
    BS = k_pool.shape[1]
    H, hd = q.shape[2:]
    c0, cl_last = _query_context(C, context_lens, start_pos, n_valid)
    # per-(row, column) context: min(c0 + c, cl_last)
    ctx = jnp.minimum(c0[:, None] + jnp.arange(C)[None, :], cl_last[:, None])
    k = k_pool[block_tables].reshape(B, NB * BS, -1, hd)
    v = v_pool[block_tables].reshape(B, NB * BS, -1, hd)
    if k.shape[2] != H:  # grouped queries: each K/V head serves H/Hkv
        k = jnp.repeat(k, H // k.shape[2], axis=2)
        v = jnp.repeat(v, H // v.shape[2], axis=2)
    # decode_step's exact math: same einsum strings, mask, f32 softmax
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    valid = (
        jnp.arange(NB * BS)[None, None, :] < ctx[:, :, None]
    )[:, None, :, :]
    scores = jnp.where(valid, scores, _NEG)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _heads_per_group(H: int, hd: int, C: int) -> int:
    """How many heads one matmul of the kernels takes.  A block of the
    pool is (BS, H*hd), heads side by side on the lane axis, and a slice
    of it that starts or ends inside a 128-lane tile costs a shuffle per
    block; so heads go in groups whose lanes fill whole tiles (two heads
    of 64), the group's query rows stacked on the sublane axis with the
    other heads' lanes zeroed.  Where no such group divides H (five heads
    a shard) or its rows would not fill a sublane tile (one decode row),
    all heads form one group: the whole lane axis, no slice at all."""
    g = 128 // math.gcd(hd, 128)
    return g if H % g == 0 and (g * C) % 8 == 0 else H


def _own_lanes(R: int, W: int, C: int, hd: int):
    """(R, W) bool: lane belongs to the head of its row (row i*C + c of
    a group is head i, whose lanes are [i*hd, (i+1)*hd))."""
    return (jax.lax.broadcasted_iota(jnp.int32, (R, W), 0) // C
            == jax.lax.broadcasted_iota(jnp.int32, (R, W), 1) // hd)


def _start_row(q_ref, qm_ref, m_ref, l_ref, acc_ref, *, C: int, G: int,
               hd: int):
    """First grid step of a batch row: reset the online softmax and lay
    the row's queries out for the grouped matmuls.  ``qm_ref`` row
    ``h*C + c`` holds query column c of head h in the lanes of h's place
    in its group and zeros in the other heads' lanes, so that
    ``qm[group rows] @ k[:, group lanes].T`` is every head's own scores
    (a zero lane adds an exact 0 to the f32 sum)."""
    m_ref[:] = jnp.full_like(m_ref, _NEG)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    R, W = G * C, G * hd
    own = _own_lanes(R, W, C, hd)
    for g in range(qm_ref.shape[0] // R):
        # in f32, whose tiles the mask shares (a bf16 select against an
        # f32-tiled mask is a relayout Mosaic refuses); the way back is exact
        qg = q_ref[:, g * W:(g + 1) * W].astype(jnp.float32)  # (C, W)
        qg = jnp.broadcast_to(qg, (R, W)) if C == 1 \
            else jnp.concatenate([qg] * G, axis=0)
        qm_ref[g * R:(g + 1) * R, :] = jnp.where(own, qg, 0.0).astype(
            qm_ref.dtype)


def _attend_block(j, c0, ctx, kb, vb, qm_ref, m_ref, l_ref, acc_ref, *,
                  block_size: int, scale: float, rep: int, C: int, G: int,
                  hd: int):
    """One visible K/V block's online-softmax update, shared by both
    kernels.  kb/vb: (BS, H*hd) VALUES — the pool's block as it lies in
    HBM.  Per group of G heads: scores (G*C, BS) of the group's stacked
    query rows against the group's lanes of K, each row's own softmax
    recurrence (m, l in f32), and ``p @ v`` over the group's lanes of V
    into acc (G*C, G*hd) f32 — of which row ``i*C + c`` is read only in
    head i's lanes (:func:`_write_out`).  ``rep`` > 1 (grouped queries):
    the ``rep`` query heads of a K/V head ride as ``rep`` neighbouring
    columns of it, so of the C columns here column ``c`` is query column
    ``c // rep``."""
    R, W = G * C, G * hd
    rows_i = jax.lax.broadcasted_iota(jnp.int32, (R, block_size), 0)
    k_pos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (R, block_size), 1
    )
    # column c attends to min(c0 + c, ctx) tokens; row i*C + c is column c
    if C == rep:  # one query column a row (decode)
        col = 0
    else:
        col = rows_i % C if rep == 1 else (rows_i % C) // rep
    col_ctx = jnp.minimum(c0 + col, ctx)
    valid = k_pos < col_ctx
    for g in range(qm_ref.shape[0] // R):
        rows = slice(g * R, (g + 1) * R)
        lanes = slice(g * W, (g + 1) * W)
        s = jax.lax.dot_general(
            qm_ref[rows], kb[:, lanes],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (R, BS)
        s = jnp.where(valid, s, _NEG)
        m_prev = m_ref[rows, :1]  # (R, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[rows] = jnp.broadcast_to(
            l_ref[rows, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
            (R, l_ref.shape[1]),
        )
        acc_ref[rows] = acc_ref[rows] * corr + jax.lax.dot_general(
            p.astype(vb.dtype), vb[:, lanes],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[rows] = jnp.broadcast_to(m_new, (R, m_ref.shape[1]))


def _write_out(o_ref, l_ref, acc_ref, *, C: int, G: int, hd: int):
    """o (C, H*hd): each head's lanes from its own rows of acc / l."""
    R, W = G * C, G * hd
    own = _own_lanes(R, W, C, hd)
    for g in range(acc_ref.shape[0] // R):
        rows = slice(g * R, (g + 1) * R)
        o = jnp.where(
            own, acc_ref[rows] / jnp.maximum(l_ref[rows, :1], 1e-20), 0.0
        )
        # one head's rows are non-zero in a lane: the sum picks them
        o = jnp.sum(o, axis=0, keepdims=True) if C == 1 \
            else sum(o[i * C:(i + 1) * C] for i in range(G))
        o_ref[:, g * W:(g + 1) * W] = o.astype(o_ref.dtype)


def _paged_kernel(li_ref, bt_ref, c0_ref, cl_ref, q_ref, k_ref, v_ref, o_ref,
                  qm_ref, m_ref, l_ref, acc_ref, *, block_size: int,
                  scale: float, rep: int, **geom):
    """Grid: (B, NB) — blocks innermost, so (m, l, acc) scratch carries the
    online softmax across one sequence's blocks.  Blocks: q and o
    (C, H*hd); k/v (block_size, H*hd) — the physical block of layer
    ``li`` that the scalar-prefetched table maps grid step j to (``li_ref``
    is only read by the index maps).  Blocks past the row's
    context (``j > jlast``) are dead: the index map pins their DMA to the
    last valid block (Pallas elides the repeated copy) and every
    ``@pl.when`` below is false, so they cost nothing."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _start_row(q_ref, qm_ref, m_ref, l_ref, acc_ref, **geom)

    c0 = c0_ref[b]       # column 0's context length
    ctx = cl_ref[b]      # the row's full context (last valid column's)
    jlast = (ctx - 1) // block_size  # last block holding attended tokens

    @pl.when(j <= jlast)  # skip blocks wholly past the context
    def _visible():
        _attend_block(j, c0, ctx, k_ref[:], v_ref[:], qm_ref, m_ref, l_ref,
                      acc_ref, block_size=block_size, scale=scale, rep=rep,
                      **geom)

    # write at the row's LAST VALID block, not the grid edge: later grid
    # steps touch nothing, and the (per-row) output block flushes when
    # the grid leaves row b
    @pl.when(j == jlast)
    def _final():
        _write_out(o_ref, l_ref, acc_ref, **geom)


def _softmax_scratch(H: int, C: int, hd: int, G: int, dtype):
    return [
        pltpu.VMEM((H * C, G * hd), dtype),        # qm: grouped queries
        pltpu.VMEM((H * C, 128), jnp.float32),     # m
        pltpu.VMEM((H * C, 128), jnp.float32),     # l
        pltpu.VMEM((H * C, G * hd), jnp.float32),  # acc
    ]


def _fold_queries(q, D: int):
    """q (B, C, H, hd) -> ((B, C * rep, D) rows for the kernels, rep), D =
    Hkv * hd the pool's minor axis.  With as many query heads as K/V heads
    this is a reshape.  With ``rep`` query heads a K/V head they become
    ``rep`` neighbouring query columns of that K/V head (column
    ``c * rep + r`` is head ``kv * rep + r`` of query column ``c``), so the
    kernels see Hkv heads and a wider row of queries."""
    B, C, H, hd = q.shape
    rep = H * hd // D
    if rep == 1:
        return q.reshape(B, C, D), 1
    q = q.reshape(B, C, H // rep, rep, hd).transpose(0, 1, 3, 2, 4)
    return q.reshape(B, C * rep, D), rep


def _unfold_queries(o, q_shape, rep: int):
    B, C, H, hd = q_shape
    if rep == 1:
        return o.reshape(B, C, H, hd)
    o = o.reshape(B, C, rep, H // rep, hd).transpose(0, 1, 3, 2, 4)
    return o.reshape(B, C, H, hd)


def _paged_ragged_fn(q, k_pool, v_pool, layer, block_tables, c0, cl, *,
                     d_true: int, interpret: bool = False):
    """q: (B, C, H, hd); pools (L, num_blocks, BS, Hkv*hd) — ALL layers'
    stacked pool, read in place at ``layer`` ((1,) int32): the block index
    maps carry the layer, so no layer is ever sliced out of the pool.  A
    block is (BS, H*hd): a whole number of the chip's tiles when H*hd is
    a multiple of 128, so nothing is lane-padded and the pool's layout in
    HBM is the one the kernel reads; c0/cl: (B,) per-row column-0 /
    last-column context lengths."""
    B, hd = q.shape[0], q.shape[3]
    BS, D = k_pool.shape[2:]
    NB = block_tables.shape[1]
    qf, rep = _fold_queries(q, D)
    C, H = qf.shape[1], D // hd
    G = _heads_per_group(H, hd, C)
    kernel = functools.partial(
        _paged_kernel, block_size=BS, scale=1.0 / np.sqrt(d_true),
        rep=rep, C=C, G=G, hd=hd,
    )

    def _kv_map(b, j, li, bt, c0, cl):
        # ragged grid: clamp dead steps to the row's last valid block so
        # their DMA is elided (same index as the previous step)
        return (li[0], bt[b, jnp.minimum(j, (cl[b] - 1) // BS)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # layer, block_tables, c0, cl
        grid=(B, NB),
        in_specs=[
            pl.BlockSpec((None, C, D),
                         lambda b, j, li, bt, c0, cl: (b, 0, 0)),
            pl.BlockSpec((None, None, BS, D), _kv_map),
            pl.BlockSpec((None, None, BS, D), _kv_map),
        ],
        out_specs=pl.BlockSpec((None, C, D),
                               lambda b, j, li, bt, c0, cl: (b, 0, 0)),
        scratch_shapes=_softmax_scratch(H, C, hd, G, q.dtype),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, D), q.dtype),
        interpret=interpret,
    )(layer, block_tables, c0, cl, qf, k_pool, v_pool)
    return _unfold_queries(out, q.shape, rep)


def _make_paged_ragged():
    """Jit the standalone kernel entry point through the device cost
    observatory (Round-14); falls back to a plain jit while the obs
    package is still importing (circular-import window)."""
    kwargs = dict(static_argnames=("d_true", "interpret"))
    try:
        from ..obs.profiler import profiled_jit

        return profiled_jit("pw.paged_attention", _paged_ragged_fn, **kwargs)
    except Exception:  # pragma: no cover - import-order edge
        return jax.jit(_paged_ragged_fn, **kwargs)


_paged_ragged = _make_paged_ragged()


def _append_kernel(li_ref, bt_ref, c0_ref, cl_ref, so_ref, q_ref, k1_ref,
                   v1_ref, k_ref, v_ref, o_ref, ko_ref, vo_ref, qm_ref, m_ref,
                   l_ref, acc_ref, *, block_size: int, scale: float, rep: int,
                   **geom):
    """Round-17 fused append+attend (decode, C=1): the incoming token's
    K/V rides into the kernel as a (1, H*hd) operand, is patched into the
    tail block IN REGISTER for the attention math, and is flushed back
    to the pool through the aliased pool outputs — the standalone
    scatter program the unfused path runs before attention disappears.
    Pool out-blocks map every grid step of row ``b`` to the row's slot
    block, so exactly ONE block per pool per row is written (at
    ``j == jlast``), the same write set as the scatter.  Same grid /
    online-softmax recurrence as :func:`_paged_kernel`."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _start_row(q_ref, qm_ref, m_ref, l_ref, acc_ref, **geom)

    c0 = c0_ref[b]
    ctx = cl_ref[b]
    so = so_ref[b]
    jlast = (ctx - 1) // block_size  # the append lands in this block

    def _patched(raw_ref, new_ref, last):
        # tail block with the new token's row substituted (the HBM copy
        # the input DMA'd predates the append)
        sel = (jax.lax.broadcasted_iota(jnp.int32, (block_size, 1), 0)
               == so) & last
        return jnp.where(sel, new_ref[:], raw_ref[:])

    @pl.when(j <= jlast)
    def _visible():
        last = j == jlast
        _attend_block(j, c0, ctx, _patched(k_ref, k1_ref, last),
                      _patched(v_ref, v1_ref, last), qm_ref, m_ref, l_ref,
                      acc_ref, block_size=block_size, scale=scale, rep=rep,
                      **geom)

    @pl.when(j == jlast)
    def _final():
        # the append itself: full tail block (input content + new row)
        # through the aliased pool output — flushed once per row
        ko_ref[:] = _patched(k_ref, k1_ref, True).astype(ko_ref.dtype)
        vo_ref[:] = _patched(v_ref, v1_ref, True).astype(vo_ref.dtype)
        _write_out(o_ref, l_ref, acc_ref, **geom)


def _paged_append_fn(q, k_new, v_new, k_pool, v_pool, layer, block_tables,
                     c0, cl, slot_offsets, *, d_true: int,
                     interpret: bool = False):
    """q: (B, 1, H, hd); k_new/v_new: (B, Hkv, hd); pools
    (L, num_blocks, BS, Hkv*hd) — ALL layers' stacked pool, returned
    UPDATED at ``layer`` ((1,) int32), aliased in place on TPU: one tail
    block per row is written, nothing else of the pool is touched or
    copied.  Contract: the slot is the tail of the attended context
    (``slot_blocks[b] == block_tables[b, (cl[b]-1)//BS]`` and
    ``slot_offsets[b] == (cl[b]-1) % BS``) — the decode append the
    engine constructs by definition."""
    B, hd = q.shape[0], q.shape[3]
    BS, D = k_pool.shape[2:]
    NB = block_tables.shape[1]
    qf, rep = _fold_queries(q, D)
    C, H = qf.shape[1], D // hd
    G = _heads_per_group(H, hd, C)
    kernel = functools.partial(
        _append_kernel, block_size=BS, scale=1.0 / np.sqrt(d_true),
        rep=rep, C=C, G=G, hd=hd,
    )

    def _kv_map(b, j, li, bt, c0, cl, so):
        return (li[0], bt[b, jnp.minimum(j, (cl[b] - 1) // BS)], 0, 0)

    def _slot_map(b, j, li, bt, c0, cl, so):
        # constant per row: the pool out-block IS the row's slot block
        return (li[0], bt[b, (cl[b] - 1) // BS], 0, 0)

    def _row(*tail):
        return lambda b, j, li, bt, c0, cl, so: (b,) + tail

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # layer, block_tables, c0, cl, slot_offsets
        grid=(B, NB),
        in_specs=[
            pl.BlockSpec((None, C, D), _row(0, 0)),
            pl.BlockSpec((None, 1, D), _row(0, 0)),
            pl.BlockSpec((None, 1, D), _row(0, 0)),
            pl.BlockSpec((None, None, BS, D), _kv_map),
            pl.BlockSpec((None, None, BS, D), _kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, C, D), _row(0, 0)),
            pl.BlockSpec((None, None, BS, D), _slot_map),
            pl.BlockSpec((None, None, BS, D), _slot_map),
        ],
        scratch_shapes=_softmax_scratch(H, C, hd, G, q.dtype),
    )
    # alias indices count the scalar-prefetch operands: pools are operands
    # 8/9 of (layer, bt, c0, cl, so, q, k_new, v_new, k_pool, v_pool)
    o, k_pool, v_pool = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, C, D), q.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        input_output_aliases={8: 1, 9: 2},
        interpret=interpret,
    )(layer, block_tables, c0, cl, slot_offsets, qf,
      k_new.reshape(B, 1, D), v_new.reshape(B, 1, D), k_pool, v_pool)
    return _unfold_queries(o, q.shape, rep), k_pool, v_pool


def _make_paged_append():
    kwargs = dict(static_argnames=("d_true", "interpret"),
                  donate_argnums=(3, 4))
    try:
        from ..obs.profiler import profiled_jit

        return profiled_jit("pw.paged_append_attend", _paged_append_fn,
                            **kwargs)
    except Exception:  # pragma: no cover - import-order edge
        return jax.jit(_paged_append_fn, **kwargs)


_paged_append = _make_paged_append()


def _stacked(k_pool, v_pool, layer):
    """Resolve the two pool conventions to (stacked pools, (1,) int32
    layer): ``layer=None`` means one layer's (num_blocks, BS, H*hd)
    slices (a leading unit axis is free); ``layer=i`` means the stacked
    (L, num_blocks, BS, H*hd) pool of all layers, used in place."""
    if layer is None:
        return k_pool[None], v_pool[None], jnp.zeros((1,), jnp.int32)
    return k_pool, v_pool, jnp.asarray(layer, jnp.int32).reshape(1)


def _layer_of(pool, layer):
    """One layer's (num_blocks, BS, H*hd) pool, for the gather reference."""
    return pool if layer is None else pool[layer]


def paged_append_attend(q, k_new, v_new, k_pool, v_pool, block_tables,
                        context_lens, slot_blocks, slot_offsets, *,
                        layer=None, use_pallas: bool | None = None,
                        interpret: bool | None = None):
    """Fused decode append+attend over one layer of the pool: scatter
    the incoming token's K/V at ``(slot_blocks, slot_offsets)`` and
    attend through ``block_tables`` in a single program.

    q: (B, 1, H, hd); k_new/v_new: (B, H, hd); block_tables (B, NB);
    context_lens/slot_blocks/slot_offsets: (B,) int32 with the slot at
    the context tail (``slot_offsets == (context_lens-1) % BS`` and
    ``slot_blocks`` the matching table entry — the decode-step layout).
    Pools: with ``layer=None`` one layer's (num_blocks, BS, H*hd)
    slices; with ``layer=i`` the stacked (L, num_blocks, BS, H*hd) pool
    of all layers, updated IN PLACE at layer i — the step programs use
    this form, so that no layer is sliced out of the pool or written
    back into it.  Returns ``(attn_out, k_pool, v_pool)`` with the pools
    updated, in the form they came in; bit-identical to
    scatter-then-:func:`paged_attention_reference` on the reference path
    (tier-1), one fused Pallas program on TPU (pool blocks aliased in
    place — the standalone scatter disappears).  The kernel reads the
    pool's blocks as they lie in HBM: nothing is padded."""
    backend = jax.default_backend()
    B, hd = q.shape[0], q.shape[-1]
    if use_pallas is None:
        use_pallas = backend == "tpu"
    if not use_pallas:
        at = (slot_blocks, slot_offsets) if layer is None \
            else (layer, slot_blocks, slot_offsets)
        k_pool = k_pool.at[at].set(k_new.reshape(B, -1))
        v_pool = v_pool.at[at].set(v_new.reshape(B, -1))
        a = paged_attention_reference(
            q, _layer_of(k_pool, layer), _layer_of(v_pool, layer),
            block_tables, context_lens,
        )
        return a, k_pool, v_pool
    _require_positive_context(1, context_lens, None, None)
    c0, cl_last = _query_context(1, context_lens, None, None)
    kk, vv, li = _stacked(k_pool, v_pool, layer)
    a, kk, vv = _paged_append(
        q, k_new, v_new, kk, vv, li,
        jnp.asarray(block_tables, jnp.int32),
        c0.astype(jnp.int32), cl_last.astype(jnp.int32),
        jnp.asarray(slot_offsets, jnp.int32),
        d_true=hd,
        interpret=(backend != "tpu") if interpret is None else interpret,
    )
    return (a, kk[0], vv[0]) if layer is None else (a, kk, vv)


def paged_attention(q, k_pool, v_pool, block_tables, context_lens=None, *,
                    start_pos=None, n_valid=None, layer=None,
                    use_pallas: bool | None = None,
                    interpret: bool | None = None):
    """Dispatch: Pallas kernel on TPU, gather reference elsewhere (the
    interpreted kernel is for tests).  Same signature/shape/raggedness
    contract as :func:`paged_attention_reference`, plus ``layer``: None
    for one layer's (num_blocks, BS, H*hd) pool slices, ``i`` for the
    stacked (L, num_blocks, BS, H*hd) pool read in place at layer i.
    The kernel reads the pools where they are, in the layout they have:
    nothing pool-sized is padded, sliced or copied by this function."""
    backend = jax.default_backend()
    if use_pallas is None:
        use_pallas = backend == "tpu"
    if not use_pallas:
        return paged_attention_reference(
            q, _layer_of(k_pool, layer), _layer_of(v_pool, layer),
            block_tables, context_lens,
            start_pos=start_pos, n_valid=n_valid,
        )
    B, C, H, hd = q.shape
    _require_positive_context(C, context_lens, start_pos, n_valid)
    c0, cl_last = _query_context(C, context_lens, start_pos, n_valid)
    kk, vv, li = _stacked(k_pool, v_pool, layer)
    return _paged_ragged(
        q, kk, vv, li,
        jnp.asarray(block_tables, jnp.int32),
        c0.astype(jnp.int32), cl_last.astype(jnp.int32),
        d_true=hd,
        interpret=(backend != "tpu") if interpret is None else interpret,
    )
