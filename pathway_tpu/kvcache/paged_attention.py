"""Ragged paged attention: multi-query attention through a block table.

Two tiers with one contract:

- :func:`paged_attention_reference` — pure-JAX gather path (tier-1,
  ``JAX_PLATFORMS=cpu``).  It mirrors ``models/decoder.decode_step``'s
  einsum strings and masking EXACTLY, so when the gathered context length
  (``num_table_blocks * block_size``) equals the dense path's cache
  length, the logits are bit-identical to the dense batch-1 decode — the
  token-identity guarantee tests/test_kvcache.py pins.
- a Pallas TPU kernel (Ragged-Paged-Attention shape, arxiv 2604.15464):
  the block table rides in scalar-prefetch SMEM and each grid step
  gathers one SPAN of physical KV blocks straight into VMEM - the
  (B, L, H, D) gathered copy the reference path materializes in HBM never
  exists.  Online softmax is carried in VMEM scratch across the
  (sequential, innermost) span dimension, same (m, l, acc) recurrence as
  ops/attention_pallas.py.

The span grid (PR 28): the allocator's block is 16 tokens, and a grid
step that attended one block filled 16 of the 128 lanes of every register
its scores, mask, ``exp`` and row sums occupy, and 16 of the MXU's
columns.  A step attends ``K = 128 // block_size`` blocks (a lane tile of
keys; :func:`span_blocks`), so the grid is ``(B, ceil(NB / K))`` and a
step over 128 keys issues the operations a step over 16 did.  The pools
stay whole in HBM (one operand each: the fused append aliases them, and
XLA copies a pool that is also passed as further operands) and the kernels
gather a span themselves through the table, two buffers deep
(:func:`_next_span`).  Where a block's lanes are no whole tiles Mosaic
cannot slice the pool for a copy: K = 1, the block comes through its block
spec.

The K/V writer (PR 30): a mixed step's new rows reach the pool through
:func:`paged_write_rows`, one kernel call a layer for both pools, in whole
blocks: the grid runs over the packed tokens, a token's block comes and
goes through one aliased block spec, and the tokens of a run patch the
block while it is resident.  (The decode step appends inside its attention
kernel; a mixed step cannot, because a row may attend what another row
writes in the same step.)

The window (PR 31): both attention kernels and the gather reference take
a static ``window`` (a sliding-window layer's width; None: full attention,
and then nothing below is traced - the kernels are the ones of before).  A
query column of context ``n`` sees the keys ``n - window <= j < n``
(:func:`_attend_span`'s mask), and a span wholly behind the window of a
row's FIRST column is dead as a span past the row's context is: no copy
started, nothing computed, and the double buffer's "next live span" skips
it (:func:`_first_span`, :func:`_next_span`).  A table's entries behind the
window may point at the null block (kvcache/windowed.py frees those
blocks); what a live span still gathers of them is masked.  Where a head's
folded query rows are many (eight query heads a K/V head at a chunk of
256: 2,048 rows), the call asks for the VMEM its scratch needs
(:func:`_vmem_limit`).

The query-column tiles (PR 37): a mixed step hands the ragged kernels
sixteen rows at the chunk's width, of which about two carry a chunk, five
one decoded token and nine nothing (an idle row: context 1 on the null
block), and a row used to pay for the chunk's width whatever it held
(only one head a group ran in row tiles, and only a span's update).  Now
a row pays for its LIVE query columns (``cl - c0 + 1``, from the
scalar-prefetched ``c0`` / ``cl``: no new operand), in every head grouping
and in all three per-row pieces: the layout and reset of
:func:`_start_row`, a span's update in :func:`_attend_span`, the division
and write of :func:`_write_out`.  The tile is a rule of shapes
(:func:`_col_tiles`: the heads a group, the folded columns, ``rep``, the
query dtype's sublane tile; no argument, no model's name): a row of no
more than ``first`` live folded columns (8 at two heads a group, a sublane
tile at one: a decode row, an idle row, a chunk's short tail) runs one tile
of ``first`` columns; a longer one runs the :data:`_ROW_TILE`-row tiles its
live columns reach into, in ONE loop (:func:`_live_tiles`), so that the
kernel's code does not grow with the chunk.  An idle row is not skipped:
it costs its first tile over one span, and the double buffer's order of
copies stays what it was.  The columns past a row's live tiles are not
computed; the output block is written as zeros there (padding no caller
reads: the step programs gather ``a_rows[tok_row, tok_col]``).  One query
column a row (``C == rep``: the append kernels of the chains and the decode
step) is ONE tile, by the same code (``body(0, C)``, static): those kernels
trace to the jaxpr they traced to before.  So is a width that is no whole
sublane tiles of the query dtype (a verify round's ``k + 1`` columns: 40
folded ones at eight query heads a K/V head), which runs as it ran before.
The engine counts how full the tiles run from the same rule
(:func:`query_tile_columns`: ``kv_query_tile_cols`` on ``pw.round.build``).

Keys and values of two widths, and the sink (PR 40): the three kernels
and the gather reference take a V pool whose heads have a width of their
own (``hd_v = V lanes / K/V heads``, read from the two pools' shapes: no
argument) and ``sinks`` (None: none), one learned f32 logit a query head
that joins its softmax's denominator and carries no value.  Both are
static and absent from a call that gives neither: with V as wide as K and
no sinks every kernel traces to the jaxpr it traced to before.  With ``hd_v
!= hd`` heads go in groups whose K lanes AND V lanes are whole tiles
(:func:`_heads_per_group`: two heads of 192 beside two of 128 are 384 and
256 lanes), the score matmul slices ``kbuf`` by the group's K lanes and ``p
@ v`` slices ``vbuf`` by its V lanes, ``acc`` and the output block are V's
width, and the writer and the fused append move a K row and a V row of their
own widths; nothing is padded in the pool.  The sinks reach the kernels as
(Hkv, rep, 128) f32 (:func:`_sink_rows`: a K/V head's ``rep`` query heads
down the sublanes, in the order the fold gives its columns) and are the
state a row STARTS from (:func:`_start_row`: ``m = sink, l = 1, acc = 0``
where it is ``-inf, 0, 0``): they cost nothing a span.

The pieces (PR 41): a mixed step's queries are a packed stream of T tokens
(B + the chunk) that its B rows of C columns hold about a sixteenth of at
the chunk's width: the rows as they stood (B x C query slots, gathered,
folded into the kernel's order, relaid where a head is no whole lane tiles,
streamed into the kernel and its output back, whatever the rows hold) cost
the long configurations a third of their step.  :func:`paged_attention`
takes the packed stream and cuts the kernel's rows from it
(:func:`_pieces`, on the device from the step's own arrays): a row of ``n``
valid columns is ``ceil(n / P)`` kernel rows of ``P`` columns each, over
the row's table, its columns' contexts from the piece's first, and only
``N = T // P + B`` of them (the rows' at most T + B columns never need
more), the output gathered back to the stream.  ``P`` is a rule of shapes
(:func:`query_layout`: a kernel row costs its query slots' bytes, which
cross HBM once, :data:`_SPLIT_TRIPS` times where the fold splits lane
tiles, and its walk of a table; P is the cheapest width of whole sublane
tiles that fits VMEM, the rows as they are where they cost no more -
GPT-2's cell).  The kernel does not change: it sees rows of P columns.
:func:`latent_attention` lays the latent pool's call out by the same rule
and the same pieces, through the same dispatch (:func:`_dispatch`; a pool
of one array: its walk is one array's, its output a query head wide).  A
query column sees the same spans in the same order as in its row (a span
behind a piece's window, which the row still walked, is all masked for the
piece's columns and leaves their softmax as it was), so the outputs are the
rows' bit for bit.  Under a window a piece's dead spans are its own, so a chunk
wider than the window computes the spans its piece's columns see, not the
chunk's (:func:`window_pairs` counts both for the engine).

Round-8 raggedness (the fused mixed decode/prefill step):

- every row carries ``C >= 1`` query tokens at CONSECUTIVE positions -
  decode rows use C=1, prefill-chunk rows up to the chunk width.  Query
  column ``c`` of row ``b`` attends to ``start_pos[b] + c + 1`` tokens
  (its own position included), clamped at the row's true context
  ``start_pos[b] + n_valid[b]`` for padding columns past ``n_valid``.
- the grid is length-aware: spans past a row's context are neither
  copied (no copy is started for a dead grid step) nor computed
  (``@pl.when`` guards), and the output is written at the row's LAST
  VALID span instead of the grid edge - a 1-block row in a 64-block
  table costs one step of work, not eight.
- a row is width-aware too (PR 37, above): a live span's work is the
  row's live column tiles, not the chunk's ``C`` columns - a decode row
  beside a chunk row pays one tile of 8 or 16 folded columns a span, the
  columns past ``n_valid`` in a live tile are computed with a clamped
  context and dropped by the caller, the tiles past them are not computed.

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
_LANES = 128  # keys one grid step attends: a lane tile of scores
_ROW_TILE = 256  # query rows (heads a group x columns) a column tile takes


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
                              n_valid=None, window: int | None = None,
                              sinks=None, v_heads: int | None = None):
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
    ``window`` (a sliding-window layer): a query at position ``p`` sees
    the keys at ``p - window < j <= p`` only.  The V pool's heads may have
    a width of their own (``hd_v = v lanes / Hkv``: the output's).
    ``sinks`` (H,) f32: one more logit a query head in its softmax, whose
    probability is dropped (it carries no value).  ``v_heads`` (None: as
    many as K/V heads): the V pool holds that many heads, each ``v lanes /
    v_heads`` wide, and K/V head ``j`` reads V head ``j % v_heads`` - a
    differential layer's two key halves over one set of values.
    Returns (B, C, H, hd_v).
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
    v = v_pool[block_tables].reshape(B, NB * BS, v_heads or k.shape[2], -1)
    if v_heads is not None:  # K/V head j reads V head j % v_heads
        v = jnp.tile(v, (1, 1, k.shape[2] // v_heads, 1))
    if k.shape[2] != H:  # grouped queries: each K/V head serves H/Hkv
        k = jnp.repeat(k, H // k.shape[2], axis=2)
        v = jnp.repeat(v, H // v.shape[2], axis=2)
    # decode_step's exact math: same einsum strings, mask, f32 softmax
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    k_pos = jnp.arange(NB * BS)[None, None, :]
    valid = k_pos < ctx[:, :, None]
    if window is not None:
        valid = valid & (k_pos >= ctx[:, :, None] - window)
    valid = valid[:, None, :, :]
    scores = jnp.where(valid, scores, _NEG).astype(jnp.float32)
    if sinks is not None:
        sink = jnp.broadcast_to(
            jnp.asarray(sinks, jnp.float32)[None, :, None, None],
            scores.shape[:3] + (1,))
        scores = jnp.concatenate([scores, sink], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)[..., :NB * BS].astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _heads_per_group(H: int, hd: int, C: int,
                     hd_v: int | None = None) -> int:
    """How many heads one matmul of the kernels takes.  A block of the
    pool is (BS, H*hd), heads side by side on the lane axis, and a slice
    of it that starts or ends inside a 128-lane tile costs a shuffle per
    block; so heads go in groups whose lanes fill whole tiles (two heads
    of 64), the group's query rows stacked on the sublane axis with the
    other heads' lanes zeroed.  Where no such group divides H (five heads
    a shard) or its rows would not fill a sublane tile (one decode row),
    all heads form one group: the whole lane axis, no slice at all.
    ``hd_v`` (a V head of another width than a K head's): the group's lanes
    are whole tiles in both pools (two heads of 192 beside two of 128)."""
    g = 128 // math.gcd(hd, 128)
    if hd_v is not None:
        g = math.lcm(g, 128 // math.gcd(hd_v, 128))
    return g if H % g == 0 and (g * C) % 8 == 0 else H


def _own_lanes(R: int, W: int, C: int, hd: int):
    """(R, W) bool: lane belongs to the head of its row (row i*C + c of
    a group, or of a column tile of ``C`` columns, is head i, whose lanes
    are [i*hd, (i+1)*hd))."""
    return (jax.lax.broadcasted_iota(jnp.int32, (R, W), 0) // C
            == jax.lax.broadcasted_iota(jnp.int32, (R, W), 1) // hd)


def _sublanes(dtype) -> int:
    """Rows of one sublane tile of ``dtype``: 8 of f32, 16 of bf16."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _col_tiles(G: int, C: int, rep: int, dtype) -> tuple | None:
    """The column tiles a group's ``C`` folded query columns lie in:
    ``(first, tile)`` columns, or None where the row is ONE tile (the
    group's ``G * C`` rows as one update, head after head).  A rule of
    shapes alone:

    - one query column a row (``C == rep``: the append kernels of the chains
      and the decode step) is one tile;
    - ``tile`` columns are :data:`_ROW_TILE` rows of the group (128 columns
      at two heads a group, 256 at one), for the MXU; a row with more than
      ``first`` live columns runs the ``tile``-wide tiles its live columns
      reach into, in a loop;
    - ``first`` is the smallest tile the layout allows: every head's piece
      whole f32 sublane tiles (the softmax state is f32), the tile's rows
      whole sublane tiles of the query dtype, whole query columns (``rep``
      folded ones each): 8 columns at two heads a group, 16 folded columns
      at one.  A row with no more live columns than that (a decode row, an
      idle row of a mixed step, a chunk's short tail) runs that tile alone.

    A width that is no whole sublane tiles of the query dtype (a verify
    round's ``k + 1`` columns: a tile's queries come in whole sublane tiles,
    which must lie inside the block) or that ``tile`` does not divide is one
    tile."""
    sub = _sublanes(dtype)
    first = math.lcm(8, sub // math.gcd(sub, G), rep)
    unit = math.lcm(first, sub)
    tile = min(max(_ROW_TILE // G // unit, 1) * unit, C)
    if C == rep or C % sub or C % tile or first >= tile:
        return None
    return first, tile


def _wide_tiles(live, first: int, tile: int):
    """How many ``tile``-wide tiles a row of ``live`` folded live columns
    runs: those that start under ``live``, or none where the ``first`` tile
    holds them all.  Operators alone, so that the kernels' traced scalars
    (:func:`_live_tiles`) and the engine's arrays
    (:func:`query_tile_columns`) walk ONE rule."""
    return (live > first) * ((live + tile - 1) // tile)


def _live_tiles(tiles: tuple | None, live, C: int, body) -> None:
    """``body(t0, Tc)`` for the column tiles of a row of ``C`` folded
    columns, ``live`` of them live (at least one): the ``first`` tile alone,
    or the ``tile``-wide ones that start under ``live``, one loop whose body
    does not grow with their number; without ``tiles`` the row is one tile,
    ``body(0, C)``, and nothing else is traced.  A tile ``[t0, t0 + Tc)``
    holds every group's rows of its columns, group after group, head i of a
    group at ``i*Tc`` within the group's (:func:`_group`): the two modes lay
    a row's first columns out differently, and every per-row piece of a
    kernel (:func:`_start_row`, :func:`_attend_span`, :func:`_write_out`)
    takes the mode from the same ``live``."""
    if tiles is None:
        body(0, C)
        return
    first, tile = tiles
    wide = _wide_tiles(live, first, tile)
    pl.when(wide == 0)(lambda: body(0, first))

    def step(t, carry):
        body(pl.multiple_of(t * tile, tile), tile)
        return carry

    jax.lax.fori_loop(0, wide, step, 0)


def _each_group(n_groups: int, tiles: tuple | None, fn) -> None:
    """``fn(g)`` for every group of heads.  One tile a row: group after
    group, as the code stands.  A tiled row: in two turns of half the groups
    each: within a turn the groups' updates stand side by side in the code
    and are scheduled together (each is a chain of a matmul, a softmax step
    and a matmul); the two turns are one loop.  A tiled kernel holds two
    tile bodies (:func:`_live_tiles`) where a one-tile kernel holds one, and
    the kernel is traced and lowered anew in every process whatever the
    compile cache holds: with the turns it traces as many group bodies as
    the one-tile kernel did, and the program's set-up time stays what it was.
    A DEBT, not a design (ROADMAP A9 (7), "lower a step program once"): the
    turns cost the overlap between groups (~1 ms a mixed round of
    ``serve_closed16``), and an odd number of groups is unrolled, at twice
    the one-tile kernel's bodies.  When a step program is lowered once a
    process, unroll every group and drop the traced ``g`` of
    :func:`_group`."""
    if tiles is None or n_groups % 2:
        for g in range(n_groups):
            fn(g)
        return
    turn = n_groups // 2

    def step(k, carry):
        for i in range(turn):
            fn(k * turn + i)
        return carry

    jax.lax.fori_loop(0, 2, step, 0)


def _rows(start, n: int):
    """``n`` rows from ``start``, a multiple of ``n`` (said where traced)."""
    return pl.ds(start if isinstance(start, int)
                 else pl.multiple_of(start, n), n)


def _group(g, n_groups: int, G: int, W: int, t0, Tc: int) -> tuple:
    """(rows, lanes) of group ``g`` in the tile of ``Tc`` columns at column
    ``t0``: the scratch rows lie (tile, group, head, column in tile), so a
    tile's rows of all groups are one slice (``_rows(n_groups * G * t0,
    n_groups * G * Tc)``: what :func:`_start_row` resets) and a one-tile
    row's are the whole scratch, group ``g`` at ``g * G * C``."""
    n = G * Tc
    lanes = _lanes(g, W)
    return _rows(n_groups * G * t0 + g * n, n), lanes


def _lanes(g, W: int):
    """Group ``g``'s ``W`` lanes of a pool's block."""
    return slice(g * W, (g + 1) * W) if isinstance(g, int) \
        else pl.ds(pl.multiple_of(g * W, W), W)


def _live_columns(b, c0_ref, cl_ref, rep: int, tiles: tuple | None):
    """Row ``b``'s live folded query columns (its valid columns, the last of
    which attends ``cl`` keys and the first ``c0``, times ``rep``), where
    its rows lie in column ``tiles``; None, and nothing traced, where the
    row is one tile (those kernels stay the jaxpr they were)."""
    if tiles is None:
        return None
    return (cl_ref[b] - c0_ref[b] + 1) * rep


def query_tile_columns(n_valid, C: int, H: int, hd: int, D: int, dtype,
                       hd_v: int | None = None, cols: int | None = None):
    """The query columns the kernels' live tiles cover for rows of
    ``n_valid`` live columns each (an idle row has one) of ``C``, ``H``
    query heads of ``hd`` over a pool of ``D`` lanes, queries in ``dtype``
    (``hd_v``: a V head of another width; ``cols``: the ``P`` of
    :func:`query_layout`, a row in pieces of its live columns - on the K/V
    pools and on the latent pool alike): the rule of :func:`_col_tiles` as
    :func:`_live_tiles` runs it (:func:`_wide_tiles`), for the engine's
    ``kv_query_tile_cols``.  (len(n_valid),) int64."""
    return _piece_tile_columns(n_valid, C, H, hd, D, dtype, hd_v,
                               cols)[0].sum(axis=1)


def _piece_tile_columns(n_valid, C: int, H: int, hd: int, D: int, dtype,
                        hd_v: int | None = None, cols: int | None = None):
    """``(run, cols)``: the query columns the live tiles of each piece of
    each row cover ((rows, pieces) int64; 0 where a row has no such piece:
    a row is the ``ceil(n / cols)`` pieces of its live columns) and a
    piece's width."""
    n = np.asarray(n_valid, np.int64)
    cols = C if cols is None else cols
    pieces = -(-C // cols)
    rep = H * hd // D
    tiles = _col_tiles(_heads_per_group(D // hd, hd, cols * rep, hd_v),
                       cols * rep, rep, dtype)
    live = np.clip(n[:, None] - cols * np.arange(pieces)[None, :], 0,
                   cols) * rep
    if tiles is None:
        run = np.full_like(live, cols * rep)
    else:
        first, tile = tiles
        wide = _wide_tiles(live, first, tile)
        run = np.where(wide == 0, first, wide * tile)
    return np.where(live > 0, run, 0) // rep, cols


def window_pairs(start_pos, n_valid, C: int, H: int, hd: int, D: int, dtype,
                 window: int, span: int, hd_v: int | None = None,
                 cols: int | None = None) -> tuple:
    """What a sliding-window layer's call of the ragged kernel sees and what
    it computes, for rows of ``n_valid`` live columns from position
    ``start_pos`` (query geometry as :func:`query_tile_columns` takes it,
    ``cols`` the layout's piece, ``span`` the keys a grid step attends):
    ``(band, run)`` query-key pairs - ``band``, each live column's
    ``min(context, window)``; ``run``, each piece's live tile columns times
    the keys of its live spans (from the span of the first key its first
    column sees to the span of its last key: :func:`_first_span`).  ``band
    / run`` is how full the kernel's work is of pairs the softmax keeps;
    both from shapes alone, for the engine's ``kv_window_band_pairs`` /
    ``kv_window_span_pairs``."""
    start = np.asarray(start_pos, np.int64)
    n = np.asarray(n_valid, np.int64)
    run, cols = _piece_tile_columns(n, C, H, hd, D, dtype, hd_v=hd_v,
                                    cols=cols)
    c0 = start[:, None] + 1 + cols * np.arange(run.shape[1])[None, :]
    cl = np.minimum((start + n)[:, None], c0 + cols - 1)
    dead = c0 > cl  # no such piece: run is 0 there
    c0, cl = np.where(dead, 1, c0), np.where(dead, 1, cl)
    spans = (cl - 1) // span - np.maximum(c0 - window, 0) // span + 1
    ctx = start[:, None] + 1 + np.arange(C)[None, :]
    band = np.where(np.arange(C)[None, :] < n[:, None],
                    np.minimum(ctx, window), 0)
    return int(band.sum()), int((run * spans).sum() * span)


def _start_row(q_ref, qm_ref, m_ref, l_ref, acc_ref, live=None, *, C: int,
               G: int, hd: int, tiles: tuple | None = None,
               hd_v: int | None = None, sink_ref=None, rep: int = 1,
               v_heads: int | None = None):
    """First grid step of a batch row: reset the online softmax and lay
    the row's queries out for the grouped matmuls, for the row's live
    column tiles (:func:`_live_tiles`; one tile of ``C`` columns without
    ``tiles``).  A tile's ``qm_ref`` row ``i*Tc + c`` of a group
    (:func:`_group`) holds query column ``t0 + c`` of the group's head i in
    the lanes of i's place in its group and zeros in the other heads' lanes,
    so that ``qm[group rows] @ k[:, group lanes].T`` is every head's own
    scores (a zero lane adds an exact 0 to the f32 sum).  The rows of the
    tiles that are not live keep what they held, and nothing reads them.
    ``hd_v``: ``acc_ref`` is as wide as the group's V heads.  ``sink_ref``
    ((Hkv, rep, 128) f32: K/V head ``kv``'s ``rep`` query heads' sinks down
    the sublanes, broadcast over the lanes): the softmax starts from the
    sink, ``m = sink, l = 1``, in place of ``-inf, 0`` - a tile's columns
    start at a whole query column (:func:`_col_tiles`), so the rows of a
    head's piece are its ``rep`` sinks over and over."""
    W, n_groups = G * hd, qm_ref.shape[0] // (G * C)
    sub = _sublanes(q_ref.dtype)

    def lay(t0, Tc: int):
        n = G * Tc
        tile = _rows(n_groups * G * t0, n_groups * n)  # every group's rows
        if sink_ref is None:
            m_ref[tile] = jnp.full((n_groups * n, m_ref.shape[1]), _NEG,
                                   m_ref.dtype)
            l_ref[tile] = jnp.zeros((n_groups * n, l_ref.shape[1]),
                                    l_ref.dtype)
        else:
            l_ref[tile] = jnp.ones((n_groups * n, l_ref.shape[1]),
                                   l_ref.dtype)
        acc_ref[tile] = jnp.zeros((n_groups * n, acc_ref.shape[1]),
                                  acc_ref.dtype)
        own = _own_lanes(n, W, Tc, hd)
        # whole sublane tiles of the query dtype come in (they lie inside
        # the block: _col_tiles); the first tile's columns are cut from them
        cols = pl.ds(t0, min(-(-Tc // sub) * sub, C))

        def group(g):
            rows, lanes = _group(g, n_groups, G, W, t0, Tc)
            # in f32, whose tiles the mask shares (a bf16 select against an
            # f32-tiled mask is a relayout Mosaic refuses); the way back is
            # exact
            qg = q_ref[cols, lanes].astype(jnp.float32)
            if qg.shape[0] > Tc:
                qg = qg[:Tc]
            qg = jnp.broadcast_to(qg, (n, W)) if Tc == 1 \
                else jnp.concatenate([qg] * G, axis=0)
            qm_ref[rows, :] = jnp.where(own, qg, 0.0).astype(qm_ref.dtype)
            if sink_ref is not None:
                m_ref[rows] = jnp.concatenate(
                    [sink_ref[g * G + i] for i in range(G)
                     for _ in range(Tc // rep)], axis=0)

        _each_group(n_groups, tiles, group)

    _live_tiles(tiles, live, C, lay)


def _attend_span(j, c0, ctx, kbuf, vbuf, slot, qm_ref, m_ref, l_ref, acc_ref,
                 live=None, *, scale: float, rep: int, C: int, G: int,
                 hd: int, window: int | None = None,
                 tiles: tuple | None = None, hd_v: int | None = None,
                 v_heads: int | None = None):
    """One visible span's online-softmax update, shared by both kernels:
    the ``span`` keys (a lane tile's worth: K blocks of the pool) that grid
    step ``j`` attends, so that scores, mask, ``exp`` and row sums fill the
    lanes of their registers and ``p @ v`` contracts over a whole tile.
    ``kbuf[slot]`` / ``vbuf[slot]``: the span's (span, H*hd) K / V - the
    pool's blocks as they lie in HBM, one under the other.
    Per LIVE column tile of the row (:func:`_live_tiles`; one tile of ``C``
    columns without ``tiles``) and group of G heads: scores (G*Tc, span) of
    the group's stacked query rows against the group's lanes of K, each
    row's own softmax recurrence (m, l in f32), and ``p @ v`` over the
    group's lanes of V into acc (G*Tc, G*hd) f32 - of which row ``i*Tc + c``
    is read only in head i's lanes (:func:`_write_out`).  So a row pays for
    its live query columns and not for the chunk's width; the columns past
    them are padding whose output the caller drops.  ``rep`` > 1 (grouped
    queries): the ``rep`` query heads of a K/V head ride as ``rep``
    neighbouring columns of it, so of the C columns here column ``c`` is
    query column ``c // rep``.  ``window``: a column of context ``n``
    (position ``n - 1``) sees the keys at ``n - window <= j < n`` only.
    ``hd_v``: ``vbuf``'s heads are that wide, so the group's lanes of V and
    acc's width are ``G * hd_v`` where K's are ``G * hd``.  ``v_heads``
    (a differential layer): ``vbuf`` holds that many heads and K/V head
    ``j`` reads V head ``j % v_heads``, so group ``g``'s V lanes are those of
    group ``g % (v_heads / G)``; a group wider than the V heads (every head
    one group) takes ``p @ v`` over all of V once and adds it to each of its
    ``G / v_heads`` copies in acc."""
    W, span = G * hd, kbuf.shape[1]
    n_groups = qm_ref.shape[0] // (G * C)

    def mask(t0, Tc: int):
        n = G * Tc
        k_pos = j * span + jax.lax.broadcasted_iota(jnp.int32, (n, span), 1)
        # column c attends to min(c0 + c, ctx) tokens; row i*Tc + c of a
        # group's tile is column t0 + c
        if C == rep:  # one query column a row (decode)
            col = 0
        else:
            col = jax.lax.broadcasted_iota(jnp.int32, (n, span), 0)
            if G > 1:
                col = col % Tc
            if not isinstance(t0, int) or t0:
                col = col + t0
            if rep > 1:
                col = col // rep
        col_ctx = jnp.minimum(c0 + col, ctx)
        valid = k_pos < col_ctx
        if window is not None:
            valid = valid & (k_pos >= col_ctx - window)
        return valid

    def update(rows, lanes, vlanes, valid, copies: int = 1):
        n = valid.shape[0]
        s = jax.lax.dot_general(
            qm_ref[rows], kbuf[slot, :, lanes],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (n, span)
        s = jnp.where(valid, s, _NEG)
        m_prev = m_ref[rows, :1]  # (n, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[rows] = jnp.broadcast_to(
            l_ref[rows, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
            (n, l_ref.shape[1]),
        )
        vb = vbuf[slot, :, vlanes]
        if copies == 1:
            acc_ref[rows] = acc_ref[rows] * corr + jax.lax.dot_general(
                p.astype(vb.dtype), vb,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            pv = jax.lax.dot_general(
                p.astype(vb.dtype), vb,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            w = pv.shape[1]
            for r in range(copies):
                part = slice(r * w, (r + 1) * w)
                acc_ref[rows, part] = acc_ref[rows, part] * corr + pv
        m_ref[rows] = jnp.broadcast_to(m_new, (n, m_ref.shape[1]))

    def attend(t0, Tc: int):
        valid = mask(t0, Tc)

        def group(g):
            rows, lanes = _group(g, n_groups, G, W, t0, Tc)
            if v_heads is None:
                update(rows, lanes,
                       lanes if hd_v is None else _lanes(g, G * hd_v), valid)
            elif G <= v_heads:
                update(rows, lanes, _lanes(g % (v_heads // G), G * hd_v),
                       valid)
            else:
                update(rows, lanes, slice(None), valid, G // v_heads)

        _each_group(n_groups, tiles, group)

    _live_tiles(tiles, live, C, attend)


def _write_out(o_ref, l_ref, acc_ref, live=None, *, C: int, G: int, hd: int,
               tiles: tuple | None = None, hd_v: int | None = None,
               v_heads: int | None = None):
    """o (C, H*hd_v): each head's lanes from its own rows of acc / l, for the
    row's live column tiles (:func:`_live_tiles`); with ``tiles`` the
    columns past them are written as zeros (padding the caller drops, which
    has to be finite)."""
    hd = hd if hd_v is None else hd_v
    W, n_groups = G * hd, acc_ref.shape[0] // (G * C)
    if tiles is not None:
        o_ref[:] = jnp.zeros_like(o_ref)

    def write(t0, Tc: int):
        own = _own_lanes(G * Tc, W, Tc, hd)

        def group(g):
            rows, lanes = _group(g, n_groups, G, W, t0, Tc)
            o = jnp.where(
                own, acc_ref[rows] / jnp.maximum(l_ref[rows, :1], 1e-20), 0.0
            )
            # one head's rows are non-zero in a lane: the sum picks them
            o = jnp.sum(o, axis=0, keepdims=True) if Tc == 1 \
                else sum(o[i * Tc:(i + 1) * Tc] for i in range(G))
            o_ref[pl.ds(t0, Tc), lanes] = o.astype(o_ref.dtype)

        _each_group(n_groups, tiles, group)

    _live_tiles(tiles, live, C, write)


def _span_copies(pools, bufs, sem, slot, block_of, *, K: int,
                 block_size: int):
    """The 2K async copies that fill buffer ``slot`` with one span: block
    ``block_of(i)`` = (layer, physical block) of each pool into rows
    ``[i*BS, (i+1)*BS)`` of its buffer, all on the slot's one semaphore."""
    return [
        pltpu.make_async_copy(
            pool.at[block_of(i)],
            buf.at[slot, pl.ds(i * block_size, block_size)], sem.at[slot])
        for i in range(K) for pool, buf in zip(pools, bufs)
    ]


def _first_span(c0, window: int, span: int):
    """The first span a row's queries see keys in under a window: the span
    of the first key its first column (context ``c0``) still sees; the
    spans before it are dead, as the spans past the context are."""
    return jnp.maximum(c0 - window, 0) // span


def _next_span(b, j, jlast, li_ref, bt_ref, c0_ref, cl_ref, pools, bufs, sem,
               n_ref, *, K: int, block_size: int, window: int | None = None):
    """The pool's blocks of this live grid step (b, j <= jlast, the row's
    last live span; with a ``window``, j >= the row's first live span
    too), in VMEM: returns the buffer slot that holds span j of row b.  The pools stay in HBM
    (one operand each, so the fused append can alias them) and the kernel
    gathers a span's K blocks itself through the scalar-prefetched block
    table, two buffers deep: the copies of the NEXT live span - span j+1
    of this row, or span 0 of the next row - start before this one's are
    waited for, so they run under this step's math; dead grid steps start
    and wait for nothing.  ``n_ref`` (SMEM) counts the live steps so far:
    its parity is the slot.  Blocks past the row's last one repeat it:
    every span is 2K copies, started and waited for alike, and no row of a
    buffer is left unwritten (it could hold NaNs that 0 * v keeps); their
    keys are masked by the context length, as a block's own dead keys are.

    K == 1 (:func:`span_blocks`: lanes that are no whole tiles, which the
    kernel's copies cannot slice, or a block that fills the lanes alone):
    ``pools`` are the step's one block of each pool, brought by its block
    spec as Pallas pipelines it; it goes into slot 0, so that what follows
    reads one place."""
    if K == 1:
        for pool, buf in zip(pools, bufs):
            buf[0] = pool[:]
        return 0
    B = cl_ref.shape[0]
    li = li_ref[0]

    def first(row):
        if window is None:
            return 0
        return _first_span(c0_ref[row], window, K * block_size)

    def copies(slot, row, span):
        last = (cl_ref[row] - 1) // block_size
        return _span_copies(
            pools, bufs, sem, slot,
            lambda i: (li, bt_ref[row, jnp.minimum(span * K + i, last)]),
            K=K, block_size=block_size)

    @pl.when((b == 0) & (j == first(0)))
    def _first():
        n_ref[0] = 0
        for c in copies(0, 0, first(0)):
            c.start()

    n = n_ref[0]
    slot = n % 2
    more = j < jlast

    @pl.when(more | (b + 1 < B))
    def _prefetch():
        row = jnp.where(more, b, jnp.minimum(b + 1, B - 1))
        for c in copies(1 - slot, row, jnp.where(more, j + 1, first(row))):
            c.start()

    for c in _span_copies(pools, bufs, sem, slot, lambda i: (0, 0), K=K,
                          block_size=block_size):
        c.wait()  # only the semaphore and the size matter to a wait
    n_ref[0] = n + 1
    return slot


def _live_span(j, c0, jlast, window: int | None, span: int):
    live = j <= jlast
    if window is not None:
        live = live & (j >= _first_span(c0, window, span))
    return live


def _paged_kernel(li_ref, bt_ref, c0_ref, cl_ref, *refs, K: int,
                  block_size: int, scale: float, rep: int,
                  window: int | None = None, sinks: bool = False, **geom):
    """Grid: (B, NS) - spans innermost, so (m, l, acc) scratch carries the
    online softmax across one sequence's spans.  Blocks: q and o
    (C, H*hd); the pools whole, in HBM, of which :func:`_next_span` brings
    grid step j's K blocks of layer ``li`` into ``kbuf`` / ``vbuf``
    ((2, K*block_size, H*hd)).  Spans past the row's context
    (``j > jlast``) and, with a ``window``, spans wholly behind the row's
    first column's window are dead: every ``@pl.when`` below is false and
    no copy is started, so they cost an empty grid step.  ``sinks``: the
    first operand is the query heads' sinks (:func:`_sink_rows`)."""
    sink, (q_ref, k_in, v_in, o_ref, kbuf, vbuf, sem, n_ref, qm_ref, m_ref,
           l_ref, acc_ref) = _split_sink(refs, sinks, rep)
    b = pl.program_id(0)
    j = pl.program_id(1)
    live = _live_columns(b, c0_ref, cl_ref, rep, geom["tiles"])

    @pl.when(j == 0)
    def _init():
        _start_row(q_ref, qm_ref, m_ref, l_ref, acc_ref, live, **geom,
                   **sink)

    c0 = c0_ref[b]       # column 0's context length
    ctx = cl_ref[b]      # the row's full context (last valid column's)
    jlast = (ctx - 1) // (K * block_size)  # last span with attended tokens

    # skip spans wholly past the context, or wholly behind the window
    @pl.when(_live_span(j, c0, jlast, window, K * block_size))
    def _visible():
        slot = _next_span(b, j, jlast, li_ref, bt_ref, c0_ref, cl_ref,
                          (k_in, v_in), (kbuf, vbuf), sem, n_ref, K=K,
                          block_size=block_size, window=window)
        _attend_span(j, c0, ctx, kbuf, vbuf, slot, qm_ref, m_ref, l_ref,
                     acc_ref, live, scale=scale, rep=rep, window=window,
                     **geom)

    # write at the row's LAST VALID span, not the grid edge: later grid
    # steps touch nothing, and the (per-row) output block flushes when
    # the grid leaves row b
    @pl.when(j == jlast)
    def _final():
        _write_out(o_ref, l_ref, acc_ref, live, **geom)


def _split_sink(refs: tuple, sinks: bool, rep: int) -> tuple:
    """A kernel's operands after the scalar-prefetched ones: ``(what
    :func:`_start_row` takes of the sinks, the other refs)``."""
    if not sinks:
        return {}, refs
    return {"sink_ref": refs[0], "rep": rep}, refs[1:]


def _sink_rows(sinks, Hkv: int, rep: int, operands: tuple,
               geom: dict) -> tuple:
    """``(operands, leading in_specs, geom)`` of a kernel call with
    ``sinks`` (None: as they came, and nothing traced).  The sinks go first,
    as the kernels take them: (Hkv, rep, 128) f32, K/V head ``kv``'s query
    heads ``kv * rep + r`` down the sublanes (the order the fold gives its
    columns, :func:`_fold_queries`), each over the lanes of the softmax
    state; their block spec is the whole array, once."""
    if sinks is None:
        return operands, [], geom
    rows = jnp.broadcast_to(
        jnp.asarray(sinks, jnp.float32).reshape(Hkv, rep, 1), (Hkv, rep, 128))
    spec = pl.BlockSpec((Hkv, rep, 128), lambda *_: (0, 0, 0))
    return (rows, *operands), [spec], {**geom, "sinks": True}


def _scratch(K: int, BS: int, D: int, pool_dtype, H: int, C: int, hd: int,
             G: int, dtype, Dv: int | None = None, hd_v: int | None = None):
    Dv, hd_v = D if Dv is None else Dv, hd if hd_v is None else hd_v
    return [
        pltpu.VMEM((2, K * BS, D), pool_dtype),    # kbuf: this span, next
        pltpu.VMEM((2, K * BS, Dv), pool_dtype),   # vbuf
        pltpu.SemaphoreType.DMA((2,)),             # one a buffer slot
        pltpu.SMEM((1,), jnp.int32),               # live steps so far
        pltpu.VMEM((H * C, G * hd), dtype),        # qm: grouped queries
        pltpu.VMEM((H * C, 128), jnp.float32),     # m
        pltpu.VMEM((H * C, 128), jnp.float32),     # l
        pltpu.VMEM((H * C, G * hd_v), jnp.float32),  # acc
    ]


def _value_geometry(hd: int, D: int, Dv: int,
                    v_heads: int | None = None) -> tuple:
    """``(H, hd_v, geom)`` of pools of ``D`` key and ``Dv`` value lanes under
    query heads of ``hd``: the K/V heads, a V head's width, and what the
    kernels' pieces take of it - nothing where V's heads are K's width, so
    that those kernels trace to what they traced to.  ``v_heads``: V holds
    that many heads (:func:`_attend_span`), none: as many as K."""
    H = D // hd
    if v_heads is not None:
        return H, Dv // v_heads, {"hd_v": Dv // v_heads, "v_heads": v_heads}
    hd_v = Dv // H
    return H, hd_v, ({} if hd_v == hd else {"hd_v": hd_v})


def _check_v_groups(G: int, v_heads: int | None) -> None:
    """A group of K/V heads reads whole groups of V heads, or all of them
    over and over (:func:`_attend_span`)."""
    if v_heads is not None and (v_heads % G if G <= v_heads else G % v_heads):
        raise ValueError(f"{G} heads a group do not tile {v_heads} V heads")


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


def span_blocks(block_size: int, table_blocks: int, lanes: int) -> int:
    """K: how many blocks of the pool one grid step of the kernels attends
    - as many as fill the 128 key lanes of the score registers (eight
    blocks of 16), never more than a row's table holds; one where a
    block's ``lanes`` (heads * head_dim) are no whole tiles (five heads of
    64 a shard): Mosaic refuses to slice such a pool for a copy."""
    if lanes % _LANES:
        return 1
    return max(1, min(_LANES // block_size, table_blocks))


def _pool_spec(K: int, BS: int, D: int, window: int | None = None):
    """How a pool reaches the kernels: whole, in HBM, for the kernel's own
    gather of K blocks a step; at K == 1 a block a step (clamped to the
    row's last block and, with a window, to its first live one, so that a
    dead step's copy is elided)."""
    if K > 1:
        return pl.BlockSpec(memory_space=pl.ANY)
    if window is None:
        return pl.BlockSpec(
            (None, None, BS, D),
            lambda b, j, li, bt, c0, cl, *_: (
                li[0], bt[b, jnp.minimum(j, (cl[b] - 1) // BS)], 0, 0))
    return pl.BlockSpec(
        (None, None, BS, D),
        lambda b, j, li, bt, c0, cl, *_: (
            li[0], bt[b, jnp.clip(j, _first_span(c0[b], window, BS),
                                  (cl[b] - 1) // BS)], 0, 0))


_VMEM_CAP = 100 * 2 ** 20   # the most a kernel call asks of the chip's 128 MiB
_VMEM_ROOM = 24 * 2 ** 20   # over the scratch and blocks: a row tile's scores


def _vmem_need(K: int, BS: int, D: int, pool_dtype, H: int, C: int, hd: int,
               G: int, dtype, Dv: int | None = None,
               hd_v: int | None = None) -> int:
    """Bytes of VMEM a ragged kernel's row holds: its scratch
    (:func:`_scratch`) and its double-buffered query and output blocks."""
    Dv, hd_v = D if Dv is None else Dv, hd if hd_v is None else hd_v
    item = jnp.dtype(dtype).itemsize
    return 2 * K * BS * (D + Dv) * jnp.dtype(pool_dtype).itemsize \
        + H * C * G * (hd * item + hd_v * 4) \
        + 2 * H * C * 128 * 4 + 2 * C * (D + H * hd_v) * item


def _vmem_limit(K: int, BS: int, D: int, pool_dtype, H: int, C: int, hd: int,
                G: int, dtype, Dv: int | None = None,
                hd_v: int | None = None) -> dict:
    """``compiler_params`` of a kernel call: none while the kernel's
    scratch and its double-buffered query and output blocks
    (:func:`_vmem_need`) fit the compiler's own scoped limit with room to
    spare (every geometry before eight folded query heads at a chunk of
    256), else a limit that holds them and the scores of a row tile."""
    need = _vmem_need(K, BS, D, pool_dtype, H, C, hd, G, dtype, Dv, hd_v)
    if need <= 10 * 2 ** 20:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=int(min(need + _VMEM_ROOM, _VMEM_CAP)))}


def _paged_ragged_fn(q, k_pool, v_pool, layer, block_tables, c0, cl,
                     sinks=None, *, d_true: int, interpret: bool = False,
                     window: int | None = None, v_heads: int | None = None):
    """q: (B, C, H, hd); pools (L, num_blocks, BS, Hkv*hd) - ALL layers'
    stacked pool, read in place at ``layer`` ((1,) int32): the kernel's own
    copies carry the layer, so no layer is ever sliced out of the pool.  A
    block is (BS, H*hd): a whole number of the chip's tiles when H*hd is
    a multiple of 128, so nothing is lane-padded and the pool's layout in
    HBM is the one the kernel reads; a grid step attends K of them
    (:func:`span_blocks`); c0/cl: (B,) per-row column-0 / last-column
    context lengths; ``window``: a sliding-window layer's width (static),
    None for full attention.  The V pool may have lanes of its own
    (``Hkv * hd_v``: a V head narrower than a K head, read from the two
    pools' shapes; the output is (B, C, H, hd_v)); ``sinks`` (H,) f32, or
    None: a learned logit a query head that joins its softmax's
    denominator (:func:`_start_row`); ``v_heads``: the V pool's heads where
    K/V head ``j`` reads V head ``j % v_heads`` (a differential layer; the
    output is (B, C, H, Dv / v_heads)).  A mixed step's rows come here as
    :func:`query_layout` lays them out: the B rows at the chunk's width, or
    N pieces of P query columns cut from the packed stream (:func:`_pieces`),
    each a row of the grid over its row's table."""
    B, hd = q.shape[0], q.shape[3]
    BS, D = k_pool.shape[2:]
    Dv = v_pool.shape[3]
    NB = block_tables.shape[1]
    K = span_blocks(BS, NB, math.gcd(D, Dv))
    qf, rep = _fold_queries(q, D)
    H, hd_v, geom = _value_geometry(hd, D, Dv, v_heads)
    C = qf.shape[1]
    G = _heads_per_group(H, hd, C, geom.get("hd_v"))
    _check_v_groups(G, v_heads)
    operands, sink_specs, geom = _sink_rows(
        sinks, H, rep, (qf, k_pool, v_pool), geom)
    kernel = functools.partial(
        _paged_kernel, K=K, block_size=BS, scale=1.0 / np.sqrt(d_true),
        rep=rep, C=C, G=G, hd=hd, tiles=_col_tiles(G, C, rep, q.dtype),
        **geom, **({} if window is None else {"window": int(window)}),
    )

    def row(lanes):
        return pl.BlockSpec((None, C, lanes), lambda b, j, *_: (b, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # layer, block_tables, c0, cl
        grid=(B, -(-NB // K)),
        in_specs=[*sink_specs, row(D), _pool_spec(K, BS, D, window),
                  _pool_spec(K, BS, Dv, window)],
        out_specs=row(H * hd_v),
        scratch_shapes=_scratch(K, BS, D, k_pool.dtype, H, C, hd, G, q.dtype,
                                Dv, hd_v),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, H * hd_v), q.dtype),
        interpret=interpret,
        **_vmem_limit(K, BS, D, k_pool.dtype, H, C, hd, G, q.dtype, Dv, hd_v),
    )(layer, block_tables, c0, cl, *operands)
    return _unfold_queries(out, q.shape[:3] + (hd_v,), rep)


def _make_paged_ragged():
    """Jit the standalone kernel entry point through the device cost
    observatory (Round-14); falls back to a plain jit while the obs
    package is still importing (circular-import window)."""
    kwargs = dict(static_argnames=("d_true", "interpret", "window",
                                   "v_heads"))
    try:
        from ..obs.profiler import profiled_jit

        return profiled_jit("pw.paged_attention", _paged_ragged_fn, **kwargs)
    except Exception:  # pragma: no cover - import-order edge
        return jax.jit(_paged_ragged_fn, **kwargs)


_paged_ragged = _make_paged_ragged()


def _append_kernel(li_ref, bt_ref, c0_ref, cl_ref, so_ref, *refs, K: int,
                   block_size: int, scale: float, rep: int,
                   window: int | None = None, sinks: bool = False, **geom):
    """Round-17 fused append+attend (decode, C=1): the incoming token's
    K/V rides into the kernel as a (1, H*hd) operand, is patched into the
    tail block IN VMEM for the attention math (the HBM copy the kernel
    gathered predates the append), and is flushed back to the pool
    through the aliased pool outputs - the standalone scatter program the
    unfused path runs before attention disappears.  The tail block is
    block ``(ctx-1) // block_size % K`` of the row's last span.  Pool
    out-blocks map every grid step of row ``b`` to the row's slot block,
    so exactly ONE block per pool per row is written (at ``j == jlast``),
    the same write set as the scatter.  Same grid / gather /
    online-softmax recurrence as :func:`_paged_kernel`."""
    sink, (q_ref, k1_ref, v1_ref, k_in, v_in, o_ref, ko_ref, vo_ref, kbuf,
           vbuf, sem, n_ref, qm_ref, m_ref, l_ref, acc_ref) = _split_sink(
        refs, sinks, rep)
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _start_row(q_ref, qm_ref, m_ref, l_ref, acc_ref, **geom, **sink)

    c0 = c0_ref[b]
    ctx = cl_ref[b]
    jlast = (ctx - 1) // (K * block_size)  # the append lands in this span

    @pl.when(_live_span(j, c0, jlast, window, K * block_size))
    def _visible():
        slot = _next_span(b, j, jlast, li_ref, bt_ref, c0_ref, cl_ref,
                          (k_in, v_in), (kbuf, vbuf), sem, n_ref, K=K,
                          block_size=block_size, window=window)

        @pl.when(j == jlast)
        def _append():
            # the append itself: full tail block (input content + new
            # row) through the aliased pool output - flushed once per row
            tail = pl.ds(pl.multiple_of(
                (ctx - 1) // block_size % K * block_size, block_size),
                block_size)
            new_row = jax.lax.broadcasted_iota(
                jnp.int32, (block_size, 1), 0) == so_ref[b]
            for buf, new_ref, out_ref in ((kbuf, k1_ref, ko_ref),
                                          (vbuf, v1_ref, vo_ref)):
                blk = jnp.where(new_row, new_ref[:], buf[slot, tail, :])
                buf[slot, tail, :] = blk
                out_ref[:] = blk.astype(out_ref.dtype)

        _attend_span(j, c0, ctx, kbuf, vbuf, slot, qm_ref, m_ref, l_ref,
                     acc_ref, scale=scale, rep=rep, window=window, **geom)

    @pl.when(j == jlast)
    def _final():
        _write_out(o_ref, l_ref, acc_ref, **geom)


def _paged_append_fn(q, k_new, v_new, k_pool, v_pool, layer, block_tables,
                     c0, cl, slot_offsets, sinks=None, *, d_true: int,
                     interpret: bool = False, window: int | None = None,
                     v_heads: int | None = None):
    """q: (B, 1, H, hd); k_new/v_new: (B, Hkv, hd); pools
    (L, num_blocks, BS, Hkv*hd) - ALL layers' stacked pool, returned
    UPDATED at ``layer`` ((1,) int32), aliased in place on TPU: one tail
    block per row is written, nothing else of the pool is touched or
    copied.  Contract: the slot is the tail of the attended context
    (``slot_blocks[b] == block_tables[b, (cl[b]-1)//BS]`` and
    ``slot_offsets[b] == (cl[b]-1) % BS``) - the decode append the
    engine constructs by definition.  V's lanes, ``sinks`` and ``v_heads``:
    as :func:`_paged_ragged_fn` (``v_new`` (B, V heads, hd_v))."""
    B, hd = q.shape[0], q.shape[3]
    BS, D = k_pool.shape[2:]
    Dv = v_pool.shape[3]
    NB = block_tables.shape[1]
    K = span_blocks(BS, NB, math.gcd(D, Dv))
    qf, rep = _fold_queries(q, D)
    H, hd_v, geom = _value_geometry(hd, D, Dv, v_heads)
    C = qf.shape[1]
    G = _heads_per_group(H, hd, C, geom.get("hd_v"))
    _check_v_groups(G, v_heads)
    operands, sink_specs, geom = _sink_rows(
        sinks, H, rep, (qf, k_new.reshape(B, 1, D), v_new.reshape(B, 1, Dv),
                        k_pool, v_pool), geom)
    kernel = functools.partial(
        _append_kernel, K=K, block_size=BS, scale=1.0 / np.sqrt(d_true),
        rep=rep, C=C, G=G, hd=hd, **geom,
        **({} if window is None else {"window": int(window)}),
    )

    def _row(rows, lanes=D):
        return pl.BlockSpec((None, rows, lanes), lambda b, j, *_: (b, 0, 0))

    def _slot_map(b, j, li, bt, c0, cl, so):
        # constant per row: the pool out-block IS the row's slot block
        return (li[0], bt[b, (cl[b] - 1) // BS], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # layer, block_tables, c0, cl, slot_offsets
        grid=(B, -(-NB // K)),
        in_specs=[*sink_specs, _row(C), _row(1), _row(1, Dv),
                  _pool_spec(K, BS, D, window),
                  _pool_spec(K, BS, Dv, window)],
        out_specs=[
            _row(C, H * hd_v),
            pl.BlockSpec((None, None, BS, D), _slot_map),
            pl.BlockSpec((None, None, BS, Dv), _slot_map),
        ],
        scratch_shapes=_scratch(K, BS, D, k_pool.dtype, H, C, hd, G, q.dtype,
                                Dv, hd_v),
    )
    # alias indices count the scalar-prefetch operands: pools are operands
    # 8/9 of (layer, bt, c0, cl, so, q, k_new, v_new, k_pool, v_pool), one
    # later behind the sinks
    first = 8 + len(sink_specs)
    o, k_pool, v_pool = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, C, H * hd_v), q.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        input_output_aliases={first: 1, first + 1: 2},
        interpret=interpret,
    )(layer, block_tables, c0, cl, slot_offsets, *operands)
    return _unfold_queries(o, q.shape[:3] + (hd_v,), rep), k_pool, v_pool


def _make_paged_append():
    kwargs = dict(static_argnames=("d_true", "interpret", "window",
                                   "v_heads"),
                  donate_argnums=(3, 4))
    try:
        from ..obs.profiler import profiled_jit

        return profiled_jit("pw.paged_append_attend", _paged_append_fn,
                            **kwargs)
    except Exception:  # pragma: no cover - import-order edge
        return jax.jit(_paged_append_fn, **kwargs)


_paged_append = _make_paged_append()


def _write_kernel(li_ref, sb_ref, so_ref, k1_ref, v1_ref, k_in, v_in, ko_ref,
                  vo_ref):
    """Grid: (T,) - the packed tokens in stream order.  Token t's pool
    blocks are ``(layer, slot_blocks[t])`` on the way in and, aliased, on
    the way out.  Consecutive tokens of a run land in one block, and a
    block whose index does not change between grid steps is neither
    fetched again nor flushed: the first token of a block copies it from
    the input and patches its own row, the later ones patch the resident
    output block, which goes back to HBM whole when the stream moves on."""
    t = pl.program_id(0)
    first = (t == 0) | (sb_ref[t] != sb_ref[jnp.maximum(t - 1, 0)])
    new_row = jax.lax.broadcasted_iota(
        jnp.int32, (ko_ref.shape[0], 1), 0) == so_ref[t]
    for new_ref, in_ref, out_ref in ((k1_ref, k_in, ko_ref),
                                     (v1_ref, v_in, vo_ref)):
        @pl.when(first)
        def _fresh():
            out_ref[:] = jnp.where(new_row, new_ref[:], in_ref[:])

        @pl.when(jnp.logical_not(first))
        def _resident():
            out_ref[:] = jnp.where(new_row, new_ref[:], out_ref[:])


def _paged_write_fn(k_rows, v_rows, k_pool, v_pool, layer, slot_blocks,
                    slot_offsets, *, interpret: bool = False):
    """k_rows/v_rows: (T, Hkv*hd) in the pools' dtype; pools
    (L, num_blocks, BS, Hkv*hd) - ALL layers' stacked pool, returned
    UPDATED at ``layer`` ((1,) int32), each pool ONE operand aliased in
    place: only the blocks the stream names are written, as whole
    (BS, Hkv*hd) blocks - the unit the chip moves without help (one bf16
    row is half a packed sublane) - and nothing of the pool is copied or
    changes its layout.  The block's shape is the pool's own: lanes that
    are no whole tiles (a tp shard) come through the same block spec."""
    T, D = k_rows.shape
    Dv = v_rows.shape[1]  # V's lanes may be its own
    BS = k_pool.shape[2]

    def row(lanes):
        return pl.BlockSpec((None, 1, lanes), lambda t, *_: (t, 0, 0))

    def block(lanes):
        return pl.BlockSpec((None, None, BS, lanes),
                            lambda t, li, sb, so: (li[0], sb[t], 0, 0))
    # alias indices count the scalar-prefetch operands: pools are operands
    # 5/6 of (layer, sb, so, k_rows, v_rows, k_pool, v_pool)
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, slot_blocks, slot_offsets
            grid=(T,),
            in_specs=[row(D), row(Dv), block(D), block(Dv)],
            out_specs=[block(D), block(Dv)],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(layer, slot_blocks, slot_offsets, k_rows.reshape(T, 1, D),
      v_rows.reshape(T, 1, Dv), k_pool, v_pool)


_paged_write = jax.jit(_paged_write_fn, static_argnames=("interpret",),
                       donate_argnums=(2, 3))


def query_pieces(C: int, H: int, hd: int, D: int, dtype,
                 hd_v: int | None = None) -> int:
    """The kernel rows :func:`query_layout` makes of ONE row of ``C`` query
    columns called alone (one, where it fits VMEM), for the benchmark's
    kernel smoke (``benchmark/tests/smoke_mimo_v2_flash.py``)."""
    Dv = D if hd_v is None else D // hd * hd_v
    return -(-C // query_layout(C, 1, C, H, hd, D, dtype, Dv=Dv,
                                keys=_LANES)[0])


_SPLIT_TRIPS = 5  # HBM round trips of a slot where the fold splits lane tiles


def query_layout(T: int, B: int, C: int, H: int, hd: int, D: int, dtype, *,
                 Dv: int | None = None, keys: int,
                 pool_dtype=None, v_heads: int | None = None) -> tuple:
    """``(P, N)``: how :func:`paged_attention` and :func:`latent_attention`
    lay a packed step of ``T`` tokens in ``B`` rows of ``C`` columns out for
    their kernel - ``H`` query heads of ``hd`` over pools of ``D`` key and
    ``Dv`` value lanes (``Dv = 0``: no V pool, the K pool's rows are the
    values too and the output is a query head wide - the latent pool),
    tables of ``keys`` keys; ``(C, B)`` where it keeps the rows, else ``N =
    T // P + B`` kernel rows of ``P`` query columns (:func:`_pieces`: a row
    of ``n`` columns takes ``ceil(n / P)``, so the rows' at most ``T + B``
    columns never need more).  A rule of shapes alone.

    A layout costs its kernel rows, each its query slots and its walk of a
    table.  A slot is ``H x (hd + hd_v)`` values that make one round trip
    through HBM (gathered into the rows, read by the kernel, its output
    written), and :data:`_SPLIT_TRIPS` where the fold splits lane tiles
    (``rep`` query heads a K/V head of a width that is no whole lane tiles:
    the fold and the unfold are then relayouts, each way, not moves of whole
    tiles).  A walk is ``keys x (D + Dv)`` values (one array's on the
    latent pool): a grid row reads its live spans, steps through its dead
    ones, and a piece of a chunk row reads its row's keys again.  P is the
    cheapest of C and the widths of whole sublane tiles under it that fit
    VMEM (:func:`_vmem_need` and the room :func:`_vmem_limit` asks, under
    :data:`_VMEM_CAP`: 64 heads of 192 beside 128 at 512 columns do not);
    the rows where they cost no more.  The cells' geometries (B = 16, T = B
    + C, bf16; ``tests/test_ragged_step.py`` pins them), as a call's time at
    each width on the chip ranks them (PERF.md section 6):

    - GPT-2-large, 20 heads of 64, rep 1, C 256, 1,024 keys: the rows;
    - LFM2, 32 heads of 64 on 8, C 512, 2,048 keys: P 64;
    - Trinity, 32 heads of 128 on 4, C 256, 8,192 keys: the rows, on full
      and window layers;
    - Qwen3-Next, 16 heads of 256 on 2, C 1,024, 8,192 keys: P 256;
    - MiMo-V2-Flash, 64 heads of 192 (values 128) on 4 (full layers) and 8
      (window layers), C 512, 8,192 keys: P 64 on both;
    - Kimi-Linear's latent pool, 32 heads folded on one stored row of 640
      lanes, C 512, 8,192 keys: P 64 (N 24).

    ``v_heads`` (a differential layer, :func:`_paged_ragged_fn`): a query
    head's output is ``Dv / v_heads`` wide."""
    Dv = D if Dv is None else Dv
    Hkv = D // hd
    rep, hd_v = H // Hkv, (Dv // v_heads if v_heads else Dv // Hkv) or hd
    split = rep > 1 and hd % _LANES != 0
    slot = H * (hd + hd_v) * jnp.dtype(dtype).itemsize \
        * (_SPLIT_TRIPS if split else 1)
    walk = keys * (D + Dv) * jnp.dtype(pool_dtype or dtype).itemsize

    def fits(cols: int) -> bool:
        R = cols * rep
        G = _heads_per_group(Hkv, hd, R, None if hd_v == hd else hd_v)
        return _vmem_need(1, _LANES, D, dtype, Hkv, R, hd, G, dtype,
                          Dv or D, hd_v) + _VMEM_ROOM <= _VMEM_CAP

    sub = _sublanes(dtype)
    widths = [C] + [sub << i for i in range((C // sub).bit_length())
                    if sub << i < C][::-1]
    best = None
    for P in [P for P in widths if fits(P)] or widths[-1:]:
        N = B if P == C else T // P + B
        cost = N * (P * slot + walk)
        if best is None or cost < best[0]:
            best = (cost, P, N)
    return best[1:]


# -- the latent pool: key and value are one stored row ----------------------
#
# Multi-head latent attention in its absorbed form: a token leaves one row
# in the cache, ``[c (kv_lora_rank) ; k_r (qk_rope_head_dim) ; 0]`` padded to
# whole lane tiles (``W`` lanes: 512 + 64 + 64 of padding at the published
# widths), every query head attends that one row (the key is the whole row,
# the value the same row: the lanes past ``c`` come out as the scores' mix of
# ``k_r`` and of zeros, and the caller drops them), and the per-head halves
# of ``W_kv_b`` are applied to the queries before and to the mix after.  To
# the kernels that is grouped-query attention with ONE K/V head of ``W``
# lanes and all query heads folded on it as neighbouring columns
# (:func:`_fold_queries`), on a pool that is one array: the kernels below
# are the paged ones (:func:`_next_span`, :func:`_attend_span`: the same
# span grid, gather and online softmax) with one pool operand, one span
# buffer and one aliased output.  A mixed step's call is laid out as the
# K/V pools' are (:func:`query_layout` with no V pool, :func:`_pieces`).
# Their jitted functions are named ``_paged_latent_fn`` /
# ``_paged_latent_append_fn`` / ``_paged_latent_write_fn`` for the device
# trace.

def _latent_scratch(K: int, BS: int, W: int, pool_dtype, R: int, dtype):
    return [
        pltpu.VMEM((2, K * BS, W), pool_dtype),    # the span, and the next
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((1,), jnp.int32),               # live steps so far
        pltpu.VMEM((R, W), dtype),                 # qm: the folded queries
        pltpu.VMEM((R, 128), jnp.float32),         # m
        pltpu.VMEM((R, 128), jnp.float32),         # l
        pltpu.VMEM((R, W), jnp.float32),           # acc
    ]


def _latent_kernel(li_ref, bt_ref, c0_ref, cl_ref, q_ref, c_in, o_ref, cbuf,
                   sem, n_ref, qm_ref, m_ref, l_ref, acc_ref, *, K: int,
                   block_size: int, scale: float, rep: int, **geom):
    """:func:`_paged_kernel` on the latent pool: one pool, whose span is
    key and value both."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    live = _live_columns(b, c0_ref, cl_ref, rep, geom["tiles"])

    @pl.when(j == 0)
    def _init():
        _start_row(q_ref, qm_ref, m_ref, l_ref, acc_ref, live, **geom)

    c0 = c0_ref[b]
    ctx = cl_ref[b]
    jlast = (ctx - 1) // (K * block_size)

    @pl.when(j <= jlast)
    def _visible():
        slot = _next_span(b, j, jlast, li_ref, bt_ref, c0_ref, cl_ref,
                          (c_in,), (cbuf,), sem, n_ref, K=K,
                          block_size=block_size)
        _attend_span(j, c0, ctx, cbuf, cbuf, slot, qm_ref, m_ref, l_ref,
                     acc_ref, live, scale=scale, rep=rep, **geom)

    @pl.when(j == jlast)
    def _final():
        _write_out(o_ref, l_ref, acc_ref, live, **geom)


def _paged_latent_fn(q, pool, layer, block_tables, c0, cl, *, scale: float,
                     interpret: bool = False):
    """q (B, C, H, W) the absorbed queries, ``W`` the pool's lanes; pool
    (L, num_blocks, BS, W), all latent layers' rows, read in place at
    ``layer`` ((1,) int32).  Returns (B, C, H, W): every head's mix of the
    rows it sees, of which the caller keeps the lanes of ``c``."""
    B = q.shape[0]
    BS, W = pool.shape[2:]
    NB = block_tables.shape[1]
    K = span_blocks(BS, NB, W)
    qf, rep = _fold_queries(q, W)
    R = qf.shape[1]
    kernel = functools.partial(_latent_kernel, K=K, block_size=BS,
                               scale=scale, rep=rep, C=R, G=1, hd=W,
                               tiles=_col_tiles(1, R, rep, q.dtype))
    row = pl.BlockSpec((None, R, W), lambda b, j, *_: (b, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # layer, block_tables, c0, cl
            grid=(B, -(-NB // K)),
            in_specs=[row, _pool_spec(K, BS, W)],
            out_specs=row,
            scratch_shapes=_latent_scratch(K, BS, W, pool.dtype, R, q.dtype),
        ),
        out_shape=jax.ShapeDtypeStruct((B, R, W), q.dtype),
        interpret=interpret,
        **_vmem_limit(K, BS, W, pool.dtype, 1, R, W, 1, q.dtype),
    )(layer, block_tables, c0, cl, qf, pool)
    return _unfold_queries(out, q.shape, rep)


_paged_latent = jax.jit(_paged_latent_fn,
                        static_argnames=("scale", "interpret"))


def _latent_append_kernel(li_ref, bt_ref, c0_ref, cl_ref, so_ref, q_ref,
                          new_ref, c_in, o_ref, co_ref, cbuf, sem, n_ref,
                          qm_ref, m_ref, l_ref, acc_ref, *, K: int,
                          block_size: int, scale: float, rep: int, **geom):
    """:func:`_append_kernel` on the latent pool: the token's new row is
    patched into the tail block in VMEM, attended with the rest, and
    flushed through the one aliased pool output."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _start_row(q_ref, qm_ref, m_ref, l_ref, acc_ref, **geom)

    c0 = c0_ref[b]
    ctx = cl_ref[b]
    jlast = (ctx - 1) // (K * block_size)

    @pl.when(j <= jlast)
    def _visible():
        slot = _next_span(b, j, jlast, li_ref, bt_ref, c0_ref, cl_ref,
                          (c_in,), (cbuf,), sem, n_ref, K=K,
                          block_size=block_size)

        @pl.when(j == jlast)
        def _append():
            tail = pl.ds(pl.multiple_of(
                (ctx - 1) // block_size % K * block_size, block_size),
                block_size)
            new_row = jax.lax.broadcasted_iota(
                jnp.int32, (block_size, 1), 0) == so_ref[b]
            blk = jnp.where(new_row, new_ref[:], cbuf[slot, tail, :])
            cbuf[slot, tail, :] = blk
            co_ref[:] = blk.astype(co_ref.dtype)

        _attend_span(j, c0, ctx, cbuf, cbuf, slot, qm_ref, m_ref, l_ref,
                     acc_ref, scale=scale, rep=rep, **geom)

    @pl.when(j == jlast)
    def _final():
        _write_out(o_ref, l_ref, acc_ref, **geom)


def _paged_latent_append_fn(q, row_new, pool, layer, block_tables, c0, cl,
                            slot_offsets, *, scale: float,
                            interpret: bool = False):
    """q (B, 1, H, W); row_new (B, W) the tokens' rows; pool
    (L, num_blocks, BS, W) returned UPDATED at ``layer``, aliased in place:
    one tail block a row is written.  The slot is the tail of the attended
    context, as :func:`_paged_append_fn` requires."""
    B = q.shape[0]
    BS, W = pool.shape[2:]
    NB = block_tables.shape[1]
    K = span_blocks(BS, NB, W)
    qf, rep = _fold_queries(q, W)
    R = qf.shape[1]
    kernel = functools.partial(_latent_append_kernel, K=K, block_size=BS,
                               scale=scale, rep=rep, C=R, G=1, hd=W)

    def _row(rows):
        return pl.BlockSpec((None, rows, W), lambda b, j, *_: (b, 0, 0))

    def _slot_map(b, j, li, bt, c0, cl, so):
        return (li[0], bt[b, (cl[b] - 1) // BS], 0, 0)

    # alias indices count the scalar-prefetch operands: the pool is operand
    # 7 of (layer, bt, c0, cl, so, q, row_new, pool)
    o, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,  # layer, block_tables, c0, cl, offsets
            grid=(B, -(-NB // K)),
            in_specs=[_row(R), _row(1), _pool_spec(K, BS, W)],
            out_specs=[_row(R),
                       pl.BlockSpec((None, None, BS, W), _slot_map)],
            scratch_shapes=_latent_scratch(K, BS, W, pool.dtype, R, q.dtype),
        ),
        out_shape=[jax.ShapeDtypeStruct((B, R, W), q.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        interpret=interpret,
    )(layer, block_tables, c0, cl, slot_offsets, qf,
      row_new.reshape(B, 1, W), pool)
    return _unfold_queries(o, q.shape, rep), pool


_paged_latent_append = jax.jit(
    _paged_latent_append_fn, static_argnames=("scale", "interpret"),
    donate_argnums=(2,))


def _latent_write_kernel(li_ref, sb_ref, so_ref, new_ref, c_in, co_ref):
    """:func:`_write_kernel` on one pool."""
    t = pl.program_id(0)
    first = (t == 0) | (sb_ref[t] != sb_ref[jnp.maximum(t - 1, 0)])
    new_row = jax.lax.broadcasted_iota(
        jnp.int32, (co_ref.shape[0], 1), 0) == so_ref[t]

    @pl.when(first)
    def _fresh():
        co_ref[:] = jnp.where(new_row, new_ref[:], c_in[:])

    @pl.when(jnp.logical_not(first))
    def _resident():
        co_ref[:] = jnp.where(new_row, new_ref[:], co_ref[:])


def _paged_latent_write_fn(rows, pool, layer, slot_blocks, slot_offsets, *,
                           interpret: bool = False):
    """rows (T, W) in the pool's dtype; pool (L, num_blocks, BS, W)
    returned UPDATED at ``layer``, whole blocks in place, as
    :func:`_paged_write_fn` writes its two."""
    T, W = rows.shape
    BS = pool.shape[2]
    row = pl.BlockSpec((None, 1, W), lambda t, *_: (t, 0, 0))
    block = pl.BlockSpec((None, None, BS, W),
                         lambda t, li, sb, so: (li[0], sb[t], 0, 0))
    # the pool is operand 4 of (layer, sb, so, rows, pool)
    return pl.pallas_call(
        _latent_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, slot_blocks, slot_offsets
            grid=(T,),
            in_specs=[row, block],
            out_specs=block,
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
    )(layer, slot_blocks, slot_offsets, rows.reshape(T, 1, W), pool)


_paged_latent_write = jax.jit(
    _paged_latent_write_fn, static_argnames=("interpret",),
    donate_argnums=(1,))


def latent_attention_reference(q, pool, block_tables, context_lens=None, *,
                               start_pos=None, n_valid=None, scale: float):
    """Gather-based latent attention: q (B, C, H, W); pool (num_blocks, BS,
    W), one layer's rows; the raggedness contract of
    :func:`paged_attention_reference`.  Returns (B, C, H, W)."""
    B, C = q.shape[:2]
    _require_positive_context(C, context_lens, start_pos, n_valid)
    NB, BS = block_tables.shape[1], pool.shape[1]
    c0, cl_last = _query_context(C, context_lens, start_pos, n_valid)
    ctx = jnp.minimum(c0[:, None] + jnp.arange(C)[None, :], cl_last[:, None])
    rows = pool[block_tables].reshape(B, NB * BS, -1)
    scores = jnp.einsum("bqhw,bkw->bhqk", q, rows) * scale
    valid = (jnp.arange(NB * BS)[None, None, :] < ctx[:, :, None])[:, None]
    scores = jnp.where(valid, scores, _NEG)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkw->bqhw", probs, rows)


def _latent_layer(pool, layer):
    if layer is None:
        return pool[None], jnp.zeros((1,), jnp.int32)
    return pool, jnp.asarray(layer, jnp.int32).reshape(1)


def latent_write_rows(pool, slot_blocks, slot_offsets, rows, *, layer=None,
                      use_pallas: bool | None = None,
                      interpret: bool | None = None):
    """``pool[layer, slot_blocks[t], slot_offsets[t]] = rows[t]``: how a
    mixed step's new latent rows reach the pool (:func:`paged_write_rows`
    for one pool).  rows (T, W)."""
    rows = rows.astype(pool.dtype)
    backend = jax.default_backend()
    if use_pallas is None:
        use_pallas = backend == "tpu"
    if not use_pallas:
        at = (slot_blocks, slot_offsets) if layer is None \
            else (layer, slot_blocks, slot_offsets)
        return pool.at[at].set(rows)
    pp, li = _latent_layer(pool, layer)
    pp = _paged_latent_write(
        rows, pp, li, jnp.asarray(slot_blocks, jnp.int32),
        jnp.asarray(slot_offsets, jnp.int32),
        interpret=(backend != "tpu") if interpret is None else interpret)
    return pp[0] if layer is None else pp


def latent_append_attend(q, row_new, pool, block_tables, context_lens,
                         slot_blocks, slot_offsets, *, scale: float,
                         layer=None, use_pallas: bool | None = None,
                         interpret: bool | None = None):
    """Fused decode append+attend on the latent pool
    (:func:`paged_append_attend` for one pool): q (B, 1, H, W), row_new
    (B, W).  Returns ``(mix (B, 1, H, W), pool)``."""
    backend = jax.default_backend()
    if use_pallas is None:
        use_pallas = backend == "tpu"
    if not use_pallas:
        pool = latent_write_rows(pool, slot_blocks, slot_offsets, row_new,
                                 layer=layer, use_pallas=False)
        a = latent_attention_reference(
            q, _layer_of(pool, layer), block_tables, context_lens,
            scale=scale)
        return a, pool
    _require_positive_context(1, context_lens, None, None)
    c0, cl_last = _query_context(1, context_lens, None, None)
    pp, li = _latent_layer(pool, layer)
    a, pp = _paged_latent_append(
        q, row_new.astype(pool.dtype), pp, li,
        jnp.asarray(block_tables, jnp.int32), c0.astype(jnp.int32),
        cl_last.astype(jnp.int32), jnp.asarray(slot_offsets, jnp.int32),
        scale=float(scale),
        interpret=(backend != "tpu") if interpret is None else interpret)
    return a, (pp[0] if layer is None else pp)


def latent_attention(q, pool, block_tables, context_lens=None, *,
                     start_pos=None, n_valid=None, packed=None, scale: float,
                     layer=None, use_pallas: bool | None = None,
                     interpret: bool | None = None):
    """Ragged latent attention (:func:`paged_attention` for the latent
    pool, with its ``packed`` form: a mixed step's stream (T, H, W) in, the
    packed output (T, H, W) out).  The kernel takes the rows as
    :func:`query_layout` lays them out for a pool of one array (``Dv = 0``):
    N kernel rows of P query columns cut from the packed stream
    (:func:`_pieces`; 24 of 64 at Kimi-Linear's cell, where the rows at the
    chunk's width were 128 pieces of 64, most of them idle), or the rows as
    they are where they cost no more; all heads of a column are folded on
    the one stored row.  Rows (B, C, H, W) without ``packed`` go the same
    way, as a stream of B x C tokens where the rule cuts them."""
    backend = jax.default_backend()
    if use_pallas is None:
        use_pallas = backend == "tpu"
    if not use_pallas:
        if packed is not None:
            idx, tok_row, tok_col = packed
            return latent_attention_reference(
                q[idx], _layer_of(pool, layer), block_tables,
                start_pos=start_pos, n_valid=n_valid,
                scale=scale)[tok_row, tok_col]
        return latent_attention_reference(
            q, _layer_of(pool, layer), block_tables, context_lens,
            start_pos=start_pos, n_valid=n_valid, scale=scale)
    pp, li = _latent_layer(pool, layer)
    call = _latent_call(
        pp, li, scale=float(scale),
        interpret=(backend != "tpu") if interpret is None else interpret)
    return _dispatch(call, q, pp, None, block_tables, context_lens,
                     start_pos, n_valid, packed)


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


def paged_write_rows(k_pool, v_pool, slot_blocks, slot_offsets, k_rows,
                     v_rows, *, layer=None, use_pallas: bool | None = None,
                     interpret: bool | None = None):
    """``pool[layer, slot_blocks[t], slot_offsets[t]] = rows[t]`` for every
    token t of a packed stream: how a mixed step's new K/V reaches the
    pool, one call a layer for both pools.

    k_rows/v_rows: (T, Hkv, hd); slot_blocks/slot_offsets: (T,) int32;
    pools as in :func:`paged_attention` (``layer=None`` one layer's
    slices, ``layer=i`` the stacked pool updated IN PLACE at layer i).
    Returns ``(k_pool, v_pool)``.  On the reference path an
    ``.at[].set``; the kernel (:func:`_paged_write_fn`) writes whole blocks
    instead of rows - beside the attention kernels an XLA scatter wants
    the pool in a layout of its own and converts both pools around every
    layer (PERF.md, PR 21), and a loop of one-row updates costs a
    microsecond a row and pool (PR 30).

    The write set is the scatter's: the other rows of a touched block keep
    their bits, untouched blocks are not written, and the returned pools
    carry every token's row, so an attention call that takes them gathers
    what the SAME step wrote.  The engine's contract makes blocks
    independent of each other: the tokens of a block are neighbours in the
    stream, and no two rows of a step write the same real block
    (``PagedDecodeEngine._build_mixed``).  Only the null block 0 - padding
    and tokens diverted from a shared prefix - is revisited out of order;
    it ends up holding any of their rows, and nothing reads it."""
    T = k_rows.shape[0]
    k_rows = k_rows.reshape(T, -1).astype(k_pool.dtype)
    v_rows = v_rows.reshape(T, -1).astype(v_pool.dtype)
    backend = jax.default_backend()
    if use_pallas is None:
        use_pallas = backend == "tpu"
    if not use_pallas:
        at = (slot_blocks, slot_offsets) if layer is None \
            else (layer, slot_blocks, slot_offsets)
        return k_pool.at[at].set(k_rows), v_pool.at[at].set(v_rows)
    kk, vv, li = _stacked(k_pool, v_pool, layer)
    kk, vv = _paged_write(
        k_rows, v_rows, kk, vv, li, jnp.asarray(slot_blocks, jnp.int32),
        jnp.asarray(slot_offsets, jnp.int32),
        interpret=(backend != "tpu") if interpret is None else interpret,
    )
    return (kk[0], vv[0]) if layer is None else (kk, vv)


def paged_append_attend(q, k_new, v_new, k_pool, v_pool, block_tables,
                        context_lens, slot_blocks, slot_offsets, *,
                        layer=None, use_pallas: bool | None = None,
                        interpret: bool | None = None,
                        window: int | None = None, sinks=None,
                        v_heads: int | None = None):
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
    hd = q.shape[-1]
    if use_pallas is None:
        use_pallas = backend == "tpu"
    if not use_pallas:
        k_pool, v_pool = paged_write_rows(
            k_pool, v_pool, slot_blocks, slot_offsets, k_new, v_new,
            layer=layer, use_pallas=False)
        a = paged_attention_reference(
            q, _layer_of(k_pool, layer), _layer_of(v_pool, layer),
            block_tables, context_lens, window=window, sinks=sinks,
            **({} if v_heads is None else {"v_heads": v_heads}),
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
        *(() if sinks is None else (sinks,)),
        d_true=hd,
        interpret=(backend != "tpu") if interpret is None else interpret,
        **({} if window is None else {"window": int(window)}),
        **({} if v_heads is None else {"v_heads": int(v_heads)}),
    )
    return (a, kk[0], vv[0]) if layer is None else (a, kk, vv)


def _pieces(row_start, row_nvalid, row_token_idx, tok_row, tok_col, P: int,
            N: int) -> tuple:
    """The kernel rows of a packed step in pieces of ``P`` query columns
    (:func:`query_layout`), from the step's own arrays on the device: a
    row of ``n`` valid columns takes ``ceil(n / P)`` pieces, in row order,
    piece ``k`` of it the columns ``k*P ..`` (a decode row and an idle row
    one each); the ``N`` pieces past the rows' are idle kernel rows
    (context 1).  Returns ``(row, token, c0, cl, back)``: each piece's row
    (N,) (its table's), the packed tokens of its columns (N, P) (past the
    row's valid columns, its last one's: padding the kernel clamps), its
    first and last columns' contexts (N,), and ``(piece, column)`` of each
    packed token, the gather of the output back to the stream."""
    B = row_nvalid.shape[0]
    n = jnp.asarray(row_nvalid, jnp.int32)
    count = (n + P - 1) // P
    end = jnp.cumsum(count)
    first = end - count
    p = jnp.arange(N, dtype=jnp.int32)
    row = jnp.minimum(
        jnp.sum(end[None, :] <= p[:, None], axis=1, dtype=jnp.int32), B - 1)
    col0 = (p - first[row]) * P
    start = jnp.asarray(row_start, jnp.int32)[row]
    live = p < end[-1]
    c0 = start + 1 + col0
    cl = jnp.minimum(start + n[row], c0 + P - 1)
    cols = jnp.minimum(col0[:, None] + jnp.arange(P, dtype=jnp.int32)[None],
                       n[row][:, None] - 1)
    return (row, row_token_idx[row[:, None], cols], jnp.where(live, c0, 1),
            jnp.where(live, cl, 1),
            (first[tok_row] + tok_col // P, tok_col % P))


def paged_attention(q, k_pool, v_pool, block_tables, context_lens=None, *,
                    start_pos=None, n_valid=None, packed=None, layer=None,
                    use_pallas: bool | None = None,
                    interpret: bool | None = None,
                    window: int | None = None, sinks=None,
                    v_heads: int | None = None):
    """Dispatch: Pallas kernel on TPU, gather reference elsewhere (the
    interpreted kernel is for tests).  Same signature/shape/raggedness
    contract as :func:`paged_attention_reference`, plus ``layer``: None
    for one layer's (num_blocks, BS, H*hd) pool slices, ``i`` for the
    stacked (L, num_blocks, BS, H*hd) pool read in place at layer i.
    The kernel reads the pools where they are, in the layout they have:
    nothing pool-sized is padded, sliced or copied by this function.

    ``packed`` (a mixed step): ``q`` is the step's packed stream (T, H, hd)
    and ``packed = (row_token_idx (B, C), tok_row (T,), tok_col (T,))`` place
    its tokens in the B rows of ``block_tables`` / ``start_pos`` /
    ``n_valid`` (the rows' valid columns are packed tokens: they add up to
    at most T); returns the packed output (T, H, hd_v).  The reference path
    gathers the rows (B, C, H, hd) and back.  The kernel takes the rows as
    :func:`query_layout` lays them out from shapes alone: as the B rows at
    the chunk's width, or as N kernel rows of P query columns cut from the
    stream (:func:`_pieces`), only as many as the live rows need - the
    queries gathered, folded and streamed into the kernel, and its output
    back, are N x P slots where the rows are B x C.  Without ``packed``
    ``q`` is (B, C, H, hd) rows: the rule keeps them (a stream of B x C
    tokens needs no fewer slots) unless they do not fit VMEM, and then a
    column past a row's valid ones returns its last valid one's output."""
    backend = jax.default_backend()
    if use_pallas is None:
        use_pallas = backend == "tpu"
    vh = {} if v_heads is None else {"v_heads": int(v_heads)}
    if not use_pallas:
        if packed is not None:
            idx, tok_row, tok_col = packed
            return paged_attention_reference(
                q[idx], _layer_of(k_pool, layer), _layer_of(v_pool, layer),
                block_tables, start_pos=start_pos, n_valid=n_valid,
                window=window, sinks=sinks, **vh)[tok_row, tok_col]
        return paged_attention_reference(
            q, _layer_of(k_pool, layer), _layer_of(v_pool, layer),
            block_tables, context_lens,
            start_pos=start_pos, n_valid=n_valid, window=window, sinks=sinks,
            **vh,
        )
    kk, vv, li = _stacked(k_pool, v_pool, layer)
    call = _ragged_call(
        kk, vv, li, sinks, hd=q.shape[-1], window=window,
        interpret=(backend != "tpu") if interpret is None else interpret,
        **vh)
    return _dispatch(call, q, kk, vv, block_tables, context_lens, start_pos,
                     n_valid, packed, **vh)


def _ragged_call(kk, vv, li, sinks, *, hd: int, window: int | None,
                 interpret: bool, **vh):
    """The ragged kernel on these pools, as :func:`_dispatch` calls it:
    ``call(rows, tables, c0, cl)``; ``vh``: ``v_heads``, where given."""
    extra = () if sinks is None else (sinks,)
    kw = dict(d_true=hd, interpret=interpret,
              **({} if window is None else {"window": int(window)}), **vh)
    return lambda q, tables, c0, cl: _paged_ragged(
        q, kk, vv, li, tables, c0, cl, *extra, **kw)


def _latent_call(pool, li, *, scale: float, interpret: bool):
    """The latent kernel on this pool, as :func:`_dispatch` calls it."""
    return lambda q, tables, c0, cl: _paged_latent(
        q, pool, li, tables, c0, cl, scale=scale, interpret=interpret)


def _layout(q, T: int, B: int, C: int, kk, vv, tables, **vh) -> tuple:
    """:func:`query_layout` of a call of these queries on these pools and
    tables (``vv`` None: the latent pool, one array)."""
    return query_layout(T, B, C, *q.shape[-2:], kk.shape[3], q.dtype,
                        Dv=0 if vv is None else vv.shape[3],
                        keys=tables.shape[1] * kk.shape[2],
                        pool_dtype=kk.dtype, **vh)


def _dispatch(call, q, kk, vv, block_tables, context_lens, start_pos,
              n_valid, packed, **vh):
    """:func:`paged_attention`'s and :func:`latent_attention`'s kernel
    ``call`` in the layout :func:`query_layout` gives for the queries and
    the pools ``kk`` / ``vv``: a packed step's stream through
    :func:`_packed`; rows (B, C, H, hd) as they are where the rule keeps
    them (a stream of B x C tokens needs no fewer slots), else as that
    stream, row b's tokens at b * C, where a column past a row's valid ones
    returns its last valid one's output."""
    tables = jnp.asarray(block_tables, jnp.int32)
    if packed is not None:
        idx = packed[0]
        _require_positive_context(1, None, start_pos, n_valid)
        return _packed(call, q, tables, jnp.asarray(start_pos, jnp.int32),
                       jnp.asarray(n_valid, jnp.int32), *packed,
                       _layout(q, q.shape[0], *idx.shape, kk, vv, tables,
                               **vh))
    B, C = q.shape[:2]
    _require_positive_context(C, context_lens, start_pos, n_valid)
    c0, cl = (x.astype(jnp.int32) for x in _query_context(
        C, context_lens, start_pos, n_valid))
    layout = _layout(q, B * C, B, C, kk, vv, tables, **vh)
    if layout == (C, B):
        return call(q, tables, c0, cl)
    start, nv = c0 - 1, cl - c0 + 1
    col = jnp.minimum(jnp.arange(C, dtype=jnp.int32)[None], nv[:, None] - 1)
    rows = jnp.arange(B, dtype=jnp.int32)
    out = _packed(call, q.reshape(B * C, *q.shape[2:]), tables, start, nv,
                  rows[:, None] * C + col, jnp.repeat(rows, C),
                  col.reshape(-1), layout)
    return out.reshape(B, C, *out.shape[1:])


def _packed(call, q, tables, start, nv, idx, tok_row, tok_col,
            layout: tuple):
    """The kernel ``call(rows, tables, c0, cl)`` on a packed step (q (T, H,
    hd), ``start`` / ``nv`` and the packing as :func:`paged_attention` takes
    them) in ``layout`` (:func:`query_layout`'s ``(P, N)``; forced by tests
    and the chip's sweeps): the B rows at the chunk's width, or N kernel
    rows of P query columns cut from the stream (:func:`_pieces`).  Returns
    the packed output."""
    P, N = layout
    B, C = idx.shape
    if (P, N) == (C, B):  # the rows as they are
        return call(q[idx], tables, start + 1, start + nv)[tok_row, tok_col]
    row, tok, c0, cl, back = _pieces(start, nv, idx, tok_row, tok_col, P, N)
    return call(q[tok], tables[row], c0, cl)[back]
