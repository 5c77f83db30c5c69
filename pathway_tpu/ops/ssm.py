"""Mamba's selective scan: a diagonal state a channel, with a step size, an
input map and an output map that the token chooses - as a token recurrence,
and as the two kernels a packed step takes.

Per channel ``e`` of ``E`` and state index ``n`` of ``N``, with a state
``s`` (N, E) in f32, a token's input ``u`` (E), step ``dt > 0`` (E, after
the softplus), ``b``, ``c`` (N) and the layer's ``a = -exp(A_log)``
(N, E)::

    s <- exp(dt a) * s + (dt u) b^T;   y = c^T s

(``y`` without ``D u``, which the caller adds with the gate).  A padded
token has ``dt = 0`` and ``u = 0``: ``exp(0) = 1`` and no update, so it
leaves the state as it was - no mask is traced inside a kernel.

Two jitted entry points, named for the device trace (the kernels carry the
same names: ``XLA Ops`` events ``_mamba_chunk_fn`` / ``_mamba_step_fn``):

- :func:`_mamba_chunk_fn`: work items of :data:`CHUNK` tokens, an item a
  piece of one row's run, the items of a row one after another (the items
  of :func:`pathway_tpu.ops.kda.chunk_items`); grid (channel blocks,
  items), items innermost, so that the state of a block of
  :data:`_CHANNELS` channels rides VMEM from an item to the next of its
  row, read from the row's slot of the arena at the row's first item (or
  started from zero: the sequence's first chunk) and written back at its
  last.  Inside an item the tokens go one after another in groups of a
  sublane tile (eight), the state in registers;
- :func:`_mamba_step_fn`: one token a row against the row's slot.

:func:`mamba_mixed` splits a packed step's rows between them (one valid
token: the recurrence; more: items) and :func:`mamba_decode` is the
recurrence alone.  Off the chip both run in plain ``jnp`` unless asked for
the interpreted kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda import _FIRST, _FRESH, _LIVE, _use_kernels, chunk_items, n_items

CHUNK = 128       # tokens a work item of the chunk kernel
_CHANNELS = 512   # channels a grid step of both kernels
_GROUP = 8        # tokens an inner step of the chunk kernel: a sublane tile
F32 = jnp.float32

__all__ = ["CHUNK", "chunk_items", "mamba_decode", "mamba_mixed",
           "mamba_recurrence", "n_items"]


def _channel_block(E: int) -> int:
    return _CHANNELS if E % _CHANNELS == 0 else E


# -- the recurrence, token by token (what the tests hold everything to) -------


def mamba_recurrence(u, dt, b, c, a, s0):
    """u, dt (T, E), b, c (T, N), a (N, E), s0 (N, E) f32 -> (y (T, E) f32,
    s (N, E))."""
    def body(s, x):
        u1, dt1, b1, c1 = (v.astype(F32) for v in x)
        s = jnp.exp(dt1[None] * a) * s + b1[:, None] * (dt1 * u1)[None]
        return s, jnp.sum(s * c1[:, None], axis=0)

    s, y = jax.lax.scan(body, s0.astype(F32), (u, dt, b, c))
    return y, s


# -- the chunk kernel ---------------------------------------------------------


def _mamba_chunk_kernel(li_ref, slot_ref, flag_ref, u_ref, dt_ref, b_ref,
                        c_ref, a_ref, s_in, y_ref, s_out, s_scr, *, n: int):
    """Grid (channel blocks, items), items innermost.  ``b_ref`` / ``c_ref``
    come as (n / 8, N, 8): a group's eight tokens side by side on the lanes,
    so that a token's ``b`` is a column that broadcasts along the channels'
    lanes.  The arena's block is the item's slot on the way in and, aliased,
    on the way out: items of one row share it, so it is fetched at the row's
    first item and flushed after its last.  Dead items (past the live ones)
    repeat the last live item's indices: no copy, no work."""
    flag = flag_ref[pl.program_id(1)]

    @pl.when((flag & _LIVE) != 0)
    def _live():
        s0 = jnp.where((flag & _FIRST) != 0, s_in[...], s_scr[...])
        s0 = jnp.where((flag & _FRESH) != 0, 0.0, s0)
        a = a_ref[...]
        rows = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, a.shape[1]), 0)

        def group(g, s):
            at = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
            u = u_ref[at, :].astype(F32)
            dt = dt_ref[at, :]
            bg, cg = b_ref[g], c_ref[g]
            y = jnp.zeros(u.shape, F32)
            for j in range(_GROUP):
                dtj = dt[j:j + 1]
                s = jnp.exp(dtj * a) * s + bg[:, j:j + 1] * (dtj * u[j:j + 1])
                y = jnp.where(rows == j, jnp.sum(s * cg[:, j:j + 1], axis=0,
                                                 keepdims=True), y)
            y_ref[at, :] = y.astype(y_ref.dtype)
            return s

        s = jax.lax.fori_loop(0, n // _GROUP, group, s0)
        s_scr[...] = s
        s_out[...] = s


def _groups(x):
    """(NW, n, N) -> (NW, n / 8, N, 8): a group's tokens on the lanes."""
    NW, n, N = x.shape
    return x.reshape(NW, n // _GROUP, _GROUP, N).transpose(0, 1, 3, 2)


def _mamba_chunk_fn(u, dt, b, c, a, state, layer, item_slot, item_flag, *,
                    interpret: bool = False):
    """u (NW, n, E) the items' inputs; dt (NW, n, E) f32 steps; b, c
    (NW, n, N) f32; a (N, E) f32 the layer's; state (L, slots, 1, N, E) f32,
    all layers' arena, updated in place at ``layer`` ((1,) int32);
    item_slot, item_flag (NW,) int32 as :func:`pathway_tpu.ops.kda
    ._kda_chunk_fn` takes them.  Returns ``(y (NW, n, E) f32, state)``."""
    NW, n, E = u.shape
    N = a.shape[0]
    eb = _channel_block(E)

    def item(e, i, *_):
        return (i, 0, e)

    tok = pl.BlockSpec((None, n, eb), item)
    grp = pl.BlockSpec((None, n // _GROUP, N, _GROUP),
                       lambda e, i, *_: (i, 0, 0, 0))
    slot_spec = pl.BlockSpec(
        (None, None, None, N, eb),
        lambda e, i, li, slot, flag: (li[0], slot[i], 0, 0, e))
    # alias indices count the scalar-prefetch operands: the arena is
    # operand 8 of (layer, slot, flag, u, dt, b, c, a, state)
    return pl.pallas_call(
        functools.partial(_mamba_chunk_kernel, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, item_slot, item_flag
            grid=(E // eb, NW),
            in_specs=[tok, tok, grp, grp,
                      pl.BlockSpec((N, eb), lambda e, i, *_: (0, e)),
                      slot_spec],
            out_specs=[tok, slot_spec],
            scratch_shapes=[pltpu.VMEM((N, eb), F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((NW, n, E), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},
        interpret=interpret, name="_mamba_chunk_fn",
    )(layer, item_slot, item_flag, u, dt, _groups(b), _groups(c), a, state)


_mamba_chunk = jax.jit(_mamba_chunk_fn, static_argnames=("interpret",),
                       donate_argnums=(5,))


def mamba_chunk_reference(u, dt, b, c, a, state, layer, item_slot,
                          item_flag):
    """The chunk kernel's contract in plain ``jnp``: the items one after
    another."""
    N, E = a.shape
    li = layer[0]

    def body(carry, x):
        state, s = carry
        ui, dti, bi, ci, slot, flag = x
        live = (flag & _LIVE) != 0
        s0 = jnp.where((flag & _FIRST) != 0, state[li, slot, 0], s)
        s0 = jnp.where((flag & _FRESH) != 0, 0.0, s0)
        y, s1 = mamba_recurrence(ui, dti, bi, ci, a, s0)
        s1 = jnp.where(live, s1, s)
        state = state.at[li, slot, 0].set(
            jnp.where(live, s1, state[li, slot, 0]))
        return (state, s1), y

    (state, _s), y = jax.lax.scan(
        body, (state, jnp.zeros((N, E), F32)),
        (u, dt, b, c, item_slot, item_flag))
    return y, state


# -- the step kernel ----------------------------------------------------------


def _mamba_step_kernel(li_ref, slot_ref, fresh_ref, u_ref, dt_ref, b_ref,
                       c_ref, a_ref, s_in, y_ref, s_out):
    """Grid (channel blocks, rows): a row's ``u`` and ``dt`` a row of lanes,
    its ``b`` and ``c`` columns; the row's slot of the arena comes and goes
    back, aliased, a channel block at a time."""
    fresh = fresh_ref[pl.program_id(1)] != 0
    s = jnp.where(fresh, 0.0, s_in[...])
    dt = dt_ref[...]
    u = u_ref[...].astype(F32)
    s = jnp.exp(dt * a_ref[...]) * s + b_ref[...] * (dt * u)
    y_ref[...] = jnp.sum(s * c_ref[...], axis=0, keepdims=True)
    s_out[...] = s


def _mamba_step_fn(u, dt, b, c, a, state, layer, row_slot, row_fresh, *,
                   interpret: bool = False):
    """u (B, 1, E); dt (B, 1, E) f32; b, c (B, N, 1) f32; a (N, E) f32;
    state (L, slots, 1, N, E) f32, updated in place at ``layer`` and the
    rows' slots (``row_slot`` (B,); a row that is not a decode row rides
    the null slot 0); ``row_fresh`` (B,): start from zero.  Returns ``(y
    (B, 1, E) f32, state)``."""
    B, _one, E = u.shape
    N = a.shape[0]
    eb = _channel_block(E)
    lane = pl.BlockSpec((None, 1, eb), lambda e, r, *_: (r, 0, e))
    col = pl.BlockSpec((None, N, 1), lambda e, r, *_: (r, 0, 0))
    slot_spec = pl.BlockSpec(
        (None, None, None, N, eb),
        lambda e, r, li, slot, fresh: (li[0], slot[r], 0, 0, e))
    # alias indices count the scalar-prefetch operands: the arena is
    # operand 8 of (layer, slot, fresh, u, dt, b, c, a, state)
    return pl.pallas_call(
        _mamba_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, row_slot, row_fresh
            grid=(E // eb, B),
            in_specs=[lane, lane, col, col,
                      pl.BlockSpec((N, eb), lambda e, r, *_: (0, e)),
                      slot_spec],
            out_specs=[lane, slot_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, 1, E), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},
        interpret=interpret, name="_mamba_step_fn",
    )(layer, row_slot, row_fresh, u, dt, b, c, a, state)


_mamba_step = jax.jit(_mamba_step_fn, static_argnames=("interpret",),
                      donate_argnums=(5,))


def mamba_step_reference(u, dt, b, c, a, state, layer, row_slot, row_fresh):
    """The step kernel's contract in plain ``jnp`` (rows that share the
    null slot leave any of their states there; nothing reads it)."""
    li = layer[0]
    s = jnp.where((row_fresh != 0)[:, None, None], 0.0,
                  state[li, row_slot, 0])                   # (B, N, E)
    s = jnp.exp(dt * a[None]) * s + b * (dt * u.astype(F32))
    return jnp.sum(s * c, axis=1, keepdims=True), \
        state.at[li, row_slot, 0].set(s)


# -- what the step programs call ----------------------------------------------


def mamba_decode(u, dt, b, c, a, state, layer: int, row_slot, row_fresh, *,
                 use_pallas: bool | None = None,
                 interpret: bool | None = None):
    """One token a row.  u, dt (B, E), b, c (B, N), a (N, E); state the
    arena; row_slot, row_fresh (B,).  Returns ``(y (B, E) f32, state)``."""
    use_pallas, interpret = _use_kernels(use_pallas, interpret)
    args = (u[:, None], dt.astype(F32)[:, None], b.astype(F32)[:, :, None],
            c.astype(F32)[:, :, None], a, state,
            jnp.asarray(layer, jnp.int32).reshape(1),
            row_slot.astype(jnp.int32), row_fresh.astype(jnp.int32))
    if use_pallas:
        y, state = _mamba_step(*args, interpret=interpret)
    else:
        y, state = mamba_step_reference(*args)
    return y[:, 0], state


def mamba_mixed(u, dt, b, c, a, state, layer: int, items: dict, row_first,
                row_fresh, row_nvalid, row_slot, row_live, *,
                use_pallas: bool | None = None,
                interpret: bool | None = None):
    """A packed step's scan.  u, dt (T, E), b, c (T, N): the stream;
    ``items``: :func:`~pathway_tpu.ops.kda.chunk_items` of the step at
    :data:`CHUNK`; ``row_live`` (B,): the row is no padding; rows of one
    valid token take the recurrence, the others the chunk kernel.  Returns
    ``(y (T, E) f32, state)``; a padding token's ``y`` is zero."""
    use_pallas, interpret = _use_kernels(use_pallas, interpret)
    T, E = u.shape
    li = jnp.asarray(layer, jnp.int32).reshape(1)
    single = row_live & (row_nvalid == 1)
    at = jnp.clip(row_first, 0, T - 1)
    y_step, state = mamba_decode(
        u[at], dt[at], b[at], c[at], a, state, layer,
        jnp.where(single, row_slot, 0), single & row_fresh,
        use_pallas=use_pallas, interpret=interpret)
    tok, ok = items["token"], items["valid"][:, :, None]

    def gather(x, mask=False):
        y = x[tok]
        return jnp.where(ok, y, jnp.zeros((), y.dtype)) if mask else y

    args = (gather(u, True), gather(dt.astype(F32), True),
            gather(b.astype(F32)), gather(c.astype(F32)), a, state, li,
            items["slot"], items["flag"])
    if use_pallas:
        y_items, state = _mamba_chunk(*args, interpret=interpret)
    else:
        y_items, state = mamba_chunk_reference(*args)
    y = jnp.zeros((T + 1, E), F32)
    y = y.at[jnp.where(items["valid"], tok, T)].set(y_items)
    y = y.at[jnp.where(single, at, T)].set(y_step)
    return y[:T], state
