"""Kimi Delta Attention: a matrix state a head, a per-channel decay, the
delta rule - as a token recurrence, and in the chunkwise form a prefill
takes.

Per head, with a state ``S`` (dk x dv), a token's ``q, k`` (dk), ``v``
(dv), log-decay ``g <= 0`` (dk) and ``beta`` in (0, 1)::

    S <- diag(exp(g)) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q

Everything here takes ``kb = beta k`` and ``vb = beta v`` in place of
``beta`` (``u = vb - S^T kb``): the callers form them once a step, and no
kernel needs a per-token scalar.

The chunkwise form (the Kimi Linear report's, arXiv:2510.26692; the WY
representation of the gated delta rule) over ``n`` tokens from a state
``S0``, with ``G`` the inclusive running sum of ``g`` in the chunk (f32:
decay products are formed in log space and nowhere else) and ``r`` a row of
``G`` the exponents are taken against (the chunk's middle: a factor
``exp(G_t - r)`` alone may pass 1, the products below never do)::

    A[t, s] = (kb_t exp(G_t - r)) . (k_s exp(r - G_s))       s < t, else 0
    T = (I + A)^-1                        unit lower triangular
    U = T vb - (T (kb exp(G))) S0         the n tokens' u
    O = (q exp(G)) S0 + tril(q exp(G - r) . k exp(r - G)) U
    S1 = exp(G_n) S0 + (k exp(G_n - G))^T U

``T`` is formed by matrix products alone (:func:`_unit_lower_inverse`:
blocks of 16 by the nilpotent series, merged by the block formula), so the
same lines run in the Pallas kernels and in plain ``jnp``.  A padded token
has ``g = 0`` and ``kb = vb = 0``: it leaves the state as it was.

Two jitted entry points, named for the device trace (the kernels carry the
same names: ``XLA Ops`` events ``_kda_chunk_fn`` / ``_kda_step_fn``):

- :func:`_kda_chunk_fn`: work items of :data:`CHUNK` tokens, an item a
  piece of one row's run, the items of a row one after another; the state
  is carried in VMEM from an item to the next of its row, read from the
  row's slot of the arena at the row's first item (or started from zero:
  the sequence's first chunk) and written back at its last;
- :func:`_kda_step_fn`: one token a row against the row's slot.

:func:`kda_mixed` splits a packed step's rows between them (one valid
token: the recurrence; more: items) and :func:`kda_decode` is the
recurrence alone.  Off the chip both run in plain ``jnp`` unless asked for
the interpreted kernels.

One decay a head (PR 38: a gated delta rule whose ``g`` is one number a
head and token, models/qwen3_next.py) is the per-channel rule with a head's
``dk`` channels equal, so the same two kernels compute it.  ``g`` then
comes as ``(T, H)`` (no trailing channel axis), crosses HBM in that form
((NW, n, H) f32 to the chunk kernel, (B, 1, H) to the step kernel) and is
broadcast over the channels in VMEM: a head's column of the item's ``(n,
H)`` block, picked by a masked lane sum because the head index is traced,
laid along the ``dk`` lanes.  Nothing of this is traced where ``g`` comes a
channel: those kernels are the jaxprs they were.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128        # tokens a work item of the chunk kernel
_HEADS_A_STEP = 8  # heads a grid step of the chunk kernel
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=F32)


def _dot_nt(a, b):
    """a (m, d) . b (n, d)^T -> (m, n)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                               preferred_element_type=F32)


def _dot_tn(a, b):
    """a (n, m)^T . b (n, d) -> (m, d)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), precision=_HI,
                               preferred_element_type=F32)


def _iota2(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0),
            jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for a strictly lower triangular ``a`` (n, n), ``n`` a
    power of two (at least 8), by matrix products alone.  Within diagonal
    blocks of 16 the nilpotent series ``(I - X)(I + X^2)(I + X^4)(I + X^8)``
    (``X^16 = 0``; its terms stay small where a series over the whole chunk
    would not); blocks are then merged pair by pair,
    ``[[L11, 0], [L21, L22]]^-1 = [[T11, 0], [-T22 L21 T11, T22]]``, as one
    product over the whole matrix a level: ``T <- T - T B T`` with ``B`` the
    pairs' lower-left blocks of ``a``."""
    n = a.shape[0]
    r, c = _iota2(n)
    b0 = min(16, n)
    x = jnp.where(r // b0 == c // b0, a, 0.0)
    t = (r == c).astype(F32) - x
    p = x
    for _ in range(max(b0.bit_length() - 2, 0)):  # X^2, X^4, X^8
        p = _dot(p, p)
        t = t + _dot(t, p)
    s = b0
    while s < n:
        pair = (r // (2 * s) == c // (2 * s)) & ((r // s) % 2 == 1) \
            & ((c // s) % 2 == 0)
        t = t - _dot(_dot(t, jnp.where(pair, a, 0.0)), t)
        s *= 2
    return t


def _chunk_math(q, k, kb, vb, g, s0):
    """One head, one item: q, k, kb (n, dk), vb (n, dv), g (n, dk) f32 log
    decays, s0 (dk, dv) f32 -> (o (n, dv) f32, s1 (dk, dv) f32).  The
    module docstring's five lines."""
    n = q.shape[0]
    q, k, kb, vb = (x.astype(F32) for x in (q, k, kb, vb))
    r, c = _iota2(n)
    big = _dot((r >= c).astype(F32), g)                  # G, inclusive
    ref = big[n // 2: n // 2 + 1]
    up, down = jnp.exp(big - ref), jnp.exp(ref - big)
    from0 = jnp.exp(big)
    k_down = k * down
    a = jnp.where(r > c, _dot_nt(kb * up, k_down), 0.0)
    t = _unit_lower_inverse(a)
    u = _dot(t, vb) - _dot(_dot(t, kb * from0), s0)
    p = jnp.where(r >= c, _dot_nt(q * up, k_down), 0.0)
    o = _dot(q * from0, s0) + _dot(p, u)
    last = big[n - 1: n]
    # diag(exp(G_n)) s0 as a product: a row cannot be laid along the
    # sublanes without a transpose
    rk, ck = _iota2(k.shape[1])
    decay = jnp.where(rk == ck, jnp.exp(last), 0.0)
    s1 = _dot(decay, s0) + _dot_tn(k * jnp.exp(last - big), u)
    return o, s1


def _step_math(s, a_col, k_col, kb_col, q_col, vb_row):
    """One head, one token, on the vector units: s (dk, dv); a (decay), k,
    kb, q as (dk, 1) columns; vb (1, dv) a row -> (o (1, dv), s)."""
    s = s * a_col
    u = vb_row - jnp.sum(s * kb_col, axis=0, keepdims=True)
    s = s + k_col * u
    return jnp.sum(s * q_col, axis=0, keepdims=True), s


# -- the recurrence, token by token (what the tests hold everything to) -------


def kda_recurrence(q, k, kb, vb, g, s0):
    """q, k, kb, g (T, H, dk), vb (T, H, dv), s0 (H, dk, dv) f32 ->
    (o (T, H, dv) f32, s (H, dk, dv)).  ``g`` (T, H): one decay a head."""
    def body(s, x):
        q1, k1, kb1, vb1, g1 = (y.astype(F32) for y in x)
        s = s * jnp.exp(g1).reshape(g1.shape[0], -1, 1)  # (H, dk | 1, 1)
        u = vb1 - jnp.einsum("hkv,hk->hv", s, kb1, precision=_HI)
        s = s + k1[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q1, precision=_HI)

    s, o = jax.lax.scan(body, s0.astype(F32), (q, k, kb, vb, g))
    return o, s


# -- the chunk kernel ---------------------------------------------------------

_LIVE, _FRESH, _FIRST = 1, 2, 4


def _head_decay(g_ref, head, dk: int):
    """One decay a head: the item's block is (n, H) f32, all heads'; head
    ``head`` (traced) is its column, picked by a masked lane sum and laid
    along the ``dk`` channels."""
    g = g_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    col = jnp.sum(jnp.where(lane == head, g, 0.0), axis=1, keepdims=True)
    return jnp.broadcast_to(col, (g.shape[0], dk))


def _kda_chunk_kernel(li_ref, slot_ref, flag_ref, q_ref, k_ref, kb_ref,
                      vb_ref, g_ref, s_in, o_ref, s_out, s_scr, *, heads: int,
                      dk: int, dv: int, head_decay: bool = False):
    """Grid (head blocks, items), items innermost: the state of ``heads``
    heads rides ``s_scr`` from an item to the next item of its row.  The
    arena's block is the item's slot on the way in and, aliased, on the way
    out: items of one row share it, so it is fetched at the row's first
    item and flushed after its last.  Dead items (past the live ones) repeat
    the last live item's indices: no copy, no work."""
    i = pl.program_id(1)
    flag = flag_ref[i]
    head0 = pl.program_id(0) * heads if head_decay else None

    @pl.when((flag & _LIVE) != 0)
    def _live():
        fresh = (flag & _FRESH) != 0
        first = (flag & _FIRST) != 0

        def head(h, carry):
            lk = pl.ds(pl.multiple_of(h * dk, dk), dk)
            lv = pl.ds(pl.multiple_of(h * dv, dv), dv)
            s0 = jnp.where(first, s_in[h], s_scr[h])
            s0 = jnp.where(fresh, 0.0, s0)
            tok = (q_ref[:, lk], k_ref[:, lk], kb_ref[:, lk], vb_ref[:, lv])
            g = _head_decay(g_ref, head0 + h, dk) \
                if head_decay else g_ref[:, lk]
            o, s1 = _chunk_math(*tok, g, s0)
            o_ref[:, lv] = o.astype(o_ref.dtype)
            s_scr[h] = s1
            s_out[h] = s1
            return carry

        jax.lax.fori_loop(0, heads, head, 0)


def _kda_chunk_fn(q, k, kb, vb, g, state, layer, item_slot, item_flag, *,
                  interpret: bool = False):
    """q, k, kb, g (NW, n, H * dk), vb (NW, n, H * dv): the items' tokens,
    heads side by side on the lanes; state (L, slots, H, dk, dv) f32, all
    layers' arena, updated in place at ``layer`` ((1,) int32); ``g`` (NW,
    n, H): one decay a head (the module docstring's last paragraph); item_slot,
    item_flag (NW,) int32 (:data:`_LIVE` | :data:`_FRESH`: start from zero |
    :data:`_FIRST`: the row's first item; dead items carry the last live
    item's slot).  Returns ``(o (NW, n, H * dv), state)``."""
    NW, n, _ = q.shape
    H, dk, dv = state.shape[2:]
    hb = _HEADS_A_STEP if H % _HEADS_A_STEP == 0 else H

    def item(h, i, li, slot, flag):
        return (i, 0, h)

    def arena(h, i, li, slot, flag):
        return (li[0], slot[i], h, 0, 0)

    tok_k = pl.BlockSpec((None, n, hb * dk), item)
    tok_v = pl.BlockSpec((None, n, hb * dv), item)
    slot_spec = pl.BlockSpec((None, None, hb, dk, dv), arena)
    head_decay = g.shape[2] != H * dk
    tok_g, extra = tok_k, {}
    if head_decay:  # every head's decay of the item, whatever the head block
        tok_g = pl.BlockSpec((None, n, H), lambda h, i, *_: (i, 0, 0))
        extra = {"head_decay": True}
    # alias indices count the scalar-prefetch operands: the arena is
    # operand 8 of (layer, slot, flag, q, k, kb, vb, g, state)
    return pl.pallas_call(
        functools.partial(_kda_chunk_kernel, heads=hb, dk=dk, dv=dv, **extra),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, item_slot, item_flag
            grid=(H // hb, NW),
            in_specs=[tok_k, tok_k, tok_k, tok_v, tok_g, slot_spec],
            out_specs=[tok_v, slot_spec],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(vb.shape, vb.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},
        interpret=interpret, name="_kda_chunk_fn",
    )(layer, item_slot, item_flag, q, k, kb, vb, g, state)


_kda_chunk = jax.jit(_kda_chunk_fn, static_argnames=("interpret",),
                     donate_argnums=(5,))


def kda_chunk_reference(q, k, kb, vb, g, state, layer, item_slot, item_flag):
    """The chunk kernel's contract in plain ``jnp``: the items one after
    another, heads side by side."""
    NW, n, _ = q.shape
    H, dk, dv = state.shape[2:]
    li = layer[0]
    math = jax.vmap(_chunk_math, in_axes=(1, 1, 1, 1, 1, 0), out_axes=(1, 0))

    def heads(x, d):
        return x.reshape(n, H, d)

    def body(carry, x):
        state, s = carry
        qi, ki, kbi, vbi, gi, slot, flag = x
        live = (flag & _LIVE) != 0
        s0 = jnp.where((flag & _FIRST) != 0, state[li, slot], s)
        s0 = jnp.where((flag & _FRESH) != 0, 0.0, s0)
        if gi.shape[1] != H * dk:  # one decay a head
            gi = jnp.repeat(gi, dk, axis=1)
        o, s1 = math(heads(qi, dk), heads(ki, dk), heads(kbi, dk),
                     heads(vbi, dv), heads(gi, dk), s0)
        s1 = jnp.where(live, s1, s)
        state = state.at[li, slot].set(
            jnp.where(live, s1, state[li, slot]))
        return (state, s1), o.reshape(n, H * dv).astype(vb.dtype)

    (state, _s), o = jax.lax.scan(
        body, (state, jnp.zeros((H, dk, dv), F32)),
        (q, k, kb, vb, g, item_slot, item_flag))
    return o, state


# -- the step kernel ----------------------------------------------------------


def _kda_step_kernel(li_ref, slot_ref, fresh_ref, a_ref, k_ref, kb_ref, q_ref,
                     vb_ref, s_in, o_ref, s_out, *, heads: int):
    """Grid (rows,).  a, k, kb, q come with dk on the sublanes and the heads
    on the lanes ((dk, H): a head's vector is a column, which broadcasts
    along the state's dv lanes); vb and o with the heads on the sublanes
    ((H, dv): a head's vector is a row).  A row's slot of the arena comes
    whole and goes back, aliased, whole."""
    fresh = fresh_ref[pl.program_id(0)] != 0
    for h in range(heads):
        s = jnp.where(fresh, 0.0, s_in[h])
        o, s = _step_math(s, a_ref[:, h:h + 1], k_ref[:, h:h + 1],
                          kb_ref[:, h:h + 1], q_ref[:, h:h + 1],
                          vb_ref[h:h + 1, :])
        o_ref[h:h + 1, :] = o.astype(o_ref.dtype)
        s_out[h] = s


def _kda_step_fn(a, k, kb, q, vb, state, layer, row_slot, row_fresh, *,
                 interpret: bool = False):
    """a (the decays ``exp(g)``: (B, dk, H), or (B, 1, H) one a head), k,
    kb, q (B, dk, H) f32; vb (B, H, dv);
    state (L, slots, H, dk, dv) f32, updated in place at ``layer`` and the
    rows' slots (``row_slot`` (B,); a row that is not a decode row rides
    the null slot 0); ``row_fresh`` (B,): start from zero.  Returns
    ``(o (B, H, dv) f32, state)``."""
    B, dk, H = k.shape
    dv = vb.shape[2]
    col = pl.BlockSpec((None, dk, H), lambda b, *_: (b, 0, 0))
    dec = col if a.shape[1] == dk \
        else pl.BlockSpec((None, 1, H), lambda b, *_: (b, 0, 0))
    row = pl.BlockSpec((None, H, dv), lambda b, *_: (b, 0, 0))
    slot_spec = pl.BlockSpec(
        (None, None, H, dk, dv),
        lambda b, li, slot, fresh: (li[0], slot[b], 0, 0, 0))
    # alias indices count the scalar-prefetch operands: the arena is
    # operand 8 of (layer, slot, fresh, a, k, kb, q, vb, state)
    return pl.pallas_call(
        functools.partial(_kda_step_kernel, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, row_slot, row_fresh
            grid=(B,),
            in_specs=[dec, col, col, col, row, slot_spec],
            out_specs=[row, slot_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},
        interpret=interpret, name="_kda_step_fn",
    )(layer, row_slot, row_fresh, a, k, kb, q, vb, state)


_kda_step = jax.jit(_kda_step_fn, static_argnames=("interpret",),
                    donate_argnums=(5,))


def kda_step_reference(a, k, kb, q, vb, state, layer, row_slot, row_fresh):
    """The step kernel's contract in plain ``jnp`` (rows that share the
    null slot leave any of their states there; nothing reads it)."""
    li = layer[0]
    s = jnp.where((row_fresh != 0)[:, None, None, None], 0.0,
                  state[li, row_slot])                      # (B, H, dk, dv)
    col = lambda x: jnp.swapaxes(x, 1, 2)[..., None]        # noqa: E731
    s = s * col(a)
    u = vb.astype(F32) - jnp.sum(s * col(kb), axis=2)
    s = s + col(k) * u[:, :, None, :]
    o = jnp.sum(s * col(q), axis=2)
    return o, state.at[li, row_slot].set(s)


# -- what the step programs call ----------------------------------------------


def _use_kernels(use_pallas, interpret):
    backend = jax.default_backend()
    if use_pallas is None:
        use_pallas = backend == "tpu"
    return use_pallas, (backend != "tpu") if interpret is None else interpret


def _columns(x):
    """(B, H, dk) -> (B, dk, H) f32: a head's vector as a column."""
    return jnp.swapaxes(x.astype(F32), 1, 2)


def kda_decode(q, k, kb, vb, g, state, layer: int, row_slot, row_fresh, *,
               use_pallas: bool | None = None, interpret: bool | None = None):
    """One token a row.  q, k, kb, g (B, H, dk), vb (B, H, dv); state the
    arena; row_slot, row_fresh (B,).  ``g`` (B, H): one decay a head.
    Returns ``(o (B, H, dv) f32, state)``."""
    use_pallas, interpret = _use_kernels(use_pallas, interpret)
    a = jnp.exp(g.astype(F32))
    args = (a[:, None, :] if g.ndim == 2 else _columns(a), _columns(k),
            _columns(kb),
            _columns(q), vb, state, jnp.asarray(layer, jnp.int32).reshape(1),
            row_slot.astype(jnp.int32), row_fresh.astype(jnp.int32))
    if not use_pallas:
        return kda_step_reference(*args)
    return _kda_step(*args, interpret=interpret)


def n_items(n_tokens: int, n_rows: int, chunk: int = CHUNK) -> int:
    """Work items that always suffice for a packed step of ``n_tokens`` in
    ``n_rows`` rows: every row wastes less than one."""
    return n_tokens // chunk + n_rows


def chunk_items(row_first, row_fresh, row_nvalid, row_slot, row_live,
                n_tokens: int, chunk: int = CHUNK) -> dict:
    """The work items of a packed step: every row with two valid tokens or
    more is cut into items of ``chunk`` tokens, the items in row order, the
    live ones first.  ``row_first`` (B,) the stream index of a row's first
    token (a row's run is contiguous in the stream); ``row_fresh`` (B,):
    the run is the sequence's first, its state starts from zero.  Returns ``token``
    (NW, chunk) each item's stream indices (clipped to the stream),
    ``valid`` (NW, chunk), ``slot`` / ``flag`` (NW,) as the chunk kernel
    takes them, and ``n_live`` ()."""
    B = row_nvalid.shape[0]
    NW = n_items(n_tokens, B, chunk)
    per_row = jnp.where(row_live & (row_nvalid >= 2),
                        -(-row_nvalid // chunk), 0)
    end = jnp.cumsum(per_row)
    n_live = end[-1]
    i = jnp.arange(NW, dtype=jnp.int32)
    live = i < n_live
    row = jnp.minimum(jnp.searchsorted(end, i, side="right"), B - 1)
    piece = i - (end - per_row)[row]
    col = piece[:, None] * chunk + jnp.arange(chunk, dtype=jnp.int32)[None]
    valid = live[:, None] & (col < row_nvalid[row][:, None])
    token = jnp.clip(row_first[row][:, None] + col, 0, n_tokens - 1)
    first = piece == 0
    flag = jnp.where(live, _LIVE, 0) | jnp.where(first, _FIRST, 0) \
        | jnp.where(first & row_fresh[row], _FRESH, 0)
    # a dead item repeats the last live item's slot (its block stays where
    # it is); with no live item all ride the null slot
    last = jnp.maximum(n_live - 1, 0)
    slot = jnp.where(n_live > 0, row_slot[row[jnp.minimum(i, last)]], 0)
    return {"token": token, "valid": valid, "slot": slot.astype(jnp.int32),
            "flag": flag.astype(jnp.int32), "n_live": n_live}


def _mask_padding(y, ok):
    """A padded token of an item as the chunk kernel must see it: zero
    (``g``: no decay; ``kb``, ``vb``: no update)."""
    return jnp.where(ok, y, jnp.zeros((), y.dtype))


def kda_mixed(q, k, kb, vb, g, state, layer: int, items: dict, row_first,
              row_fresh, row_nvalid, row_slot, row_live, *,
              use_pallas: bool | None = None, interpret: bool | None = None):
    """A packed step's KDA mixing.  q, k, kb, g (T, H, dk), vb (T, H, dv):
    the stream (``g`` (T, H): one decay a head); ``items``: :func:`chunk_items` of the step (the same for
    every layer); ``row_live`` (B,): the row is no padding; rows of one
    valid token take the recurrence, the others the chunk kernel.  Returns ``(o (T, H, dv) f32, state)``; a padding
    token's ``o`` is zero."""
    use_pallas, interpret = _use_kernels(use_pallas, interpret)
    T, H, dk = q.shape
    dv = vb.shape[2]
    li = jnp.asarray(layer, jnp.int32).reshape(1)
    # decode rows (and a prompt's one-token remainder): the recurrence
    single = row_live & (row_nvalid == 1)
    at = jnp.clip(row_first, 0, T - 1)
    o_step, state = kda_decode(
        q[at], k[at], kb[at], vb[at], g[at], state, layer,
        jnp.where(single, row_slot, 0), single & row_fresh,
        use_pallas=use_pallas, interpret=interpret)
    # the other rows: items of the chunk kernel
    tok, ok = items["token"], items["valid"][:, :, None]
    NW, n = tok.shape

    def gather(x, d, mask=False):
        y = x[tok]                                        # (NW, n, H, d)
        if mask:
            y = _mask_padding(y, ok[..., None])
        return y.reshape(NW, n, H * d)

    g_items = gather(g.astype(F32)[..., None], 1, True) if g.ndim == 2 \
        else gather(g.astype(F32), dk, True)
    args = (gather(q, dk), gather(k, dk), gather(kb, dk, True),
            gather(vb, dv, True), g_items, state, li,
            items["slot"], items["flag"])
    if use_pallas:
        o_items, state = _kda_chunk(*args, interpret=interpret)
    else:
        o_items, state = kda_chunk_reference(*args)
    o = jnp.zeros((T + 1, H, dv), F32)
    o = o.at[jnp.where(items["valid"], tok, T)].set(
        o_items.reshape(NW, n, H, dv).astype(F32))
    o = o.at[jnp.where(single, at, T)].set(o_step)
    return o[:T], state
