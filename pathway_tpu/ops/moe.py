"""Sparse expert layer: the router, routed pairs grouped by expert, and the
grouped matmul over the tokens each expert received.

A step carries few tokens (a decode step 16, a mixed step 272 to 1,040:
sixteen rows and the engine's prefill chunk) and many experts (32 of 3 x
2048 x 1792, 128 of 3 x 2048 x 1024, 64 of 256 or 128 of 512 held), so the
layer is bound by the bytes of the expert weights it touches, not by its
FLOPs, as long as an expert's rows pass the MXU in few tiles: a weight
tile is latched once a row tile, whatever the tile's height.  The layout
follows from that:

- :func:`route` scores every token against every expert (sigmoid, or a
  softmax over all the experts' logits; f32), chooses the ``top_k`` largest
  of ``score + bias`` and weighs them by the scores alone, renormalised
  over the chosen;
- :func:`group_rows` lays the ``tokens x top_k`` routed pairs out sorted by
  expert, each expert's group padded to whole tiles of ``tm`` rows, so
  that a tile belongs to exactly one expert; :func:`row_tile` gives ``tm``
  from the rows an expert can expect: :data:`TM` where a step brings an
  expert a handful of pairs, 32 where it brings ~17 to 20, 128 at ~66;
- :func:`grouped_matmul` multiplies each tile by its expert's matrix.  The
  Pallas kernel takes ``tile_expert`` and the number of live tiles by
  scalar prefetch: its weight block index is the tile's expert, so an
  expert no token chose is never fetched, consecutive tiles of one expert
  share one fetch, and the tiles past the live ones repeat the last index
  (no DMA) and compute nothing.  The ``jnp`` path gathers each tile's
  matrix and is what the tests hold the kernel to.

Padding tokens of a step (``valid`` false) are routed nowhere: they take
no row, touch no expert and count in no counter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TM = 16  # the unit of a row tile: one packed bf16 sublane tile
ROW_TILES = (128, 64, 32, TM)  # the heights a row tile takes, tallest first


def row_tile(tokens: int, top_k: int, router_experts: int) -> int:
    """Rows a tile of the grouped matmul, from a step's shapes alone: the
    tallest of :data:`ROW_TILES` that the mean rows an expert can expect,
    ``tokens x top_k / router_experts``, fill at least half; :data:`TM`
    under that.  The MXU latches a weight tile once a row tile whatever
    the tile's height, so the kernel's time follows its live tiles: a
    taller tile pays while it makes them fewer, which ends near one tile
    an expert, and from there on it only runs padding (PERF.md section 6,
    PR 39: the table behind the half)."""
    return next((t for t in ROW_TILES
                 if t * router_experts <= 2 * tokens * top_k), TM)


def route(h, wg, bias, *, top_k: int, norm_topk: bool = True,
          scale: float = 1.0, renorm_eps: float = 1e-6,
          score: str = "sigmoid"):
    """h (T, D), wg (D, E), bias (E,) or None -> (experts (T, k) int32,
    weights (T, k) f32, scores (T, E) f32).  Scores in f32 at the highest
    matmul precision: the choice is a comparison of neighbours.  ``score``:
    ``"sigmoid"`` of each logit, or ``"softmax"`` over all ``E`` logits (the
    chosen scores over their sum are then a softmax over the chosen logits;
    ``renorm_eps=0``: no epsilon).  ``bias`` moves the choice only; the
    weights are the chosen experts' scores, over their sum + ``renorm_eps``
    where ``norm_topk``."""
    logits = jnp.dot(
        h.astype(jnp.float32), wg.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.softmax(logits, axis=-1) if score == "softmax" \
        else jax.nn.sigmoid(logits)
    sel = s if bias is None else s + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(sel, top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + renorm_eps)
    return idx.astype(jnp.int32), w * scale, s


def n_tiles(n_pairs: int, n_experts: int, tm: int = TM) -> int:
    """Tiles that always suffice: every group wastes less than one."""
    return -(-n_pairs // tm) + n_experts


def group_rows(experts, valid, n_experts: int, tm: int = TM) -> dict:
    """The sorted, tile-aligned layout of the routed pairs.

    experts (T, k) int32; valid (T,) bool.  Returns ``row_token`` (M,) the
    token each row of the layout holds (padding rows: token 0),
    ``pair_row`` (T, k) the row of each pair, ``tile_expert`` (NT,) (past
    the live tiles: the last live tile's expert), ``n_live`` (1,) and
    ``counts`` (E,) tokens per expert, valid tokens only."""
    T, k = experts.shape
    P = T * k
    NT = n_tiles(P, n_experts, tm)
    e = jnp.where(valid[:, None], experts, n_experts).reshape(P)
    onehot = (e[:, None] == jnp.arange(n_experts, dtype=jnp.int32)[None, :]
              ).astype(jnp.int32)                       # (P, E)
    counts = jnp.sum(onehot, axis=0)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    n_live = tile_end[-1]
    start = (tile_end - tiles)[jnp.minimum(e, n_experts - 1)] * tm
    row = jnp.where(e < n_experts, start + rank, NT * tm)
    token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    row_token = jnp.zeros((NT * tm,), jnp.int32).at[row].set(
        token, mode="drop")
    tile = jnp.minimum(jnp.arange(NT, dtype=jnp.int32),
                       jnp.maximum(n_live - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, tile, side="right"), n_experts - 1)
    return {"row_token": row_token,
            "pair_row": jnp.minimum(row, NT * tm - 1).reshape(T, k),
            "tile_expert": tile_expert.astype(jnp.int32),
            "n_live": n_live.astype(jnp.int32).reshape(1),
            "counts": counts}


def _silu(a):
    return a * jax.nn.sigmoid(a)


def _gmm_kernel(te_ref, nl_ref, x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) < nl_ref[0])
    def _live():
        o_ref[:] = jnp.dot(x_ref[:], w_ref[:],
                           preferred_element_type=jnp.float32
                           ).astype(o_ref.dtype)


def _gmm_swiglu_kernel(te_ref, nl_ref, x_ref, w1_ref, w3_ref, o_ref):
    @pl.when(pl.program_id(1) < nl_ref[0])
    def _live():
        x = x_ref[:]
        a = jnp.dot(x, w1_ref[:], preferred_element_type=jnp.float32)
        b = jnp.dot(x, w3_ref[:], preferred_element_type=jnp.float32)
        o_ref[:] = (_silu(a) * b).astype(o_ref.dtype)


def _col_tile(n: int, want: int) -> int:
    """The widest multiple of 128 up to ``want`` that divides ``n``
    (``n`` itself where none does: a toy width)."""
    for tn in range(min(want, n) // 128 * 128, 0, -128):
        if n % tn == 0:
            return tn
    return n


def _gmm_call(kernel, x, ws, tile_expert, n_live, *, tm: int, tn: int,
              interpret: bool, name: str):
    """Grid (column tiles, row tiles), rows innermost: for one column tile
    the row tiles walk the experts in order, so each touched expert's
    ``(K, tn)`` panel is fetched once; a dead tile repeats the last live
    tile's indices on every operand, which Pallas turns into no copy."""
    M, K = x.shape
    N = ws[0].shape[2]
    NT = M // tm

    def rows(n, i, te, nl):
        return (jnp.minimum(i, jnp.maximum(nl[0] - 1, 0)), 0)

    def panel(n, i, te, nl):
        return (te[i], 0, n)

    def out(n, i, te, nl):
        return (jnp.minimum(i, jnp.maximum(nl[0] - 1, 0)), n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # tile_expert, n_live
        grid=(N // tn, NT),
        in_specs=[pl.BlockSpec((tm, K), rows)]
        + [pl.BlockSpec((None, K, tn), panel) for _ in ws],
        out_specs=pl.BlockSpec((tm, tn), out),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret, name=name,
    )(tile_expert, n_live, x, *ws)


def _moe_gmm_fn(x, w1, w3, w2, tile_expert, n_live, *, tm: int = TM,
                interpret: bool = False):
    """The experts' SwiGLU over a tile-aligned layout, two kernels:
    ``silu(x w1[e]) * (x w3[e])`` then ``. w2[e]``, ``e`` the tile's
    expert.  x (M, D) with M a multiple of ``tm``; w1/w3 (E, D, F);
    w2 (E, F, D).  Rows of tiles past ``n_live`` come back unwritten."""
    h = _gmm_call(_gmm_swiglu_kernel, x, (w1, w3), tile_expert, n_live,
                  tm=tm, tn=_col_tile(w1.shape[2], 256), interpret=interpret,
                  name="_moe_gmm_fn_w13")
    return _gmm_call(_gmm_kernel, h, (w2,), tile_expert, n_live, tm=tm,
                     tn=_col_tile(w2.shape[2], 512), interpret=interpret,
                     name="_moe_gmm_fn_w2")


# one lowering a program, however many layers call it; the name is what
# the device trace shows, with the kernels' own suffixes (benchmark:
# moe_gmm_w13_ms, moe_gmm_w2_ms, moe_expert_roofline)
_moe_gmm = jax.jit(_moe_gmm_fn, static_argnames=("tm", "interpret"))


def moe_gmm_reference(x, w1, w3, w2, tile_expert, n_live, *, tm: int = TM):
    """The same contract in plain ``jnp``: every tile against its expert's
    gathered matrices (f32 accumulation, results in x's dtype)."""
    NT = x.shape[0] // tm
    xt = x.reshape(NT, tm, -1)
    f32 = jnp.float32
    a = jnp.einsum("tmk,tkn->tmn", xt, w1[tile_expert],
                   preferred_element_type=f32)
    b = jnp.einsum("tmk,tkn->tmn", xt, w3[tile_expert],
                   preferred_element_type=f32)
    h = (_silu(a) * b).astype(x.dtype)
    y = jnp.einsum("tmk,tkn->tmn", h, w2[tile_expert],
                   preferred_element_type=f32)
    return y.astype(x.dtype).reshape(x.shape[0], -1)


def grouped_matmul(x, w1, w3, w2, tile_expert, n_live, *, tm: int = TM,
                   use_pallas: bool | None = None,
                   interpret: bool | None = None):
    """Dispatch: the Pallas kernels on a TPU, the gather reference
    elsewhere (the interpreted kernels are for tests)."""
    backend = jax.default_backend()
    if use_pallas is None:
        use_pallas = backend == "tpu"
    if not use_pallas:
        return moe_gmm_reference(x, w1, w3, w2, tile_expert, n_live, tm=tm)
    return _moe_gmm(
        x, w1, w3, w2, tile_expert, n_live, tm=tm,
        interpret=(backend != "tpu") if interpret is None else interpret)


def expert_ffn(h, layer: dict, valid, *, top_k: int, norm_topk: bool = True,
               scale: float = 1.0, renorm_eps: float = 1e-6, h_route=None,
               use_pallas: bool | None = None, first_expert=None,
               score: str = "sigmoid", tm: int | None = None):
    """One expert layer over a packed stream.  h (T, D) normed input in
    the experts' dtype, ``h_route`` the same before it was rounded to that
    dtype (f32; default h: the router then sees what the experts see);
    ``layer``: ``wg`` (D, E), ``expert_bias`` (E,) or absent, ``w1`` /
    ``w3`` (E, D, F), ``w2`` (E, F, D); valid (T,) bool; ``score``:
    :func:`route`'s.  Returns ``(sum_e w_e expert_e(h) (T, D), the layer's
    device counters int32[held + len(COUNTER_TAIL)])``: the tokens each held
    expert received and then :data:`COUNTER_TAIL`.  A step program sums the
    vector over its expert layers and hands it to its cache
    (kvcache/backend.py ``ExpertCounts``).

    ``first_expert`` (an int): the layer is one share of an expert-parallel
    deployment and holds the experts ``first_expert .. first_expert + held``
    only, ``held`` the leading axis of ``w1``.  The router keeps its ``E``
    outputs and its ``top_k``; a pair routed to an expert held elsewhere
    takes no row, touches no expert and adds nothing (its part of the sum
    is the other shares', which no code here stands in for) and is counted
    in the vector's ``moe_pairs_elsewhere``.

    ``tm``: rows a tile of the layout; default :func:`row_tile` of the
    shapes here (a test passes it to hold two heights against each other:
    a pair's row is the same dot products under any)."""
    E = layer["wg"].shape[1]
    if tm is None:
        tm = row_tile(h.shape[0], top_k, E)
    experts, weights, _s = route(h if h_route is None else h_route,
                                 layer["wg"], layer.get("expert_bias"),
                                 top_k=top_k, norm_topk=norm_topk,
                                 scale=scale, renorm_eps=renorm_eps,
                                 score=score)
    held = E
    if first_expert is not None:
        held = layer["w1"].shape[0]
        local = experts - first_expert
        mine = (local >= 0) & (local < held)
        experts = jnp.where(mine, local, held)  # held: no expert's group
    g = group_rows(experts, valid, held, tm)
    y = grouped_matmul(h[g["row_token"]], layer["w1"], layer["w3"],
                       layer["w2"], g["tile_expert"], g["n_live"], tm=tm,
                       use_pallas=use_pallas)
    here = valid[:, None, None] if first_expert is None \
        else (valid[:, None] & mine)[:, :, None]
    pairs = jnp.where(here, y[g["pair_row"]], 0)
    out = jnp.sum(pairs.astype(jnp.float32) * weights[:, :, None], axis=1)
    elsewhere = jnp.int32(0) if first_expert is None \
        else jnp.sum(valid[:, None] & ~mine).astype(jnp.int32)
    n_live = g["n_live"][0]
    tail = jnp.stack([elsewhere, n_live * (tm // TM),
                      jnp.sum(g["counts"] > 0).astype(jnp.int32),
                      jnp.int32(1), n_live])
    return out.astype(h.dtype), jnp.concatenate([g["counts"], tail])


# what follows the tokens-per-held-expert in a layer's counter vector:
# pairs routed to experts held elsewhere; the grouped matmul's live rows in
# units of TM (a live tile of 64 rows counts 4); held experts that received
# at least one pair; the passes themselves (1 a call: every count here is a
# sum over them); the kernel's own live row tiles, whatever their height
# (TM x moe_live_tiles / moe_row_tiles: the mean height that ran)
COUNTER_TAIL = ("moe_pairs_elsewhere", "moe_live_tiles",
                "moe_experts_touched", "moe_expert_passes", "moe_row_tiles")
