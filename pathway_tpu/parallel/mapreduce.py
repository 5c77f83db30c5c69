"""Per-shard map/reduce/join building blocks for the sharded data plane.

DrJAX-style (PAPERS.md, arxiv 2403.07128): per-shard work is expressed as
`map` over shard-local arrays and segment reductions over group codes, so
a shard's aggregation is ONE device program and only aggregates cross the
process fabric.  The family (Round-19):

  - :func:`segment_sum` / :func:`segment_reduce` — per-group
    sum/count/min/max/avg over int group codes; the exact numpy kernel
    or a jitted, shape-bucketed device program (``pw.reduce.segment_*``
    in the cost observatory).  `GroupbyOperator._process_bulk_np` routes
    its scatter-add segment sums through here.
  - :func:`hash_join_membership` — vectorized build-side membership of
    probe join keys (``pw.join.member``); `JoinOperator`'s columnar bulk
    path uses it to skip arrangement probes for rows that provably
    produce no output.
  - :func:`jit_map` — element-wise fn vmapped+jitted once
    (``pw.map.<fn>``).
  - :func:`combine_for_exchange` — the cluster exchange
    (`ClusterRunner._deliver`) consolidates batches bound for a remote
    key-insensitive groupby by ROW VALUE: the multiset of (row, diff) is
    preserved exactly — a receiver's reducers see byte-identical state —
    while the wire carries one frame entry per DISTINCT row instead of
    one per input row (wordcount: ~2000 distinct words for 100k rows).

Exactness rules (the cluster pins 2-proc output byte-identical to
1-proc):

  - consolidation never does arithmetic on VALUES — only diffs (ints)
    are summed — so it is exact for count/min/max unconditionally;
  - sum/avg reducers additionally require int-typed value columns
    (int addition is associative; float partial sums would re-order
    additions vs the serial walk), checked per ROW at runtime — rows
    whose sum/avg values are all ints consolidate, the rest pass
    through raw in place (Round-19: one float row no longer forces the
    whole batch onto the wire);
  - the jitted segment paths are used only for dtypes they represent
    exactly (float32 stays float32, int32-range ints) — everything else
    takes the numpy path; min/max/membership do no arithmetic at all,
    so both paths are exact by construction.

The jit/numpy crossover is no longer a hardcoded constant: unless
pinned by ``PW_MAPREDUCE_JIT_MIN`` (or a test monkeypatching
``_JIT_MIN_ELEMENTS``), it comes from the auto-planner's measured
costdb pair ``pw.reduce.segment_sum.{jit,numpy}`` (obs/planner.py) —
both sides record their wall time per call below, so the crossover is
this backend's, not a guess baked in on someone else's machine.
"""

from __future__ import annotations

import os
import time as _time
from typing import Any

# the documented fresh-host default: below this many elements the jitted
# path cannot beat its dispatch overhead on any backend we measured
_JIT_MIN_DEFAULT = 65536
# operator pin (env) or test monkeypatch; None defers to the planner
_env_jit_min = os.environ.get("PW_MAPREDUCE_JIT_MIN")
_JIT_MIN_ELEMENTS: int | None = int(_env_jit_min) if _env_jit_min else None
# consolidation overhead (one dict pass) is only worth paying when the
# batch could plausibly compress
_COMBINE_MIN_ROWS = 32
# wall-time samples below this size are dispatch noise, not signal
_RECORD_MIN_ELEMENTS = 4096

_jit_cache: dict[tuple, Any] = {}


def jit_min_elements() -> int:
    """The active jit/numpy crossover: an explicit pin
    (``PW_MAPREDUCE_JIT_MIN`` / monkeypatched ``_JIT_MIN_ELEMENTS``)
    wins; otherwise the planner's measured costdb crossover, defaulting
    to :data:`_JIT_MIN_DEFAULT` on a fresh host."""
    if _JIT_MIN_ELEMENTS is not None:
        return _JIT_MIN_ELEMENTS
    try:
        from ..obs import planner

        return planner.cached_crossover(
            "pw.reduce.segment_sum", default=_JIT_MIN_DEFAULT
        )
    except Exception:  # noqa: BLE001 - planning must never take the
        return _JIT_MIN_DEFAULT  # data plane down


def _record_cost(program: str, n: int, ms: float) -> None:
    """One measured wall-time sample into the costdb (``n<pow2>``
    bucket).  ``ms_best`` converges to the warm cost, washing compile
    and scheduler noise out of the planner's comparison."""
    try:
        from ..obs import costdb

        costdb.default_db().observe(program, f"n{_pow2_bucket(n)}", ms=ms)
    except Exception:  # noqa: BLE001 - a read-only cache dir must not
        pass           # take the hot path down


def _pow2_bucket(n: int, floor: int = 1024) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


def _profiled(program: str, prog):
    """profiled_jit with the jax.jit fallback (import-order edge)."""
    try:
        from ..obs.profiler import profiled_jit

        return profiled_jit(program, prog)
    except Exception:  # pragma: no cover - import-order edge
        import jax

        return jax.jit(prog)


def _jit_segment_reduce(kind: str, n_padded: int, n_groups_padded: int,
                        dtype_str: str):
    """One compiled program per (kind, padded length, padded groups,
    dtype) bucket: pad-and-jit keeps the program count logarithmic in
    batch size (the repo-wide bucketing idiom, ops/_tiling.bucket_for).
    Registered in the device cost observatory as
    ``pw.reduce.segment_<kind>`` alongside the serving-path programs."""
    key = (kind, n_padded, n_groups_padded, dtype_str)
    fn = _jit_cache.get(key)
    if fn is None:
        import jax

        if kind == "sum":
            def prog(values, codes):
                return jax.ops.segment_sum(
                    values, codes, num_segments=n_groups_padded
                )
        elif kind == "min":
            def prog(values, codes):
                return jax.ops.segment_min(
                    values, codes, num_segments=n_groups_padded
                )
        else:  # max
            def prog(values, codes):
                return jax.ops.segment_max(
                    values, codes, num_segments=n_groups_padded
                )

        fn = _profiled(f"pw.reduce.segment_{kind}", prog)
        _jit_cache[key] = fn
    return fn


def _run_jit_segment_sum(values, codes, n_groups: int):
    """The padded/bucketed jit dispatch (shared by :func:`segment_sum`
    and the planner's calibration loop, so both measure the SAME
    program).  Pad rows scatter into the last segment; the slice guards
    against a real group sharing it only when n_groups == g_pad (then
    pad adds 0 anyway because padded values are zero)."""
    import numpy as np

    n_pad = _pow2_bucket(values.size)
    g_pad = _pow2_bucket(n_groups, floor=256)
    v = np.zeros(n_pad, values.dtype)
    v[: values.size] = values
    c = np.full(n_pad, g_pad - 1, np.int32)
    c[: values.size] = codes
    out = _jit_segment_reduce("sum", n_pad, g_pad, str(values.dtype))(v, c)
    return np.asarray(out)[:n_groups]


def segment_sum(values, codes, n_groups: int, *, weights=None):
    """reduce_sum building block: per-group sums of ``values`` (optionally
    ``values * weights``) over int group ``codes`` in [0, n_groups).

    Picks the jitted device program when the batch clears the planner's
    measured crossover and the dtype is device-native (int32/float32);
    the exact numpy scatter-add otherwise.  Integer reductions are
    bit-identical on both paths; float32 sums follow the executing
    backend's reduction order, which is why exactness-sensitive callers
    (the engine's int64/float64 columns) always land on the numpy
    path."""
    import numpy as np

    values = np.asarray(values)
    if weights is not None:
        values = values * np.asarray(weights)
    use_jit = (
        values.size >= jit_min_elements()
        and values.dtype in (np.float32, np.int32)
    )
    record = values.size >= _RECORD_MIN_ELEMENTS
    t0 = _time.perf_counter() if record else 0.0
    if not use_jit:
        acc = np.zeros(n_groups, values.dtype)
        np.add.at(acc, codes, values)
        if record:
            _record_cost("pw.reduce.segment_sum.numpy", values.size,
                         (_time.perf_counter() - t0) * 1e3)
        return acc
    out = _run_jit_segment_sum(values, codes, n_groups)
    if record:
        _record_cost("pw.reduce.segment_sum.jit", values.size,
                     (_time.perf_counter() - t0) * 1e3)
    return out


def segment_reduce(values, codes, n_groups: int, kind: str = "sum", *,
                   weights=None):
    """Generalized per-group reduction over int group ``codes``:

    - ``"sum"``  — :func:`segment_sum` (optionally diff-weighted);
    - ``"count"`` — sum of ``weights`` (the diffs), or of ones;
    - ``"min"`` / ``"max"`` — per-group extrema; empty groups hold the
      dtype's identity (max for min, min for max).  No arithmetic is
      performed, so numpy and jit agree bit-for-bit on every dtype the
      jit path admits;
    - ``"avg"`` — the (sums, counts) PAIR; the caller divides, because
      the division's rounding belongs to the reducer's own semantics,
      not the primitive's.

    numpy/jit dual path with the same planner-owned crossover and
    exactness rules as :func:`segment_sum`; jitted programs register as
    ``pw.reduce.segment_<kind>``."""
    import numpy as np

    if kind == "sum":
        return segment_sum(values, codes, n_groups, weights=weights)
    if kind == "count":
        if weights is None:
            weights = np.ones(np.asarray(codes).size, np.int64)
        return segment_sum(weights, codes, n_groups)
    if kind == "avg":
        w = weights if weights is not None else np.ones(
            np.asarray(values).size, np.int64
        )
        return (
            segment_sum(values, codes, n_groups, weights=weights),
            segment_sum(np.asarray(w), codes, n_groups),
        )
    if kind not in ("min", "max"):
        raise ValueError(f"unknown segment_reduce kind: {kind!r}")

    values = np.asarray(values)
    if np.issubdtype(values.dtype, np.floating):
        ident = np.inf if kind == "min" else -np.inf
    else:
        info = np.iinfo(values.dtype)
        ident = info.max if kind == "min" else info.min
    use_jit = (
        values.size >= jit_min_elements()
        and values.dtype in (np.float32, np.int32)
    )
    record = values.size >= _RECORD_MIN_ELEMENTS
    t0 = _time.perf_counter() if record else 0.0
    if not use_jit:
        acc = np.full(n_groups, ident, values.dtype)
        (np.minimum if kind == "min" else np.maximum).at(acc, codes, values)
        if record:
            _record_cost(f"pw.reduce.segment_{kind}.numpy", values.size,
                         (_time.perf_counter() - t0) * 1e3)
        return acc
    n_pad = _pow2_bucket(values.size)
    g_pad = _pow2_bucket(n_groups, floor=256)
    v = np.full(n_pad, ident, values.dtype)
    v[: values.size] = values
    c = np.full(n_pad, g_pad - 1, np.int32)
    c[: values.size] = codes
    out = _jit_segment_reduce(kind, n_pad, g_pad, str(values.dtype))(v, c)
    out = np.asarray(out)[:n_groups]
    if record:
        _record_cost(f"pw.reduce.segment_{kind}.jit", values.size,
                     (_time.perf_counter() - t0) * 1e3)
    return out


def _jit_membership(n_probe_pad: int, n_build_pad: int, dtype_str: str):
    """Sorted-searchsorted membership as one device program
    (``pw.join.member``): for each probe key, whether it occurs in the
    sorted build array.  Pure comparisons — bit-exact on any dtype."""
    key = ("member", n_probe_pad, n_build_pad, dtype_str)
    fn = _jit_cache.get(key)
    if fn is None:
        import jax.numpy as jnp

        def prog(probe, build_sorted):
            idx = jnp.searchsorted(build_sorted, probe)
            idx = jnp.clip(idx, 0, n_build_pad - 1)
            return build_sorted[idx] == probe

        fn = _profiled("pw.join.member", prog)
        _jit_cache[key] = fn
    return fn


def hash_join_membership(probe, build):
    """Vectorized hash-join building block: a bool mask over ``probe``
    marking keys present in ``build`` (both 1-d int arrays of join-key
    codes).  The numpy path is ``np.isin``; above the planner's
    crossover the jitted sorted-searchsorted program runs instead.
    Membership is pure comparison — both paths are exact — so the join
    operator may use the mask to SKIP work, never to change output."""
    import numpy as np

    probe = np.asarray(probe)
    build = np.asarray(build)
    if build.size == 0:
        return np.zeros(probe.size, bool)
    use_jit = (
        probe.size >= jit_min_elements()
        and probe.dtype == build.dtype
        and probe.dtype in (np.int32, np.int64)
    )
    record = probe.size >= _RECORD_MIN_ELEMENTS
    t0 = _time.perf_counter() if record else 0.0
    if not use_jit:
        out = np.isin(probe, build)
        if record:
            _record_cost("pw.join.member.numpy", probe.size,
                         (_time.perf_counter() - t0) * 1e3)
        return out
    import jax

    bs = np.sort(build)
    n_pad = _pow2_bucket(probe.size)
    b_pad = _pow2_bucket(build.size, floor=256)
    p = np.full(n_pad, probe[0], probe.dtype)
    p[: probe.size] = probe
    b = np.full(b_pad, bs[-1], bs.dtype)  # pad with the max: order kept,
    b[: bs.size] = bs                     # membership unchanged
    with jax.enable_x64(True):
        mask = _jit_membership(n_pad, b_pad, str(probe.dtype))(p, b)
    out = np.asarray(mask)[: probe.size]
    if record:
        _record_cost("pw.join.member.jit", probe.size,
                     (_time.perf_counter() - t0) * 1e3)
    return out


def jit_map(fn):
    """map building block: element-wise `fn` vmapped+jitted once — the
    per-shard transform of a map/reduce pipeline as one device program
    (registered in the device cost observatory under the fn's name)."""
    import jax

    name = getattr(fn, "__name__", "fn")
    try:
        from ..obs.profiler import profiled_jit

        return profiled_jit(f"pw.map.{name}", jax.vmap(fn))
    except Exception:  # pragma: no cover - import-order edge
        return jax.jit(jax.vmap(fn))


# -- exchange consolidation (aggregates-only fabric traffic) ---------------

def exchange_combine_spec(op) -> tuple | None:
    """Eligibility of a groupby operator's input exchange for row-value
    consolidation.  Requires the operator's columnar `simple_spec` (plain
    column groupings with count/sum/avg/min/max reducers — exactly the
    key-insensitive reducer set: no reducer reads the engine row key, so
    an update's identity is its (row, diff), not its key).  Returns
    (int_value_positions,) — row positions that must hold ints for a ROW
    to combine (sum/avg exactness), or None when ineligible."""
    spec = getattr(op, "simple_spec", None)
    if spec is None:
        return None
    if getattr(op, "key_fn", None) is not None:
        # custom id_expr may read the key — row identity is not enough
        return None
    _gb_pos, red_plan = spec
    int_positions = tuple(
        p[1] for p in red_plan if p[0] in ("sum", "avg")
    )
    return (int_positions,)


def combine_for_exchange(updates: list, spec: tuple) -> list | None:
    """Consolidate an outgoing exchange batch by ROW VALUE: updates with
    identical rows merge into one (first_key, row, summed_diff) entry and
    cancelled rows (net diff 0) vanish.  The multiset of (row, diff) is
    preserved exactly, so a key-insensitive groupby receiver computes
    byte-identical state.

    Eligibility is per ROW (Round-19): a row whose sum/avg value columns
    are all ints merges; a row holding a float there (or an unhashable
    value) passes through RAW in its original relative position — merged
    float partial sums would re-order additions, but an exact row's
    consolidation is exact regardless of its batch-mates.  Returns None
    (send raw) when the batch is too small or nothing compressed."""
    if len(updates) < _COMBINE_MIN_ROWS:
        return None
    (int_positions,) = spec
    acc: dict = {}
    # emission walk in first-occurrence order: a merged row's slot, or a
    # raw passthrough update pinned in place
    order: list = []
    for u in updates:
        row = u[1]
        entry = None
        try:
            for p in int_positions:
                v = row[p]
                if not isinstance(v, int):  # bool is int; floats are not
                    entry = False  # ineligible: pass through raw
                    break
            if entry is None:
                entry = acc.get(row)
        except TypeError:
            entry = False  # unhashable row values: pass through raw
        if entry is False:
            order.append((None, u))
        elif entry is None:
            acc[row] = [u[0], u[2]]
            order.append((row, None))
        else:
            entry[1] += u[2]
    out: list = []
    for row, raw in order:
        if raw is not None:
            out.append(raw)
        else:
            key, diff = acc[row]
            if diff != 0:
                out.append((key, row, diff))
    if len(out) >= len(updates):
        return None  # nothing compressed: the pass bought no wire bytes
    return out
