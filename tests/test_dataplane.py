"""Columnar data plane: struct-of-arrays flow, numpy/JAX tiers, factorized
groupby.  These tests assert the vectorized paths actually RAN (via
vectorize.STATS), not just that results are correct."""

import random

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.debug import table_from_rows
from pathway_tpu.engine import vectorize
from pathway_tpu.engine.columnar import ColumnarBatch
from pathway_tpu.engine.runner import run_tables
from pathway_tpu.internals import parse_graph as pg


class S(pw.Schema):
    g: str
    a: int
    b: float


def _rows(n, seed=0):
    rng = random.Random(seed)
    return [
        (f"g{rng.randrange(20)}", rng.randrange(1000), rng.random())
        for _ in range(n)
    ]


def _pipeline(rows):
    t = table_from_rows(S, rows)
    t2 = t.select(g=t.g, x=t.a * 2 + 1, y=t.b * 0.5)
    t3 = t2.filter(t2.x > 400)
    return t3.groupby(t3.g).reduce(
        t3.g, s=pw.reducers.sum(t3.x), mn=pw.reducers.min(t3.y),
        mx=pw.reducers.max(t3.x), c=pw.reducers.count(),
    )


def _reset_stats():
    vectorize.STATS.update(np_batches=0, jax_batches=0, row_batches=0)


def _run_row_path(rows):
    """Ground truth: force the row interpreter + per-row groupby."""
    import pathway_tpu.engine.runner as rmod

    orig_plan = vectorize.compile_plan
    orig_spec = rmod._groupby_simple_spec
    vectorize.compile_plan = lambda *a, **k: None
    rmod._groupby_simple_spec = lambda *a, **k: None
    try:
        pg.G.clear()
        [cap] = run_tables(_pipeline(rows))
        return cap.squash()
    finally:
        vectorize.compile_plan = orig_plan
        rmod._groupby_simple_spec = orig_spec
        pg.G.clear()


def test_columnar_pipeline_matches_row_path_and_vectorizes():
    rows = _rows(5000)
    expected = _run_row_path(rows)
    _reset_stats()
    pg.G.clear()
    [cap] = run_tables(_pipeline(rows))
    got = cap.squash()
    assert got == expected
    assert vectorize.STATS["np_batches"] >= 2  # select + filter vectorized
    assert vectorize.STATS["row_batches"] == 0


def test_columnar_batch_flows_between_operators():
    """The filter must receive a ColumnarBatch from select (no re-extract)."""
    from pathway_tpu.engine import operators as ops

    seen = {}
    orig = ops.StatelessFilter.process

    def spy(self, port, updates, time):
        seen["type"] = type(updates).__name__
        return orig(self, port, updates, time)

    ops.StatelessFilter.process = spy
    try:
        pg.G.clear()
        [cap] = run_tables(_pipeline(_rows(2000)))
    finally:
        ops.StatelessFilter.process = orig
        pg.G.clear()
    assert seen["type"] == "ColumnarBatch"


def test_jax_tier_runs_when_forced(monkeypatch):
    monkeypatch.setenv("PW_FORCE_JAX_TIER", "1")
    monkeypatch.setattr(vectorize, "_JAX_TIER_ON", None)
    monkeypatch.setattr(vectorize, "JAX_THRESHOLD", 256)
    rows = _rows(4000, seed=5)
    expected = _run_row_path(rows)
    _reset_stats()
    pg.G.clear()
    [cap] = run_tables(_pipeline(rows))
    assert cap.squash() == expected
    assert vectorize.STATS["jax_batches"] >= 1, vectorize.STATS
    monkeypatch.setattr(vectorize, "_JAX_TIER_ON", None)


def test_groupby_minmax_with_retractions():
    """Factorized min/max must honor multiset retraction semantics."""
    rows = []
    for i in range(3000):
        rows.append((f"g{i % 4}", i % 50, float(i % 30), 0, 1))
    # retract the minimum values at a later time
    for i in range(3000):
        if i % 50 == 0:
            rows.append((f"g{i % 4}", i % 50, float(i % 30), 2, -1))

    class SS(pw.Schema):
        g: str
        a: int
        b: float

    pg.G.clear()
    t = table_from_rows(SS, rows, is_stream=True)
    out = t.groupby(t.g).reduce(
        t.g, mn=pw.reducers.min(t.a), mx=pw.reducers.max(t.a),
        s=pw.reducers.sum(t.a),
    )
    [cap] = run_tables(out)
    res = cap.squash()
    by_g = {row[0]: row for row in res.values()}
    # after retraction of a==0 rows, min is 1..., recompute expected directly
    state: dict = {}
    for g, a, b, tt, d in rows:
        state.setdefault(g, []).append((a, d))
    for g, pairs in state.items():
        ms: dict = {}
        s = 0
        for a, d in pairs:
            ms[a] = ms.get(a, 0) + d
            s += a * d
        live = [a for a, c in ms.items() if c > 0]
        assert by_g[g][1] == min(live)
        assert by_g[g][2] == max(live)
        assert by_g[g][3] == s
    pg.G.clear()


def test_method_call_vectorizes():
    """.str-style MethodCallExpression lowers to a fused column map."""
    rows = [(f"word{i}", i, float(i)) for i in range(200)]
    pg.G.clear()
    t = table_from_rows(S, rows)
    out = t.select(u=t.g.str.upper(), n=t.g.str.len())
    _reset_stats()
    [cap] = run_tables(out)
    res = cap.squash()
    vals = sorted(res.values())
    assert vals[0][0].startswith("WORD")
    assert all(v[1] == len(v[0]) for v in vals)
    assert vectorize.STATS["np_batches"] >= 1
    assert vectorize.STATS["row_batches"] == 0
    pg.G.clear()


def test_columnar_batch_compat_protocol():
    cb = ColumnarBatch([1, 2, 3], [[10, 20, 30], ["a", "b", "c"]], [1, 1, -1])
    assert len(cb) == 3
    assert list(cb) == [(1, (10, "a"), 1), (2, (20, "b"), 1), (3, (30, "c"), -1)]
    assert cb[1] == (2, (20, "b"), 1)
    arr = cb.np_col(0)
    assert arr.dtype == np.int64
    sel = cb.select_mask(np.array([True, False, True]))
    assert list(sel) == [(1, (10, "a"), 1), (3, (30, "c"), -1)]
    # validated cache inherited on slice
    assert 0 in sel._np_cache


def test_np_col_type_rules():
    assert ColumnarBatch([1], [[True]], [1]).np_col(0) is None  # bool bails
    assert ColumnarBatch([1], [[None]], [1]).np_col(0) is None
    assert ColumnarBatch([1], [[1, 2.5]], [1, 1]).np_col(0) is None  # mixed
    big = ColumnarBatch([1], [[2**50]], [1])
    assert big.np_col(0) is None  # over leaf bound
    s = ColumnarBatch([1], [["x", "y"]], [1, 1]).np_col(0)
    assert s.dtype == object


def test_int_overflow_falls_back_exact():
    """Ints beyond the leaf bound take the row path and stay exact."""
    big = 2**60
    rows = [("g", big, 0.0)] * 40

    class SB(pw.Schema):
        g: str
        a: int
        b: float

    pg.G.clear()
    t = table_from_rows(SB, rows)
    out = t.select(x=t.a + t.a)
    [cap] = run_tables(out)
    assert all(r[0] == 2**61 for r in cap.squash().values())
    pg.G.clear()


def test_is_none_over_method_call_not_vectorized_wrong():
    """is_none/coalesce over maybe-None method results must match the row
    interpreter (review regression: the static-False shortcut was unsound)."""

    class ST(pw.Schema):
        s: str

    rows = [(str(i) if i % 3 else f"x{i}",) for i in range(200)]
    pg.G.clear()
    t = table_from_rows(ST, rows)
    p = t.s.str.parse_int(optional=True)
    out = t.select(flag=p.is_none(), filled=pw.coalesce(p, -1))
    [cap] = run_tables(out)
    res = cap.squash()
    flags = sorted(v[0] for v in res.values())
    assert flags.count(True) == len([r for r in rows if not r[0].isdigit()])
    for v in res.values():
        if v[0]:
            assert v[1] == -1
        else:
            assert isinstance(v[1], int) and v[1] != -1 or v[1] >= 0
    pg.G.clear()


def test_division_by_zero_poisons_even_vectorized(monkeypatch):
    monkeypatch.setenv("PW_FORCE_JAX_TIER", "1")
    monkeypatch.setattr(vectorize, "_JAX_TIER_ON", None)
    monkeypatch.setattr(vectorize, "JAX_THRESHOLD", 64)

    class SD(pw.Schema):
        a: int
        b: int

    rows = [(i, i % 50) for i in range(500)]  # ten zero divisors
    pg.G.clear()
    t = table_from_rows(SD, rows)
    out = t.select(q=pw.fill_error(t.a / t.b, -1.0))
    [cap] = run_tables(out)
    res = list(cap.squash().values())
    assert sum(1 for (q,) in res if q == -1.0) == 10
    assert not any(isinstance(q, float) and (q != q or q in (float("inf"),))
                   for (q,) in res)
    monkeypatch.setattr(vectorize, "_JAX_TIER_ON", None)
    pg.G.clear()
