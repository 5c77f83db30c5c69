"""Vectorized (columnar) expression evaluation.

The reference evaluates expressions batch-vectorized per AST node
(src/engine/expression.rs Expressions::eval over whole batches,
dataflow.rs:1572-1604).  Here the same plan compiles twice:

  - a numpy tier (host SIMD) used for any batch over VEC_THRESHOLD rows;
  - a JAX tier (jit -> XLA, fused elementwise chains; the TPU lowering)
    used for numeric plans over JAX_THRESHOLD rows — built lazily on first
    use and traced under enable_x64 so int64/float64 results stay
    byte-identical to the Python row interpreter.

Batches arrive either as row-tuple lists (extracted via try_columns) or as
ColumnarBatch struct-of-arrays (columns reused directly — no per-row
extraction; see engine/columnar.py).

Correctness contract vs the row interpreter:
  - any arithmetic fault or unsupported value shape aborts the columnar
    path and the batch re-runs through the row interpreter (which yields
    per-row Error poisoning);
  - integer expressions carry a static magnitude-bound analysis so int64
    can never wrap (inputs are bounded at column-extraction time), keeping
    results byte-identical to Python bignum semantics.
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np

from ..internals import expression as E
from ..internals.value import Error
from .columnar import ColumnarBatch, _INT_LEAF_BOUND

VEC_THRESHOLD = 32
# fresh-host default; a module-level override (env / test monkeypatch)
# pins it, otherwise the planner's measured pw.map.vecplan crossover
# applies — see _jax_threshold()
_JAX_THRESHOLD_DEFAULT = 65536
JAX_THRESHOLD = _JAX_THRESHOLD_DEFAULT


def _jax_threshold() -> int:
    """Active jax-tier row threshold: an explicit pin (tests monkeypatch
    :data:`JAX_THRESHOLD` directly) wins; otherwise the auto-planner's
    measured jit/numpy crossover for the vectorized plan programs."""
    if JAX_THRESHOLD != _JAX_THRESHOLD_DEFAULT:
        return JAX_THRESHOLD
    try:
        from ..obs import planner

        return planner.cached_crossover(
            "pw.map.vecplan", default=_JAX_THRESHOLD_DEFAULT
        )
    except Exception:  # noqa: BLE001 - planning never takes the plane down
        return _JAX_THRESHOLD_DEFAULT
# the column magnitude bound is enforced at extraction time in columnar.py;
# 2**44 admits millisecond epoch timestamps while keeping sums analyzable
_INT_LEAF_EXP = _INT_LEAF_BOUND.bit_length() - 1
_INT_SAFE_EXP = 62  # results must provably fit in int64

# observability: which tier actually executed (tests assert on these).
# jax_failures counts jax-tier builds or dispatches that raised anything
# but Unsupported: the plan then stays on the numpy tier (the refusal is
# cached, not retried per batch) and the count says it happened
STATS = {"np_batches": 0, "jax_batches": 0, "row_batches": 0,
         "jax_failures": 0}


class Unsupported(Exception):
    pass


class _Node:
    __slots__ = ("fn", "kind", "exp", "jaxable", "nonefree")

    def __init__(self, fn, kind: str, exp: int, jaxable: bool = True,
                 nonefree: bool = True):
        self.fn = fn
        self.kind = kind  # "int" | "float" | "bool" | "str" | "any"
        self.exp = exp  # log2 magnitude bound for ints (overflow analysis)
        self.jaxable = jaxable
        # provably never None within a vectorized batch (input columns are
        # None-free by extraction; method-call results are NOT)
        self.nonefree = nonefree


class Plan:
    """Compiled columnar evaluator. plan(cols) -> list of arrays/scalars."""

    def __init__(self, exprs, nodes: list[_Node], used: set[int], positions):
        self.nodes = nodes
        self.used_columns = used
        self._exprs = exprs
        self._positions = positions
        # XLA offload covers the jaxable SUBSET of output expressions (a
        # string passthrough column must not block fusing the numeric ones);
        # the exact subset depends on runtime column dtypes, so jitted
        # callables are cached per subset signature
        self._jax_static = [i for i, nd in enumerate(nodes) if nd.jaxable]
        # expressions that compute in float64 whatever their columns hold
        self._float_static = [_has_float(e) for e in exprs]
        self._node_deps: list[set[int]] = []
        for e in exprs:
            deps = set()
            for r in e._dependencies():
                ci = positions.get((id(r.table), r._name))
                if ci is not None:
                    deps.add(ci)
            self._node_deps.append(deps)
        self._jax_cache: dict[tuple, Any] = {}

    def _get_jax(self, idx: tuple):
        if idx not in self._jax_cache:
            try:
                self._jax_cache[idx] = _build_jax(
                    [self._exprs[i] for i in idx], self._positions
                )
            except Exception:  # noqa: BLE001 - counted, logged, cached
                self._jax_refused(idx, "build")
        return self._jax_cache[idx]

    def _jax_refused(self, idx: tuple, stage: str) -> None:
        import logging

        STATS["jax_failures"] += 1
        self._jax_cache[idx] = None
        logging.getLogger(__name__).exception(
            "jax tier %s failed; this plan stays on the numpy tier", stage
        )

    def __call__(self, cols: list, n: int | None = None):
        if n is not None and n >= _jax_threshold() and self._jax_static:
            numeric = {
                ci
                for ci in self.used_columns
                if isinstance(cols[ci], np.ndarray) and cols[ci].dtype != object
            }
            if not _f64_is_ieee():
                # integer plans only (see _f64_is_ieee)
                numeric = {ci for ci in numeric if cols[ci].dtype.kind in "iu"}
            idx = tuple(
                i for i in self._jax_static
                if self._node_deps[i] <= numeric
                and (_f64_is_ieee() or not self._float_static[i])
            )
            jf = self._get_jax(idx) if idx else None
            if jf is not None:
                try:
                    jouts = jf(cols)
                except Unsupported:
                    jouts = None  # non-numeric inputs: numpy tier
                except Exception:  # noqa: BLE001 - counted, logged, cached
                    self._jax_refused(idx, "dispatch")
                    jouts = None
                if jouts is not None:
                    out: list = [None] * len(self.nodes)
                    for i, o in zip(idx, jouts):
                        out[i] = np.asarray(o)
                    with np.errstate(
                        divide="raise", invalid="raise", over="raise"
                    ):
                        for i, node in enumerate(self.nodes):
                            if out[i] is None:
                                out[i] = node.fn(cols)
                    STATS["jax_batches"] += 1
                    return out
        # error-poisoning parity: arithmetic faults abort the columnar path;
        # the caller falls back to the row interpreter
        with np.errstate(divide="raise", invalid="raise", over="raise"):
            out = [node.fn(cols) for node in self.nodes]
        STATS["np_batches"] += 1
        return out


def compile_plan(exprs, positions: dict[tuple[int, str], int]):
    """Compile expressions to a columnar Plan; None when unsupported."""
    try:
        nodes = [_compile(e, positions) for e in exprs]
    except Unsupported:
        return None

    used: set[int] = set()
    for e in exprs:
        for ref in e._dependencies():
            idx = positions.get((id(ref.table), ref._name))
            if idx is not None:
                used.add(idx)
    return Plan(exprs, nodes, used, positions)


_JAX_TIER_ON: bool | None = None


def _jax_tier_on() -> bool:
    """The jax tier exists for accelerators: it is on when the default
    backend is not the CPU (there numpy wins — no dispatch or transfer
    overhead), and PW_FORCE_JAX_TIER=1 turns it on anywhere so the CPU
    suite exercises it.  Decided once per process."""
    global _JAX_TIER_ON
    if _JAX_TIER_ON is None:
        import os

        import jax

        _JAX_TIER_ON = (
            jax.default_backend() != "cpu"
            or os.environ.get("PW_FORCE_JAX_TIER") == "1"
        )
    return _JAX_TIER_ON


_F64_IS_IEEE: bool | None = None


def _f64_is_ieee() -> bool:
    """Whether the backend's float64 is numpy's.  A TPU emulates it (f32
    exponent range, ~48 mantissa bits): on a v5e 96% of float64 products
    differed from numpy's in their last bits (chip probe, PR 21).  A plan
    that computes in float64 there could not be byte-identical to the row
    interpreter, which is the tier's contract, so on a TPU the jax tier
    takes integer plans only; int64 is exact there."""
    global _F64_IS_IEEE
    if _F64_IS_IEEE is None:
        import jax

        _F64_IS_IEEE = jax.default_backend() != "tpu"
    return _F64_IS_IEEE


def _has_float(e) -> bool:
    """Does the expression compute in float64 whatever its columns hold —
    a float constant, a cast to float, a true division?"""
    from ..internals import dtype as dt

    if isinstance(e, E.ConstExpression):
        return isinstance(e._value, float)
    if isinstance(e, E.CastExpression):
        return (e._target.strip_optional() == dt.FLOAT
                or _has_float(e._expr))
    if isinstance(e, E.BinaryOpExpression):
        return (e._op == "/" or _has_float(e._left)
                or _has_float(e._right))
    if isinstance(e, E.UnaryOpExpression):
        return _has_float(e._expr)
    if isinstance(e, E.IfElseExpression):
        return any(_has_float(x) for x in (e._cond, e._then, e._else))
    if isinstance(e, E.CoalesceExpression):
        return any(_has_float(x) for x in e._args)
    return False


def _build_jax(exprs, positions):
    """JAX tier: trace the same AST over jnp under x64 so dtypes match the
    row engine exactly; jit gives XLA fusion (and the device path on TPU).
    Returns None when the tier is off or the plan holds an expression the
    tier does not cover (:class:`Unsupported`); anything else raises."""
    if not _jax_tier_on():
        return None
    import jax
    import jax.numpy as jnp

    from ..obs.profiler import profiled_jit

    try:
        nodes = [_compile(e, positions, xp=jnp) for e in exprs]
    except Unsupported:
        return None
    used = sorted(
        {
            positions[(id(r.table), r._name)]
            for e in exprs
            for r in e._dependencies()
            if (id(r.table), r._name) in positions
        }
    )
    pos_map = {ci: j for j, ci in enumerate(used)}

    def raw(arrs):
        cols: list = [None] * (max(used) + 1 if used else 0)
        for ci, j in pos_map.items():
            cols[ci] = arrs[j]
        return [node.fn(cols) for node in nodes]

    jitted = profiled_jit("pw.map.vecplan", raw)

    def call(all_cols):
        arrs = [all_cols[ci] for ci in used]
        if any(
            a is None or not isinstance(a, np.ndarray) or a.dtype == object
            for a in arrs
        ):
            raise Unsupported("non-numeric column in jax tier")
        with jax.enable_x64(True):
            return jitted(arrs)

    return call


def _compile(e, positions, xp=np) -> _Node:
    if isinstance(e, E.ColumnReference):
        if e._name == "id":
            raise Unsupported("id column")
        idx = positions.get((id(e._table), e._name))
        if idx is None:
            raise Unsupported("unknown column")
        # column kind resolved at runtime by try_columns; assume numeric-int
        # bound for the overflow analysis (strings get kind "any")
        return _Node(lambda cols: cols[idx], "any", _INT_LEAF_EXP)
    if isinstance(e, E.ConstExpression):
        v = e._value
        if isinstance(v, bool):
            return _Node(lambda cols: v, "bool", 0)
        if isinstance(v, int):
            exp = max(v.bit_length(), 1)
            if exp > 62:
                raise Unsupported("large int const")
            return _Node(lambda cols: v, "int", exp)
        if isinstance(v, float):
            return _Node(lambda cols: v, "float", 0)
        if isinstance(v, str):
            return _Node(lambda cols: v, "str", 0, jaxable=False)
        raise Unsupported("const type")
    if isinstance(e, E.BinaryOpExpression):
        n1 = _compile(e._left, positions, xp)
        n2 = _compile(e._right, positions, xp)
        op = e._op
        fn = _vec_binop(op, xp)
        if fn is None:
            raise Unsupported(op)
        exp = _bound(op, n1, n2)
        if exp > _INT_SAFE_EXP:
            raise Unsupported("possible int64 overflow")
        f1, f2 = n1.fn, n2.fn
        kind = "bool" if op in _CMP_OPS else "any"
        # division stays on the numpy tier: XLA's x/0 yields inf (int: 0)
        # where the row interpreter poisons with Error — errstate parity
        # exists only under numpy
        jaxable = n1.jaxable and n2.jaxable and op not in ("/", "//", "%")
        return _Node(
            lambda cols: fn(f1(cols), f2(cols)), kind, exp,
            jaxable=jaxable,
            nonefree=n1.nonefree and n2.nonefree,
        )
    if isinstance(e, E.UnaryOpExpression):
        n1 = _compile(e._expr, positions, xp)
        f1 = n1.fn
        if e._op == "-":
            return _Node(
                lambda cols: -f1(cols), n1.kind, n1.exp + 1, n1.jaxable,
                n1.nonefree,
            )

        def invert(cols):
            a = xp.asarray(f1(cols))
            return ~a

        return _Node(invert, n1.kind, n1.exp, n1.jaxable, n1.nonefree)
    if isinstance(e, E.IfElseExpression):
        nc = _compile(e._cond, positions, xp)
        nt = _compile(e._then, positions, xp)
        ne = _compile(e._else, positions, xp)
        fc, ft, fe = nc.fn, nt.fn, ne.fn
        return _Node(
            lambda cols: xp.where(fc(cols), ft(cols), fe(cols)),
            "any", max(nt.exp, ne.exp),
            nc.jaxable and nt.jaxable and ne.jaxable,
            nc.nonefree and nt.nonefree and ne.nonefree,
        )
    if isinstance(e, E.IsNoneExpression):
        # the static shortcut is only sound for provably None-free operands
        # (input columns); a method-call result CAN be None — row path then
        inner = _compile(e._expr, positions, xp)
        if not inner.nonefree:
            raise Unsupported("is_none over maybe-None operand")
        result = isinstance(e, E.IsNotNoneExpression)
        return _Node(lambda cols: result, "bool", 0)
    if isinstance(e, E.CoalesceExpression):
        # None-free first argument wins outright; maybe-None args (method
        # calls) fall back to the row interpreter
        for a in e._args:
            if isinstance(a, E.ConstExpression) and a._value is None:
                continue
            node = _compile(a, positions, xp)
            if not node.nonefree:
                raise Unsupported("coalesce over maybe-None argument")
            return node
        raise Unsupported("coalesce of all-None")
    if isinstance(e, E.CastExpression):
        inner = _compile(e._expr, positions, xp)
        from ..internals import dtype as dt

        if not inner.nonefree:
            raise Unsupported("cast over maybe-None operand")
        target = e._target.strip_optional()
        fi = inner.fn
        if target == dt.FLOAT:
            return _Node(
                lambda cols: xp.asarray(fi(cols), _f64(xp)), "float", 0,
                inner.jaxable,
            )
        if target == dt.INT:
            return _Node(
                lambda cols: xp.asarray(fi(cols), _i64(xp)), "int",
                _INT_LEAF_EXP, inner.jaxable,
            )
        raise Unsupported("cast target")
    if isinstance(e, E.MethodCallExpression) and xp is np:
        # .dt/.str/.num method calls vectorize as a single fused column map:
        # no per-row env dicts, one Python-level loop per batch (host tier
        # only — the per-value fn is arbitrary Python)
        arg_nodes = [_compile(a, positions, np) for a in e._args]
        fn = e._fn
        if fn is None:
            raise Unsupported("method without fn")
        if len(arg_nodes) == 1:
            f1 = arg_nodes[0].fn

            def mapped(cols, _fn=fn, _f1=f1):
                a = _f1(cols)
                if isinstance(a, np.ndarray):
                    vals = a.tolist()
                elif isinstance(a, list):
                    vals = a
                else:
                    return _fn(a)
                # object dtype: results may be None/heterogeneous, and any
                # consumer must do elementwise Python ops, never list concat
                out = np.empty(len(vals), object)
                out[:] = [_fn(v) for v in vals]
                return out

            return _Node(
                mapped, "any", _INT_LEAF_EXP, jaxable=False, nonefree=False
            )

        fns = [a.fn for a in arg_nodes]

        def mapped_n(cols, _fn=fn, _fns=fns):
            vals = [f(cols) for f in _fns]
            n = None
            for v in vals:
                if isinstance(v, (np.ndarray, list)):
                    n = len(v)
                    break
            if n is None:
                return _fn(*vals)
            lists = [
                v.tolist() if isinstance(v, np.ndarray)
                else (v if isinstance(v, list) else [v] * n)
                for v in vals
            ]
            out = np.empty(n, object)
            out[:] = [_fn(*vs) for vs in zip(*lists)]
            return out

        return _Node(
            mapped_n, "any", _INT_LEAF_EXP, jaxable=False, nonefree=False
        )
    raise Unsupported(type(e).__name__)


def _f64(xp):
    return np.float64 if xp is np else xp.float64


def _i64(xp):
    return np.int64 if xp is np else xp.int64


_CMP_OPS = {"==", "!=", "<", "<=", ">", ">="}


def _bound(op: str, n1: _Node, n2: _Node) -> int:
    if op in _CMP_OPS or op in ("&", "|", "^"):
        return 0
    if op in ("+", "-"):
        return max(n1.exp, n2.exp) + 1
    if op == "*":
        return n1.exp + n2.exp
    if op == "//":
        return n1.exp
    if op == "%":
        return n2.exp
    if op == "/":
        return 0  # float result; errstate traps overflow/div0
    if op == "**":
        raise Unsupported("** not vectorized (unbounded int growth)")
    return 63


def _vec_binop(op: str, xp):
    if op == "/":
        return lambda a, b: xp.asarray(a, _f64(xp)) / b
    return _PY_BINOPS.get(op)


_PY_BINOPS = {
    "+": lambda a, b: a + b,
    "-": lambda a, b: a - b,
    "*": lambda a, b: a * b,
    "//": lambda a, b: a // b,
    "%": lambda a, b: a % b,
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
    "&": lambda a, b: a & b,
    "|": lambda a, b: a | b,
    "^": lambda a, b: a ^ b,
}


def try_columns(updates, ncols: int, used: set[int]):
    """Extract used columns as homogeneous numpy arrays.

    ColumnarBatch inputs reuse their cached column arrays (no per-row work).
    Returns None (forcing the row-interpreter path) when a column mixes
    types, contains None/Error, or holds ints outside the overflow-safe
    leaf bound.
    """
    if isinstance(updates, ColumnarBatch):
        cols: list = [None] * max(ncols, len(updates.cols))
        for ci in used:
            arr = updates.np_col(ci)
            if arr is None:
                return None
            cols[ci] = arr
        return cols
    n = len(updates)
    cols = [None] * ncols
    for ci in used:
        kinds = set()
        for _k, row, _d in updates:
            v = row[ci]
            if v is None or isinstance(v, Error):
                return None
            if isinstance(v, (bool, np.bool_)):
                kinds.add("bool")
            elif isinstance(v, (int, np.integer)):
                kinds.add("int")
            elif isinstance(v, (float, np.floating)):
                kinds.add("float")
            elif isinstance(v, str):
                kinds.add("str")
            else:
                return None
            if len(kinds) > 1:
                return None
        kind = kinds.pop() if kinds else "int"
        if kind == "bool":
            # numpy bool arithmetic (True+True -> True) diverges from Python
            # int semantics; bool columns stay on the row interpreter
            return None
        if kind == "int":
            dt_ = np.int64
        elif kind == "float":
            dt_ = np.float64
        else:
            dt_ = object  # strings
        try:
            arr = np.empty(n, dt_)
            for i, (_k, row, _d) in enumerate(updates):
                arr[i] = row[ci]
            if kind == "int" and (
                np.any(arr > _INT_LEAF_BOUND) or np.any(arr < -_INT_LEAF_BOUND)
            ):
                return None
            cols[ci] = arr
        except (TypeError, ValueError, OverflowError):
            return None
    return cols
