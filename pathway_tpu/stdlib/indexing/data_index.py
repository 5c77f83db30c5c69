"""DataIndex: index-as-a-join over live tables.

Reference: stdlib/indexing/data_index.py:206,278 — `query()` is fully
incremental (answers are revised as data changes), `query_as_of_now()` is
request/response (answered once, never revised; the serving path).
Lowered to a single engine operator keeping an InnerIndex plus the data rows
(src/engine/dataflow/operators/external_index.rs equivalent).
"""

from __future__ import annotations

import time as _time
from collections import deque
from typing import Any, Callable

from ... import obs
from ...engine.graph import DiffOutputOperator
from ...engine.runner import register_lowering, _env_for, _compile
from ...engine.types import consolidate
from ...internals import dtype as dt
from ...internals import parse_graph as pg
from ...internals.expression import (ApplyExpression, ColumnExpression,
                                     ColumnReference, wrap)
from ...internals.table import Table, Universe
from ...internals.value import ERROR, Error


class ExternalIndexOperator(DiffOutputOperator):
    """Port 0: queries, port 1: data."""

    def __init__(
        self,
        query_env,
        data_env,
        index_factory: Callable[[], Any],
        query_item_fn,
        data_item_fn,
        data_meta_fn,
        k_fn,
        filter_fn,
        n_data_cols: int,
        as_of_now: bool,
        name="external_index",
        query_expr=None,
        data_expr=None,
    ):
        super().__init__(2, name)
        self.query_env, self.data_env = query_env, data_env
        self.index = index_factory()
        self.query_item_fn = query_item_fn
        self.data_item_fn = data_item_fn
        # the item expressions themselves: an embedder's apply carries a
        # batch_fn, which _eval_items calls once per micro-batch
        self._query_expr, self._data_expr = query_expr, data_expr
        self._items: deque = deque()  # this batch's precomputed data items
        self.data_meta_fn = data_meta_fn
        self.k_fn = k_fn
        self.filter_fn = filter_fn
        self.n_data_cols = n_data_cols
        self.as_of_now = as_of_now
        self.emitted: dict[int, tuple] = {}  # as-of-now answers
        self._pending: list = []

    # -- index maintenance -------------------------------------------------
    @staticmethod
    def _eval_items(expr, item_fn, env_builder, rows: list) -> list:
        """The item of every ``(key, row)``.  An apply with a ``batch_fn``
        (the embedders': pad -> one device forward -> per-row handles) is
        called ONCE for the whole micro-batch, as a select would call it —
        that is what keeps ingested vectors on the device; any other
        expression evaluates row by row."""
        envs = [env_builder.build(k, r) for k, r in rows]
        if (len(rows) > 1 and isinstance(expr, ApplyExpression)
                and expr._batch_fn is not None and not expr._kwargs):
            # a failing batch raises: an embedder that cannot reach the
            # device must not read as a document that could not be indexed
            return expr._eval_batch(envs, row_fallback=False)
        return [item_fn(env) for env in envs]

    def _precompute_data_items(self, updates) -> None:
        self._items = deque(self._eval_items(
            self._data_expr, self.data_item_fn, self.data_env,
            [(k, r) for k, r, d in updates if d > 0],
        ))

    def pre_apply(self, port, key, row, diff):
        if port != 1:
            return
        if diff > 0:
            env = self.data_env.build(key, row)
            item = (self._items.popleft() if self._items
                    else self.data_item_fn(env))
            if item is None or isinstance(item, Error):
                return
            meta = self.data_meta_fn(env) if self.data_meta_fn else None
            self.index.add(key, item, meta)
        else:
            self.index.remove(key)

    def dirty_keys_for(self, port, key):
        if self.as_of_now:
            return ()
        if port == 0:
            return (key,)
        return tuple(self.last_out.keys()) or tuple(self.state[0].keys())

    def process(self, port, updates, time):
        if port == 1:
            self._precompute_data_items(updates)
        if not self.as_of_now:
            if port == 1:
                # mark all queries dirty BEFORE updating the index
                self._dirty.update(self.state[0].keys())
            super().process(port, updates, time)
            if port == 1:
                self._dirty.update(self.state[0].keys())
            return
        # as-of-now: data updates apply immediately; query batches buffer
        # until flush so EVERY data update at this logical time is visible
        # to queries at this time, independent of intra-time arrival order
        # (the canonical level-ordered walk delivers all of an op's input
        # batches for a time before its flush)
        if port == 1:
            for key, row, diff in updates:
                self.pre_apply(1, key, row, diff)
                self.state[1].apply(key, row, diff)
            return
        self._pending.append(list(updates))

    def flush(self, time):
        if not self.as_of_now:
            super().flush(time)
            return
        for updates in self._pending:
            self._answer_query_batch(updates, time)
        self._pending.clear()

    def _answer_query_batch(self, updates, time):
        # answer query inserts, never revise.  Inserts are answered in
        # arrival order (batched per consecutive run) so a same-batch
        # insert+delete cancels correctly.
        out = []
        pending_inserts: list = []

        def flush_inserts():
            if not pending_inserts:
                return
            # per-pass index-probe span (Round-11): attributes the RAG
            # serving path's time to the index stage — the sub-index
            # probes/fusion and embedder nest under the same timeline
            t0 = _time.perf_counter()
            if len(pending_inserts) >= 4:
                answers = self._answer_batch(pending_inserts)
            else:
                answers = [self._answer(k, r) for k, r in pending_inserts]
            obs.record_span("index.query", t0, _time.perf_counter(),
                            index=self.name, n=len(pending_inserts))
            # backpressure observability: how many concurrent queries each
            # index pass actually served (serve/metrics.py; the engine-side
            # counterpart of the REST scheduler's batch occupancy)
            try:
                from ...serve.metrics import serve_stats

                stats = serve_stats(f"index:{self.name}")
                stats.record_admitted(len(pending_inserts))
                stats.record_batch(len(pending_inserts))
                stats.record_completed(len(pending_inserts))
            except Exception:
                pass
            for (key, _row), ans in zip(pending_inserts, answers):
                out.append((key, ans, 1))
                self.emitted[key] = ans
            pending_inserts.clear()

        for key, row, diff in updates:
            if diff > 0:
                self.state[0].apply(key, row, diff)
                pending_inserts.append((key, row))
            else:
                flush_inserts()
                self.state[0].apply(key, row, diff)
                prev = self.emitted.pop(key, None)
                if prev is not None:
                    out.append((key, prev, -1))
        flush_inserts()
        if out:
            self.emit(time, consolidate(out))

    def _answer_batch(self, inserts: list) -> list[tuple]:
        """Batched as-of-now answers: one device dispatch when the index
        supports it; per-query filters or odd rows fall back individually."""
        if not hasattr(self.index, "search_batch") or self.filter_fn is not None:
            return [self._answer(k, r) for k, r in inserts]
        qs = self._eval_items(self._query_expr, self.query_item_fn,
                              self.query_env, inserts)
        metas = [
            (q, self.k_fn(self.query_env.build(key, row)))
            for q, (key, row) in zip(qs, inserts)
        ]
        empty = ((), ()) + ((),) * self.n_data_cols
        valid = [
            i for i, (q, k) in enumerate(metas)
            if q is not None and not isinstance(q, Error) and not isinstance(k, Error)
        ]
        ks = {int(metas[i][1]) for i in valid}
        answers: list = [empty] * len(inserts)
        if not valid:
            return answers
        if len(ks) != 1:
            for i in valid:
                answers[i] = self._pack(
                    self.index.search(metas[i][0], int(metas[i][1]), None)
                )
            return answers
        k = ks.pop()
        try:
            results = self.index.search_batch([metas[i][0] for i in valid], k)
        except (TypeError, ValueError):
            # queries that do not stack into one (Q, d) batch; a device
            # failure is not one of these and propagates
            for i in valid:
                answers[i] = self._pack(self.index.search(metas[i][0], k, None))
            return answers
        for i, matches in zip(valid, results):
            answers[i] = self._pack(matches)
        return answers

    def _pack(self, matches: list) -> tuple:
        keys = tuple(m[0] for m in matches)
        scores = tuple(float(m[1]) for m in matches)
        cols = []
        for i in range(self.n_data_cols):
            vals = []
            for mk in keys:
                drow = self.state[1].get_row(mk)
                vals.append(drow[i] if drow is not None else None)
            cols.append(tuple(vals))
        return (keys, scores) + tuple(cols)

    def _answer(self, key, row) -> tuple:
        env = self.query_env.build(key, row)
        q = self.query_item_fn(env)
        if q is None or isinstance(q, Error):
            return ((), ()) + ((),) * self.n_data_cols
        k = self.k_fn(env)
        mf = self.filter_fn(env) if self.filter_fn else None
        return self._pack(self.index.search(q, int(k), mf))

    def compute(self, key):
        row = self.state[0].get_row(key)
        if row is None:
            return None
        return self._answer(key, row)


@register_lowering("external_index")
def _lower_external_index(node, lg):
    p = node.params
    qt, data = node.input_tables
    return ExternalIndexOperator(
        _env_for(qt),
        _env_for(data),
        p["index_factory"],
        _compile(p["query_item"]),
        _compile(p["data_item"]),
        _compile(p["data_meta"]) if p.get("data_meta") is not None else None,
        _compile(p["k_expr"]),
        _compile(p["filter_expr"]) if p.get("filter_expr") is not None else None,
        len(data._colnames),
        p["as_of_now"],
        query_expr=p["query_item"],
        data_expr=p["data_item"],
    )


class DataIndex:
    """An index over `data_table` built from `data_column`."""

    def __init__(
        self,
        data_table: Table,
        data_column: ColumnExpression,
        *,
        index_factory: Callable[[], Any],
        metadata_column: ColumnExpression | None = None,
        embedder: Callable | None = None,
    ):
        self.data_table = data_table
        self.embedder = embedder
        if embedder is not None:
            data_column = embedder(data_column)
        self.data_column = data_table._desugar(data_column)
        self.metadata_column = (
            data_table._desugar(metadata_column) if metadata_column is not None else None
        )
        self.index_factory = index_factory

    def _query(
        self,
        query_column: ColumnExpression,
        *,
        number_of_matches: Any = 3,
        metadata_filter: ColumnExpression | None = None,
        as_of_now: bool,
    ) -> Table:
        deps = [
            r.table for r in wrap(query_column)._dependencies() if isinstance(r.table, Table)
        ]
        if not deps:
            raise ValueError("query column must reference the query table")
        qt = deps[0]
        qcol = qt._desugar(query_column)
        if self.embedder is not None:
            qcol = qt._desugar(self.embedder(qcol))
        k_expr = qt._desugar(number_of_matches) if isinstance(
            number_of_matches, ColumnExpression
        ) else wrap(number_of_matches)
        f_expr = qt._desugar(metadata_filter) if metadata_filter is not None else None
        node = pg.new_node(
            "external_index",
            [qt, self.data_table],
            index_factory=self.index_factory,
            query_item=qcol,
            data_item=self.data_column,
            data_meta=self.metadata_column,
            k_expr=k_expr,
            filter_expr=f_expr,
            as_of_now=as_of_now,
        )
        data_cols = self.data_table.column_names()
        out_names = ["_pw_index_reply_id", "_pw_index_reply_score"] + data_cols
        dtypes: dict[str, dt.DType] = {
            "_pw_index_reply_id": dt.List(dt.POINTER),
            "_pw_index_reply_score": dt.List(dt.FLOAT),
        }
        for n in data_cols:
            dtypes[n] = dt.List(self.data_table._dtype_of(n))
        return Table(node, out_names, dtypes, qt._universe, name="index_reply")

    def query(self, query_column, *, number_of_matches=3, collapse_rows=True,
              metadata_filter=None, **kwargs) -> Table:
        return self._query(
            query_column,
            number_of_matches=number_of_matches,
            metadata_filter=metadata_filter,
            as_of_now=False,
        )

    def query_as_of_now(self, query_column, *, number_of_matches=3, collapse_rows=True,
                        metadata_filter=None, **kwargs) -> Table:
        return self._query(
            query_column,
            number_of_matches=number_of_matches,
            metadata_filter=metadata_filter,
            as_of_now=True,
        )
