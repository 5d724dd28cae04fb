"""Aggregate pushdown: answer count/min/max/sum/mean from footer statistics.

The paper's scan story ("statistics replace indexes") extends naturally to
aggregation: the same per-row-group ``ColumnStats`` that prune a filtered
scan can often *answer* an aggregate outright — a ``count`` or ``min`` over
a predicate needs no decoded page when statistics already decide the
predicate for every row of a row group.  :class:`AggregatePlan` implements
that three-way classification on top of the scan planner:

  fully-pruned   — ``Expr.prune`` refutes the row group (or its whole
                   fragment): contributes nothing, costs nothing.
  fully-covered  — ``Expr.all_match`` proves every row matches (or there
                   is no filter) and no delta shadows the group: the
                   contribution is read straight from the footer
                   (``num_values``/``null_count``/``nan_count``, ``min``/
                   ``max``, and the ``sum`` statistic the writer records
                   per chunk).  **Zero pages decoded.**
  partial        — statistics cannot decide: the row group flows through
                   the normal vectorized scan (morsel-parallel, late
                   materialization, delta overlay, residual filter) and
                   the decoded batches are reduced — min/max through
                   ``active_backend().minmax`` (the Pallas ``page_minmax``
                   kernel on the jax backend).

Merge-on-read deltas fold in **exactly**: a row group whose id range
intersects any upserted or tombstoned id is never answered from its
(stale or to-be-filtered) statistics — it drops to the partial path, where
the :class:`~repro.core.scan.DeltaOverlay` substitutes/drops rows before
the reduction, and upsert-overlapped fragments are fully decoded just as
in a plain scan.

Semantics (SQL-flavored, documented in docs/ARCHITECTURE.md):

  - ``count(col)``  — non-null values (NaN counts: it is a value);
  - ``count(*)``    — rows (spec key ``"*"``);
  - ``min``/``max`` — over non-null values, NaN excluded (numeric or
                      string columns);
  - ``sum``/``mean``— over non-null, non-NaN numeric values; ``None``
                      when no such value exists.

``explain`` surfaces the win: ``ScanCounters.groups_answered_by_stats``
and ``bytes_skipped_agg`` (stored bytes of the read set that were never
decoded because footer statistics answered them).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from ..spans import span
from .backend import active_backend
from .dtypes import KIND_NUMERIC, KIND_STRING
from .expressions import Expr
from .fileformat import TPQReader
from .scan import DeltaOverlay, ScanCounters, ScanPlan, ScanReport
from .schema import ID_COLUMN, Schema
from .statistics import _STR_STAT_MAX, ColumnStats, exact_int_sum
from .table import Table
from .transactions import DeltaEntry

__all__ = ["AggregatePlan", "AGG_OPS"]

AGG_OPS = ("count", "min", "max", "sum", "mean")

AggSpec = Dict[str, Union[str, Sequence[str]]]


def _normalize_spec(spec: AggSpec, schema: Schema) -> Dict[str, List[str]]:
    if not spec:
        raise ValueError("aggregate spec is empty")
    out: Dict[str, List[str]] = {}
    for col, ops in spec.items():
        ops = [ops] if isinstance(ops, str) else list(ops)
        if not ops:
            raise ValueError(f"no aggregate ops for column {col!r}")
        for op in ops:
            if op not in AGG_OPS:
                raise ValueError(f"unknown aggregate op {op!r} "
                                 f"(expected one of {AGG_OPS})")
        if col == "*":
            if ops != ["count"]:
                raise ValueError("'*' supports only the 'count' aggregate")
        else:
            if col not in schema:
                raise KeyError(f"unknown column {col!r}")
            kind = schema[col].dtype.kind
            for op in ops:
                if op in ("sum", "mean") and kind != KIND_NUMERIC:
                    raise TypeError(f"{op}({col}): column is not numeric")
                if op in ("min", "max") and kind not in (KIND_NUMERIC,
                                                         KIND_STRING):
                    raise TypeError(f"{op}({col}): column is not orderable")
                if op == "count":
                    continue
        out[col] = ops
    return out


def _scalar(v: Any) -> Any:
    return v.item() if isinstance(v, np.generic) else v


@dataclasses.dataclass
class _ColAcc:
    """Running reduction state for one aggregated column."""
    count: int = 0       # non-null values (rows, for the "*" accumulator)
    vcount: int = 0      # non-null AND non-NaN — the sum/mean domain
    total: Any = 0       # sum over the vcount domain
    min: Any = None
    max: Any = None

    def add_minmax(self, lo: Any, hi: Any) -> None:
        self.min = lo if self.min is None else min(self.min, lo)
        self.max = hi if self.max is None else max(self.max, hi)


class AggregatePlan:
    """Plan + execute one aggregate query over a manifest snapshot.

    Parameters mirror :class:`~repro.core.scan.ScanPlan` (same
    ``reader_of`` injection, config duck-typing and delta chain); ``spec``
    maps column name — or ``"*"`` — to one op or a list of ops from
    :data:`AGG_OPS`.  ``execute`` returns ``{column: {op: value}}``;
    :meth:`report` (after execute) returns a :class:`ScanReport` whose
    counters include ``groups_answered_by_stats`` / ``bytes_skipped_agg``.
    """

    def __init__(self, files: Sequence[str],
                 reader_of: Callable[[str], TPQReader],
                 schema: Schema, spec: AggSpec,
                 filter_expr: Optional[Expr] = None,
                 cfg=None, deltas: Sequence[DeltaEntry] = (),
                 partitioning=None):
        self._reader_of = reader_of
        self._schema = schema
        self._spec = _normalize_spec(spec, schema)
        self._expr = filter_expr
        self._cfg = cfg
        self._files = list(files)
        self._deltas = list(deltas)
        self._need = [c for c in self._spec if c != "*"]
        # the decode path needs at least one physical column to carry row
        # counts for count(*); id is always present.  Aggregation is
        # order-insensitive, so the plan skips the partition id-merge
        # (ordered=False) while keeping manifest-level partition pruning.
        scan_cols = self._need or [ID_COLUMN]
        self._plan = ScanPlan(files, reader_of, schema, columns=scan_cols,
                              filter_expr=filter_expr, cfg=cfg, deltas=deltas,
                              partitioning=partitioning, ordered=False)
        self._counters: Optional[ScanCounters] = None
        self._executed = False

    # ---------------------------------------------------------------- classify
    def _shadow_free(self, rd: TPQReader, i: int,
                     ov: Optional[DeltaOverlay]) -> bool:
        """No upserted or tombstoned id can fall inside this row group."""
        if ov is None or not ov.has_work:
            return True
        st = rd.row_group_stats(i).get(ID_COLUMN)
        if st is None or st.min is None:
            return False  # cannot bound the group's ids: assume shadowed
        lo = np.searchsorted(ov.shadow_ids, st.min, "left")
        hi = np.searchsorted(ov.shadow_ids, st.max, "right")
        return not bool(hi > lo)

    def _stats_sufficient(self, rd: TPQReader,
                          stats: Dict[str, ColumnStats]) -> bool:
        """Can every requested op be answered from this group's footer?"""
        for col, ops in self._spec.items():
            if col == "*":
                continue  # row count is always in the footer
            st = stats.get(col)
            if st is None:
                continue  # column absent from this file: aligns to null,
                #           contributes nothing — answerable by definition
            all_null = st.num_values == st.null_count
            for op in ops:
                if op == "count":
                    continue
                if all_null:
                    continue  # no valid values: zero contribution
                if op in ("min", "max"):
                    if st.min is None:
                        return False  # e.g. all-NaN float group
                    if isinstance(st.min, str) and (
                            len(st.min) >= _STR_STAT_MAX
                            or len(st.max) >= _STR_STAT_MAX):
                        # long-string bounds are truncated/padded — sound
                        # for pruning, but NOT actual column values, so an
                        # aggregate must not report them: decode instead
                        return False
                if op in ("sum", "mean") and st.sum is None:
                    return False  # pre-`sum`-statistic file: decode it
        return True

    def _covered(self, frag, rd: TPQReader, i: int,
                 ov: Optional[DeltaOverlay]) -> bool:
        if frag.delta_overlap:
            return False  # stale stats: the scan decodes these fully anyway
        if not self._shadow_free(rd, i, ov):
            return False
        stats = rd.row_group_stats(i)
        if self._expr is not None:
            if not frag.pushdown:
                return False  # file is missing a filter column: residual path
            if not self._expr.all_match(stats):
                return False
        return self._stats_sufficient(rd, stats)

    # ----------------------------------------------------------------- reduce
    def _acc_stats(self, accs: Dict[str, _ColAcc], rd: TPQReader,
                   i: int) -> None:
        """Fold one fully-covered row group's footer into the accumulators."""
        stats = rd.row_group_stats(i)
        if "*" in accs:
            accs["*"].count += rd.row_group_num_rows(i)
        for col in self._need:
            st = stats.get(col)
            if st is None:
                continue  # absent column: all null after alignment
            a = accs[col]
            valid = st.num_values - st.null_count
            a.count += valid
            vc = valid - st.nan_count
            a.vcount += vc
            if vc and st.sum is not None:
                a.total = a.total + st.sum
            if st.min is not None:
                a.add_minmax(st.min, st.max)

    def _acc_table(self, accs: Dict[str, _ColAcc], t: Table) -> None:
        """Fold one decoded (filtered, delta-merged) batch into the
        accumulators — same semantics as the footer path."""
        if "*" in accs:
            accs["*"].count += t.num_rows
        for col in self._need:
            c = t.column(col)
            a = accs[col]
            if c.dtype.kind == KIND_NUMERIC:
                vals = c.values if c.validity is None else \
                    c.values[c.validity]
                a.count += int(len(vals))
                nn = vals[~np.isnan(vals)] if c.dtype.is_float else vals
                a.vcount += int(len(nn))
                if len(nn):
                    ops = self._spec[col]
                    if "sum" in ops or "mean" in ops:
                        a.total = a.total + (float(nn.sum())
                                             if c.dtype.is_float
                                             else exact_int_sum(nn))
                    if "min" in ops or "max" in ops:
                        lo, hi = active_backend().minmax(nn)
                        a.add_minmax(_scalar(lo), _scalar(hi))
            elif c.dtype.kind == KIND_STRING:
                valid = int(len(c) - c.null_count)
                a.count += valid
                a.vcount += valid
                ops = self._spec[col]
                if valid and ("min" in ops or "max" in ops):
                    # materialize only when an order statistic needs the
                    # values; a bare count comes from the validity mask
                    vals = [v for v in c.to_pylist() if v is not None]
                    a.add_minmax(min(vals), max(vals))
            else:  # null column (schema-evolved rows): nothing to add
                continue

    # ---------------------------------------------------------------- execute
    def execute(self) -> Dict[str, Dict[str, Any]]:
        """Run the aggregate; returns ``{column: {op: value}}``.

        Covered row groups are answered from footers in plan order; the
        remaining partial groups run through one restricted
        :class:`ScanPlan` (morsel-parallel, delta-exact).
        """
        with span("query.plan"):  # footer statistics answer what they can
            frags = self._plan.fragments()
            ov = self._plan._overlay()
            c = dataclasses.replace(self._plan._plan_counters)
            accs: Dict[str, _ColAcc] = {col: _ColAcc()
                                         for col in self._spec}
            restrict: Dict[str, List[int]] = {}
            read_names = self._plan._read_schema.names
            for frag in frags:
                if frag.partition_pruned:
                    # the filter provably excludes this whole partition:
                    # contributes nothing, and the footer stays unopened
                    continue
                rd = self._reader_of(frag.file)
                cols_here = [n for n in read_names if n in rd.schema]
                for i in frag.row_groups:
                    if self._covered(frag, rd, i, ov):
                        self._acc_stats(accs, rd, i)
                        c.groups_answered_by_stats += 1
                        c.bytes_skipped_agg += rd.read_row_group_bytes(
                            i, cols_here)
                    else:
                        restrict.setdefault(frag.file, []).append(i)
        if restrict:
            part = ScanPlan([f for f in self._files if f in restrict],
                            self._reader_of, self._schema,
                            columns=self._need or [ID_COLUMN],
                            filter_expr=self._expr, cfg=self._cfg,
                            deltas=self._deltas, overlay=ov,
                            restrict=restrict)
            for t in part.execute(counters=c):
                with span("query.compute"):
                    self._acc_table(accs, t)
        self._counters = c
        self._executed = True
        with span("query.compute"):
            return self._results(accs)

    def _results(self, accs: Dict[str, _ColAcc]) -> Dict[str, Dict[str, Any]]:
        out: Dict[str, Dict[str, Any]] = {}
        for col, ops in self._spec.items():
            a = accs[col]
            vals: Dict[str, Any] = {}
            for op in ops:
                if op == "count":
                    vals[op] = a.count
                elif op == "min":
                    vals[op] = _scalar(a.min)
                elif op == "max":
                    vals[op] = _scalar(a.max)
                elif op == "sum":
                    vals[op] = _scalar(a.total) if a.vcount else None
                elif op == "mean":
                    vals[op] = (_scalar(a.total) / a.vcount) if a.vcount \
                        else None
            out[col] = vals
        return out

    # ----------------------------------------------------------------- report
    def report(self) -> ScanReport:
        """Post-execution :class:`ScanReport` with the aggregate counters.

        ``groups_answered_by_stats`` / ``bytes_skipped_agg`` quantify the
        pushdown win; scan-side counters (pages/rows/bytes decoded) cover
        only the partial row groups that actually decoded.
        """
        if not self._executed:
            self.execute()
        return ScanReport(counters=self._counters,
                          fragments=self._plan.fragments(),
                          columns=list(self._need),
                          filter=repr(self._expr)
                          if self._expr is not None else None,
                          executed=True)
