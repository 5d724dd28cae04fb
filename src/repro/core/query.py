"""Lazy composable Query API: one plan-builder behind every read path.

The paper's interface grew four parallel entrypoints — ``read``,
``aggregate``, ``explain`` and the filter halves of ``update``/``delete`` —
that each re-spell columns/filters/threads and cannot be composed.  This
module unifies them behind a DuckDB-style *relational builder*:

    db.query()
      .where(field("age") >= 30)            # fused with later wheres
      .select("name", "age", bonus=field("salary") * 0.1)
      .order_by("age", desc=True)
      .limit(10)
      .to_table()

A :class:`Query` is **immutable** and **lazy**: every builder method
returns a new Query, nothing touches disk until a terminal
(``to_table`` / ``iter_batches`` / ``to_pylist`` / ``count`` / ``agg`` /
``explain``) runs.  Column names are validated at plan-build time against
the dataset schema — a typo raises a clear ``KeyError`` naming the column
and the schema instead of failing deep inside the scan.

Compilation pushes work down as far as statistics allow:

  - adjacent ``where`` calls fuse into one AND predicate, pushed into
    :class:`~repro.core.scan.ScanPlan` (file/row-group/page pruning);
  - the projection pushed to the scan is the union of selected columns,
    computed-expression inputs, group keys and aggregate columns — nothing
    else is decoded;
  - an ungrouped ``agg`` routes through the footer-statistics fast path in
    :class:`~repro.core.aggregate.AggregatePlan` (identical results and
    counters to the legacy ``db.aggregate``);
  - ``group_by(...).agg(...)`` runs a hash aggregation: numpy
    factorize-style grouping of each decoded batch into **partial** group
    states *inside the morsel workers* (``ScanPlan.execute(map_fn=...)``),
    merged single-threaded on the consumer — aggregation overlaps decode;
  - ``limit(n)`` / ``offset(n)`` on an un-ordered query terminate the scan
    early: once ``limit + offset`` rows survive the residual filter the
    result generator is closed, which stops submitting morsels — a needle
    query with ``limit(1)`` decodes a fraction of the full scan (visible
    in ``explain(execute=True)`` counters);
  - ``order_by`` with a ``limit`` keeps a running top-``limit+offset``
    accumulator per batch instead of materializing the full result.

The legacy surface (``ParquetDB.read/aggregate/explain``, ``Dataset``, and
the probe scans inside ``update``/``delete``) is a set of thin shims over
this module — one plan-construction code path, byte-identical results.

Semantics notes (SQL-flavored, matching :mod:`repro.core.aggregate`):
``count(col)`` counts non-null values, ``count(*)`` counts rows,
``min/max/sum/mean`` reduce over non-null non-NaN values and yield None
for empty groups.  Grouping treats null as one group and (float) NaN as
another; sorts are stable with nulls last (NaN sorts after all values).
Grouped integer sums accumulate in int64 (the footer fast path keeps
arbitrary precision).
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, Generator, List, Optional, Sequence, Tuple

import numpy as np

from . import nested
from ..spans import query_span, rooted, span
from .aggregate import AggregatePlan, _normalize_spec
from .backend import active_backend
from .dtypes import DType, KIND_NULL, KIND_NUMERIC, KIND_STRING
from .expressions import (And, Arith, Comparison, Expr, FieldRef, IsIn,
                          IsNaN, IsNull, Not, Or)
from .scan import ScanCounters, ScanPlan, ScanReport, rechunk
from .schema import Field, ID_COLUMN, Schema
from .table import (Column, Table, concat_tables, infer_column,
                    null_column_of)

__all__ = ["Query", "GroupedQuery", "QueryReport", "canonical_expr"]

# Singleton NaN used as a grouping key: dict lookups on tuples hit the
# identity fast path, so every NaN row lands in ONE group even though
# nan != nan.
_NAN_KEY = float("nan")

_GROUPABLE_KINDS = (KIND_NUMERIC, KIND_STRING, KIND_NULL)


def _no_such_column(name: str, schema: Schema) -> KeyError:
    return KeyError(f"unknown column {name!r}; schema columns are "
                    f"{schema.names}")


def _resolve_names(schema: Schema, cols: Sequence[str]) -> List[str]:
    """Expand dotted parents against ``schema``; KeyError names the typo."""
    out: List[str] = []
    for c in cols:
        kids = nested.children_of(schema.names, c)
        if not kids:
            raise _no_such_column(c, schema)
        out.extend(kids)
    return out


# ---------------------------------------------------------------------------
# plan canonicalization: fused-expression fingerprints for plan caches
# ---------------------------------------------------------------------------
def _canon_value(v: Any) -> str:
    """Type-tagged scalar rendering so ``1`` and ``1.0`` and ``True`` key
    differently (they filter differently on mixed columns)."""
    if isinstance(v, FieldRef):
        return f"field({v.name})"
    if isinstance(v, (np.integer, np.floating, np.bool_)):
        v = v.item()
    return f"{type(v).__name__}:{v!r}"


def canonical_expr(e: Optional[Expr]) -> str:
    """Canonical text for a predicate tree, stable under the rewrites that
    don't change its meaning: AND/OR chains are flattened, their operands
    sorted and deduped (commutative + associative + idempotent), and
    ``isin`` value lists are sorted and deduped.  Two ``where`` chains that
    ask the same question — ``where(a).where(b)`` vs ``where(b).where(a)``
    — render identically, which is what lets a plan cache key on the fused
    expression instead of its construction order.  ``None`` (no filter)
    renders as the empty string."""
    if e is None:
        return ""
    if isinstance(e, (And, Or)):
        op = "and" if isinstance(e, And) else "or"
        parts: List[str] = []
        stack: List[Expr] = [e]
        while stack:
            node = stack.pop()
            if type(node) is type(e):
                stack.append(node.a)  # type: ignore[attr-defined]
                stack.append(node.b)  # type: ignore[attr-defined]
            else:
                parts.append(canonical_expr(node))
        parts = sorted(set(parts))
        if len(parts) == 1:  # a & a
            return parts[0]
        return f"{op}({','.join(parts)})"
    if isinstance(e, Not):
        return f"not({canonical_expr(e.a)})"
    if isinstance(e, Comparison):
        return f"cmp({e.name},{e.op},{_canon_value(e.value)})"
    if isinstance(e, IsIn):
        vals = sorted(set(_canon_value(v) for v in e.values))
        return f"isin({e.name},[{','.join(vals)}])"
    if isinstance(e, IsNull):
        return f"{'isvalid' if e._negated else 'isnull'}({e.name})"
    if isinstance(e, IsNaN):
        return f"isnan({e.name})"
    # unknown Expr subclass: fall back to repr — correct (never conflates
    # distinct plans) just not order-insensitive
    return repr(e)


def _canon_computed(ve: Any) -> str:
    """Structural rendering of a value expression (computed column)."""
    if isinstance(ve, FieldRef):
        return f"field({ve.name})"
    if isinstance(ve, Arith):
        return (f"arith({ve.op},{_canon_computed(ve.a)},"
                f"{_canon_computed(ve.b)})")
    return _canon_value(ve)


# ---------------------------------------------------------------------------
# hash grouping: numpy factorize + segmented reduction
# ---------------------------------------------------------------------------
def _sorted_codes(vals: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``np.unique(vals, return_inverse=True)``; integers over a span not
    much wider than their count go through a dense table instead of a
    sort."""
    if vals.dtype.kind in "iu" and len(vals):
        lo = vals.min()
        if int(vals.max()) - int(lo) < 2 * len(vals) + 4096:
            # offsets from the minimum, in a width that cannot wrap
            wide = np.int64 if vals.dtype.kind == "i" else np.uint64
            at = (vals.astype(wide) - wide(lo)).astype(np.intp)
            seen = np.zeros(int(at.max()) + 1, bool)
            seen[at] = True
            rank = np.cumsum(seen) - 1
            u = np.flatnonzero(seen).astype(wide) + wide(lo)
            return u.astype(vals.dtype), rank[at]
    return np.unique(vals, return_inverse=True)


def _string_codes(col: Column, valid: Optional[np.ndarray]
                  ) -> Optional[Tuple[np.ndarray, List[str]]]:
    """(codes, keys) of a string column's valid rows (``valid`` None: all)
    from its offsets and blob, keys in code-point order as Python's
    ``str`` sorts them (UTF-8 bytes compare alike).  Each value is its
    bytes, zero-padded, then its length, which ties a value to any longer
    one it is a prefix of: up to seven bytes that is one integer (no
    length where every value has the same), longer ones sort by
    big-endian words.  None when padding every value would take far more
    room than the blob (a few very long values)."""
    off = col.offsets
    starts, lens = off[:-1], np.diff(off)
    if valid is not None:
        starts, lens = starts[valid], lens[valid]
    nv = len(lens)
    blob = np.ascontiguousarray(col.blob)
    wmax = int(lens.max()) if nv else 0
    if wmax <= 7:
        same = bool(nv) and int(lens.min()) == wmax
        key = np.zeros(nv, np.uint64)
        for j in range(wmax):
            if same:
                b = blob[starts + j]
            else:
                b = np.zeros(nv, np.uint8)
                has = lens > j
                b[has] = blob[starts[has] + j]
            key = (key << np.uint64(8)) | b
        if not same:
            key = (key << np.uint64(3)) | lens.astype(np.uint64)
        u, inv = _sorted_codes(key)
        keys = [(x if same else x >> 3).to_bytes(wmax, "big")
                [:wmax if same else x & 7].decode("utf-8")
                for x in u.tolist()]
        return inv, keys
    width = -(-wmax // 8) * 8
    if nv * width > 16 * len(blob) + (1 << 20):
        return None
    pad = np.zeros((nv, width), np.uint8)
    row = np.repeat(np.arange(nv), lens)
    new_off = np.zeros(nv + 1, np.int64)
    np.cumsum(lens, out=new_off[1:])
    pos = np.arange(len(row)) - new_off[:-1][row]
    pad[row, pos] = blob[starts[row] + pos]
    words = pad.view(">u8")
    order = np.lexsort([lens] + [words[:, j]
                                 for j in range(width // 8 - 1, -1, -1)])
    w, ln = words[order], lens[order]
    new = np.r_[True, (w[1:] != w[:-1]).any(axis=1) | (ln[1:] != ln[:-1])]
    inv = np.empty(nv, np.int64)
    inv[order] = np.cumsum(new) - 1
    first = order[np.flatnonzero(new)]
    keys = [bytes(blob[starts[i]:starts[i] + lens[i]]).decode("utf-8")
            for i in first]
    return inv, keys


def _factorize(col: Column) -> Tuple[np.ndarray, List[Any]]:
    """Per-column dictionary encoding: (codes[n], keys) with keys[codes[i]]
    the python key value of row i, keys sorted.  Null rows form one group,
    float-NaN rows another (keyed by the ``_NAN_KEY`` singleton)."""
    n = len(col)
    k = col.dtype.kind
    if k == KIND_NULL:
        return np.zeros(n, np.int64), [None]
    if k == KIND_NUMERIC:
        valid = np.ones(n, bool) if col.validity is None else col.validity
        vals = col.values
        nan = (np.isnan(vals) & valid if vals.dtype.kind == "f"
               else np.zeros(n, bool))
        ok = valid & ~nan
        u, inv = _sorted_codes(vals[ok])
        codes = np.zeros(n, np.int64)
        codes[ok] = inv
        keys: List[Any] = [v.item() for v in u]
        if nan.any():
            codes[nan] = len(keys)
            keys.append(_NAN_KEY)
        if not valid.all():
            codes[~valid] = len(keys)
            keys.append(None)
        return codes, keys
    if k == KIND_STRING:
        valid = col.validity
        got = _string_codes(col, valid)
        if got is None:  # a few very long values: sort them as objects
            pl = col.to_pylist()
            present = np.array([v for v in pl if v is not None],
                               dtype=object)
            u, inv = np.unique(present, return_inverse=True)
            got = inv, list(u)
        inv, keys = got
        if valid is None:
            return inv.astype(np.int64, copy=False), keys
        codes = np.full(n, len(keys), np.int64)
        codes[valid] = inv
        if not valid.all():
            keys.append(None)
        return codes, keys
    raise TypeError(f"cannot group/dedupe on a {col.dtype} column")


def _row_codes(t: Table, key_cols: Sequence[str]
               ) -> Tuple[np.ndarray, List[tuple]]:
    """Row-wise group codes over ``key_cols``; keys are python tuples.

    No keys means one global group (the ungrouped-aggregate fallback).
    """
    if not key_cols:
        return np.zeros(t.num_rows, np.int64), [()]
    per = [_factorize(t.column(k)) for k in key_cols]
    if len(per) == 1:
        codes, keys = per[0]
        return codes, [(kv,) for kv in keys]
    # mixed-radix combine, re-compacted after every key so the running
    # value stays < (distinct rows so far) * (next cardinality) <= n^2 —
    # no int64 overflow however many near-unique keys are combined
    codes, keys0 = per[0]
    codes = codes.astype(np.int64, copy=True)
    keys_out: List[tuple] = [(kv,) for kv in keys0]
    for codes_i, keys_i in per[1:]:
        card = max(len(keys_i), 1)
        combined = codes * card + codes_i
        u, inv = _sorted_codes(combined)
        keys_out = [keys_out[c // card] + (keys_i[c % card],)
                    for c in u.tolist()]
        codes = inv.astype(np.int64, copy=False)
    return codes, keys_out


class _GroupPartial:
    """Per-morsel partial aggregation state (built inside scan workers)."""
    __slots__ = ("keys", "rows", "cols")

    def __init__(self, keys: List[tuple], rows: np.ndarray,
                 cols: Dict[str, dict]):
        self.keys, self.rows, self.cols = keys, rows, cols


def _device_partial(t: Table, codes: np.ndarray, g: int,
                    spec: Dict[str, List[str]],
                    computed: Dict[str, Any]
                    ) -> Optional[Tuple[np.ndarray, Dict[str, dict]]]:
    """``(rows, columns)`` of a partial from the decode backend's grouped
    sums, or None where the backend leaves the morsel to the host.  A column's
    term is its computed expression or the column itself; one with a
    ``min`` or ``max`` has none (they are not sums)."""
    names = [c for c in spec if c != "*"]
    terms = [None if {"min", "max"} & set(spec[c])
             else computed.get(c, FieldRef(c)) for c in names]
    got = active_backend().group_sums(codes, g, terms, t)
    if got is None:
        return None
    counts, sums = got
    cols: Dict[str, dict] = {}
    for c, total in zip(names, sums):
        entry = {"count": counts, "vcount": counts}
        if "sum" in spec[c] or "mean" in spec[c]:
            entry["sum"] = total
        cols[c] = entry
    return counts, cols


def _partial_groups(t: Table, key_cols: Sequence[str],
                    spec: Dict[str, List[str]],
                    computed: Sequence[Tuple[str, Any]] = ()
                    ) -> _GroupPartial:
    """Factorize one batch and reduce every aggregate column per group.

    ``computed`` defines aggregate columns that are not in ``t``: where
    the decode backend takes the sums (integer inputs, few groups), it
    forms them from their inputs itself; otherwise they are evaluated
    here, and every column is reduced by one stable sort +
    ``ufunc.reduceat`` segmented reduction (sum keeps the source dtype,
    so int64 sums do not round-trip through float).
    """
    codes, keys = _row_codes(t, key_cols)
    g = len(keys)
    got = _device_partial(t, codes, g, spec, dict(computed))
    if got is not None:
        return _GroupPartial(keys, *got)
    for name, ve in computed:
        t = t.set_column(name, ve.evaluate_column(t))
    rows = np.bincount(codes, minlength=g)
    cols = {}
    for col, ops in spec.items():
        if col == "*":
            continue
        c = t.column(col)
        entry: Dict[str, Any] = {}
        need_sum = "sum" in ops or "mean" in ops
        need_mm = "min" in ops or "max" in ops
        if c.dtype.kind == KIND_NUMERIC:
            vals = c.values
            if vals.dtype.kind == "b":
                vals = vals.astype(np.int64)
            valid = (np.ones(len(c), bool) if c.validity is None
                     else c.validity)
            nn = valid.copy()
            if vals.dtype.kind == "f":
                nn &= ~np.isnan(vals)
            entry["count"] = np.bincount(codes[valid], minlength=g)
            entry["vcount"] = np.bincount(codes[nn], minlength=g)
            if need_sum:
                entry["sum"] = np.zeros(g, vals.dtype)
            if need_mm:
                entry["min"] = np.zeros(g, vals.dtype)
                entry["max"] = np.zeros(g, vals.dtype)
            sel = np.nonzero(nn)[0]
            if len(sel) and (need_sum or need_mm):
                order = np.argsort(codes[sel], kind="stable")
                cc = codes[sel][order]
                xx = vals[sel][order]
                starts = np.nonzero(np.r_[True, cc[1:] != cc[:-1]])[0]
                gids = cc[starts]
                if need_sum:
                    entry["sum"][gids] = np.add.reduceat(xx, starts)
                if need_mm:
                    entry["min"][gids] = np.minimum.reduceat(xx, starts)
                    entry["max"][gids] = np.maximum.reduceat(xx, starts)
        elif c.dtype.kind == KIND_STRING:
            valid = (np.ones(len(c), bool) if c.validity is None
                     else c.validity)
            entry["count"] = np.bincount(codes[valid], minlength=g)
            entry["vcount"] = entry["count"]
            if need_mm:
                # the column's own codes are ranks of its sorted values
                amn = np.full(g, None, object)
                amx = np.full(g, None, object)
                sel = np.nonzero(valid)[0]
                if len(sel):
                    ranks, values = _factorize(c)
                    order = np.argsort(codes[sel], kind="stable")
                    cc = codes[sel][order]
                    rr = ranks[sel][order]
                    starts = np.nonzero(np.r_[True, cc[1:] != cc[:-1]])[0]
                    gids = cc[starts]
                    amn[gids] = [values[r] for r in
                                 np.minimum.reduceat(rr, starts)]
                    amx[gids] = [values[r] for r in
                                 np.maximum.reduceat(rr, starts)]
                entry["min"], entry["max"] = amn, amx
        else:
            # null column (schema-evolved rows) or count over exotic types:
            # only validity-derived facts are defined
            valid = (np.zeros(len(c), bool) if c.dtype.kind == KIND_NULL
                     else np.ones(len(c), bool) if c.validity is None
                     else c.validity)
            entry["count"] = np.bincount(codes[valid], minlength=g)
            entry["vcount"] = entry["count"]
            if need_mm:
                entry["min"] = np.full(g, None, object)
                entry["max"] = np.full(g, None, object)
        cols[col] = entry
    return _GroupPartial(keys, rows, cols)


class _GroupedAcc:
    """Merged (global) group state; fed partials in plan order.

    The merge is the single-threaded half of the morsel-parallel
    aggregation: workers build :class:`_GroupPartial` objects, the
    consumer folds them here — no accumulator is ever shared across
    threads.
    """

    def __init__(self, spec: Dict[str, List[str]]):
        self.spec = spec
        self.index: Dict[tuple, int] = {}
        self.keys: List[tuple] = []
        self.rows: List[int] = []
        self.cols: Dict[str, Dict[str, list]] = {
            col: {"count": [], "vcount": [], "sum": [], "min": [], "max": []}
            for col in spec if col != "*"}

    def merge(self, p: _GroupPartial) -> None:
        idx_map: List[int] = []
        for k in p.keys:
            j = self.index.get(k)
            if j is None:
                j = len(self.keys)
                self.index[k] = j
                self.keys.append(k)
                self.rows.append(0)
                for st in self.cols.values():
                    st["count"].append(0)
                    st["vcount"].append(0)
                    st["sum"].append(0)
                    st["min"].append(None)
                    st["max"].append(None)
            idx_map.append(j)
        for gi, j in enumerate(idx_map):
            self.rows[j] += int(p.rows[gi])
            for col, entry in p.cols.items():
                st = self.cols[col]
                st["count"][j] += int(entry["count"][gi])
                vc = int(entry["vcount"][gi])
                if not vc:
                    continue
                st["vcount"][j] += vc
                if "sum" in entry:
                    st["sum"][j] = st["sum"][j] + entry["sum"][gi].item()
                if "min" in entry:
                    mn, mx = entry["min"][gi], entry["max"][gi]
                    mn = mn.item() if isinstance(mn, np.generic) else mn
                    mx = mx.item() if isinstance(mx, np.generic) else mx
                    st["min"][j] = (mn if st["min"][j] is None
                                    else min(st["min"][j], mn))
                    st["max"][j] = (mx if st["max"][j] is None
                                    else max(st["max"][j], mx))

    # -- shaping ------------------------------------------------------------
    def _op_value(self, col: str, op: str, j: int) -> Any:
        if col == "*":
            return self.rows[j]
        st = self.cols[col]
        if op == "count":
            return st["count"][j]
        if st["vcount"][j] == 0:
            return None
        if op == "sum":
            return st["sum"][j]
        if op == "mean":
            return st["sum"][j] / st["vcount"][j]
        return st[op][j]  # min / max

    def scalars(self) -> Dict[str, Dict[str, Any]]:
        """Ungrouped (zero-key) shape: ``{column: {op: value}}``."""
        out: Dict[str, Dict[str, Any]] = {}
        have = len(self.keys) > 0
        for col, ops in self.spec.items():
            vals: Dict[str, Any] = {}
            for op in ops:
                if have:
                    vals[op] = self._op_value(col, op, 0)
                else:
                    vals[op] = 0 if op == "count" else None
            out[col] = vals
        return out

    def to_table(self, key_cols: Sequence[str], schema: Schema) -> Table:
        """Grouped result: key columns + one ``{col}_{op}`` column per agg."""
        fields: List[Field] = []
        cols: Dict[str, Column] = {}
        n = len(self.keys)
        for i, kc in enumerate(key_cols):
            if n:
                col, _ = infer_column([k[i] for k in self.keys],
                                      dtype_hint=schema[kc].dtype)
            else:
                col = null_column_of(schema[kc].dtype, 0)
            cols[kc] = col
            fields.append(Field(kc, col.dtype))
        for col_name, ops in self.spec.items():
            for op in ops:
                out_name = agg_column_name(col_name, op)
                if n:
                    vals = [self._op_value(col_name, op, j) for j in range(n)]
                    c, _ = infer_column(vals)
                else:
                    c = null_column_of(_agg_dtype(schema, col_name, op), 0)
                cols[out_name] = c
                fields.append(Field(out_name, c.dtype))
        return Table(Schema(fields), cols)


def agg_column_name(col: str, op: str) -> str:
    """Output column name of one aggregate: ``count`` for ``("*",
    "count")``, else ``{col}_{op}``."""
    return "count" if col == "*" else f"{col}_{op}"


def _agg_dtype(schema: Schema, col: str, op: str) -> DType:
    if op == "count" or col == "*":
        return DType.numeric("i8")
    if op == "mean":
        return DType.numeric("f8")
    return schema[col].dtype  # min/max/sum keep the source dtype


# ---------------------------------------------------------------------------
# ordering
# ---------------------------------------------------------------------------
def _order_codes(col: Column, desc: bool) -> Tuple[np.ndarray, np.ndarray]:
    """(null_marker, rank_codes) for one sort key: sortable int64 arrays.

    Rank-based so int64 never rounds through float and strings sort
    without materializing per-comparison; nulls always sort last (the
    marker outranks the code), NaN ranks above every value.
    """
    codes, keys = _factorize(col)
    n = len(codes)
    # keys order from _factorize: sorted values, then NaN, then None —
    # exactly ascending rank order with NaN greatest, so codes ARE ranks
    # except the null code, which the marker handles.
    null_code = len(keys) - 1 if keys and keys[-1] is None else None
    null_m = np.zeros(n, np.int64)
    rank = codes.astype(np.int64, copy=True)
    if null_code is not None:
        is_null = codes == null_code
        null_m[is_null] = 1
        rank[is_null] = 0
    if desc:
        rank = -rank
    return null_m, rank


def _sort_indices(t: Table, order: Sequence[Tuple[str, bool]]) -> np.ndarray:
    """Stable row permutation for ``ORDER BY`` (ties keep arrival order)."""
    arrays: List[np.ndarray] = []
    for col, desc in order:  # most significant first
        null_m, rank = _order_codes(t.column(col), desc)
        arrays.append(null_m)
        arrays.append(rank)
    return np.lexsort(tuple(reversed(arrays)))


def _distinct_batch(t: Table, seen: set) -> Table:
    """Drop rows whose full output tuple was already emitted (stateful)."""
    codes, keys = _row_codes(t, t.column_names)
    u, first = np.unique(codes, return_index=True)
    keep: List[int] = []
    for code, fi in zip(u.tolist(), first.tolist()):
        k = keys[code]
        if k not in seen:
            seen.add(k)
            keep.append(fi)
    if len(keep) == t.num_rows:
        return t
    keep.sort()
    return t.take(np.array(keep, np.int64))


# ---------------------------------------------------------------------------
# compiled plan + report
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Compiled:
    man: Any                     # Manifest snapshot
    schema: Schema
    plan: ScanPlan
    scan_cols: List[str]         # projection pushed into the scan
    out_pre: List[str]           # pre-aggregation output columns
    computed: List[Tuple[str, Any]]


@dataclasses.dataclass
class QueryReport:
    """What :meth:`Query.explain` returns: the operator tree + scan report.

    ``ops`` lists the operators outermost-first (Limit → OrderBy →
    Distinct → Aggregate → Project → Filter → Scan) with a human-readable
    detail string each; ``scan`` is the underlying
    :class:`~repro.core.scan.ScanReport` whose :class:`ScanCounters`
    carry the pruning/decoding/pushdown counters.  When ``executed`` is
    True the query actually ran, so the counters reflect observed work —
    including the effect of early-terminating ``limit`` scans (fewer
    pages/rows decoded than the plan selected), and the integrity /
    degraded-mode counters (``files_quarantined`` delta files skipped under
    ``on_corruption="quarantine"``, ``pool_rebuilds`` and
    ``morsels_decoded_inline`` after a process-pool worker crash).
    """
    ops: List[Tuple[str, str]]
    scan: ScanReport
    executed: bool

    @property
    def counters(self) -> ScanCounters:
        return self.scan.counters

    def to_dict(self) -> dict:
        return {"ops": [{"op": o, "detail": d} for o, d in self.ops],
                "scan": self.scan.to_dict(),
                "executed": self.executed}

    def __str__(self) -> str:
        lines = ["Query"]
        depth = 1
        for op, detail in self.ops:
            pad = "  " * depth
            lines.append(f"{pad}{op}[{detail}]" if detail else f"{pad}{op}")
            depth += 1
        pad = "  " * depth
        lines.extend(pad + ln for ln in str(self.scan).splitlines())
        return "\n".join(lines)


class GroupedQuery:
    """Intermediate of ``Query.group_by(*cols)`` — call :meth:`agg`."""

    def __init__(self, query: "Query", keys: List[str]):
        self._query, self._keys = query, keys

    def agg(self, spec) -> "Query":
        """Aggregate each group; ``spec`` maps column (or ``"*"``) to one
        op or a list of ops from ``("count", "min", "max", "sum",
        "mean")``: a stored column or one that ``select(**computed)``
        defined.  The result relation has the group-key columns plus one
        ``{col}_{op}`` column per aggregate (``count`` for ``"*"``), and
        composes with ``order_by`` / ``limit`` / ``offset``."""
        q = self._query
        norm = _normalize_spec(spec, _agg_schema(q._schema(), q._computed))
        return q._replace(group_keys=list(self._keys), agg_spec=norm)


def _agg_schema(schema: Schema, computed: Sequence[Tuple[str, Any]]
                ) -> Schema:
    """``schema`` with each computed column in it, typed as its
    expression evaluates over no rows; a computed name shadows a stored
    one, as ``select`` does."""
    if not computed:
        return schema
    names = {n for n, _ in computed}
    fields = [f for f in schema if f.name not in names]
    for name, ve in computed:
        empty = Table.empty(schema.select(list(dict.fromkeys(ve.columns()))))
        fields.append(Field(name, ve.evaluate_column(empty).dtype))
    return Schema(fields)


class Query:
    """Immutable, lazily-evaluated query over one ParquetDB dataset.

    Build with :meth:`ParquetDB.query` / :meth:`Dataset.query`; chain
    ``where`` / ``select`` / ``group_by().agg()`` / ``order_by`` /
    ``limit`` / ``offset`` / ``distinct``; finish with a terminal —
    ``to_table()``, ``iter_batches()``, ``to_pylist()``, ``count()``,
    ``agg(spec)`` or ``explain()``.  Every builder step validates column
    names against the dataset schema immediately.  See the module
    docstring for what the compiler pushes into the scan.
    """

    def __init__(self, db, cfg=None, man=None):
        self._db = db
        self._cfg = cfg
        self._man = man          # bound manifest (write paths); None = committed
        self._where: Optional[Expr] = None
        self._nwhere = 0
        self._select: Optional[List[str]] = None
        self._computed: List[Tuple[str, Any]] = []
        self._group_keys: Optional[List[str]] = None
        self._agg_spec: Optional[Dict[str, List[str]]] = None
        self._order: List[Tuple[str, bool]] = []
        self._limit: Optional[int] = None
        self._offset = 0
        self._distinct = False

    # ------------------------------------------------------------- plumbing
    def _replace(self, **kw) -> "Query":
        q = Query.__new__(Query)
        for slot in ("_db", "_cfg", "_man", "_where", "_nwhere", "_select",
                     "_computed", "_group_keys", "_agg_spec", "_order",
                     "_limit", "_offset", "_distinct"):
            setattr(q, slot, getattr(self, slot))
        for name, val in kw.items():
            setattr(q, "_" + name, val)
        return q

    def _snapshot(self):
        if self._man is not None:
            return self._man, self._db._manifest_schema(self._man)
        return self._db._load_snapshot()

    def _schema(self) -> Schema:
        return self._snapshot()[1]

    def _aggregated(self) -> bool:
        return self._agg_spec is not None

    def _agg_out_names(self) -> List[str]:
        names = list(self._group_keys or [])
        for col, ops in (self._agg_spec or {}).items():
            names.extend(agg_column_name(col, op) for op in ops)
        return names

    def _output_names(self, schema: Schema) -> List[str]:
        if self._aggregated():
            return self._agg_out_names()
        computed = [n for n, _ in self._computed]
        if self._select is not None:
            return list(self._select)
        return schema.names + computed

    # ------------------------------------------------------- fingerprinting
    def plan_fingerprint(self) -> str:
        """Canonical one-line description of this plan, stable under
        meaning-preserving rewrites: commutative ``where`` conjuncts,
        ``isin`` value order and projection order all render identically
        (rows come back as name-addressed records, so projection order
        is not part of the question being asked).  Order-sensitive parts
        — ``order_by`` keys, ``limit``/``offset``, ``distinct`` — stay
        order-sensitive.  This is the payload behind :meth:`plan_key`."""
        sel = "*" if self._select is None else ",".join(sorted(self._select))
        computed = ";".join(f"{n}={_canon_computed(ve)}"
                            for n, ve in sorted(self._computed))
        agg = ""
        if self._agg_spec is not None:
            agg = ";".join(f"{c}:{'+'.join(sorted(ops))}"
                           for c, ops in sorted(self._agg_spec.items()))
        order = ";".join(f"{c}:{'desc' if d else 'asc'}"
                         for c, d in self._order)
        return "|".join([
            f"where={canonical_expr(self._where)}",
            f"select={sel}",
            f"computed={computed}",
            f"group={','.join(self._group_keys) if self._group_keys is not None else ''}",
            f"agg={agg}",
            f"order={order}",
            f"limit={self._limit}",
            f"offset={self._offset}",
            f"distinct={self._distinct}",
        ])

    def plan_key(self) -> str:
        """Stable hex digest of :meth:`plan_fingerprint` — the cache key
        used by the serving tier's normalized-plan and result caches.
        Equivalent plans share a key; plans that can answer differently
        (different ``limit``/``offset``/``order_by``) never do."""
        return hashlib.blake2b(self.plan_fingerprint().encode(),
                               digest_size=16).hexdigest()

    # ------------------------------------------------------------- builders
    def _require_before_window(self, what: str) -> None:
        """Filters/projections execute below OrderBy/Limit in the fixed
        operator tree, so allowing them after would silently answer a
        different question than the chain reads — reject, like group_by."""
        if self._order or self._limit is not None or self._offset:
            raise ValueError(f"{what} must come before order_by()/limit()/"
                             f"offset(); it executes below them")

    def where(self, expr: Expr) -> "Query":
        """Filter rows; consecutive calls fuse into one AND predicate that
        is pushed down to footer statistics (file/row-group/page pruning).
        Must precede ``group_by().agg()`` and ``order_by``/``limit``."""
        if self._aggregated():
            raise ValueError("where() must precede group_by().agg(); "
                             "filter the rows before aggregating them")
        self._require_before_window("where()")
        if not isinstance(expr, Expr):
            raise TypeError(f"where() expects an Expr (e.g. field('x') > 0),"
                            f" got {type(expr).__name__}")
        schema = self._schema()
        for c in expr.columns():
            if c not in schema:
                raise _no_such_column(c, schema)
        fused = expr if self._where is None else (self._where & expr)
        return self._replace(where=fused, nwhere=self._nwhere + 1)

    def select(self, *cols: str, **computed) -> "Query":
        """Project and/or add computed columns.

        Positional names project (dotted parents expand to their nested
        children); keyword arguments define computed columns from value
        expressions — ``select("name", bonus=field("salary") * 0.1)``.
        With no positional names the current projection is kept and the
        computed columns are appended.  Unknown names raise ``KeyError``
        at plan-build time.
        """
        if self._aggregated():
            raise ValueError("select() must precede group_by().agg(); "
                             "aggregate output columns are defined by the "
                             "agg spec")
        self._require_before_window("select()")
        schema = self._schema()
        prev_computed = dict(self._computed)
        new_computed = list(self._computed)
        for name, ve in computed.items():
            if not isinstance(ve, (FieldRef, Arith)):
                raise TypeError(
                    f"computed column {name!r} must be a value expression "
                    f"(field(...) arithmetic), got {type(ve).__name__}")
            for c in ve.columns():
                if c not in schema:
                    raise _no_such_column(c, schema)
            if name in prev_computed:
                new_computed = [(n, v) if n != name else (name, ve)
                                for n, v in new_computed]
            else:
                new_computed.append((name, ve))
        computed_names = {n for n, _ in new_computed}
        if cols:
            out: List[str] = []
            for c in cols:
                if c in computed_names:
                    out.append(c)
                else:
                    out.extend(_resolve_names(schema, [c]))
            out.extend(n for n in computed.keys() if n not in out)
            return self._replace(select=out, computed=new_computed)
        if self._select is not None:
            out = list(self._select)
            out.extend(n for n in computed.keys() if n not in out)
            return self._replace(select=out, computed=new_computed)
        return self._replace(computed=new_computed)

    def _project_exact(self, names: Sequence[str]) -> "Query":
        """Internal: set the projection to exactly ``names`` (already
        resolved/validated by the caller — the legacy ``read`` shim, whose
        ``columns=[]`` means *no* data columns, unlike ``select()``)."""
        return self._replace(select=list(names))

    def group_by(self, *cols: str) -> GroupedQuery:
        """Start a grouped aggregation (follow with ``.agg(spec)``).

        Group keys must be physical numeric/string columns.  Null keys
        form one group, float-NaN keys another.  ``group_by`` must come
        before ``order_by``/``limit``/``offset``/``distinct`` (those apply
        to the aggregated result)."""
        if self._aggregated():
            raise ValueError("group_by() cannot follow another agg()")
        if self._order or self._limit is not None or self._offset \
                or self._distinct:
            raise ValueError("group_by() must come before order_by()/"
                             "limit()/offset()/distinct()")
        schema = self._schema()
        keys: List[str] = []
        for c in cols:
            if c not in schema:
                raise _no_such_column(c, schema)
            if schema[c].dtype.kind not in _GROUPABLE_KINDS:
                raise TypeError(f"cannot group by {c!r} of type "
                                f"{schema[c].dtype}")
            keys.append(c)
        return GroupedQuery(self, keys)

    def order_by(self, col: str, desc: bool = False) -> "Query":
        """Sort the result by ``col`` (stable; nulls last, NaN greatest).
        Repeated calls append secondary sort keys.  With ``limit`` the
        executor keeps a running top-k instead of a full materialize."""
        avail = self._output_names(self._schema())
        if col not in avail:
            raise KeyError(f"unknown order_by column {col!r}; output "
                           f"columns are {avail}")
        return self._replace(order=self._order + [(col, bool(desc))])

    def limit(self, n: int) -> "Query":
        """Keep at most ``n`` rows.  Without ``order_by`` the scan stops
        early: once ``limit + offset`` rows survive, pending morsels are
        cancelled (observable in ``explain(execute=True)``)."""
        if n < 0:
            raise ValueError("limit must be >= 0")
        return self._replace(limit=int(n))

    def offset(self, n: int) -> "Query":
        """Skip the first ``n`` result rows."""
        if n < 0:
            raise ValueError("offset must be >= 0")
        return self._replace(offset=int(n))

    def distinct(self) -> "Query":
        """Drop duplicate output rows (first occurrence wins, order kept).
        Must come before ``order_by``/``limit`` (it executes below them)."""
        if not self._aggregated():
            self._require_before_window("distinct()")
        return self._replace(distinct=True)

    # -------------------------------------------------------------- compile
    def _compile(self) -> _Compiled:
        with span("query.plan"):
            return self._compile_plan()

    def _compile_plan(self) -> _Compiled:
        man, schema = self._snapshot()
        out_pre = ([] if self._aggregated()
                   else self._output_names(schema))
        # a computed column dropped by a later positional select() is dead:
        # don't decode its inputs or evaluate it per batch (order_by keys
        # are always output columns, so this can never drop a sort key);
        # a grouped query keeps those it aggregates
        live = self._agg_spec if self._aggregated() else out_pre
        computed = [(n, ve) for n, ve in self._computed if n in live]
        computed_names = {n for n, _ in computed}
        scan_cols: List[str] = []

        def need(name: str) -> None:
            if name not in scan_cols:
                scan_cols.append(name)

        if self._aggregated():
            for kcol in self._group_keys:
                need(kcol)
            for col in self._agg_spec:
                if col in computed_names:
                    for c in dict(computed)[col].columns():
                        need(c)
                elif col != "*":
                    need(col)
            if not scan_cols:
                # count(*)-only grouped spec still needs one physical
                # column to carry row counts: the fixed-width id, never a
                # wide var-len column
                need(ID_COLUMN if ID_COLUMN in schema else schema.names[0])
        else:
            for name in out_pre:
                if name in computed_names:
                    continue
                if name not in schema:
                    raise _no_such_column(name, schema)
                need(name)
            for _, ve in computed:
                for c in ve.columns():
                    if c not in schema:
                        raise _no_such_column(c, schema)
                    need(c)
            if self._distinct:
                for name in out_pre:
                    if name in schema \
                            and schema[name].dtype.kind not in _GROUPABLE_KINDS:
                        raise TypeError(
                            f"distinct() cannot compare column {name!r} "
                            f"of type {schema[name].dtype}")
        if self._where is not None:
            for c in self._where.columns():
                if c not in schema:
                    raise _no_such_column(c, schema)
        avail = self._agg_out_names() if self._aggregated() else out_pre
        for c, _ in self._order:
            if c not in avail:
                raise KeyError(f"unknown order_by column {c!r}; output "
                               f"columns are {avail}")
        plan = ScanPlan(man.files, self._db._reader_of, schema,
                        columns=scan_cols, filter_expr=self._where,
                        cfg=self._cfg, deltas=man.deltas,
                        partitioning=self._db._partitioning_of(man))
        return _Compiled(man, schema, plan, scan_cols, out_pre, computed)

    # ------------------------------------------------------------ execution
    def _batches(self, cp: _Compiled, counters: Optional[ScanCounters]
                 ) -> Generator[Table, None, None]:
        """Scan → computed columns → projection → distinct (streaming)."""
        gen = cp.plan.execute(counters=counters)
        seen: Optional[set] = set() if self._distinct else None
        try:
            for t in gen:
                with span("query.compute"):
                    for name, ve in cp.computed:
                        t = t.set_column(name, ve.evaluate_column(t))
                    t = t.select(cp.out_pre)
                    if seen is not None:
                        t = _distinct_batch(t, seen)
                yield t
        finally:
            gen.close()

    def _empty_out(self, cp: _Compiled) -> Table:
        t = Table.empty(cp.schema.select(cp.scan_cols))
        for name, ve in cp.computed:
            t = t.set_column(name, ve.evaluate_column(t))
        return t.select(cp.out_pre)

    def _slice_limit(self, t: Table) -> Table:
        if self._offset == 0 and self._limit is None:
            return t
        start = min(self._offset, t.num_rows)  # clamp: offset may overshoot
        stop = (t.num_rows if self._limit is None
                else min(start + self._limit, t.num_rows))
        return t.slice(start, stop)

    def _run_plain(self, cp: _Compiled, counters: Optional[ScanCounters],
                   opstats: Optional[dict] = None) -> Table:
        stream = self._batches(cp, counters)
        if self._order:
            cap = (None if self._limit is None
                   else self._limit + self._offset)
            if cap is None:
                # full sort: collect once, concat once (no per-batch copy)
                parts = list(stream)
                with span("query.compute"):
                    acc = (concat_tables(parts) if parts
                           else self._empty_out(cp))
            else:
                # top-k: fold each batch into a pruned accumulator
                acc = None
                for t in stream:
                    with span("query.compute"):
                        acc = t if acc is None else concat_tables([acc, t])
                        if acc.num_rows > cap:
                            idx = _sort_indices(acc, self._order)[:cap]
                            acc = acc.take(np.sort(idx))  # arrival order
                if acc is None:
                    acc = self._empty_out(cp)
            with span("query.compute"):
                acc = acc.take(_sort_indices(acc, self._order))
                if opstats is not None:
                    opstats["rows_sorted"] = acc.num_rows
                out = self._slice_limit(acc)
        else:
            cap = (None if self._limit is None
                   else self._limit + self._offset)
            parts: List[Table] = []
            got = 0
            if cap == 0:
                stream.close()
            else:
                for t in stream:
                    parts.append(t)
                    got += t.num_rows
                    if cap is not None and got >= cap:
                        stream.close()  # early stop: cancels queued morsels
                        break
            with span("query.compute"):
                table = (concat_tables(parts) if parts
                         else self._empty_out(cp))
                out = self._slice_limit(table)
        if opstats is not None:
            opstats["rows_out"] = out.num_rows
        return out

    def _run_grouped(self, cp: _Compiled,
                     counters: Optional[ScanCounters],
                     opstats: Optional[dict] = None) -> Table:
        key_cols, spec = self._group_keys, self._agg_spec
        acc = _GroupedAcc(spec)

        def partials(t: Table) -> _GroupPartial:
            with span("query.compute"):
                return _partial_groups(t, key_cols, spec, cp.computed)

        # partial aggregation runs inside the morsel workers (map_fn);
        # the merge below is the single-threaded consumer half
        for part in cp.plan.execute(counters=counters, map_fn=partials):
            with span("query.compute"):
                acc.merge(part)
        with span("query.compute"):
            table = acc.to_table(key_cols,
                                 _agg_schema(cp.schema, cp.computed))
            if opstats is not None:
                opstats["groups"] = table.num_rows
            if self._order:
                table = table.take(_sort_indices(table, self._order))
            out = self._slice_limit(table)
        if opstats is not None:
            opstats["rows_out"] = out.num_rows
        return out

    def _run(self, cp: _Compiled, counters: Optional[ScanCounters] = None,
             opstats: Optional[dict] = None) -> Table:
        if self._aggregated():
            return self._run_grouped(cp, counters, opstats)
        return self._run_plain(cp, counters, opstats)

    # ------------------------------------------------------------ terminals
    def to_table(self) -> Table:
        """Execute and materialize the full result as one Table."""
        with query_span():
            return self._run(self._compile())

    def to_pylist(self) -> List[dict]:
        """Execute and materialize as a list of row dicts."""
        return self.to_table().to_pylist()

    def iter_batches(self, batch_size: Optional[int] = None
                     ) -> Generator[Table, None, None]:
        """Stream the result as Tables of ``batch_size`` rows (lazy).

        Ordered or grouped queries materialize first (a sort/aggregation
        is a pipeline breaker); everything else streams, honoring
        ``limit``/``offset`` with early scan termination.  Each batch is
        produced inside its own ``repro.query`` span.
        """
        return rooted(self._iter_batches(batch_size))

    def _iter_batches(self, batch_size: Optional[int]
                      ) -> Generator[Table, None, None]:
        bs = batch_size or int(getattr(self._cfg, "batch_size", 131_072))
        if self._aggregated() or self._order:
            yield from rechunk(iter([self.to_table()]), bs)
            return
        cp = self._compile()
        stream = self._batches(cp, None)

        def limited() -> Generator[Table, None, None]:
            togo_skip, togo = self._offset, self._limit
            if togo is not None and togo <= 0:
                stream.close()
                return
            for t in stream:
                if togo_skip:
                    if t.num_rows <= togo_skip:
                        togo_skip -= t.num_rows
                        continue
                    t = t.slice(togo_skip, t.num_rows)
                    togo_skip = 0
                if togo is not None:
                    if t.num_rows >= togo:
                        yield t.slice(0, togo)
                        stream.close()
                        return
                    togo -= t.num_rows
                yield t

        yield from rechunk(limited(), bs)

    def count(self) -> int:
        """Number of result rows.

        For a plain filtered query this is answered through the aggregate
        fast path (footer statistics — typically zero pages decoded) with
        ``limit``/``offset`` applied arithmetically; computed columns and
        projections don't change the row count, so they stay on the fast
        path too.  Grouped and ``distinct`` queries run the pipeline.
        """
        with query_span():
            if self._aggregated() or self._distinct:
                return self.to_table().num_rows
            with span("query.plan"):
                man, schema = self._snapshot()
                plan = AggregatePlan(
                    man.files, self._db._reader_of, schema, {"*": "count"},
                    filter_expr=self._where, cfg=self._cfg, deltas=man.deltas,
                    partitioning=self._db._partitioning_of(man))
            total = plan.execute()["*"]["count"]
            total = max(0, total - self._offset)
            return total if self._limit is None else min(total, self._limit)

    def agg(self, spec, explain: bool = False):
        """Ungrouped aggregate terminal — ``{column: {op: value}}``.

        A simple query (where/select only) routes through the footer-
        statistics fast path and returns results and (with
        ``explain=True``) the same :class:`ScanReport` as the legacy
        ``db.aggregate`` — including ``groups_answered_by_stats`` /
        ``bytes_skipped_agg`` counters.  Queries with computed columns,
        ``distinct``, ``order_by`` or ``limit`` aggregate their
        materialized output instead, in one execution (explain then
        returns a :class:`QueryReport`).

        On both paths the spec may reference any physical column —
        matching the legacy surface, where projections never restrict
        ``aggregate`` — plus, on the materialized path, any computed
        output column.  ``distinct()`` is the exception: its spec is
        restricted to the distinct output columns (aggregating a column
        that did not participate in deduplication would be ill-defined).
        """
        if self._aggregated():
            raise ValueError("agg() cannot follow group_by().agg(); the "
                             "query is already aggregated")
        with query_span():
            return self._agg(spec, explain)

    def _agg(self, spec, explain: bool):
        simple = (not self._computed and not self._distinct
                  and not self._order and self._limit is None
                  and self._offset == 0)
        with span("query.plan"):
            man, schema = self._snapshot()
            if simple:
                _normalize_spec(spec, schema)  # plan-build-time validation
                plan = AggregatePlan(
                    man.files, self._db._reader_of, schema, spec,
                    filter_expr=self._where, cfg=self._cfg,
                    deltas=man.deltas,
                    partitioning=self._db._partitioning_of(man))
        if simple:
            values = plan.execute()
            return (values, plan.report()) if explain else values
        q = self
        if not self._distinct and self._select is not None:
            # keep fast-path semantics: a projection does not hide
            # physical columns from the aggregate
            out = set(self._output_names(schema))
            missing = [c for c in spec if c != "*" and c not in out
                       and c in schema]
            if missing:
                q = self._replace(select=self._select + missing)
        if explain:
            table, report = q._run_reported()
        else:
            table = q.to_table()
        with span("query.compute"):
            norm = _normalize_spec(spec, table.schema)
            acc = _GroupedAcc(norm)
            if table.num_rows:
                acc.merge(_partial_groups(table, [], norm))
            values = acc.scalars()
        return (values, report) if explain else values

    # -------------------------------------------------------------- explain
    def _op_descriptions(self) -> List[Tuple[str, str]]:
        ops: List[Tuple[str, str]] = []
        if self._limit is not None or self._offset:
            detail = []
            if self._limit is not None:
                detail.append(f"limit={self._limit}")
            if self._offset:
                detail.append(f"offset={self._offset}")
            ops.append(("Limit", " ".join(detail)))
        if self._order:
            detail = ", ".join(f"{c} {'DESC' if d else 'ASC'}"
                               for c, d in self._order)
            ops.append(("OrderBy", detail))
        if self._distinct and not self._aggregated():
            ops.append(("Distinct", ""))
        if self._aggregated():
            aggs = ", ".join(agg_column_name(c, op)
                             for c, o in self._agg_spec.items() for op in o)
            keys = ", ".join(self._group_keys) or "<global>"
            ops.append(("Aggregate", f"group_by=[{keys}] → {aggs}"))
        elif self._select is not None or self._computed:
            parts = []
            for n in self._output_names(self._schema()):
                ve = dict(self._computed).get(n)
                parts.append(f"{n}={ve!r}" if ve is not None else n)
            ops.append(("Project", ", ".join(parts)))
        if self._where is not None:
            fused = f"  ({self._nwhere} predicates fused)" \
                if self._nwhere > 1 else ""
            ops.append(("Filter", f"{self._where!r}{fused}"))
        return ops

    def explain(self, execute: bool = False) -> QueryReport:
        """Render the operator tree plus the scan's pruning report.

        ``execute=True`` actually runs the query, so the counters show
        observed decode work — including how much an early-terminating
        ``limit`` scan *didn't* decode — and per-operator row counts are
        appended to the tree.
        """
        with query_span():
            if execute:
                return self._run_reported()[1]
            cp = self._compile()
            scan = cp.plan.explain(execute=False)
        return QueryReport(ops=self._op_descriptions(),
                           scan=scan, executed=False)

    def _run_reported(self) -> Tuple[Table, QueryReport]:
        """One execution that yields both the result and the full report."""
        cp = self._compile()
        ops = self._op_descriptions()
        cp.plan.fragments()  # force planning so the counters exist
        counters = dataclasses.replace(cp.plan._plan_counters)
        counters.bytes_total, counters.bytes_selected = \
            cp.plan._bytes_accounting()
        opstats: dict = {}
        table = self._run(cp, counters, opstats)
        decorated: List[Tuple[str, str]] = []
        for op, detail in ops:
            extra = ""
            if op == "Limit" and "rows_out" in opstats:
                extra = f" → {opstats['rows_out']} rows"
            elif op == "Aggregate" and "groups" in opstats:
                extra = f" → {opstats['groups']} groups"
            elif op == "OrderBy" and "rows_sorted" in opstats:
                extra = f" → {opstats['rows_sorted']} rows sorted"
            decorated.append((op, detail + extra))
        scan_rep = ScanReport(
            counters=counters, fragments=cp.plan.fragments(),
            columns=list(cp.plan._out_schema.names),
            filter=repr(self._where) if self._where is not None else None,
            executed=True)
        return table, QueryReport(ops=decorated, scan=scan_rep,
                                  executed=True)

    def __repr__(self) -> str:
        bits = []
        if self._where is not None:
            bits.append(f"where={self._where!r}")
        if self._select is not None:
            bits.append(f"select={self._select}")
        if self._computed:
            bits.append(f"computed={[n for n, _ in self._computed]}")
        if self._aggregated():
            bits.append(f"group_by={self._group_keys} agg={self._agg_spec}")
        if self._order:
            bits.append(f"order_by={self._order}")
        if self._limit is not None:
            bits.append(f"limit={self._limit}")
        if self._offset:
            bits.append(f"offset={self._offset}")
        if self._distinct:
            bits.append("distinct")
        return f"Query({', '.join(bits)})"
