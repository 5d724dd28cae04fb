"""Predicate expression language with statistics-based pruning.

The paper exposes PyArrow compute expressions (``pc.field('energy') > -1.0``).
This module provides the same surface: ``field(name)`` returns a reference with
overloaded comparison operators; expressions combine with ``&``, ``|``, ``~``
and evaluate to boolean masks against an in-memory Table.

The crucial part for the paper's "statistics replace indexes" claim is
``Expr.prune(stats)``: given per-chunk ColumnStats it returns False only when
the chunk *provably* cannot contain a matching row — that is predicate
pushdown.  Pruning is conservative: True means "must read".

Every expression renders as a SQL-ish, fully parenthesized string via
``repr`` — ``((age >= 30) AND (city == 'SF'))`` — which is what
``ScanReport`` and ``Query.explain()`` print, so plans stay readable.

Beyond predicates, :class:`Arith` is the *value* expression used by
``Query.select(**computed)``: ``field('x') + field('y')``, ``field('x') * 2``
etc. build an arithmetic tree that evaluates to a numeric Column per batch
(null if any operand is null).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .statistics import ColumnStats
from .table import Column, Table
from .dtypes import KIND_NUMERIC, KIND_STRING

StatsMap = Dict[str, ColumnStats]


class Expr:
    def __and__(self, other: "Expr") -> "Expr":
        return And(self, other)

    def __or__(self, other: "Expr") -> "Expr":
        return Or(self, other)

    def __invert__(self) -> "Expr":
        return Not(self)

    # subclasses implement:
    def evaluate(self, table: Table) -> np.ndarray:  # bool mask (n,)
        raise NotImplementedError

    def prune(self, stats: StatsMap) -> bool:  # may-match?
        raise NotImplementedError

    def all_match(self, stats: StatsMap) -> bool:
        """True only when statistics prove EVERY row in the chunk matches.

        The dual of :meth:`prune` (which proves *no* row matches):
        together they classify a chunk as fully-covered / fully-pruned /
        partial, which is what lets ``ParquetDB.aggregate`` answer a
        predicate-filtered aggregate from footer statistics without
        decoding a page.  Conservative: False means "must decode", so a
        subclass that cannot decide simply inherits this default.  Null
        semantics follow :meth:`evaluate` (null rows match no comparison),
        hence comparisons require ``null_count == 0``; NaN rows are
        invisible to min/max, hence ordering ops require ``nan_count == 0``.
        """
        return False

    def columns(self) -> List[str]:
        raise NotImplementedError

    def negate(self) -> Optional["Expr"]:
        """Logical negation under this engine's null semantics, or None.

        ``evaluate`` treats null as non-matching for comparisons, so the
        negation of ``x == v`` is ``(x != v) | x.is_null()`` — rows where x
        is null DO match ``~(x == v)``.  Used by ``Not.prune`` to push
        negations down to stats-prunable leaves; None means "cannot be
        expressed prunably", in which case pruning stays conservative.
        """
        return None

    def as_range(self) -> Optional[tuple]:
        """``(column, lo, lo_open, hi, hi_open)`` when this expression is
        exactly a contiguous range test on one column, else None.

        ``lo``/``hi`` may be None (unbounded end); the ``*_open`` flags mark
        strict inequalities.  The two-phase reader converts the bounds to an
        inclusive interval in the column's dtype and routes the row-group
        mask through the decode backend's fused ``range_mask`` (the
        Pallas ``filter_range`` kernel on the jax backend).  Must be
        *exact*: the converted mask on a fully-valid numeric column equals
        ``evaluate``'s mask.
        """
        return None


def _column_values(table: Table, name: str):
    """Numeric -> ndarray; string -> object ndarray; else error."""
    if name not in table:
        raise KeyError(
            f"filter references unknown column {name!r}; have {table.column_names}")
    col = table.column(name)
    k = col.dtype.kind
    if k == KIND_NUMERIC:
        return col.values, col.validity
    if k == KIND_STRING:
        return np.array(col.to_pylist(), dtype=object), col.validity
    if col.dtype.kind == "null":  # all-null: nothing ever matches
        return np.zeros(len(col)), np.zeros(len(col), bool)
    raise TypeError(f"cannot filter on column {name!r} of type {col.dtype}")


_OPS = {
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
}


class Comparison(Expr):
    def __init__(self, name: str, op: str, value: Any):
        self.name, self.op, self.value = name, op, value

    def evaluate(self, table: Table) -> np.ndarray:
        vals, validity = _column_values(table, self.name)
        if isinstance(self.value, FieldRef):
            other, ov = _column_values(table, self.value.name)
            mask = _OPS[self.op](vals, other)
            if ov is not None:
                mask &= ov
        else:
            mask = _OPS[self.op](vals, self.value)
        mask = np.asarray(mask, bool)
        if validity is not None:
            mask &= validity  # null never matches (SQL-like)
        return mask

    def prune(self, stats: StatsMap) -> bool:
        if isinstance(self.value, FieldRef):
            return True  # column-vs-column: no pushdown
        st = stats.get(self.name)
        if st is None or st.min is None:
            return not (st is not None and st.all_null())
        v, lo, hi = self.value, st.min, st.max
        try:
            if self.op == "==":
                return st.may_contain(v)
            if self.op == "!=":
                # NaN rows match "!=" but are invisible to min/max
                if st.nan_count:
                    return True
                return not (lo == hi == v)
            if self.op == "<":
                return lo < v
            if self.op == "<=":
                return lo <= v
            if self.op == ">":
                return hi > v
            if self.op == ">=":
                return hi >= v
        except TypeError:
            return True
        return True

    def all_match(self, stats: StatsMap) -> bool:
        if isinstance(self.value, FieldRef):
            return False  # column-vs-column: stats cannot decide
        st = stats.get(self.name)
        if st is None:
            return False
        if st.num_values == 0:
            return True  # vacuous: an empty chunk has no non-matching row
        if st.null_count or st.min is None:
            return False  # null rows never match a comparison
        v, lo, hi = self.value, st.min, st.max
        try:
            if self.op == "!=":
                # NaN rows DO match "!=" — only equality to v must be
                # excluded, which may_contain can refute via min/max or
                # the bloom fingerprint
                return not st.may_contain(v)
            if st.nan_count:
                return False  # NaN matches no ordering op / equality
            if self.op == "==":
                return bool(lo == hi == v)
            if self.op == "<":
                return bool(hi < v)
            if self.op == "<=":
                return bool(hi <= v)
            if self.op == ">":
                return bool(lo > v)
            if self.op == ">=":
                return bool(lo >= v)
        except TypeError:
            return False
        return False

    def columns(self) -> List[str]:
        cols = [self.name]
        if isinstance(self.value, FieldRef):
            cols.append(self.value.name)
        return cols

    def as_range(self) -> Optional[tuple]:
        v = self.value
        if isinstance(v, FieldRef) or isinstance(v, (bool, np.bool_)) \
                or not isinstance(v, (int, float, np.integer, np.floating)):
            return None
        if self.op == "==":
            return (self.name, v, False, v, False)
        if self.op == ">=":
            return (self.name, v, False, None, False)
        if self.op == ">":
            return (self.name, v, True, None, False)
        if self.op == "<=":
            return (self.name, None, False, v, False)
        if self.op == "<":
            return (self.name, None, False, v, True)
        return None  # "!=" is not a contiguous range

    _NEG_OP = {"==": "!=", "!=": "==", "<": ">=", "<=": ">",
               ">": "<=", ">=": "<"}

    def negate(self) -> Optional[Expr]:
        if isinstance(self.value, FieldRef):
            return None  # col-vs-col has no pushdown either way
        # null rows match the negation (evaluate masks them out of `self`)
        neg = Or(Comparison(self.name, self._NEG_OP[self.op], self.value),
                 IsNull(self.name))
        if self.op in ("<", "<=", ">", ">="):
            # NaN rows also match ~(x < v) etc. but the negated comparison's
            # min/max prune cannot see them — add an explicit NaN term
            neg = Or(neg, IsNaN(self.name))
        return neg

    def __repr__(self):
        return f"({self.name} {self.op} {self.value!r})"


class IsIn(Expr):
    def __init__(self, name: str, values: Sequence[Any]):
        self.name, self.values = name, list(values)

    def evaluate(self, table: Table) -> np.ndarray:
        vals, validity = _column_values(table, self.name)
        mask = np.isin(vals, np.array(self.values, dtype=vals.dtype if vals.dtype != object else object))
        if validity is not None:
            mask &= validity
        return mask

    def prune(self, stats: StatsMap) -> bool:
        st = stats.get(self.name)
        if st is None:
            return True
        return any(st.may_contain(v) for v in self.values)

    def all_match(self, stats: StatsMap) -> bool:
        st = stats.get(self.name)
        if st is None:
            return False
        if st.num_values == 0:
            return True
        if st.null_count or st.nan_count or st.min is None:
            return False
        # decidable only for a constant chunk whose single value is listed
        try:
            return bool(st.min == st.max and
                        any(st.min == v for v in self.values))
        except TypeError:
            return False

    def columns(self):
        return [self.name]

    def __repr__(self):
        vals = ", ".join(repr(v) for v in self.values)
        return f"({self.name} IN ({vals}))"


class IsNull(Expr):
    def __init__(self, name: str, *, negate: bool = False):
        # stored as _negated so the attribute doesn't shadow Expr.negate()
        self.name, self._negated = name, negate

    def evaluate(self, table: Table) -> np.ndarray:
        col = table.column(self.name)
        valid = (np.ones(len(col), bool) if col.validity is None
                 else col.validity.copy())
        return valid if self._negated else ~valid

    def prune(self, stats: StatsMap) -> bool:
        st = stats.get(self.name)
        if st is None:
            return True
        if self._negated:  # is_valid
            return st.null_count < st.num_values
        return st.null_count > 0

    def all_match(self, stats: StatsMap) -> bool:
        st = stats.get(self.name)
        if st is None:
            return False
        if self._negated:  # is_valid: every row non-null
            return st.null_count == 0
        return st.null_count == st.num_values

    def columns(self):
        return [self.name]

    def negate(self) -> Optional[Expr]:
        return IsNull(self.name, negate=not self._negated)

    def __repr__(self):
        return (f"({self.name} IS NOT NULL)" if self._negated
                else f"({self.name} IS NULL)")


class IsNaN(Expr):
    """Matches float NaN rows.

    Produced by ``Comparison.negate`` for ordering ops: NaN rows match the
    negation of any ordering comparison yet are excluded from min/max stats,
    so the negated expression carries this term to keep pruning sound.
    Prunes against ``ColumnStats.nan_count``.
    """

    def __init__(self, name: str):
        self.name = name

    def evaluate(self, table: Table) -> np.ndarray:
        vals, validity = _column_values(table, self.name)
        if getattr(vals.dtype, "kind", None) != "f":
            return np.zeros(len(vals), bool)
        mask = np.isnan(vals)
        if validity is not None:
            mask &= validity
        return mask

    def prune(self, stats: StatsMap) -> bool:
        st = stats.get(self.name)
        return True if st is None else st.nan_count > 0

    def all_match(self, stats: StatsMap) -> bool:
        st = stats.get(self.name)
        if st is None:
            return False
        return st.null_count == 0 and st.nan_count == st.num_values

    def columns(self):
        return [self.name]

    def __repr__(self):
        return f"isnan({self.name})"


def _tighter_bound(va, oa, vb, ob, *, hi: bool):
    """Intersect two one-sided bounds ((value, open); value None = unbounded)."""
    if va is None:
        return vb, ob
    if vb is None:
        return va, oa
    if va == vb:
        return va, oa or ob
    take_a = va < vb if hi else va > vb
    return (va, oa) if take_a else (vb, ob)


class And(Expr):
    def __init__(self, a: Expr, b: Expr):
        self.a, self.b = a, b

    def evaluate(self, table):
        return self.a.evaluate(table) & self.b.evaluate(table)

    def prune(self, stats):
        return self.a.prune(stats) and self.b.prune(stats)

    def all_match(self, stats: StatsMap) -> bool:
        return self.a.all_match(stats) and self.b.all_match(stats)

    def columns(self):
        return self.a.columns() + self.b.columns()

    def negate(self) -> Optional[Expr]:
        na, nb = self.a.negate(), self.b.negate()
        return Or(na, nb) if na is not None and nb is not None else None

    def as_range(self) -> Optional[tuple]:
        # (lo <= x) & (x < hi) on the same column is still one range
        ra, rb = self.a.as_range(), self.b.as_range()
        if ra is None or rb is None or ra[0] != rb[0]:
            return None
        lo, lo_open = _tighter_bound(ra[1], ra[2], rb[1], rb[2], hi=False)
        hi, hi_open = _tighter_bound(ra[3], ra[4], rb[3], rb[4], hi=True)
        return (ra[0], lo, lo_open, hi, hi_open)

    def __repr__(self):
        return f"({self.a!r} AND {self.b!r})"


class Or(Expr):
    def __init__(self, a: Expr, b: Expr):
        self.a, self.b = a, b

    def evaluate(self, table):
        return self.a.evaluate(table) | self.b.evaluate(table)

    def prune(self, stats):
        return self.a.prune(stats) or self.b.prune(stats)

    def all_match(self, stats: StatsMap) -> bool:
        # sufficient, not necessary (a/b may cover disjoint halves) — but
        # False only ever costs a decode, never correctness
        return self.a.all_match(stats) or self.b.all_match(stats)

    def columns(self):
        return self.a.columns() + self.b.columns()

    def negate(self) -> Optional[Expr]:
        na, nb = self.a.negate(), self.b.negate()
        return And(na, nb) if na is not None and nb is not None else None

    def __repr__(self):
        return f"({self.a!r} OR {self.b!r})"


class Not(Expr):
    def __init__(self, a: Expr):
        self.a = a

    def evaluate(self, table):
        return ~self.a.evaluate(table)

    def prune(self, stats):
        # push the negation down to prunable leaves (null-safe, see
        # Expr.negate); unsupported shapes stay conservative
        neg = self.a.negate()
        return True if neg is None else neg.prune(stats)

    def all_match(self, stats: StatsMap) -> bool:
        # ~a matches everything iff a matches nothing, which is exactly
        # what a.prune refuting the chunk proves (evaluate's null/NaN
        # semantics make ~ a plain mask complement, so no extra terms)
        if not self.a.prune(stats):
            return True
        neg = self.a.negate()
        return neg.all_match(stats) if neg is not None else False

    def columns(self):
        return self.a.columns()

    def negate(self) -> Optional[Expr]:
        return self.a

    def __repr__(self):
        return f"(NOT {self.a!r})"


class _ArithOps:
    """Mixin giving FieldRef/Arith the ``+ - * /`` operators (value exprs)."""

    def __add__(self, other):
        return Arith("+", self, other)

    def __radd__(self, other):
        return Arith("+", other, self)

    def __sub__(self, other):
        return Arith("-", self, other)

    def __rsub__(self, other):
        return Arith("-", other, self)

    def __mul__(self, other):
        return Arith("*", self, other)

    def __rmul__(self, other):
        return Arith("*", other, self)

    def __truediv__(self, other):
        return Arith("/", self, other)

    def __rtruediv__(self, other):
        return Arith("/", other, self)

    def __neg__(self):
        return Arith("-", 0, self)


_ARITH_FNS = {"+": np.add, "-": np.subtract, "*": np.multiply,
              "/": np.true_divide}


def _operand_values(x, table: Table):
    """(values ndarray-or-scalar, validity-or-None) of one Arith operand."""
    if isinstance(x, FieldRef):
        col = table.column(x.name)
        if col.dtype.kind != KIND_NUMERIC:
            raise TypeError(f"computed expression needs a numeric column, "
                            f"but {x.name!r} is {col.dtype}")
        vals = col.values
        if vals.dtype.kind == "b":
            # bool is numeric (b1), but numpy's +|*|- on bool arrays are
            # logical ops / errors — arithmetic means ints here
            vals = vals.astype(np.int64)
        return vals, col.validity
    if isinstance(x, Arith):
        col = x.evaluate_column(table)
        return col.values, col.validity
    if isinstance(x, (int, float, np.integer, np.floating)) \
            and not isinstance(x, (bool, np.bool_)):
        return x, None
    raise TypeError(f"unsupported operand in computed expression: {x!r}")


def _operand_repr(x) -> str:
    if isinstance(x, FieldRef):
        return x.name
    return repr(x)


class Arith(_ArithOps):
    """Arithmetic *value* expression over numeric columns and scalars.

    Built by operator overloading — ``field('x') * 2 + field('y')`` — and
    consumed by ``Query.select(**computed)``: :meth:`evaluate_column`
    produces one numeric Column per batch.  Null semantics: a row is null
    in the result when any column operand is null in that row (validity
    masks AND together).  Division always yields float64 (``0/0`` and
    ``x/0`` follow IEEE NaN/inf, warnings suppressed).
    """

    def __init__(self, op: str, a, b):
        assert op in _ARITH_FNS, op
        self.op, self.a, self.b = op, a, b

    def evaluate_column(self, table: Table) -> Column:
        av, avd = _operand_values(self.a, table)
        bv, bvd = _operand_values(self.b, table)
        with np.errstate(all="ignore"):
            out = _ARITH_FNS[self.op](av, bv)
        out = np.asarray(out)
        if out.ndim == 0:  # scalar-only tree: broadcast to the batch
            out = np.full(table.num_rows, out[()])
        if avd is None:
            validity = None if bvd is None else bvd.copy()
        else:
            validity = avd.copy() if bvd is None else (avd & bvd)
        return Column.numeric(np.ascontiguousarray(out), validity=validity)

    def columns(self) -> List[str]:
        cols: List[str] = []
        for x in (self.a, self.b):
            if isinstance(x, FieldRef):
                cols.append(x.name)
            elif isinstance(x, Arith):
                cols.extend(x.columns())
        return cols

    def __repr__(self):
        return f"({_operand_repr(self.a)} {self.op} {_operand_repr(self.b)})"


class FieldRef(_ArithOps):
    """``field('energy') > -1.0`` builds a Comparison."""

    def __init__(self, name: str):
        self.name = name

    def evaluate_column(self, table: Table) -> Column:
        """A bare FieldRef used as a computed column is a copy/rename."""
        return table.column(self.name)

    def columns(self) -> List[str]:
        return [self.name]

    def __eq__(self, v):  # type: ignore[override]
        return Comparison(self.name, "==", v)

    def __ne__(self, v):  # type: ignore[override]
        return Comparison(self.name, "!=", v)

    def __lt__(self, v):
        return Comparison(self.name, "<", v)

    def __le__(self, v):
        return Comparison(self.name, "<=", v)

    def __gt__(self, v):
        return Comparison(self.name, ">", v)

    def __ge__(self, v):
        return Comparison(self.name, ">=", v)

    def isin(self, values: Sequence[Any]) -> Expr:
        return IsIn(self.name, values)

    def is_null(self) -> Expr:
        return IsNull(self.name)

    def is_valid(self) -> Expr:
        return IsNull(self.name, negate=True)

    def __hash__(self):
        return hash(("FieldRef", self.name))

    def __repr__(self):
        return f"field({self.name!r})"


def field(name: str) -> FieldRef:
    return FieldRef(name)


def combine_filters(filters: Optional[Sequence[Expr]]) -> Optional[Expr]:
    """Paper semantics: a list of filters is AND-combined."""
    if not filters:
        return None
    expr = filters[0]
    for f in filters[1:]:
        expr = expr & f
    return expr
