"""Plain reference: the query language of ``spec.py`` in numpy, over the
generated arrays, and the comparison that decides ``correct``.

Nothing here imports the store.  The reference is as plain as it can be:
a boolean mask, fancy indexing, ``np.unique`` and ``np.bincount``.  An
answer, from the store or from here, is normalised to one of two shapes:

- ``{"table": {column: ndarray}}`` for rows (and grouped aggregates);
- ``{"agg": {column: {op: number}}}`` for an ungrouped aggregate.

The control is :func:`evaluate` over :func:`lower_precision` arrays: every
column one step below the precision the configuration states (float32 ->
bfloat16, int64 -> int32, int32 -> int16), with the arithmetic in that
width.  A sound comparison must call its answers wrong.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

_CMP = {"==": np.equal, "!=": np.not_equal, "<": np.less,
        "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal}


def bfloat16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest, ties to even), kept
    in float32 storage."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def lower_precision(arrays: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    out = {}
    for name, a in arrays.items():
        if a.dtype == np.float32:
            out[name] = bfloat16(a)
        elif a.dtype == np.int64:
            out[name] = a.astype(np.int32)
        elif a.dtype == np.int32:
            out[name] = a.astype(np.int16)
        else:
            out[name] = a
    return out


def mask_of(where: Optional[list], arrays: Dict[str, np.ndarray],
            n: int) -> np.ndarray:
    if where is None:
        return np.ones(n, bool)
    tag = where[0]
    if tag == "and":
        return mask_of(where[1], arrays, n) & mask_of(where[2], arrays, n)
    if tag == "or":
        return mask_of(where[1], arrays, n) | mask_of(where[2], arrays, n)
    if tag == "not":
        return ~mask_of(where[1], arrays, n)
    if tag == "cmp":
        _, col, op, v = where
        a = arrays[col]
        return _CMP[op](a, np.asarray(v).astype(a.dtype)
                        if a.dtype.kind in "iuf" else v)
    raise ValueError(f"reference has no expression {tag!r}")


def _arith(expr: Any, arrays: Dict[str, np.ndarray]) -> Any:
    """A computed column: numbers, columns and ``add``/``sub``/``mul``/
    ``div`` (true division, float64), with numpy's own type rules."""
    if isinstance(expr, (int, float)):
        return expr
    tag = expr[0]
    if tag == "field":
        return arrays[expr[1]]
    a, b = _arith(expr[1], arrays), _arith(expr[2], arrays)
    fn = {"add": np.add, "sub": np.subtract, "mul": np.multiply,
          "div": np.true_divide}.get(tag)
    if fn is None:
        raise ValueError(f"reference has no arithmetic {tag!r}")
    with np.errstate(all="ignore"):
        return fn(a, b)


def _reduce(vals: np.ndarray, op: str) -> Any:
    if op == "count":
        return int(len(vals))
    if vals.dtype.kind in "iu":
        # sums in the column's own width: int64 for the stated precision,
        # int32 (wrapping) for the control
        if op == "sum":
            return int(np.sum(vals, dtype=vals.dtype))
        if op in ("min", "max"):
            return int(getattr(vals, op)())
        return float(np.sum(vals, dtype=np.float64) / len(vals))
    v64 = vals.astype(np.float64)
    if op == "sum":
        return float(v64.sum())
    if op == "mean":
        return float(v64.sum() / len(v64))
    return float(getattr(v64, op)())


def rows_of(where: Optional[list], arrays: Dict[str, np.ndarray]):
    """The rows a filter keeps: a binary search on the sorted ``id`` for
    ``id == k``, a boolean mask otherwise."""
    ids = arrays["id"]
    if where is not None and where[:3] == ["cmp", "id", "=="]:
        k = where[3]
        return slice(int(np.searchsorted(ids, k, "left")),
                     int(np.searchsorted(ids, k, "right")))
    return mask_of(where, arrays, len(ids))


def evaluate(q: dict, arrays: Dict[str, np.ndarray]) -> dict:
    """The answer to one instantiated query over the whole table."""
    rows = rows_of(q.get("where"), arrays)
    cols = {k: v[rows] for k, v in arrays.items()}
    for name, expr in (q.get("computed") or {}).items():
        cols[name] = _arith(expr, cols)
    if q.get("group_by"):
        return {"table": _grouped(q["group_by"], q["agg"], cols)}
    if q["terminal"] == "agg":
        return {"agg": {c: {op: _reduce(cols[c] if c != "*" else cols["id"],
                                        op)
                            for op in ([ops] if isinstance(ops, str)
                                       else ops)}
                        for c, ops in q["agg"].items()}}
    sel = q.get("select") or ["*"]
    names = list(arrays) if sel == ["*"] else sel
    return {"table": {c: _plain(cols[c]) for c in names}}


def _plain(a: np.ndarray) -> np.ndarray:
    """Byte strings as ``str`` objects, as the store returns them."""
    return np.char.decode(a, "ascii").astype(object) if a.dtype.kind == "S" \
        else a


def _grouped(keys: List[str], spec: dict, cols: Dict[str, np.ndarray]
             ) -> Dict[str, np.ndarray]:
    """Grouped aggregates, groups in key order: each key column factorised
    by ``np.unique``, the codes combined into one group number.  Output
    columns are named as the store names them: ``count`` for ``"*"``,
    ``<col>_<op>`` otherwise."""
    uniqs, codes = zip(*(np.unique(cols[k], return_inverse=True)
                         for k in keys))
    group = np.ravel_multi_index([c.ravel() for c in codes],
                                 [len(u) for u in uniqs])
    present, inv = np.unique(group, return_inverse=True)
    inv = inv.ravel()
    n = len(present)
    counts = np.bincount(inv, minlength=n)
    idx = np.unravel_index(present, [len(u) for u in uniqs])
    out = {k: _plain(u[i]) for k, u, i in zip(keys, uniqs, idx)}
    for c, ops in spec.items():
        for op in ([ops] if isinstance(ops, str) else ops):
            if c == "*" or op == "count":
                out["count" if c == "*" else f"{c}_count"] = counts
            else:
                out[f"{c}_{op}"] = _group_reduce(cols[c], inv, n, counts, op)
    return out


def _group_reduce(vals: np.ndarray, inv: np.ndarray, n: int,
                  counts: np.ndarray, op: str) -> np.ndarray:
    """One aggregate per group; integer sums exact in the column's own
    width, means and float sums in float64."""
    if op == "sum" and vals.dtype.kind in "iu":
        out = np.zeros(n, vals.dtype)
        np.add.at(out, inv, vals)
        return out
    if op in ("sum", "mean"):
        if vals.dtype.kind in "iu":
            tot = np.zeros(n, np.int64)
            np.add.at(tot, inv, vals.astype(np.int64))
            tot = tot.astype(np.float64)
        else:
            tot = np.bincount(inv, weights=vals.astype(np.float64),
                              minlength=n)
        return tot if op == "sum" else tot / counts
    if op in ("min", "max"):
        fn = np.minimum if op == "min" else np.maximum
        out = np.full(n, vals.max() if op == "min" else vals.min(),
                      vals.dtype)
        fn.at(out, inv, vals)
        return out
    raise ValueError(f"reference has no grouped {op!r}")


def device_values(q: dict, arrays: Dict[str, np.ndarray]) -> int:
    """Values of 32-bit device-routable columns that the query must
    produce: each filter column over every row, each other column it
    reads over the rows that match (all rows when there is no filter).
    A computed column named in ``group_by``, ``agg`` or ``select`` is no
    data column: the columns its expression reads are counted instead.
    A column is routable when it is float32, or integer with every value
    inside int32.  Used for the memory-bound floor of the device time."""
    def routable(a: np.ndarray) -> bool:
        if a.dtype == np.float32:
            return True
        return (a.dtype.kind in "iu" and len(a) > 0
                and int(a.min()) >= -(1 << 31) and int(a.max()) < (1 << 31))

    n = len(arrays["id"])
    where = q.get("where")
    fcols = set(_columns(where)) if where is not None else set()
    matched = int(mask_of(where, arrays, n).sum())
    computed = q.get("computed") or {}
    read = set()
    if q.get("group_by"):
        read |= set(q["group_by"]) | {c for c in q["agg"] if c != "*"}
    for e in computed.values():
        read |= set(_columns(e))
    sel = q.get("select") or []
    read |= set(arrays) if sel == ["*"] else set(sel)
    read -= set(computed)
    total = 0
    for c in fcols | read:
        if routable(arrays[c]):
            total += n if c in fcols else matched
    return total


def _columns(e: Any) -> List[str]:
    if not isinstance(e, list) or not e:
        return []
    if e[0] == "field":
        return [e[1]]
    if e[0] == "cmp":
        return [e[1]]
    return [c for x in e[1:] for c in _columns(x)]


# -- comparison ---------------------------------------------------------------
def _sorted_by(t: Dict[str, np.ndarray], keys: List[str]
               ) -> Dict[str, np.ndarray]:
    order = np.lexsort([np.asarray(t[k]).astype(str)
                        if np.asarray(t[k]).dtype.kind == "O"
                        else np.asarray(t[k]) for k in reversed(keys)])
    return {k: np.asarray(v)[order] for k, v in t.items()}


def compare(answer: dict, ref: dict, group_keys: Optional[List[str]] = None
            ) -> Tuple[int, float]:
    """``(wrong_values, agg_rel_gap)`` of one answer against the
    reference: values that differ, go missing or are extra, counted one
    by one; and the largest relative gap of a floating aggregate (a mean
    or a sum of floats), which rounding may move."""
    wrong, gap = 0, 0.0
    if "agg" in ref:
        got = answer.get("agg")
        for c, ops in ref["agg"].items():
            for op, r in ops.items():
                a = (got or {}).get(c, {}).get(op)
                if a is None:
                    wrong += 1
                elif isinstance(r, float) and op in ("mean", "sum"):
                    gap = max(gap, abs(a - r) / max(abs(r), 1e-300))
                elif a != r:
                    wrong += 1
        return wrong, gap
    got = answer.get("table")
    if got is None:
        return sum(len(v) for v in ref["table"].values()), gap
    rt = ref["table"]
    if group_keys:
        if all(k in got for k in group_keys):
            got = _sorted_by(got, group_keys)
        rt = _sorted_by(rt, group_keys)
    for c, r in rt.items():
        a = got.get(c)
        if a is None or len(a) != len(r):
            wrong += max(len(r), 0 if a is None else len(a))
            continue
        a = np.asarray(a)
        if group_keys and r.dtype.kind == "f" and c not in group_keys:
            rel = np.abs(a.astype(np.float64) - r) / np.maximum(
                np.abs(r), 1e-300)
            gap = max(gap, float(rel.max()) if len(rel) else 0.0)
        else:
            wrong += int(np.count_nonzero(a != r))
    wrong += sum(len(v) for c, v in got.items() if c not in rt)
    return wrong, gap
