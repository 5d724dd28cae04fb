"""The store's side of a query: an instantiated query of ``spec.py`` run
through ``ParquetDB.query()``, and its answer in the shape that
``reference.compare`` reads."""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

_SQL_OPS = ("==", "!=", "<", "<=", ">", ">=")


def expr(where: list):
    """The wire form of an expression as a ``repro.core`` Expr."""
    from repro.core import field
    tag = where[0]
    if tag == "and":
        return expr(where[1]) & expr(where[2])
    if tag == "or":
        return expr(where[1]) | expr(where[2])
    if tag == "not":
        return ~expr(where[1])
    if tag == "cmp" and where[2] in _SQL_OPS:
        _, col, op, v = where
        f = field(col)
        return {"==": f.__eq__, "!=": f.__ne__, "<": f.__lt__,
                "<=": f.__le__, ">": f.__gt__, ">=": f.__ge__}[op](v)
    raise ValueError(f"no store expression for {where!r}")


def _arith(e):
    from repro.core import field
    if isinstance(e, (int, float)):
        return e
    if e[0] == "field":
        return field(e[1])
    a, b = _arith(e[1]), _arith(e[2])
    return {"add": lambda: a + b, "sub": lambda: a - b,
            "mul": lambda: a * b, "div": lambda: a / b}[e[0]]()


def run(db, q: dict) -> Any:
    """Run one query; returns the store's own result object."""
    if q.get("op", "query") != "query":
        raise ValueError("a closed loop runs reads only; writes are checked "
                         "in an open loop")
    query = db.query()
    if q.get("where") is not None:
        query = query.where(expr(q["where"]))
    computed = {k: _arith(v) for k, v in (q.get("computed") or {}).items()}
    sel = q.get("select") or ["*"]
    if computed or sel != ["*"]:
        query = query.select(*([] if sel == ["*"] else sel), **computed)
    if q.get("group_by"):
        return query.group_by(*q["group_by"]).agg(q["agg"]).to_table()
    if q["terminal"] == "agg":
        return query.agg(q["agg"])
    return query.to_table()


def normalise(result: Any) -> Dict[str, Any]:
    """A store result (Table or aggregate dict) as ``{"table": ...}`` or
    ``{"agg": ...}``; a null becomes None, so it never equals a value."""
    if isinstance(result, dict):
        return {"agg": result}
    out = {}
    for name in result.column_names:
        col = result.column(name)
        vals = col.values
        if vals is None:  # strings and other variable-width columns
            vals = np.array(col.to_pylist(), dtype=object)
        elif col.validity is not None and not col.validity.all():
            vals = np.where(col.validity, vals.astype(object), None)
        out[name] = vals
    return {"table": out}


def rows_to_table(rows: List[dict]) -> Dict[str, Any]:
    """Rows as the server returns them, as a ``{"table": ...}`` answer."""
    names = sorted({k for r in rows for k in r})
    return {"table": {k: np.array([r.get(k) for r in rows])
                      for k in names}}
