"""TPC-H LINEITEM columns by dbgen's rules (``tpch_lineitem_sf1.json``).

Decimals are scaled int64 (DECIMAL(15,2) as Parquet stores it), dates
are int32 days since 1970-01-01 (Parquet DATE), flags are one-byte
strings.  ``rows`` below the configured count scales the orders with it.
"""
import datetime
from typing import Dict, Tuple

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)


def _day(ymd) -> int:
    return (datetime.date(*ymd) - _EPOCH).days


def _lines_per_order(n_orders: int, rows: int,
                     rng: np.random.Generator) -> np.ndarray:
    """1 .. 7 lines per order, uniform, then nudged one line at a time on
    random orders until the total is ``rows``."""
    per = rng.integers(1, 8, n_orders)
    diff = rows - int(per.sum())
    step = 1 if diff > 0 else -1
    while diff:
        room = np.nonzero((per < 7) if step > 0 else (per > 1))[0]
        pick = rng.choice(room, min(abs(diff), len(room)), replace=False)
        per[pick] += step
        diff -= step * len(pick)
    return per


def orders_and_lines(cfg: dict, rows: int, seed: int
                     ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """``(o_orderdate of each line, stored columns)``."""
    rng = np.random.default_rng(seed)
    n_orders = max(1, int(round(rows * cfg["orders"] / cfg["rows"])))
    per = _lines_per_order(n_orders, rows, rng)
    i = np.arange(1, n_orders + 1, dtype=np.int64)
    okey = ((i >> 3) << 5) | (i & 7)
    start, end, now = (_day(cfg[k]) for k in
                       ("start_date", "end_date", "current_date"))
    odate = rng.integers(start, end - 151 + 1, n_orders)
    orderdate = np.repeat(odate, per)
    partkey = rng.integers(1, cfg["parts"] + 1, rows)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    qty = rng.integers(1, 51, rows)
    ship = orderdate + rng.integers(1, 122, rows)
    receipt = ship + rng.integers(1, 31, rows)
    rflag = np.where(receipt <= now,
                     np.where(rng.random(rows) < 0.5, b"R", b"A"), b"N")
    return orderdate, {
        "l_orderkey": np.repeat(okey, per),
        "l_quantity": qty * 100,
        "l_extendedprice": qty * retail,
        "l_discount": rng.integers(0, 11, rows),
        "l_tax": rng.integers(0, 9, rows),
        "l_shipdate": ship.astype(np.int32),
        "l_returnflag": rflag.astype("S1"),
        "l_linestatus": np.where(ship > now, b"O", b"F").astype("S1"),
    }


def generate(cfg: dict, rows: int, seed: int) -> Dict[str, np.ndarray]:
    return orders_and_lines(cfg, rows, seed)[1]
