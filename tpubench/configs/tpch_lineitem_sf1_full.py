"""All 16 TPC-H LINEITEM columns by dbgen's rules
(``tpch_lineitem_sf1_full.json``).

The eight columns that ``tpch_lineitem_sf1`` stores come from the same
draws in the same order on the same seed, so they are equal there; the
other eight are drawn after them.  Decimals are scaled int64, dates int32
days since 1970-01-01, the flags one-byte strings; instructions, modes
and comments are ``str`` objects, stored with their own lengths.
"""
import importlib.util
import os
from typing import Dict

import numpy as np


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    s = importlib.util.spec_from_file_location("tpubench_config_" + name,
                                               path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


_BASE = _sibling("tpch_lineitem_sf1")


def text_pool(cfg: dict, rng: np.random.Generator) -> str:
    """Sentences from the pool's word lists up to its length: an optional
    adjective, a noun, an optional auxiliary, a verb, an optional adverb,
    an optional preposition with a noun, then a terminator."""
    p = cfg["text_pool"]

    def pick(words: list, share: float = 1.0) -> list:
        return [words[rng.integers(len(words))]] \
            if rng.random() < share else []

    out, size = [], 0
    while size < p["characters"]:
        words = (pick(p["adjectives"], 0.5) + pick(p["nouns"])
                 + pick(p["auxiliaries"], 0.3) + pick(p["verbs"])
                 + pick(p["adverbs"], 0.5))
        if rng.random() < 0.5:
            words += pick(p["prepositions"]) + ["the"] + pick(p["nouns"])
        s = " ".join(words) + pick(p["terminators"])[0] + " "
        out.append(s)
        size += len(s)
    return "".join(out)[:p["characters"]]


def generate(cfg: dict, rows: int, seed: int) -> Dict[str, np.ndarray]:
    c = cfg["columns"]
    # tpch_lineitem_sf1's draws, in its order
    rng = np.random.default_rng(seed)
    n_orders = max(1, int(round(rows * cfg["orders"] / cfg["rows"])))
    per = _BASE._lines_per_order(n_orders, rows, rng)
    i = np.arange(1, n_orders + 1, dtype=np.int64)
    okey = ((i >> 3) << 5) | (i & 7)
    start, end, now = (_BASE._day(cfg[k]) for k in
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
    discount = rng.integers(0, 11, rows)
    tax = rng.integers(0, 9, rows)
    # the other eight columns
    s = cfg["suppliers"]
    corr = rng.integers(0, 4, rows)
    first = np.repeat(np.cumsum(per) - per, per)
    instruct = np.array(c["l_shipinstruct"]["values"], dtype=object)
    modes = np.array(c["l_shipmode"]["values"], dtype=object)
    commit = orderdate + rng.integers(30, 91, rows)
    instruct = instruct[rng.integers(0, len(instruct), rows)]
    modes = modes[rng.integers(0, len(modes), rows)]
    pool = text_pool(cfg, rng)
    lo, hi = c["l_comment"]["min_length"], c["l_comment"]["max_length"]
    lens = rng.integers(lo, hi + 1, rows)
    offs = rng.integers(0, len(pool) - hi + 1, rows)
    comment = np.empty(rows, dtype=object)
    comment[:] = [pool[o:o + n] for o, n in zip(offs.tolist(),
                                                 lens.tolist())]
    return {
        "l_orderkey": np.repeat(okey, per),
        "l_partkey": partkey,
        "l_suppkey": (partkey + corr * (s // 4 + (partkey - 1) // s)) % s + 1,
        "l_linenumber": np.arange(rows, dtype=np.int64) - first + 1,
        "l_quantity": qty * 100,
        "l_extendedprice": qty * retail,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": rflag.astype("S1"),
        "l_linestatus": np.where(ship > now, b"O", b"F").astype("S1"),
        "l_shipdate": ship.astype(np.int32),
        "l_commitdate": commit.astype(np.int32),
        "l_receiptdate": receipt.astype(np.int32),
        "l_shipinstruct": instruct,
        "l_shipmode": modes,
        "l_comment": comment,
    }
