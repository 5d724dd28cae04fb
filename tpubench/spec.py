"""Benchmark definitions as data: cells, configurations, traffic mixes.

Everything here is plain Python and numpy.  It imports neither JAX nor
the store, so the open-loop load generator (a child process that must
never touch the chip) can use it too.

A traffic mix (``traffic/<name>.json``) is read by one general generator,
:func:`requests`.  Its keys:

- ``loop``: ``"closed"`` (one client, back to back) or ``"open"``
  (Poisson arrivals at ``rate_per_s``, sent by ``loadgen.py``).
- ``queries``: query templates, used in turn.  A template has ``where``
  (the wire protocol's expression form, ``["cmp", col, op, value]``,
  ``["and", a, b]``), ``select`` (column names, ``["*"]`` for all),
  ``computed`` (``{name: arithmetic}``, where arithmetic is a number,
  ``["field", col]`` or ``[op, a, b]`` with op ``add``, ``sub``, ``mul``
  or ``div``; ``div`` gives float64), ``group_by``, ``agg`` (``{col: op
  or [ops]}``, ops ``count``, ``sum``, ``mean``, ``min``, ``max``; col
  ``"*"`` for the row count) and ``terminal`` (``"table"`` or ``"agg"``).
  A value may be a number or ``{"param": name}``, ``{"add": [a, b]}``,
  ``{"sub": [a, b]}``, ``{"mul": [a, b]}``, ``{"div": [a, b]}``,
  ``{"date": [y, m, d]}`` (days since 1970-01-01), and in an open loop
  ``{"seq": [start, step]}``, start + step × the request's index, a value
  no other request writes.
- An open loop's template may instead be a write: ``"op": "update"``,
  ``where`` ``["cmp", "id", "==", key]`` and ``set`` ``{column: value}``.
  Templates are used in turn, so two make a 50/50 mix.
- ``params``: each either a list of candidates (``{"values": [...]}`` or
  ``{"arange": [start, stop, step]}``), whose product gives the parameter
  sets, or a per-request draw (``{"zipf": {"theta": t, "scrambled": b}}``
  over the configuration's ids; ``"items": n`` draws n ranks only, which
  scrambling spreads over the ids).
- ``limits``: the limit of each number the correctness check compares.

An open loop gets the same multiset of keys and arrival gaps on every
seed, in an order drawn from the seed.  A closed loop cycles through all
parameter sets in an order drawn from the seed; a window runs a prefix of
that cycle, so seeds differ in which sets they run, and in the work those
sets match.
"""
from __future__ import annotations

import datetime
import itertools
import json
import os
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_EPOCH = datetime.date(1970, 1, 1)
# the multiset of draws is fixed; only its order comes from the seed
_FIXED_STREAM = 20260817


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str) -> dict:
    """The ``workloads`` entry of ``name`` with its configuration and
    traffic mix loaded beside it."""
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = dict(cells[name])
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    w["config_file"] = load_json(os.path.join(ROOT, conf["file"]))
    w["traffic_file"] = load_json(
        os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    w["bench"] = bench
    return w


def seeded(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named use of the run's seed."""
    return np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32,
         int.from_bytes(stream.encode(), "little") & 0xFFFFFFFF])


# -- values ------------------------------------------------------------------
def value(v: Any, params: Dict[str, Any]) -> Any:
    """Resolve one value of a query template."""
    if isinstance(v, dict):
        if "param" in v:
            return params[v["param"]]
        if "add" in v:
            a, b = (value(x, params) for x in v["add"])
            return a + b
        if "sub" in v:
            a, b = (value(x, params) for x in v["sub"])
            return a - b
        if "mul" in v:
            a, b = (value(x, params) for x in v["mul"])
            return a * b
        if "div" in v:
            a, b = (value(x, params) for x in v["div"])
            return a / b
        if "seq" in v:
            start, step = v["seq"]
            return start + step * params["_index"]
        if "date" in v:
            y, m, d = (int(value(x, params)) for x in v["date"])
            return (datetime.date(y, m, d) - _EPOCH).days
        raise ValueError(f"unknown value form {v!r}")
    if isinstance(v, list):
        return [value(x, params) for x in v]
    return v


def instantiate(template: dict, params: Dict[str, Any]) -> dict:
    """A template with every parameter replaced by its value."""
    out = dict(template)
    if template.get("where") is not None:
        out["where"] = value(template["where"], params)
    if template.get("computed"):
        out["computed"] = {k: value(v, params)
                           for k, v in template["computed"].items()}
    if template.get("set"):
        out["set"] = {k: value(v, params) for k, v in template["set"].items()}
    return out


# -- parameter sets ----------------------------------------------------------
def _candidates(spec: dict) -> Optional[list]:
    if "values" in spec:
        return list(spec["values"])
    if "arange" in spec:
        start, stop, step = spec["arange"]
        n = int(round((stop - start) / step))
        return [start + i * step for i in range(n)]
    return None


def param_sets(traffic: dict) -> List[Dict[str, Any]]:
    """The product of every candidate-list parameter, in a fixed order."""
    lists = {}
    for name, spec in sorted(traffic.get("params", {}).items()):
        c = _candidates(spec)
        if c is not None:
            lists[name] = c
    names = list(lists)
    return [dict(zip(names, combo))
            for combo in itertools.product(*(lists[n] for n in names))]


def zipf_ranks(n_items: int, theta: float, count: int,
               rng: np.random.Generator) -> np.ndarray:
    """``count`` ranks in ``[0, n_items)`` with P(rank i) ~ 1/(i+1)^theta,
    YCSB's Zipfian distribution, by inverse transform."""
    w = np.arange(1, n_items + 1, dtype=np.float64) ** -theta
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(count)), n_items - 1)


def fnv1a64(x: np.ndarray) -> np.ndarray:
    """FNV-1a over the 8 little-endian bytes of each value, as YCSB's
    scrambled Zipfian generator hashes ranks onto keys."""
    h = np.full(x.shape, 0xCBF29CE484222325, np.uint64)
    v = x.astype(np.uint64)
    prime = np.uint64(0x100000001B3)
    with np.errstate(over="ignore"):
        for i in range(8):
            h ^= (v >> np.uint64(8 * i)) & np.uint64(0xFF)
            h *= prime
    return h


def draws(traffic: dict, n_rows: int, count: int, seed: int
          ) -> Dict[str, np.ndarray]:
    """Per-request draws: a fixed multiset, permuted by the seed."""
    out = {}
    for name, spec in sorted(traffic.get("params", {}).items()):
        if "zipf" not in spec:
            continue
        z = spec["zipf"]
        items = min(int(z.get("items", n_rows)), n_rows)
        fixed = np.random.default_rng([_FIXED_STREAM, len(name)])
        ranks = zipf_ranks(items, float(z["theta"]), count, fixed)
        keys = (fnv1a64(ranks) % np.uint64(n_rows)).astype(np.int64) \
            if z.get("scrambled") else ranks.astype(np.int64)
        out[name] = keys[seeded(seed, "draw:" + name).permutation(count)]
    return out


# -- request streams ---------------------------------------------------------
def closed_requests(traffic: dict, seed: int) -> Iterator[dict]:
    """Endless stream of instantiated queries for a closed loop.  Each
    carries ``template`` (its index in ``queries``)."""
    templates = traffic["queries"]
    sets = param_sets(traffic)
    order = seeded(seed, "params").permutation(len(sets))
    for i in itertools.count():
        t = i % len(templates)
        p = sets[order[(i // len(templates)) % len(sets)]]
        q = instantiate(templates[t], p)
        q["template"] = t
        yield q


def open_schedule(traffic: dict, n_rows: int, seconds: float, seed: int
                  ) -> List[dict]:
    """Requests of an open loop with their due times (seconds from the
    window's start).  The gaps are a fixed set of exponential draws at
    ``rate_per_s`` scaled to fill the window, permuted by the seed."""
    rate = float(traffic["rate_per_s"])
    count = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng([_FIXED_STREAM, 0]).exponential(
        1.0, count)
    gaps *= seconds / gaps.sum()
    gaps = gaps[seeded(seed, "arrivals").permutation(count)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    per = draws(traffic, n_rows, count, seed)
    templates = traffic["queries"]
    out = []
    for i in range(count):
        p = {k: int(v[i]) for k, v in per.items()}
        p["_index"] = i
        q = instantiate(templates[i % len(templates)], p)
        q["template"] = i % len(templates)
        q["due"] = float(due[i])
        out.append(q)
    return out
