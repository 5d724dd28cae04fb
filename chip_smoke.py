"""End-to-end smoke of the store's device decode path on one TPU chip.

Builds an Alexandria-shaped table from ``--seed`` (4,800,000 rows by
default, the paper's Alexandria record count), loads it with
``ParquetDB.create`` at the default page and row-group sizes, and drives
the main read path under the ``jax`` decode backend:

- ``kernels``: every Pallas kernel against its jnp oracle at 65,536 values;
- ``full_scan``: ``db.query().to_table()`` — fused morsel decode;
- ``range_filter``: a selective range on a float32 column — two-phase,
  each column decoded in row-group batches, plus the ``filter_range``
  kernel once per row group;
- ``filtered_agg``: min/max/sum/mean/count over an id range that cuts two
  row groups, so partial groups decode and reduce through ``page_minmax``;
- ``group_by``: a count per space group;
- ``update`` of a few hundred rows, then ``filter_after_update`` (the
  delta overlay);
- ``server``: ``query``, ``count`` and ``agg`` requests to an in-process
  ``DBServer`` through ``DBClient``.

Every answer is checked against the same operation under the ``numpy``
backend on the same data: tables byte for byte, aggregates and server
responses for equality.  Each phase prints one JSON line with its wall
time and the pages the backend decoded on the device and on the host per
kernel family; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

Run it from the root of the repository on a machine with a TPU::

    python chip_smoke.py [--rows N] [--seed S]

Without a TPU it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from collections import Counter
from typing import Callable, Dict, List

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ROWS = 4_800_000  # Alexandria's record count (the paper's Fig. 9 dataset)

# The most frequent space groups of inorganic crystal databases, most
# common first; drawn with Zipf weights, so a page holds a few dozen
# distinct values spread over 1..230 and encodes as DICT.
COMMON_SPG = np.array([225, 62, 14, 194, 166, 221, 12, 139, 2, 15, 227, 216,
                       63, 191, 123, 71, 164, 129, 187, 148, 65, 11, 1, 4])

ENERGY_LO, ENERGY_HI = -32.0, -31.5  # float32-exact bounds, ~2% of rows


class SmokeFailure(RuntimeError):
    """A phase's answer differs from the numpy reference, or the device
    path did not run where it must."""


def make_table(n: int, seed: int):
    """Alexandria-shaped numeric columns (``benchmarks/alexandria.py``):
    space group (DICT), site count 1..11 (BITPACK), float32 energy (BSS)
    and a 40-bit structure fingerprint (BITPACK wider than 32 bits, so the
    32-bit gate sends it to the host).  ``create`` adds the sorted ``id``
    (DELTA)."""
    from repro.core import Table
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, len(COMMON_SPG) + 1)
    return Table.from_pydict({
        "spg": COMMON_SPG[rng.choice(len(COMMON_SPG), n, p=w / w.sum())],
        "n_sites": rng.integers(1, 12, n),
        "energy": np.round(rng.normal(-30.0, 10.0, n), 5).astype(np.float32),
        "fingerprint": rng.integers(1 << 33, 1 << 40, n),
    })


def _same(a, b) -> bool:
    """Byte-identical tables; plain equality for everything else."""
    from repro.core import Table
    if isinstance(a, Table):
        if a.column_names != b.column_names or a.num_rows != b.num_rows:
            return False
        for name in a.column_names:
            ca, cb = a.column(name), b.column(name)
            if ca.values.dtype != cb.values.dtype \
                    or ca.values.tobytes() != cb.values.tobytes():
                return False
            if (ca.validity is None) != (cb.validity is None) or (
                    ca.validity is not None
                    and not np.array_equal(ca.validity, cb.validity)):
                return False
        return True
    return a == b


def check_kernels(interpret: bool) -> List[str]:
    """Each Pallas kernel against its jnp oracle at 65,536 values."""
    import jax.numpy as jnp

    from repro.core import encodings as enc
    from repro.kernels import (bitunpack, bss_decode, delta_decode,
                               dict_decode, filter_range, page_minmax, ref)
    n = 65_536
    rng = np.random.default_rng(1)
    done = []

    def agree(name, got, want):
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            if g.dtype != w.dtype or g.tobytes() != w.tobytes():
                raise SmokeFailure(f"kernel {name} differs from its oracle")
        done.append(name)

    for k in (3, 8, 17, 31):
        vals = rng.integers(0, 1 << k, n).astype(np.uint64)
        packed = enc.pack_bits(vals, k)
        words = jnp.asarray(np.frombuffer(
            packed + b"\0" * (-len(packed) % 4), np.uint32))
        agree(f"bitunpack/k={k}",
              [bitunpack(words, n, k, interpret=interpret)],
              [ref.bitunpack(words, n, k)])
    zz = jnp.asarray(rng.integers(0, 1 << 12, n).astype(np.uint32))
    first = jnp.int32(-12345)
    agree("delta_decode", [delta_decode(zz, first, interpret=interpret)],
          [ref.delta_decode(zz, first)])
    for dt in (np.int32, np.float32):
        x = jnp.asarray((rng.standard_normal(n) * 100).astype(dt))
        agree(f"filter_range/{np.dtype(dt).name}",
              [filter_range(x, -50, 50, interpret=interpret)[0]],
              [ref.filter_range(x, dt(-50), dt(50))])
        agree(f"page_minmax/{np.dtype(dt).name}",
              page_minmax(x, 4096, interpret=interpret),
              ref.page_minmax(x, 4096))
    d = jnp.asarray(rng.integers(-1000, 1000, 230).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, 230, n).astype(np.int32))
    agree("dict_decode", [dict_decode(idx, d, interpret=interpret)],
          [ref.dict_decode(idx, d)])
    planes = jnp.asarray(rng.integers(0, 256, (4, n)).astype(np.uint8))
    agree("bss_decode", [bss_decode(planes, interpret=interpret)],
          [ref.bss_decode(planes)])
    return done


def run_phases(n_rows: int, seed: int, workdir: str,
               log: Callable[[dict], None] = print) -> Dict[str, dict]:
    """Drive every phase under the jax backend and check each against the
    numpy backend; returns ``{phase: record}`` as logged.  Raises
    :class:`SmokeFailure` on the first difference."""
    from repro.core import ParquetDB, field
    from repro.core.backend import get_backend, set_backend
    from repro.serve.dbserver import DBServer
    from repro.serve.protocol import DBClient

    jax_be = get_backend("jax")
    records: Dict[str, dict] = {}

    def emit(rec: dict) -> None:
        records[rec["phase"]] = rec
        log(rec)

    def on_both(name: str, fn: Callable) -> None:
        dev0, host0 = Counter(jax_be.device_pages), Counter(jax_be.host_pages)
        set_backend("jax")
        try:
            t0 = time.perf_counter()
            got = fn()
            t_jax = time.perf_counter() - t0
            set_backend("numpy")
            t0 = time.perf_counter()
            want = fn()
            t_np = time.perf_counter() - t0
        finally:
            set_backend(None)
        if not _same(got, want):
            raise SmokeFailure(f"phase {name}: jax backend answer differs "
                               "from the numpy backend")
        emit({"phase": name, "seconds": t_jax, "numpy_seconds": t_np,
              "device_pages": dict(jax_be.device_pages - dev0),
              "host_pages": dict(jax_be.host_pages - host0),
              "parity": True})

    t0 = time.perf_counter()
    emit({"phase": "kernels", "checked": check_kernels(jax_be.interpret),
          "seconds": time.perf_counter() - t0})

    t0 = time.perf_counter()
    table = make_table(n_rows, seed)
    db = ParquetDB(os.path.join(workdir, "alexandria"), "alexandria")
    db.create(table)
    del table
    emit({"phase": "load", "rows": db.n_rows,
          "seconds": time.perf_counter() - t0})

    energy = (field("energy") >= ENERGY_LO) & (field("energy") <= ENERGY_HI)
    lo_id, hi_id = n_rows // 5 + 3, (3 * n_rows) // 4 + 17
    ids = (field("id") >= lo_id) & (field("id") < hi_id)

    on_both("full_scan", lambda: db.query().to_table())
    on_both("range_filter", lambda: db.query().where(energy).select(
        "id", "spg", "n_sites", "energy", "fingerprint").to_table())
    on_both("filtered_agg", lambda: db.query().where(ids).agg(
        {"*": "count", "energy": ["min", "max", "sum", "mean"],
         "spg": ["min", "max"], "n_sites": ["sum", "mean"]}))
    on_both("group_by", lambda: db.query().group_by("spg").agg(
        {"*": "count"}).to_table())

    rng = np.random.default_rng(seed + 1)
    upd = np.sort(rng.choice(n_rows, 300, replace=False))
    new_energy = np.where(np.arange(300) % 2 == 0, -31.75, 7.25)
    set_backend("jax")
    try:
        t0 = time.perf_counter()
        n_upd = db.update([{"id": int(i), "energy": float(e)}
                           for i, e in zip(upd, new_energy)])
        emit({"phase": "update", "rows": n_upd,
              "seconds": time.perf_counter() - t0})
    finally:
        set_backend(None)
    if n_upd != len(upd):
        raise SmokeFailure(f"update touched {n_upd} rows, not {len(upd)}")
    on_both("filter_after_update", lambda: db.query().where(energy).select(
        "id", "spg", "energy").to_table())

    def serve() -> list:
        srv = DBServer(db, max_concurrent=4, max_queue=16)
        host, port = srv.start()
        try:
            with DBClient(host, port) as c:
                resps = [c.query(where=energy, select=["id", "spg", "energy"],
                                 limit=2_000),
                         c.count(where=energy),
                         c.agg({"energy": ["min", "max", "sum"]}, where=ids)]
        finally:
            srv.stop()
        for r in resps:
            if r.get("status") != 200:
                raise SmokeFailure(f"server answered {r}")
        return [{k: v for k, v in r.items() if k != "cache"} for r in resps]

    on_both("server", serve)

    scan = records["full_scan"]["device_pages"]
    for fam in ("bitpack", "dict", "delta"):
        if not scan.get(fam):
            raise SmokeFailure(f"full_scan decoded no {fam} page on device")
    if not records["range_filter"]["device_pages"].get("filter"):
        raise SmokeFailure("range_filter ran no filter_range on device")
    if not records["filtered_agg"]["device_pages"].get("minmax"):
        raise SmokeFailure("filtered_agg ran no page_minmax on device")
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.core.backend import get_backend
    cache_dir = enable_compile_cache()
    cache = Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache.update([event.rsplit("/", 1)[-1]])
        if event.startswith("/jax/compilation_cache/cache_") else None)
    if get_backend("jax").interpret:
        print("chip_smoke: the jax backend is in interpret mode",
              file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as workdir:
        run_phases(args.rows, args.seed, workdir,
                   lambda rec: print(json.dumps(rec), flush=True))
    print(json.dumps({"phase": "total", "seconds": time.perf_counter() - t0,
                      "rows": args.rows, "compile_cache": cache_dir,
                      "cache_hits": cache["cache_hits"],
                      "cache_misses": cache["cache_misses"]}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
