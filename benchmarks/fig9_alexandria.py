"""Paper Fig. 9: the (synthetic) Alexandria materials dataset, two ways.

Phase 1 (the paper's figure): JSON load time vs ParquetDB create time per
shard, into one flat dataset.

Phase 2 (this repo's partitioned layout): the same records re-created into
a hive-partitioned dataset (``part = spg % N_PARTS``), then

- ``fig9/scan-full/n=...``       full materializing read,
- ``fig9/scan-selective/n=...``  one-partition query — the manifest prunes
  every other partition before a single footer is opened (the pruning
  counters ride along in the derived fields), and
- ``fig9/scan-sharded-w<k>/n=...``  a multi-process shard-per-worker scan:
  partitions are placed onto worker processes in contiguous blocks (what
  a 1-D data mesh gives on one host); each worker opens the dataset
  itself and reads only its partitions.  The parent never touches JAX
  before it spawns, and under the ``jax`` decode backend the row is not
  run: the device belongs to the parent, and the workers would have to
  take it or decode on the host.

``scripts/check_perf.py`` gates ``fig9 partition-prune`` on the
selective-vs-full ratio of this suite's artifact.
"""
from __future__ import annotations

import json
import math
import os
from typing import List

from repro.core import ParquetDB
from repro.core.backend import active_backend
from repro.core.expressions import IsIn, field

from .alexandria import write_json_shards
from .common import TmpDir, row, timeit, timeit_median

N_PARTS = 16  # hive partitions: part = spg % N_PARTS
SELECTIVE_PART = 3


def _placement(n_parts: int, n_workers: int) -> List[List[int]]:
    """Partition indices per worker, in contiguous blocks."""
    step = math.ceil(n_parts / n_workers)
    return [list(range(i, min(i + step, n_parts)))
            for i in range(0, n_parts, step)]


def _scan_shard(args) -> int:
    """Worker: open the dataset and read only this worker's partitions."""
    path, parts = args
    db = ParquetDB(path, "alexandria_part")
    return db.read(filters=[IsIn("part", parts)]).num_rows


def _sharded_scan(path: str, n_workers: int, assign) -> int:
    import multiprocessing as mp
    from concurrent.futures import ProcessPoolExecutor
    ctx = mp.get_context("spawn")
    with ProcessPoolExecutor(max_workers=n_workers, mp_context=ctx) as ex:
        return sum(ex.map(_scan_shard,
                          [(path, parts) for parts in assign if parts]))


def _assert_verified(pdb) -> None:
    report = pdb.verify(deep=True)
    assert report.ok, f"integrity scrub failed:\n{report}"


def run(scale: str = "small") -> List[dict]:
    n_total, per_file = {"quick": (4_000, 2_000),
                         "small": (2_000, 500),
                         "medium": (20_000, 5_000),
                         "paper": (500_000, 100_000)}[scale]
    out: List[dict] = []
    with TmpDir() as tmp:
        shards = write_json_shards(os.path.join(tmp, "json"), n_total,
                                   per_file)
        db = ParquetDB(os.path.join(tmp, "pdb"), "alexandria")
        shard_data = []
        for i, p in enumerate(shards):
            holder = {}
            t_load = timeit(lambda: holder.setdefault(
                "d", json.load(open(p))))
            data = holder["d"]["entries"]
            for r in data:
                r["part"] = r["data"]["spg"] % N_PARTS
            shard_data.append(data)
            t_create = timeit(lambda: db.create(
                data, treat_fields_as_ragged=["data.elements"]))
            out.append(row(f"fig9/json_load/shard={i}", t_load,
                           rows=len(data)))
            out.append(row(f"fig9/create/shard={i}", t_create,
                           rows=len(data)))
        out.append(row("fig9/total_rows", 0.0, rows=db.n_rows))

        # ---- phase 2: the same records, hive-partitioned by spg bucket
        ppath = os.path.join(tmp, "pdb_part")
        pdb = ParquetDB(ppath, "alexandria_part", partition_by=["part"])

        def create_part():
            for data in shard_data:
                pdb.create(data, treat_fields_as_ragged=["data.elements"])
        t_create_part = timeit(create_part)
        out.append(row(f"fig9/create-part/n={n_total}", t_create_part,
                       rows=n_total, partitions=N_PARTS))

        t_full = timeit_median(lambda: pdb.read(), k=3)
        sel = field("part") == SELECTIVE_PART
        t_sel = timeit_median(lambda: pdb.read(filters=[sel]), k=3)
        rep = pdb.explain(filters=[sel], execute=True)
        c = rep.counters
        out.append(row(f"fig9/scan-full/n={n_total}", t_full, rows=n_total))
        out.append(row(f"fig9/scan-selective/n={n_total}", t_sel,
                       rows=c.rows_matched,
                       partitions_total=c.partitions_total,
                       partitions_pruned=c.partitions_pruned,
                       partitions_scanned=c.partitions_scanned,
                       speedup_vs_full=round(t_full / t_sel, 2)))

        # ---- integrity scrub of the real-data fixture: every committed
        # file's footer + page checksums must hold (the --quick CI smoke
        # runs this, so a writer bug that commits damaged bytes trips here)
        t_verify = timeit(lambda: _assert_verified(pdb))
        out.append(row(f"fig9/verify-deep/n={n_total}", t_verify,
                       rows=n_total))

        n_workers = min(4, os.cpu_count() or 1)
        if n_workers > 1 and active_backend().name != "jax":
            assign = _placement(N_PARTS, n_workers)
            holder = {}
            t_shard = timeit(lambda: holder.setdefault(
                "n", _sharded_scan(ppath, n_workers, assign)))
            assert holder["n"] == n_total, (holder["n"], n_total)
            out.append(row(f"fig9/scan-sharded-w{n_workers}/n={n_total}",
                           t_shard, rows=n_total, workers=n_workers))
    return out
