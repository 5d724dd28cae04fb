"""Scan planner: fragment/row-group pruning, projection pushdown, explain().

This is the read-path query planner behind :meth:`ParquetDB.read` (see
docs/ARCHITECTURE.md for the full data-flow diagram).  The paper's central
performance claim is that footer statistics *replace* indexes ("reduced
dependency on indexing through predicate pushdown filtering", ParquetDB
§4.5); this module is where that claim is implemented end to end:

    plan   — for each manifest file (a *fragment*), consult whole-file
             ``ColumnStats`` (min/max + bloom, merged from row-group stats)
             via ``Expr.prune``; a fragment that provably cannot contain a
             matching row is never opened for data.  Surviving fragments are
             narrowed to the row groups whose stats may match.
    prune  — inside a scanned row group the reader additionally prunes at
             page granularity (aligned page stats) before touching bytes.
    decode — only the projected-plus-filter columns of surviving pieces are
             decoded; the two-phase reader decodes filter columns first so a
             non-matching page never decodes the payload columns.
    filter — the residual ``Expr`` mask is applied to decoded rows.
    project— filter-only columns are dropped; output schema == projection.

All pruning is *sound*: ``Expr.prune`` returns False only when statistics
prove no row can match, so a planned scan is row-identical to a full scan.
Every stage records counters (:class:`ScanCounters`); ``ScanPlan.explain``
returns them as a :class:`ScanReport` so pruning decisions are observable
and testable — ``db.explain(filters=...)`` from user code.

**Parallel execution.**  Surviving fragments are split into *morsels* —
contiguous runs of row groups capped at ``MORSEL_ROWS`` rows — and decoded
on a shared, process-wide :class:`~concurrent.futures.ThreadPoolExecutor`
(work-stealing: idle workers pull the next morsel from the shared queue).
The pool is sized from ``LoadConfig.num_threads`` (default
``os.cpu_count()``); each worker obtains its own per-thread ``TPQReader``
handle over the shared file mapping (see ``store._get_reader``), decodes
its morsel into Tables, and records work into a **morsel-local**
:class:`ScanCounters`.  The consumer merges results with an
order-preserving bounded merge: morsel outputs are yielded strictly in
plan order (so ``read()`` output is byte-identical to the serial scan,
order included) and at most ``num_threads + fragment_readahead`` morsels
are in flight, bounding memory.  Counters are merged single-threaded in
the consumer (:meth:`ScanCounters.merge_from`), so no increment is ever
lost to a data race.  ``num_threads=1`` (or ``use_threads=False``) falls
back to the serial path with the classic readahead thread
(:func:`prefetch`).

**Process executor.**  Threads only overlap while the GIL is released
(codec decompression); raw and entropy-coded pages decode in pure numpy
*under* the GIL, where a thread pool convoys.  ``LoadConfig.executor=
"process"`` decodes morsels on a shared spawn-context
:class:`~concurrent.futures.ProcessPoolExecutor` instead: workers run the
*decode half* of a morsel (prune → pushdown → decode) against their own
stat-validated reader cache and ship results back through one
shared-memory segment per morsel (:mod:`repro.core.shm`, pickle-5
out-of-band buffers); the parent runs the *finish half* (overlay,
residual filter, ``map_fn``) and the same order-preserving bounded merge,
so output is byte-identical to the serial scan.  The default
``executor=None`` is AUTO: the footer's codec split picks threads for
codec-compressed read sets and processes for GIL-bound ones big enough to
amortize worker spawn (``PROCESS_MIN_ROWS``).  Workers always decode with
the numpy backend; under the ``jax`` backend the device belongs to this
process, so AUTO never picks processes and an explicit ``"process"`` raises.

**Merge-on-read deltas.**  A manifest may carry a chain of delta files
(:class:`repro.core.transactions.DeltaEntry`) — *upsert* files holding
full-width replacement rows and *tombstone* files holding deleted ids.
:class:`DeltaOverlay` resolves the chain once per scan (last commit wins
per id) and the planner overlays it on the base fragments **in place**:

  - a base row whose id has a live upsert is substituted with the upsert
    row at its original position (row order is preserved, and the residual
    filter sees the *merged* values);
  - a base row whose final state is a tombstone is dropped;
  - fragments whose id range can contain an upserted row lose stats
    pruning and reader pushdown (their stored statistics describe stale
    values), are decoded fully, and are filtered after substitution —
    soundness over speed.  Compaction folds the chain back into base files
    and restores full pruning; ``maintenance_stats()`` reports the decay.

Tombstones never disable pruning: dropping rows commutes with filtering,
so a fragment shadowed only by deletes keeps its pushdown.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import multiprocessing
import os
import queue
import threading
import warnings
from concurrent.futures import (BrokenExecutor, ProcessPoolExecutor,
                                ThreadPoolExecutor)
from typing import (Any, Callable, Dict, Generator, Iterable, List, Optional,
                    Sequence, Tuple)

import numpy as np

from . import shm
from ..spans import span
from .backend import active_backend, set_backend
from .expressions import Expr
from .fileformat import TPQReader, page_codec_split
from .integrity import CorruptFooterError, IntegrityError, with_read_retries
from .schema import ID_COLUMN, Schema
from .table import Table, concat_tables
from .transactions import DELTA_TOMBSTONE, DeltaEntry

__all__ = ["ScanCounters", "FragmentPlan", "ScanReport", "ScanPlan",
           "DeltaOverlay", "MorselBudget", "file_may_match", "prefetch",
           "scan_pool", "process_scan_pool", "resolve_num_threads",
           "MORSEL_ROWS", "PROCESS_MIN_ROWS"]

# Target rows per morsel: small enough that a handful of fragments yields
# enough parallelism, large enough that per-task overhead (submit, counter
# merge) stays invisible next to decode cost.  A row group larger than the
# target is one morsel (morsels never split a row group: page pruning,
# two-phase decode and selection vectors all operate per row group).
MORSEL_ROWS = 65_536

# AUTO executor selection sends GIL-bound scans to worker *processes* only
# past this many planned rows: below it the spawn + result-shipping constant
# outweighs what the GIL convoy costs.
PROCESS_MIN_ROWS = 200_000

# multiprocessing start method for the scan workers.  "spawn" by default:
# fork would duplicate whatever threads/jax state the parent holds (a
# classic deadlock with the shared thread pool warm); override for
# experiments via the environment.
ENV_MP_CONTEXT = "REPRO_SCAN_MP_CONTEXT"

_POOL_LOCK = threading.Lock()
_POOL: Optional[ThreadPoolExecutor] = None
_POOL_WORKERS = 0

_PPOOL_LOCK = threading.Lock()
_PPOOL: Optional[ProcessPoolExecutor] = None
_PPOOL_WORKERS = 0


def resolve_num_threads(cfg) -> int:
    """Worker count for a scan config (duck-typed, like the readahead knob).

    ``use_threads=False`` forces 1; ``num_threads=None`` (the default)
    means ``os.cpu_count()``.  Always >= 1.
    """
    if not getattr(cfg, "use_threads", True):
        return 1
    nt = getattr(cfg, "num_threads", None)
    if nt is None:
        nt = os.cpu_count() or 1
    return max(1, int(nt))


class MorselBudget:
    """Cooperative cap on in-flight morsels shared across concurrent scans.

    Attach one instance to several ``LoadConfig``s (``morsel_budget=...``)
    and every scan using them charges one permit per *submitted* morsel,
    releasing it when the morsel's result is consumed.  With the budget
    exhausted, further submission **blocks** — concurrent scans throttle
    each other to a bounded total of decoded-but-unconsumed work instead
    of racing the shared pool into memory bloat.  This is the
    backpressure primitive behind the serving tier's admission control.

    Progress guarantee (no deadlock): every executor loop follows the
    discipline *block for a permit only while holding none* — refills of
    an already-primed window use :meth:`try_acquire` and simply skip the
    refill when the budget is dry (the scan then drains its own in-flight
    morsels, releasing as it goes).  So any charged permit is always held
    by a scan that is actively consuming, and a scan blocked in
    :meth:`acquire` holds nothing anyone is waiting on.  ``limit >= 1`` is
    enforced, so even a budget of one serializes morsels rather than
    stalling them.

    Counters (read via :meth:`stats`): ``in_flight`` (currently charged),
    ``peak_in_flight``, ``total_acquired`` and ``waits`` (acquisitions
    that blocked or were denied — the saturation signal a server sheds
    on).
    """

    def __init__(self, limit: int):
        if int(limit) < 1:
            raise ValueError(f"morsel budget must be >= 1, got {limit}")
        self.limit = int(limit)
        self._cv = threading.Condition()
        self.in_flight = 0
        self.peak_in_flight = 0
        self.total_acquired = 0
        self.waits = 0

    def acquire(self) -> None:
        """Charge one permit, blocking while the budget is exhausted.
        Callers must hold no other permit (see the class docstring)."""
        with self._cv:
            if self.in_flight >= self.limit:
                self.waits += 1
                while self.in_flight >= self.limit:
                    self._cv.wait()
            self._charge()

    def try_acquire(self) -> bool:
        """Charge one permit if available; never blocks.  A ``False``
        counts toward ``waits`` — denial is the same saturation signal."""
        with self._cv:
            if self.in_flight >= self.limit:
                self.waits += 1
                return False
            self._charge()
            return True

    def _charge(self) -> None:
        self.in_flight += 1
        self.total_acquired += 1
        if self.in_flight > self.peak_in_flight:
            self.peak_in_flight = self.in_flight

    def release(self) -> None:
        """Return one permit and wake one blocked acquirer."""
        with self._cv:
            self.in_flight -= 1
            self._cv.notify()

    @property
    def saturated(self) -> bool:
        """True while every permit is charged (admission-control signal)."""
        with self._cv:
            return self.in_flight >= self.limit

    def stats(self) -> Dict[str, int]:
        with self._cv:
            return {"limit": self.limit,
                    "in_flight": self.in_flight,
                    "peak_in_flight": self.peak_in_flight,
                    "total_acquired": self.total_acquired,
                    "waits": self.waits}


def scan_pool(num_threads: int) -> ThreadPoolExecutor:
    """The shared scan/compaction worker pool, grown to >= ``num_threads``.

    One process-wide pool serves every concurrent scan (morsels from
    different scans interleave on the same workers — work stealing across
    queries, not just within one).  Workers never submit work back to the
    pool, so sharing cannot deadlock.  The pool only ever grows: when a
    larger size is requested a bigger executor replaces the global slot,
    but the old one is **not** shut down — an in-flight scan that cached
    it keeps submitting refill morsels to it until that scan completes
    (shutting it down would make those submits raise).  Abandoned
    executors idle until interpreter exit; growth is monotonic, so at
    most a handful ever exist.
    """
    global _POOL, _POOL_WORKERS
    with _POOL_LOCK:
        if _POOL is None or _POOL_WORKERS < num_threads:
            _POOL = ThreadPoolExecutor(max_workers=num_threads,
                                       thread_name_prefix="tpq-scan")
            _POOL_WORKERS = num_threads
    return _POOL


def _ensure_child_import_path() -> None:
    """Make ``repro`` importable in spawned workers.

    Spawn children resolve :func:`_process_morsel` by qualified name, so the
    package root must be on *their* ``sys.path``; when the parent imported
    it off a source tree (tests, benchmarks) rather than site-packages, the
    child only inherits that via ``PYTHONPATH``.  Prepending is idempotent.
    """
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    pp = os.environ.get("PYTHONPATH", "")
    parts = pp.split(os.pathsep) if pp else []
    if root not in parts:
        os.environ["PYTHONPATH"] = os.pathsep.join([root] + parts)


def process_scan_pool(num_workers: int) -> ProcessPoolExecutor:
    """The shared morsel worker *process* pool, grown to >= ``num_workers``.

    Same grow-only contract as :func:`scan_pool` (an in-flight scan that
    cached a smaller pool keeps it; growth is monotonic so at most a
    handful ever exist), but workers are spawn-context processes — each
    decodes with its own GIL, which is the whole point: entropy-coded and
    raw pages decode in pure Python/numpy and convoy on a thread pool.
    Workers are started lazily by the executor on first submit and are
    reaped by ``concurrent.futures``'s atexit hook, so a completed scan
    leaves idle workers, never orphans.
    """
    global _PPOOL, _PPOOL_WORKERS
    with _PPOOL_LOCK:
        # a pool whose workers died (BrokenProcessPool) rejects every
        # future submit — replace it instead of caching the corpse
        broken = _PPOOL is not None and getattr(_PPOOL, "_broken", False)
        if _PPOOL is None or broken or _PPOOL_WORKERS < num_workers:
            _ensure_child_import_path()
            ctx = multiprocessing.get_context(
                os.environ.get(ENV_MP_CONTEXT, "spawn"))
            _PPOOL = ProcessPoolExecutor(max_workers=num_workers,
                                         mp_context=ctx,
                                         initializer=set_backend,
                                         initargs=("numpy",))
            _PPOOL_WORKERS = num_workers
    return _PPOOL


def _warn_broken_pool(state: dict) -> None:
    """Flag a scan as degraded (once) when its process pool dies."""
    if not state["broken"]:
        state["broken"] = True
        warnings.warn(
            "scan process pool broke mid-scan (worker died — commonly a "
            "script using executor='process' without an "
            "`if __name__ == '__main__':` guard under the spawn start "
            "method); finishing this scan with inline decode",
            RuntimeWarning, stacklevel=3)


# Per-process reader cache for morsel workers, validated by (size,
# mtime_ns): data files are immutable-by-name within a dataset generation,
# but a worker can outlive many scans, so stale paths must re-open.
_WORKER_READERS: Dict[str, tuple] = {}
_WORKER_READERS_MAX = 64


def _worker_reader(path: str) -> TPQReader:
    st = os.stat(path)
    sig = (st.st_size, st.st_mtime_ns)
    hit = _WORKER_READERS.get(path)
    if hit is None or hit[0] != sig:
        hit = (sig, with_read_retries(lambda: TPQReader(path), path))
        _WORKER_READERS[path] = hit
        if len(_WORKER_READERS) > _WORKER_READERS_MAX:
            _WORKER_READERS.pop(next(iter(_WORKER_READERS)))
    return hit[1]


# Fault-injection switch for the worker-crash tests: module-level hooks do
# not survive the spawn boundary, so the kill order rides the environment
# (inherited by pool workers).  A worker seeing it dies before decoding —
# deterministically producing the BrokenProcessPool path.
ENV_TEST_KILL_WORKER = "REPRO_TEST_KILL_WORKER"


def _process_morsel(path: str, row_groups: tuple, columns: tuple,
                    expr: Optional[Expr],
                    verify: Optional[str] = None) -> shm.Envelope:
    """Decode one morsel inside a worker process (the *decode half*).

    Runs page pruning, pushdown filtering and decode exactly like a thread
    worker; overlay substitution, residual filters and ``map_fn`` stay in
    the parent (closures and overlay state don't cross a pickle boundary).
    The decoded tables + morsel-local counters ship back through
    :mod:`repro.core.shm` as one out-of-band envelope.  ``verify`` is the
    scan's ``LoadConfig.verify`` mode; a :class:`CorruptPageError` raised
    here pickles back to the parent with its coordinates intact.
    """
    if os.environ.get(ENV_TEST_KILL_WORKER):
        os._exit(1)
    local = ScanCounters()
    rd = _worker_reader(path)
    tables = list(rd.iter_row_group_tables(list(columns), expr,
                                           row_groups=list(row_groups),
                                           counters=local, verify=verify))
    return shm.pack((tables, local))


@dataclasses.dataclass
class ScanCounters:
    """Per-stage pruning/decoding counters for one scan.

    Planning fills the file/row-group fields; ``explain()`` fills the byte
    totals (a footer walk plain reads skip); execution (the reader) fills
    pages/rows/bytes-decoded.  ``rows_matched`` counts rows surviving the
    residual filter — i.e. the rows the caller actually receives.
    """
    files_total: int = 0
    files_scanned: int = 0
    files_skipped: int = 0
    # hive partitioning (planning fills these from manifest metadata):
    # a *pruned* partition was eliminated before any footer was opened —
    # partition-pruned files count into files_skipped but their row groups
    # are unknown (footer never read) and excluded from row_groups_total.
    # A partition whose every file was pruned by footer stats instead
    # counts in neither pruned nor scanned.
    partitions_total: int = 0
    partitions_scanned: int = 0
    partitions_pruned: int = 0
    row_groups_total: int = 0
    row_groups_scanned: int = 0
    row_groups_skipped: int = 0
    pages_scanned: int = 0
    pages_skipped: int = 0
    rows_scanned: int = 0
    rows_matched: int = 0
    bytes_total: int = 0        # stored bytes of every chunk in every file
    bytes_selected: int = 0     # projected columns of surviving row groups
    bytes_decoded: int = 0      # actually decoded (after page pruning)
    # late materialization (two-phase reader): payload rows the selection
    # vector kept out of result batches, and the bytes of their values —
    # var-len bytes are never copied out of the page buffer; fixed-width
    # pages decode to a transient and only the selection is kept
    rows_skipped_late: int = 0
    bytes_saved_late: int = 0
    # two-phase reader: column pages decoded inside one row-group batch of
    # two or more pages, and column pages decoded one at a time (a lone
    # surviving page, or a var-len, tensor, null or nullable column)
    two_phase_pages_batched: int = 0
    two_phase_pages_single: int = 0
    # merge-on-read delta work (planning fills the first three from the
    # delta chain; execution fills applied/shadowed as rows are merged)
    delta_files: int = 0            # delta files in the overlaid chain
    delta_upsert_rows: int = 0      # rows staged in upsert files
    delta_tombstone_rows: int = 0   # ids staged in tombstone files
    delta_rows_applied: int = 0     # base rows substituted with upsert rows
    rows_shadowed: int = 0          # base rows dropped by tombstones
    # aggregate pushdown (AggregatePlan): row groups whose contribution was
    # answered from footer statistics alone, and the stored bytes of their
    # read set that were therefore never decoded
    groups_answered_by_stats: int = 0
    bytes_skipped_agg: int = 0
    # integrity / fault tolerance (LoadConfig.verify / on_corruption):
    # delta files dropped from the overlay because they failed
    # verification (on_corruption="quarantine" only — base files raise),
    # process-pool rebuilds after a worker crash (at most one per scan),
    # and morsels that fell back to inline decode (broken pool or a
    # compaction race GC'ing a planned file)
    files_quarantined: int = 0
    pool_rebuilds: int = 0
    morsels_decoded_inline: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def merge_from(self, other: "ScanCounters") -> None:
        """Fold another counter set into this one (all fields are sums).

        This is the single-threaded merge point of the parallel scan:
        every worker increments a morsel-local ``ScanCounters`` and the
        consumer merges, so no ``+=`` ever races another thread.
        """
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))


@dataclasses.dataclass
class FragmentPlan:
    """Planning outcome for one manifest file."""
    file: str
    num_row_groups: int
    row_groups: List[int]       # surviving row-group indices
    pushdown: bool              # filter evaluated inside the reader
    pruned: bool                # whole file eliminated by stats
    delta_overlap: bool = False  # may hold upserted rows: full decode
    partition: Optional[str] = None   # hive partition key ("a=1/b=x")
    # eliminated from manifest metadata alone — footer never opened, so
    # num_row_groups is 0 (unknown) and byte accounting skips the file
    partition_pruned: bool = False

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ScanReport:
    """What ``explain()`` returns: counters + per-fragment decisions.

    When ``executed`` is False the counters describe the *plan* (row groups
    selected for scanning); page/row/bytes-decoded fields are zero because
    nothing was decoded.  When True, the scan ran and all counters reflect
    observed work.
    """
    counters: ScanCounters
    fragments: List[FragmentPlan]
    columns: List[str]
    filter: Optional[str]
    executed: bool

    def to_dict(self) -> dict:
        return {"counters": self.counters.to_dict(),
                "fragments": [f.to_dict() for f in self.fragments],
                "columns": list(self.columns),
                "filter": self.filter,
                "executed": self.executed}

    def __str__(self) -> str:
        c = self.counters
        lines = [
            f"ScanPlan  filter={self.filter or '<none>'}  "
            f"columns={len(self.columns)}",
            f"  files:      {c.files_scanned} scanned, "
            f"{c.files_skipped} pruned (of {c.files_total})",
            f"  row groups: {c.row_groups_scanned} scanned, "
            f"{c.row_groups_skipped} pruned (of {c.row_groups_total})",
            f"  bytes:      {c.bytes_selected} selected "
            f"of {c.bytes_total} stored",
        ]
        if c.partitions_total:
            lines.append(
                f"  partitions: {c.partitions_scanned} scanned, "
                f"{c.partitions_pruned} pruned from manifest metadata "
                f"(of {c.partitions_total})")
            lines.extend(self._partition_tree())
        if c.delta_files:
            d = (f"  deltas:     {c.delta_files} files "
                 f"({c.delta_upsert_rows} upsert rows, "
                 f"{c.delta_tombstone_rows} tombstoned ids)")
            if self.executed:
                d += (f"; {c.delta_rows_applied} applied, "
                      f"{c.rows_shadowed} rows dropped")
            lines.append(d)
        if c.groups_answered_by_stats or c.bytes_skipped_agg:
            lines.append(
                f"  aggregate:  {c.groups_answered_by_stats} row groups "
                f"answered from footer stats, {c.bytes_skipped_agg} stored "
                f"bytes never decoded")
        if c.files_quarantined:
            lines.append(
                f"  integrity:  {c.files_quarantined} corrupt delta "
                f"file(s) QUARANTINED (serving base + surviving deltas)")
        if c.pool_rebuilds or c.morsels_decoded_inline:
            lines.append(
                f"  degraded:   {c.pool_rebuilds} pool rebuild(s), "
                f"{c.morsels_decoded_inline} morsel(s) decoded inline")
        if self.executed:
            lines.append(
                f"  executed:   {c.pages_scanned} pages decoded "
                f"({c.pages_skipped} pruned), {c.rows_scanned} rows scanned, "
                f"{c.rows_matched} matched, {c.bytes_decoded} bytes decoded")
            if c.rows_skipped_late or c.bytes_saved_late:
                lines.append(
                    f"  late mat.:  {c.rows_skipped_late} payload rows "
                    f"skipped, {c.bytes_saved_late} value bytes kept out "
                    f"of result batches")
            if c.two_phase_pages_batched or c.two_phase_pages_single:
                lines.append(
                    f"  two-phase:  {c.two_phase_pages_batched} column pages "
                    f"decoded in row-group batches, "
                    f"{c.two_phase_pages_single} one at a time")
        else:
            lines.append("  (planned only — pass execute=True for decode "
                         "counters)")
        return "\n".join(lines)

    _TREE_MAX = 12  # partition-tree lines rendered before eliding

    def _partition_tree(self) -> List[str]:
        """One line per partition: files scanned / pruned, pruning source."""
        parts: Dict[str, List[FragmentPlan]] = {}
        for f in self.fragments:
            if f.partition is not None:
                parts.setdefault(f.partition, []).append(f)
        out = []
        for key in sorted(parts):
            fs = parts[key]
            if all(f.partition_pruned for f in fs):
                verdict = "pruned (manifest, 0 footers opened)"
            elif not any(f.row_groups for f in fs):
                verdict = "pruned (footer stats)"
            else:
                scanned = sum(1 for f in fs if f.row_groups)
                verdict = f"{scanned}/{len(fs)} files scanned"
            out.append(f"    {key}/  {verdict}")
            if len(out) == self._TREE_MAX and len(parts) > self._TREE_MAX:
                out.append(f"    … and {len(parts) - self._TREE_MAX} "
                           f"more partitions")
                break
        return out


class DeltaOverlay:
    """Resolved merge-on-read state of a delta chain, for one scan snapshot.

    Built once per scan from the manifest's delta entries, **in commit
    order**: for every id touched by the chain, the last delta wins —

      - final state *upsert*  → the id is in ``upsert_ids`` and its
        replacement row (aligned to the scan's read schema) is in
        ``upserts``;
      - final state *tombstone* → the id is in ``dead_ids``.

    ``apply`` overlays a decoded base-fragment table: upserted rows are
    substituted in place (row order preserved), tombstoned rows dropped.
    Upserts only take effect where their base row is scanned, which is what
    makes overlaying a *subset* of base files (compaction's merge set)
    correct: rows of untouched files stay untouched.

    ``on_corruption="quarantine"`` drops a delta file that fails
    verification (typed :class:`~repro.core.integrity.IntegrityError` on
    open or read) from the overlay instead of raising: the scan serves
    base + surviving deltas, a warning names the file, and
    ``self.quarantined`` records ``(name, error)`` pairs for the scan
    counters.  The default ``"raise"`` propagates — corruption is never
    absorbed silently either way.
    """

    def __init__(self, entries: Sequence[DeltaEntry],
                 reader_of: Callable[[str], TPQReader],
                 read_schema: Schema, on_corruption: str = "raise"):
        if on_corruption not in ("raise", "quarantine"):
            raise ValueError(f"unknown on_corruption {on_corruption!r} "
                             "(expected 'raise' or 'quarantine')")
        self.entries = list(entries)
        self.quarantined: List[Tuple[str, str]] = []
        self.upsert_rows_total = 0     # rows staged across all upsert files
        self.tombstone_rows_total = 0  # ids staged across all tombstone files
        ids_parts: List[np.ndarray] = []
        pos_parts: List[np.ndarray] = []
        row_parts: List[np.ndarray] = []
        up_tables: List[Table] = []
        up_offset = 0
        for pos, e in enumerate(self.entries):
            # every read of this entry happens before any overlay state
            # mutates, so quarantining a file that fails mid-read leaves
            # no half-applied residue from it
            try:
                rd = reader_of(e.name)
                if rd.file_kind != e.kind:
                    raise CorruptFooterError(
                        e.name, f"footer kind {rd.file_kind!r} does not "
                        f"match manifest kind {e.kind!r}")
                if e.kind == DELTA_TOMBSTONE:
                    t = None
                    ids = rd.read(columns=[ID_COLUMN]).column(ID_COLUMN) \
                            .values.astype(np.int64, copy=False)
                else:
                    cols = [n for n in read_schema.names if n in rd.schema]
                    t = rd.read(columns=cols).align_to_schema(read_schema)
                    ids = t.column(ID_COLUMN).values \
                           .astype(np.int64, copy=False)
            except IntegrityError as err:
                if on_corruption != "quarantine":
                    raise
                warnings.warn(
                    f"quarantining corrupt delta file {e.name}: {err} "
                    "(scan serves base + surviving deltas)",
                    RuntimeWarning, stacklevel=2)
                self.quarantined.append((e.name, str(err)))
                continue
            if t is None:
                self.tombstone_rows_total += len(ids)
                rows = np.full(len(ids), -1, np.int64)
            else:
                self.upsert_rows_total += len(ids)
                rows = up_offset + np.arange(len(ids), dtype=np.int64)
                up_tables.append(t)
                up_offset += len(ids)
            ids_parts.append(ids)
            pos_parts.append(np.full(len(ids), pos, np.int64))
            row_parts.append(rows)
        if ids_parts:
            ids = np.concatenate(ids_parts)
            pos = np.concatenate(pos_parts)
            rows = np.concatenate(row_parts)
            order = np.lexsort((pos, ids))   # by id, then commit position
            ids, rows = ids[order], rows[order]
            last = np.ones(len(ids), bool)   # last occurrence per id wins
            last[:-1] = ids[1:] != ids[:-1]
            self.shadow_ids = ids[last]      # sorted, unique
            win_rows = rows[last]
        else:
            self.shadow_ids = np.empty(0, np.int64)
            win_rows = np.empty(0, np.int64)
        live = win_rows >= 0
        self.upsert_ids = self.shadow_ids[live]   # sorted
        self.dead_ids = self.shadow_ids[~live]    # sorted
        if len(self.upsert_ids):
            all_up = (up_tables[0] if len(up_tables) == 1
                      else concat_tables(up_tables).align_to_schema(read_schema))
            self.upserts: Optional[Table] = all_up.take(win_rows[live])
        else:
            self.upserts = None

    @property
    def has_work(self) -> bool:
        return len(self.shadow_ids) > 0

    @staticmethod
    def _member_mask(sorted_arr: np.ndarray, ids: np.ndarray) -> np.ndarray:
        if not len(sorted_arr) or not len(ids):
            return np.zeros(len(ids), bool)
        p = np.clip(np.searchsorted(sorted_arr, ids), 0, len(sorted_arr) - 1)
        return sorted_arr[p] == ids

    def upsert_pos(self, ids: np.ndarray) -> np.ndarray:
        """Per id: row index into ``upserts``, or -1 if not upserted."""
        out = np.full(len(ids), -1, np.int64)
        if len(self.upsert_ids) and len(ids):
            p = np.clip(np.searchsorted(self.upsert_ids, ids), 0,
                        len(self.upsert_ids) - 1)
            hit = self.upsert_ids[p] == ids
            out[hit] = p[hit]
        return out

    def file_overlaps_upserts(self, rd: TPQReader) -> bool:
        """Can this base file contain a row replaced by a live upsert?

        Exact against the file's id [min, max] (ids are unique across base
        files, so range containment of any upsert id is the right test);
        conservative True when the stats are missing.
        """
        if not len(self.upsert_ids):
            return False
        st = rd.file_stats().get(ID_COLUMN)
        if st is None or st.min is None:
            return True
        lo = np.searchsorted(self.upsert_ids, st.min, "left")
        hi = np.searchsorted(self.upsert_ids, st.max, "right")
        return bool(hi > lo)

    def apply(self, t: Table, counters: ScanCounters) -> Table:
        """Overlay one decoded base table: substitute upserts, drop dead."""
        ids = t.column(ID_COLUMN).values
        up = self.upsert_pos(ids)
        upd = up >= 0
        if upd.any():
            n = t.num_rows
            need = up[upd]  # only the upsert rows this batch references
            sel = np.arange(n, dtype=np.int64)
            sel[upd] = n + np.arange(len(need), dtype=np.int64)
            t = concat_tables([t, self.upserts.take(need)]).take(sel)
            counters.delta_rows_applied += int(len(need))
        dead = self._member_mask(self.dead_ids, ids)
        if dead.any():
            counters.rows_shadowed += int(dead.sum())
            t = t.filter_mask(~dead)
        return t


class ScanPlan:
    """Plan + execute a pruned, projected scan over a set of TPQ files.

    Parameters
    ----------
    files:       manifest file names, in scan order.
    reader_of:   ``name -> TPQReader`` (the store injects its footer cache).
    schema:      unified dataset schema; files may each hold a subset.
    columns:     output column names (already resolved), None = all.
    filter_expr: AND-combined predicate, or None.
    cfg:         duck-typed config — ``use_threads`` / ``num_threads`` /
                 ``fragment_readahead`` (both ``LoadConfig`` and
                 ``NormalizeConfig`` qualify).
    prune:       set False to disable all stats pruning (oracle/testing).
    deltas:      merge-on-read chain (manifest ``DeltaEntry`` list, commit
                 order) to overlay on the base files; empty = plain scan.
    overlay:     an already-resolved :class:`DeltaOverlay` for ``deltas``
                 to reuse (compaction resolves the chain once for
                 affected-file selection and passes it through); its read
                 schema must cover this plan's read set.
    restrict:    optional ``{file: row-group indices}`` cap — planning
                 intersects its stats-selected row groups with this map
                 (files absent from the map scan nothing).  The aggregate
                 layer uses it to decode only the *partial* row groups
                 that footer statistics could not answer.
    partitioning: the dataset's :class:`~repro.core.partition.Partitioning`
                 (or None).  Enables manifest-level partition pruning —
                 whole partitions eliminated *before any footer is
                 opened* — and, when several partitions survive, the
                 order-preserving id merge that keeps the output
                 byte-identical to an unpartitioned scan (each create
                 splits one ascending id range across partitions, so
                 partition streams must be re-interleaved by id).
                 Partition pruning is disabled while the chain holds
                 upsert deltas: an upsert carries *new* non-partition
                 values the recorded partition values cannot bound
                 (tombstones are fine — dropping commutes with
                 filtering).  Compaction folds the chain and restores it.
    ordered:     set False when the caller does not need globally
                 id-ordered output (aggregation): skips the merge and the
                 implied id-column read.
    """

    def __init__(self, files: Sequence[str],
                 reader_of: Callable[[str], TPQReader],
                 schema: Schema,
                 columns: Optional[Sequence[str]] = None,
                 filter_expr: Optional[Expr] = None,
                 cfg=None, prune: bool = True,
                 deltas: Sequence[DeltaEntry] = (),
                 overlay: Optional[DeltaOverlay] = None,
                 restrict: Optional[Dict[str, Sequence[int]]] = None,
                 partitioning=None, ordered: bool = True):
        self._files = list(files)
        self._reader_of = reader_of
        self._schema = schema
        self._expr = filter_expr
        self._prune = prune
        self._deltas = list(deltas)
        self._use_threads = bool(getattr(cfg, "use_threads", True))
        self._readahead = int(getattr(cfg, "fragment_readahead", 4))
        self._num_threads = resolve_num_threads(cfg)
        self._executor = getattr(cfg, "executor", None)
        if self._executor not in (None, "thread", "process"):
            raise ValueError(f"unknown scan executor {self._executor!r} "
                             "(expected 'thread', 'process' or None)")
        self._verify = getattr(cfg, "verify", None)
        if self._verify not in (None, "page", "footer", "off"):
            raise ValueError(f"unknown verify mode {self._verify!r} "
                             "(expected 'page', 'footer' or 'off')")
        self._on_corruption = getattr(cfg, "on_corruption", "raise")
        self._budget = getattr(cfg, "morsel_budget", None)
        # num_threads=None is "auto": size from cpu_count but only engage
        # the pool when the decode work can actually overlap (see
        # _parallel_profitable); an explicit thread count always engages.
        self._threads_auto = getattr(cfg, "num_threads", None) is None
        self._restrict = ({fn: set(rgs) for fn, rgs in restrict.items()}
                          if restrict is not None else None)
        out_names = list(columns) if columns is not None else schema.names
        self._out_schema = schema.select(out_names)
        self._filter_cols = [c for c in dict.fromkeys(
            filter_expr.columns() if filter_expr is not None else [])]
        read_names = out_names + [c for c in self._filter_cols
                                  if c in schema and c not in out_names]
        if self._deltas and ID_COLUMN not in read_names:
            read_names.append(ID_COLUMN)  # overlay needs row identity
        self._partitioning = partitioning
        # the ordered merge engages only when >1 partition stream can
        # actually appear in this plan (and row identity is available)
        self._merge_parts = False
        if partitioning is not None and ordered and ID_COLUMN in schema:
            keys = {partitioning.key_of(f) for f in self._files}
            self._merge_parts = len(keys) > 1
        if self._merge_parts and ID_COLUMN not in read_names:
            read_names.append(ID_COLUMN)  # merge needs row identity
        # what _finish_table emits: output columns, plus id while an
        # ordered merge still needs it (stripped again after the merge)
        self._emit_names = list(out_names)
        if self._merge_parts and ID_COLUMN not in out_names:
            self._emit_names.append(ID_COLUMN)
        self._read_schema = schema.select(read_names)
        self._fragments: Optional[List[FragmentPlan]] = None
        self._plan_counters: Optional[ScanCounters] = None
        self._byte_totals: Optional[tuple] = None
        self._overlay_obj: Optional[DeltaOverlay] = overlay
        self.last_counters: Optional[ScanCounters] = None

    def _overlay(self) -> Optional[DeltaOverlay]:
        if not self._deltas:
            return None
        if self._overlay_obj is None:
            self._overlay_obj = DeltaOverlay(self._deltas, self._reader_of,
                                             self._read_schema,
                                             on_corruption=self._on_corruption)
        return self._overlay_obj

    # ------------------------------------------------------------------ plan
    def fragments(self) -> List[FragmentPlan]:
        self._build()
        return list(self._fragments)

    def _build(self) -> None:
        """Planning: footer-only over the base files.

        When a delta chain is overlaid, the (small, by construction) delta
        files themselves are read here to resolve the chain — base-file data
        pages are still never touched during planning.
        """
        if self._fragments is not None:
            return
        with span("query.plan"):
            self._plan_fragments()

    def _plan_fragments(self) -> None:
        ov = self._overlay()
        c = ScanCounters()
        c.delta_files = len(self._deltas)
        if ov is not None:
            c.delta_upsert_rows = ov.upsert_rows_total
            c.delta_tombstone_rows = ov.tombstone_rows_total
            c.files_quarantined = len(ov.quarantined)
        frags: List[FragmentPlan] = []
        # manifest-level partition pruning: sound only when no upsert delta
        # is pending (an upsert's new values are unbounded by the recorded
        # partition values for non-partition columns; tombstones commute
        # with filtering).  A pruned partition opens zero footers.
        part = self._partitioning
        may_scan = None
        if part is not None and self._prune and self._expr is not None \
                and (ov is None or not len(ov.upsert_ids)):
            may_scan = part.pruner(self._expr)
        for fn in self._files:
            pk = part.key_of(fn) if part is not None else None
            if may_scan is not None and pk is not None \
                    and not may_scan(fn):
                c.files_total += 1
                c.files_skipped += 1
                frags.append(FragmentPlan(fn, 0, [], False, pruned=True,
                                          partition=pk,
                                          partition_pruned=True))
                continue
            rd = self._reader_of(fn)
            n = rd.num_row_groups
            have = set(rd.schema.names)
            c.files_total += 1
            c.row_groups_total += n
            # A fragment that may hold upserted rows cannot be pruned or
            # pushed down from its stored statistics (they describe stale
            # values): decode it fully and filter after the overlay.
            overlap = ov is not None and ov.file_overlaps_upserts(rd)
            # pushdown is only sound when the file has every filter column;
            # otherwise missing columns align to null *after* decode and the
            # residual filter runs there (null semantics differ per Expr).
            # prune=False forces the residual path: full decode, no stats.
            pushdown = (not overlap and self._prune
                        and self._expr is not None
                        and all(col in have for col in self._filter_cols))
            selected = list(range(n))
            if pushdown:
                if not self._expr.prune(rd.file_stats()):
                    selected = []          # fragment pruned outright
                else:
                    selected = [i for i in range(n)
                                if self._expr.prune(rd.row_group_stats(i))]
            if self._restrict is not None:
                allowed = self._restrict.get(fn, set())
                selected = [i for i in selected if i in allowed]
            c.row_groups_skipped += n - len(selected)
            if selected:
                c.files_scanned += 1
            else:
                c.files_skipped += 1
            frags.append(FragmentPlan(fn, n, selected, pushdown,
                                      pruned=not selected,
                                      delta_overlap=overlap,
                                      partition=pk))
        if part is not None:
            by_key: Dict[str, List[FragmentPlan]] = {}
            for f in frags:
                if f.partition is not None:
                    by_key.setdefault(f.partition, []).append(f)
            c.partitions_total = len(by_key)
            c.partitions_pruned = sum(
                1 for fs in by_key.values()
                if all(f.partition_pruned for f in fs))
            c.partitions_scanned = sum(
                1 for fs in by_key.values()
                if any(f.row_groups for f in fs))
        self._fragments, self._plan_counters = frags, c

    # --------------------------------------------------------------- execute
    def execute(self, batch_size: Optional[int] = None,
                counters: Optional[ScanCounters] = None,
                map_fn: Optional[Callable[[Table], Any]] = None
                ) -> Generator[Any, None, None]:
        """Yield result tables, decoding morsels on the shared worker pool.

        With ``num_threads > 1`` (the default is ``os.cpu_count()``) the
        surviving row groups are split into morsels and decoded in
        parallel; output order and content are byte-identical to the
        serial scan (order-preserving merge).  Counters accumulate into
        ``counters`` (or a fresh copy of the plan counters, exposed as
        ``self.last_counters``) — per-morsel counters are merged in the
        consumer, never incremented across threads.

        ``map_fn`` (exclusive with ``batch_size``) transforms each result
        table *inside the decoding worker* on the parallel path, so
        CPU-bound per-batch work (e.g. the Query layer's partial
        group-by aggregation) overlaps with decode; mapped values are
        yielded in plan order.  Closing the generator early (e.g. a
        ``limit`` that is already satisfied) cancels not-yet-started
        morsels, so an abandoned scan stops submitting work.
        """
        assert not (batch_size is not None and map_fn is not None), \
            "batch_size and map_fn are mutually exclusive"
        self._build()
        if counters is None:
            counters = dataclasses.replace(self._plan_counters)
        self.last_counters = counters

        morsels = self._morsels()
        # the ordered partition merge applies to table output only; mapped
        # values (grouped partial aggregation) are order-insensitive and
        # consumed in (deterministic) submission order
        merge = self._merge_parts and map_fn is None \
            and len({m[0].partition for m in morsels}) > 1
        tagged = self._execute_stream(morsels, counters, map_fn)
        if merge:
            stream = self._merge_streams(tagged, morsels)
        else:
            def flat() -> Generator[Any, None, None]:
                for _frag, vals in tagged:
                    yield from vals
            stream = flat()
        if map_fn is None and self._emit_names != self._out_schema.names:
            out_names = self._out_schema.names
            inner = stream

            def strip() -> Generator[Table, None, None]:
                for t in inner:
                    yield t.select(out_names)
            stream = strip()
        if batch_size is None:
            yield from stream
        else:
            yield from rechunk(stream, batch_size)

    def _execute_stream(self, morsels, counters: ScanCounters,
                        map_fn: Optional[Callable[[Table], Any]] = None
                        ) -> Generator[Any, None, None]:
        """Run the chosen executor; yields ``(frag, [values])`` per morsel
        in submission order (empty morsels included, so a merge consumer
        can account stream progress exactly)."""
        mode = self._choose_executor(morsels)
        if mode == "process":
            return self._execute_process(morsels, counters, map_fn)
        if mode == "thread":
            return self._execute_parallel(morsels, counters, map_fn)

        def pieces() -> Generator[Any, None, None]:
            for frag, rgs in morsels:
                self._budget_acquire()
                try:
                    vals = [t if map_fn is None else map_fn(t)
                            for t in self._fragment_tables(frag, counters,
                                                           row_groups=rgs)]
                finally:
                    self._budget_release()
                yield frag, vals
        return (prefetch(pieces(), self._readahead)
                if self._use_threads else pieces())

    def _budget_acquire(self) -> None:
        if self._budget is not None:
            self._budget.acquire()

    def _budget_try_acquire(self, block: bool) -> bool:
        """Charge one morsel permit; blocking only allowed when the caller
        holds no other permit (the deadlock-freedom discipline)."""
        if self._budget is None:
            return True
        if block:
            self._budget.acquire()
            return True
        return self._budget.try_acquire()

    def _budget_release(self) -> None:
        if self._budget is not None:
            self._budget.release()

    def _merge_streams(self, tagged, morsels
                       ) -> Generator[Table, None, None]:
        """K-way watermark merge: re-interleave partition streams by id.

        Every partition's files (manifest order) form an ascending id
        stream — one ``create`` splits its ascending id range across
        partitions, so reconstructing the unpartitioned row order is
        exactly a merge of those streams.  Tables buffer per stream; rows
        up to the *watermark* (the smallest last-buffered id among
        streams that may still produce rows) are provably complete and
        are emitted sorted.  Round-robin morsel submission (see
        :meth:`_morsels`) keeps every stream advancing together, so
        buffers stay ~morsel-sized.
        """
        remaining: Dict[Optional[str], int] = {}
        for frag, _rgs in morsels:
            remaining[frag.partition] = remaining.get(frag.partition, 0) + 1
        bufs: Dict[Optional[str], List[Table]] = \
            {k: [] for k in remaining}

        def flush(final: bool) -> Optional[Table]:
            if final:
                wm = None
            else:
                wm_ids = []
                for k, rem in remaining.items():
                    if not bufs[k]:
                        if rem > 0:
                            return None  # stream not bounded yet
                        continue
                    last = bufs[k][-1].column(ID_COLUMN).values
                    if rem > 0:
                        wm_ids.append(int(last[-1]))
                if not wm_ids:
                    wm = None  # every live stream exhausted: emit all
                else:
                    wm = min(wm_ids)
            parts: List[Table] = []
            for k in bufs:
                keep: List[Table] = []
                for t in bufs[k]:
                    ids = t.column(ID_COLUMN).values
                    if wm is None or ids[-1] <= wm:
                        parts.append(t)
                    else:
                        cut = int(np.searchsorted(ids, wm, "right"))
                        if cut:
                            parts.append(t.slice(0, cut))
                            keep.append(t.slice(cut, t.num_rows))
                        else:
                            keep.append(t)
                bufs[k] = keep
            if not parts:
                return None
            merged = concat_tables(parts)
            order = np.argsort(
                merged.column(ID_COLUMN).values, kind="stable")
            return merged.take(order)

        for frag, tables in tagged:
            key = frag.partition
            bufs[key].extend(t for t in tables if t.num_rows)
            remaining[key] -= 1
            out = flush(final=False)
            if out is not None and out.num_rows:
                yield out
        out = flush(final=True)
        if out is not None and out.num_rows:
            yield out

    # ------------------------------------------------------- morsel dispatch
    def _morsels(self) -> List[Tuple[FragmentPlan, List[int]]]:
        """Split surviving row groups into scan-ordered morsels.

        A morsel is a contiguous run of row groups within one fragment,
        capped at ``MORSEL_ROWS`` rows — the unit of work the shared pool
        schedules.  Never crosses a fragment boundary and never splits a
        row group.
        """
        out: List[Tuple[FragmentPlan, List[int]]] = []
        for frag in self._fragments:
            if not frag.row_groups:
                continue
            rd = self._reader_of(frag.file)
            run: List[int] = []
            rows = 0
            for i in frag.row_groups:
                run.append(i)
                rows += rd.row_group_num_rows(i)
                if rows >= MORSEL_ROWS:
                    out.append((frag, run))
                    run, rows = [], 0
            if run:
                out.append((frag, run))
        if self._merge_parts:
            # round-robin across partition streams: every stream advances
            # together, so the ordered merge's buffers stay morsel-sized
            # instead of holding whole partitions
            streams: Dict[Optional[str], List] = {}
            for m in out:
                streams.setdefault(m[0].partition, []).append(m)
            if len(streams) > 1:
                out = [m for tup in itertools.zip_longest(*streams.values())
                       for m in tup if m is not None]
        return out

    def _choose_executor(self, morsels) -> str:
        """Pick the execution strategy: ``serial`` / ``thread`` / ``process``.

        An explicit ``LoadConfig.executor`` wins.  AUTO consults the
        footer's codec split (:func:`page_codec_split`): codec-compressed
        read sets go to the shared *thread* pool (zlib &c release the GIL,
        so decode genuinely overlaps); GIL-bound read sets (raw or
        entropy-coded pages, which decode in pure numpy under the GIL and
        would convoy on threads) go to the *process* pool when the scan is
        big enough to amortize worker spawn (``PROCESS_MIN_ROWS``).  Either
        way the output stays byte-identical — only wall-clock changes.

        Under the ``jax`` decode backend this process holds the device, and
        a worker process could neither take it nor decode there, so AUTO
        never picks ``"process"`` and an explicit ``"process"`` raises.
        """
        on_device = active_backend().name == "jax"
        if self._executor == "process" and on_device:
            raise RuntimeError(
                "executor='process' cannot run under the jax decode backend: "
                "the device belongs to this process, and scan workers would "
                "decode on the host; use executor='thread' or None")
        if self._num_threads <= 1 or len(morsels) <= 1:
            return "serial"
        if self._executor is not None:
            return self._executor
        if self._parallel_profitable():
            return "thread"
        rows = 0
        for frag, rgs in morsels:
            rd = self._reader_of(frag.file)
            rows += sum(rd.row_group_num_rows(i) for i in rgs)
        if rows >= PROCESS_MIN_ROWS and not on_device:
            return "process"
        return "serial" if self._threads_auto else "thread"

    def _parallel_profitable(self) -> bool:
        """Footer-only heuristic for auto mode: will threads overlap?

        CPython morsel workers only run concurrently while the GIL is
        released, which on the decode path means codec decompression
        (zlib/&c release it; raw and entropy-coded buffers decode under
        the GIL, where extra threads just convoy).  Sample the first
        surviving row group's read set: go parallel when at least half of
        its stored bytes are codec-compressed.
        """
        for frag in self._fragments:
            if not frag.row_groups:
                continue
            rd = self._reader_of(frag.file)
            have = set(rd.schema.names)
            rg = rd.row_groups[frag.row_groups[0]]
            stored = compressed = 0
            for name in self._read_schema.names:
                if name not in have:
                    continue
                for p in rg["columns"][name]["pages"]:
                    s, c = page_codec_split(p)
                    stored += s
                    compressed += c
            return stored > 0 and compressed * 2 >= stored
        return False

    def _execute_parallel(self, morsels, counters: ScanCounters,
                          map_fn: Optional[Callable[[Table], Any]] = None
                          ) -> Generator[Any, None, None]:
        """Decode morsels on the shared pool; order-preserving bounded merge.

        Up to ``num_threads + fragment_readahead`` morsels are in flight;
        completed results are consumed strictly in submission (= plan)
        order, so the output stream is identical to the serial scan.  A
        worker exception propagates to the caller with its original
        traceback (``Future.result`` re-raises), and the ``finally`` block
        cancels not-yet-started morsels so an abandoned scan leaves no
        queued work behind.  ``map_fn`` (if any) runs inside the worker,
        right after each table is decoded.
        """
        pool = scan_pool(self._num_threads)
        max_inflight = self._num_threads + max(self._readahead, 1)

        def run_morsel(frag: FragmentPlan, rgs: List[int]):
            local = ScanCounters()  # morsel-local: no cross-thread `+=`
            tables = [t if map_fn is None else map_fn(t)
                      for t in self._fragment_tables(frag, local,
                                                     row_groups=rgs)]
            return tables, local

        it = iter(morsels)
        inflight: "collections.deque" = collections.deque()

        def refill() -> None:
            # charge one budget permit per submitted morsel; block for a
            # permit only while holding none (an empty window), otherwise
            # try-acquire and let this scan drain what it already holds —
            # the discipline that keeps a shared budget deadlock-free
            while len(inflight) < max_inflight:
                if not self._budget_try_acquire(block=not inflight):
                    return
                nxt = next(it, None)
                if nxt is None:
                    self._budget_release()
                    return
                inflight.append((pool.submit(run_morsel, *nxt), nxt[0]))

        try:
            while True:
                refill()
                if not inflight:
                    break  # morsels exhausted
                fut, frag = inflight.popleft()
                try:
                    tables, local = fut.result()
                finally:
                    self._budget_release()
                counters.merge_from(local)  # single-threaded merge point
                yield frag, tables
        finally:
            for fut, _ in inflight:
                fut.cancel()
                self._budget_release()

    def _execute_process(self, morsels, counters: ScanCounters,
                         map_fn: Optional[Callable[[Table], Any]] = None
                         ) -> Generator[Any, None, None]:
        """Decode morsels in worker *processes*; finish + merge in the parent.

        Workers run only the decode half (:func:`_process_morsel`); the
        parent applies the finish half (:meth:`_finish_table`) — overlay
        substitution, residual filter, ``map_fn`` — and merges counters
        single-threaded, so results are byte-identical to the serial and
        thread paths, order included.  Three failure modes are handled:

        - a racing compaction GC'd a base file after planning: the worker's
          open raises ``FileNotFoundError`` and the parent decodes that
          morsel inline off its still-cached mapping (same bytes — data
          files are immutable);
        - the pool itself breaks mid-scan (``BrokenProcessPool`` — e.g. a
          spawn child of a ``__main__``-guard-less user script dies
          bootstrapping, or a worker is OOM-killed): morsels whose
          futures died decode inline, the pool is **rebuilt once**
          (:func:`process_scan_pool` swaps out the broken one) and the
          remaining morsels go to the fresh workers; if the rebuilt pool
          breaks too, the scan degrades to inline decode for the rest
          with a one-line warning.  ``counters.pool_rebuilds`` /
          ``morsels_decoded_inline`` record the degradation — never a
          hang, never an unexplained slowdown;
        - early termination (``limit`` satisfied, generator closed): the
          ``finally`` cancels queued morsels and *drains* already-running
          ones through :func:`shm.discard`, so no worker is orphaned
          mid-result and no shared-memory segment outlives the scan
          (``shm.live_segments()`` stays empty — regression-tested).
        """
        max_inflight = self._num_threads + max(self._readahead, 1)
        state = {"broken": False, "rebuilt": False,
                 "pool": process_scan_pool(self._num_threads)}

        def rebuild_once() -> bool:
            """Swap in a fresh pool after a worker crash — once per scan."""
            if state["rebuilt"]:
                return False
            state["rebuilt"] = True
            counters.pool_rebuilds += 1
            # process_scan_pool replaces a broken cached pool outright
            state["pool"] = process_scan_pool(self._num_threads)
            return True

        def submit(frag: FragmentPlan, rgs: List[int]):
            if not state["broken"]:
                rd = self._reader_of(frag.file)
                have = set(rd.schema.names)
                cols = tuple(n for n in self._read_schema.names if n in have)
                expr = self._expr if frag.pushdown else None
                for _attempt in range(2):
                    sub_pool = state["pool"]
                    try:
                        return (sub_pool.submit(
                            _process_morsel, rd.path, tuple(rgs),
                            cols, expr, self._verify), frag, rgs, sub_pool)
                    except BrokenExecutor:
                        if not rebuild_once():
                            break
                _warn_broken_pool(state)
            return (None, frag, rgs, None)  # degraded: inline on arrival

        it = iter(morsels)
        inflight: "collections.deque" = collections.deque()

        def refill() -> None:
            # same budget discipline as the thread path: block for a
            # permit only with an empty window, otherwise try-acquire
            while len(inflight) < max_inflight:
                if not self._budget_try_acquire(block=not inflight):
                    return
                nxt = next(it, None)
                if nxt is None:
                    self._budget_release()
                    return
                inflight.append(submit(*nxt))

        try:
            while True:
                refill()
                if not inflight:
                    break  # morsels exhausted
                fut, frag, rgs, sub_pool = inflight.popleft()
                try:
                    try:
                        if fut is None:
                            raise BrokenExecutor
                        tables, local = shm.unpack(fut.result())
                    except FileNotFoundError:
                        local = ScanCounters()
                        tables = list(self._decode_tables(frag, local, rgs))
                        local.morsels_decoded_inline += 1
                    except BrokenExecutor:
                        # this morsel's future died with its pool: decode it
                        # inline, and give the *remaining* morsels a fresh
                        # pool (once per scan) before writing the scan off.
                        # A corpse future from an already-replaced pool is
                        # expected fallout of the rebuild, not a second
                        # crash.
                        if fut is not None and sub_pool is state["pool"] \
                                and not rebuild_once() and not state["broken"]:
                            _warn_broken_pool(state)
                        local = ScanCounters()
                        tables = list(self._decode_tables(frag, local, rgs))
                        local.morsels_decoded_inline += 1
                finally:
                    self._budget_release()
                counters.merge_from(local)  # single-threaded merge point
                done = []
                for t in tables:
                    with span("scan.morsel"):
                        t = self._finish_table(t, frag, counters)
                    if t is not None:
                        done.append(t if map_fn is None else map_fn(t))
                yield frag, done
        finally:
            for fut, _, _, _ in inflight:
                self._budget_release()
                if fut is not None and not fut.cancel():
                    try:
                        shm.discard(fut.result())
                    except Exception:
                        pass

    def _decode_tables(self, frag: FragmentPlan, counters: ScanCounters,
                       row_groups: Optional[List[int]] = None
                       ) -> Generator[Table, None, None]:
        """The decode half: prune, pushdown-filter and decode one morsel.

        Worker-safe given any reader handle — this is exactly what
        :func:`_process_morsel` runs in a worker process.
        """
        rd = self._reader_of(frag.file)
        have = set(rd.schema.names)
        cols_here = [n for n in self._read_schema.names if n in have]
        pushdown = self._expr if frag.pushdown else None
        rgs = frag.row_groups if row_groups is None else row_groups
        return rd.iter_row_group_tables(cols_here, pushdown, row_groups=rgs,
                                        counters=counters,
                                        verify=self._verify)

    def _finish_table(self, t: Table, frag: FragmentPlan,
                      counters: ScanCounters) -> Optional[Table]:
        """The finish half: align, overlay, residual-filter, project.

        Holds all the state that cannot cross a process boundary (the
        resolved overlay, the residual ``Expr`` against merged values).
        """
        t = t.align_to_schema(self._read_schema)
        ov = self._overlay()
        if ov is not None and ov.has_work:
            # merge-on-read: substitute upserts in place, drop dead rows
            # *before* the residual filter so it sees merged values
            t = ov.apply(t, counters)
        if self._expr is not None and not frag.pushdown:
            with span("query.compute"):  # the residual filter
                mask = self._expr.evaluate(t)
                if not mask.all():
                    t = t.filter_mask(mask)
        if t.num_rows:
            counters.rows_matched += t.num_rows
            # _emit_names keeps the id column while an ordered partition
            # merge still needs it; execute() strips it after merging
            return t.select(self._emit_names)
        return None

    def _fragment_tables(self, frag: FragmentPlan, counters: ScanCounters,
                         row_groups: Optional[List[int]] = None
                         ) -> Generator[Table, None, None]:
        tables = self._decode_tables(frag, counters, row_groups)
        while True:
            # one span per decoded table, closed before the yield
            with span("scan.morsel"):
                t = next(tables, None)
                if t is None:
                    return
                t = self._finish_table(t, frag, counters)
            if t is not None:
                yield t

    def _bytes_accounting(self) -> tuple:
        """(bytes_total, bytes_selected) — footer walk, lazy: explain() only.

        Plain reads skip this; it touches every page dict of every file.
        """
        if self._byte_totals is None:
            self._build()
            total = selected = 0
            for frag in self._fragments:
                if frag.partition_pruned:
                    continue  # footer never opened: bytes unknown
                rd = self._reader_of(frag.file)
                have = set(rd.schema.names)
                cols_here = [x for x in self._read_schema.names if x in have]
                total += sum(rd.read_row_group_bytes(i)
                             for i in range(frag.num_row_groups))
                selected += sum(rd.read_row_group_bytes(i, cols_here)
                                for i in frag.row_groups)
            self._byte_totals = (total, selected)
        return self._byte_totals

    # --------------------------------------------------------------- explain
    def explain(self, execute: bool = False) -> ScanReport:
        """Report pruning decisions; optionally run the scan for decode stats."""
        self._build()
        c = dataclasses.replace(self._plan_counters)
        c.bytes_total, c.bytes_selected = self._bytes_accounting()
        if execute:
            for _ in self.execute(counters=c):
                pass
        else:
            c.row_groups_scanned = c.row_groups_total - c.row_groups_skipped
        return ScanReport(counters=c, fragments=list(self._fragments),
                          columns=self._out_schema.names,
                          filter=repr(self._expr) if self._expr is not None
                          else None,
                          executed=execute)


# ---------------------------------------------------------------------------
# shared helpers (also used by the write paths in store.py)
# ---------------------------------------------------------------------------
def file_may_match(rd: TPQReader, expr: Expr) -> bool:
    """Fragment-level pruning check: can this file contain a matching row?

    Conservative (True = must read).  Used by ``update``/``delete`` to skip
    rewriting files that provably hold no affected rows.  Checks merged
    whole-file stats first (cheap reject), then per-row-group stats, which
    are strictly stronger: merging widens min/max ranges and drops blooms of
    mismatched sizes.
    """
    if not all(c in rd.schema for c in expr.columns()):
        return True
    if not expr.prune(rd.file_stats()):
        return False
    return any(expr.prune(rd.row_group_stats(i))
               for i in range(rd.num_row_groups))


def rechunk(stream: Iterable[Table], batch_size: int
            ) -> Generator[Table, None, None]:
    """Re-slice a table stream into exact ``batch_size``-row batches."""
    buf: List[Table] = []
    count = 0
    for t in stream:
        while t.num_rows:
            take = min(batch_size - count, t.num_rows)
            buf.append(t.slice(0, take))
            t = t.slice(take, t.num_rows)
            count += take
            if count == batch_size:
                yield concat_tables(buf)
                buf, count = [], 0
    if buf:
        yield concat_tables(buf)


def prefetch(gen: Iterable[Table], depth: int) -> Generator[Table, None, None]:
    """Background-thread readahead (LoadConfig.fragment_readahead).

    Failure semantics (regression-tested in ``tests/test_parallel_scan.py``):

    - a producer exception propagates to the consumer **with its original
      traceback** (the exception object is re-raised as captured, so the
      failing frame inside ``gen`` stays visible);
    - the worker can never be left blocked on a full queue: every ``put``
      polls a stop event, and the consumer's ``finally`` (normal exit,
      error, or an early ``close()`` of the generator) sets the event,
      drains the queue, and joins the thread.
    """
    q: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
    DONE = object()
    stop = threading.Event()

    def offer(item) -> bool:
        """Put, but give up promptly once the consumer is gone."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for item in gen:
                if not offer(item):
                    return
            offer(DONE)
        except BaseException as e:  # propagate WITH the worker traceback
            offer(e)

    th = threading.Thread(target=worker, name="tpq-prefetch", daemon=True)
    th.start()
    try:
        while True:
            item = q.get()
            if item is DONE:
                return
            if isinstance(item, BaseException):
                raise item  # __traceback__ captured in the worker survives
            yield item
    finally:
        stop.set()
        while True:  # drain so a blocked put wakes and sees the stop flag
            try:
                q.get_nowait()
            except queue.Empty:
                break
        th.join(timeout=5.0)
