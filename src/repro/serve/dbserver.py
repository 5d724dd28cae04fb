"""Concurrent query server over one ParquetDB dataset.

An asyncio TCP server speaking the length-prefixed JSON protocol of
:mod:`repro.serve.protocol`, exposing the full ``db.query()`` surface —
``where`` / ``select`` / ``group_by`` / ``order_by`` / ``limit`` /
aggregates — plus ``update`` / ``delete`` writes.  Three mechanisms make
it safe to point real traffic at:

**Admission control + backpressure.**  At most ``max_concurrent`` requests
execute at once; up to ``max_queue`` more wait.  Beyond that the server
*sheds*: an immediate ``503`` response with the current queue depth, never
an unbounded queue or an OOM.  Below the admission gate, every executing
query charges its decode work against one shared
:class:`~repro.core.scan.MorselBudget`, so even admitted queries cannot
stack unbounded in-flight morsels — concurrent scans throttle each other
cooperatively inside :class:`~repro.core.scan.ScanPlan`.

**Normalized-plan cache.**  Request specs are prepared once into unbound
:class:`~repro.core.query.Query` templates keyed by the raw spec; the
template's :meth:`~repro.core.query.Query.plan_key` canonicalizes the
fused expression tree (commuted conjuncts, shuffled ``isin`` values,
reordered projections all collapse to one key).

**Snapshot-consistent result cache.**  Each read pins the manifest
snapshot *first* (``Query`` binds the manifest, so concurrent commits
cannot shear a running query), then consults the result cache under
``(plan_key, generation)``.  Every response states the generation its rows
came from; a cached response is byte-identical to re-running the plan
against that generation.  MVCC commits bump the generation — in-process
commits additionally fire the
:func:`~repro.core.transactions.register_commit_listener` hook, which
eagerly drops the superseded generations' entries.

The module is importable without jax (the LM serving engine in
:mod:`repro.serve.engine` is untouched); ``python -m repro.serve.dbserver
--path DB --name DS`` runs a standalone server.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import hashlib
import json
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional, Tuple

from repro.compile_cache import enable_compile_cache
from repro.core import LoadConfig, MorselBudget, ParquetDB
from repro.core.query import Query
from repro.core.transactions import register_commit_listener
from repro.serve.cache import CachedPlan, PlanCache, ResultCache, ServerStats
from repro.serve.protocol import (MAX_FRAME, ProtocolError, encode_frame,
                                  expr_from_json, read_frame)

__all__ = ["DBServer", "main"]

# request fields that define a read plan (order-free: raw keys are built
# with sort_keys, so two dicts with the same fields share one raw key)
_PLAN_FIELDS = ("op", "where", "select", "group_by", "agg", "order_by",
                "limit", "offset", "distinct")


class DBServer:
    """Serve one dataset over TCP.  See the module docstring.

    ``port=0`` binds an ephemeral port; :meth:`start` runs the server on a
    background thread and returns the bound ``(host, port)`` — the pattern
    the tests and the benchmark driver use.  For a foreground server call
    :meth:`serve_forever` (or use the CLI).
    """

    def __init__(self, db: ParquetDB, host: str = "127.0.0.1",
                 port: int = 0, *, max_concurrent: int = 4,
                 max_queue: int = 16,
                 morsel_budget: Optional[int] = None,
                 num_threads: Optional[int] = None,
                 plan_cache_entries: int = 512,
                 result_cache_entries: int = 256,
                 result_cache_bytes: int = 64 << 20,
                 max_frame: int = MAX_FRAME):
        if max_concurrent < 1:
            raise ValueError("max_concurrent must be >= 1")
        self._db = db
        self._host, self._port = host, int(port)
        self.max_concurrent = int(max_concurrent)
        self.max_queue = int(max_queue)
        self._max_frame = int(max_frame)
        budget_permits = (morsel_budget if morsel_budget is not None
                          else 2 * self.max_concurrent)
        self.budget = MorselBudget(budget_permits)
        self._cfg = LoadConfig(num_threads=num_threads,
                               morsel_budget=self.budget)
        self.plan_cache = PlanCache(plan_cache_entries)
        self.result_cache = ResultCache(result_cache_entries,
                                        result_cache_bytes)
        self.stats = ServerStats()
        self._pending = 0            # admitted, not yet finished (loop-only)
        self._sem: Optional[asyncio.Semaphore] = None
        self._exec = ThreadPoolExecutor(max_workers=self.max_concurrent,
                                        thread_name_prefix="dbserve")
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._stop_evt: Optional[asyncio.Event] = None
        self._started = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._address: Optional[Tuple[str, int]] = None
        # eager invalidation on in-process commits; cross-process commits
        # are caught by the generation observed at snapshot-pin time
        self._unregister = register_commit_listener(
            db._dir.path, self._on_commit)

    # ------------------------------------------------------------ lifecycle
    @property
    def address(self) -> Tuple[str, int]:
        if self._address is None:
            raise RuntimeError("server not started")
        return self._address

    def start(self) -> Tuple[str, int]:
        """Run the server on a daemon thread; returns ``(host, port)``."""
        self._thread = threading.Thread(
            target=lambda: asyncio.run(self._amain()), daemon=True)
        self._thread.start()
        if not self._started.wait(timeout=10.0):
            raise RuntimeError("server failed to start within 10s")
        return self.address

    def stop(self) -> None:
        """Stop accepting, drain the executor, detach the commit listener."""
        if self._loop is not None and self._stop_evt is not None:
            try:
                self._loop.call_soon_threadsafe(self._stop_evt.set)
            except RuntimeError:
                pass  # loop already closed
        if self._thread is not None:
            self._thread.join(timeout=10.0)
        self._exec.shutdown(wait=False)
        self._unregister()

    def serve_forever(self) -> None:
        """Run in the foreground until interrupted (the CLI entrypoint)."""
        asyncio.run(self._amain())

    async def _amain(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._stop_evt = asyncio.Event()
        self._sem = asyncio.Semaphore(self.max_concurrent)
        server = await asyncio.start_server(
            self._handle_conn, self._host, self._port)
        self._address = server.sockets[0].getsockname()[:2]
        self._started.set()
        async with server:
            await self._stop_evt.wait()

    def _on_commit(self, generation: int) -> None:
        self.result_cache.invalidate_below(generation)

    # ----------------------------------------------------------- connection
    async def _handle_conn(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                try:
                    req = await read_frame(reader, self._max_frame)
                except ProtocolError as e:
                    # framing is broken: answer once, then hang up
                    writer.write(encode_frame(
                        {"status": 400, "error": str(e)}))
                    await writer.drain()
                    break
                if req is None:
                    break  # clean close
                resp = await self._dispatch(req)
                writer.write(encode_frame(resp))
                await writer.drain()
        except (ConnectionResetError, BrokenPipeError):
            pass
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass

    async def _dispatch(self, req: Any) -> dict:
        if not isinstance(req, dict) or "op" not in req:
            self.stats.bump("errors")
            return {"status": 400, "error": "request must be an object "
                                            "with an 'op' field"}
        op = req["op"]
        if op == "ping":
            return {"status": 200, "pong": True}
        if op == "stats":
            return self._stats_response()
        if op not in ("query", "count", "agg", "explain",
                      "update", "delete"):
            self.stats.bump("errors")
            return {"status": 400, "error": f"unknown op {op!r}"}
        # -- admission control: bounded queue, immediate shed beyond it
        if self._pending >= self.max_concurrent + self.max_queue:
            self.stats.bump("shed")
            return {"status": 503, "error": "server busy",
                    "queue_depth": self._pending - self.max_concurrent,
                    "retry": True}
        self._pending += 1
        t0 = time.perf_counter()
        try:
            async with self._sem:
                resp = await self._loop.run_in_executor(
                    self._exec, self._execute, req)
        finally:
            self._pending -= 1
        self.stats.record((time.perf_counter() - t0) * 1e6)
        return resp

    def _stats_response(self) -> dict:
        return {"status": 200,
                "stats": self.stats.snapshot(),
                "budget": self.budget.stats(),
                "plan_cache_entries": len(self.plan_cache),
                "result_cache_entries": len(self.result_cache),
                "result_cache_bytes": self.result_cache.nbytes,
                "result_cache_invalidated": self.result_cache.invalidated,
                "result_cache_evicted": self.result_cache.evicted,
                "queue_depth": max(0, self._pending - self.max_concurrent),
                "max_concurrent": self.max_concurrent,
                "max_queue": self.max_queue}

    # ------------------------------------------------------------ execution
    def _execute(self, req: dict) -> dict:
        """Blocking half, runs on the executor; returns the response."""
        op = req["op"]
        try:
            if op in ("query", "count", "agg", "explain"):
                return self._execute_read(req)
            if op == "update":
                return self._execute_update(req)
            return self._execute_delete(req)
        except (ProtocolError, KeyError, TypeError, ValueError) as e:
            self.stats.bump("errors")
            return {"status": 400, "error": f"{type(e).__name__}: {e}"}
        except Exception as e:  # noqa: BLE001 — a query must not kill the server
            self.stats.bump("errors")
            return {"status": 500, "error": f"{type(e).__name__}: {e}"}

    def _prepare(self, req: dict) -> CachedPlan:
        """Raw spec -> CachedPlan via the normalized-plan cache."""
        raw_key = json.dumps({k: req.get(k) for k in _PLAN_FIELDS},
                             sort_keys=True, separators=(",", ":"),
                             default=str)
        plan = self.plan_cache.get(raw_key)
        if plan is not None:
            self.stats.bump("plan_hits")
            return plan
        q, scalar_agg, fp_suffix = self._build_query(req)
        fp = q.plan_fingerprint() + fp_suffix
        plan_key = hashlib.blake2b(fp.encode(), digest_size=16).hexdigest()
        plan = CachedPlan(plan_key, q, scalar_agg)
        self.plan_cache.put(raw_key, plan)
        self.stats.bump("plan_misses")
        return plan

    def _build_query(self, req: dict):
        """Decode one read request into an unbound Query template.

        Returns ``(query, scalar_agg, fingerprint_suffix)`` — the suffix
        distinguishes terminals that are not part of the builder state
        (``count``, ungrouped ``agg``) so they never share a plan key
        with a row-returning query of the same shape.
        """
        op = req["op"]
        q = self._db.query(load_config=self._cfg)
        if req.get("where") is not None:
            q = q.where(expr_from_json(req["where"]))
        if req.get("select") is not None:
            sel = req["select"]
            if not isinstance(sel, (list, tuple)):
                raise ProtocolError("select must be a list of column names")
            q = q.select(*sel)
        scalar_agg, fp_suffix = None, ""
        if op == "count":
            fp_suffix = "|terminal=count"
        elif op == "agg":
            spec = req.get("agg")
            if not isinstance(spec, dict) or not spec:
                raise ProtocolError("agg op needs a non-empty agg spec")
            scalar_agg = spec
            canon = ";".join(
                f"{c}:{'+'.join(sorted([ops] if isinstance(ops, str) else ops))}"
                for c, ops in sorted(spec.items()))
            fp_suffix = f"|terminal=agg|spec={canon}"
        else:  # query / explain
            if req.get("group_by") is not None:
                spec = req.get("agg")
                if not isinstance(spec, dict) or not spec:
                    raise ProtocolError("group_by needs a non-empty agg "
                                        "spec")
                q = q.group_by(*req["group_by"]).agg(spec)
            elif req.get("agg") is not None:
                raise ProtocolError("use op 'agg' for ungrouped "
                                    "aggregation")
            if req.get("distinct"):
                q = q.distinct()
        for entry in req.get("order_by") or []:
            if isinstance(entry, str):
                q = q.order_by(entry)
            elif (isinstance(entry, (list, tuple)) and len(entry) == 2):
                q = q.order_by(entry[0], desc=bool(entry[1]))
            else:
                raise ProtocolError(f"bad order_by entry {entry!r}")
        if req.get("limit") is not None:
            q = q.limit(int(req["limit"]))
        if req.get("offset"):
            q = q.offset(int(req["offset"]))
        return q, scalar_agg, fp_suffix

    def _execute_read(self, req: dict) -> dict:
        plan = self._prepare(req)
        # pin the snapshot FIRST: everything below — cache lookup, scan,
        # cache fill — is in terms of exactly this generation, so a commit
        # landing mid-request can neither shear the scan nor mis-key the
        # cached result
        man, _schema = self._db._load_snapshot()
        gen = man.generation
        self.stats.bump("queries")
        if req["op"] != "explain":
            cached = self.result_cache.get(plan.plan_key, gen)
            if cached is not None:
                self.stats.bump("result_hits")
                resp = dict(cached)
                resp["cache"] = "hit"
                return resp
            self.stats.bump("result_misses")
        q = plan.query._replace(man=man)  # bind the pinned snapshot
        resp: Dict[str, Any] = {"status": 200, "generation": gen,
                                "plan_key": plan.plan_key}
        if req["op"] == "explain":
            report = q.explain(execute=bool(req.get("execute")))
            resp["ops"] = [list(t) for t in report.ops]
            resp["counters"] = dataclasses.asdict(report.counters)
            resp["executed"] = report.executed
            return resp
        if req["op"] == "count":
            resp["count"] = q.count()
        elif req["op"] == "agg":
            resp["values"] = q.agg(plan.scalar_agg)
        else:
            resp["rows"] = q.to_pylist()
        nbytes = len(encode_frame(resp))
        self.result_cache.put(plan.plan_key, gen, dict(resp), nbytes)
        resp["cache"] = "miss"
        return resp

    def _execute_update(self, req: dict) -> dict:
        rows = req.get("rows")
        if not isinstance(rows, list) or not rows:
            raise ProtocolError("update needs a non-empty 'rows' list")
        n = self._db.update(rows)
        self.stats.bump("writes")
        gen = self._db._load_snapshot()[0].generation
        return {"status": 200, "updated": n, "generation": gen}

    def _execute_delete(self, req: dict) -> dict:
        ids = req.get("ids")
        filters = ([expr_from_json(req["where"])]
                   if req.get("where") is not None else None)
        if ids is None and filters is None:
            raise ProtocolError("delete needs 'ids' and/or 'where'")
        n = self._db.delete(ids=ids, filters=filters)
        self.stats.bump("writes")
        gen = self._db._load_snapshot()[0].generation
        return {"status": 200, "deleted": n, "generation": gen}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Serve one ParquetDB dataset over TCP "
                    "(length-prefixed JSON protocol)")
    ap.add_argument("--path", required=True, help="database directory")
    ap.add_argument("--name", required=True, help="dataset name")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=7887)
    ap.add_argument("--max-concurrent", type=int, default=4)
    ap.add_argument("--max-queue", type=int, default=16)
    ap.add_argument("--morsel-budget", type=int, default=None)
    ap.add_argument("--num-threads", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    db = ParquetDB(args.path, args.name)
    server = DBServer(db, args.host, args.port,
                      max_concurrent=args.max_concurrent,
                      max_queue=args.max_queue,
                      morsel_budget=args.morsel_budget,
                      num_threads=args.num_threads)
    print(f"serving {args.name} on {args.host}:{args.port}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
