"""One run of one cell: data from the seed, warm-up, a measured window,
the check against the plain reference, and the result line.

The window drives ``ParquetDB.query()`` under the ``jax`` decode backend
(closed loops), or a ``DBServer`` with its default settings fed by
``loadgen.py`` in a child process (open loops).  Every answer kept from
the window is compared with ``reference.py`` after the window has closed
and the device's peak memory has been read.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import subprocess
import sys
import tempfile
import time
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import reference, spec

HERE = spec.HERE
CACHE_DIR = os.path.join(spec.ROOT, ".tpubench_cache", "jax")
# the backend's page counters by encoding; "filter" and "minmax" count
# calls of the filter and statistics kernels, not decoded pages
DECODE_FAMILIES = ("bitpack", "dict", "delta", "bss", "plain", "rle")
PAGES = ("device_pages", "host_pages")
WARMUP_LOOKUPS = 8


class NoChip(RuntimeError):
    """The run cannot measure what the cell asks for on this machine."""


def load_module(path: str, name: str):
    s = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def peaks() -> dict:
    return spec.load_json(os.path.join(HERE, "peaks.json"))


def check_device(chips: int) -> dict:
    """The device as JAX reports it; raises :class:`NoChip` without a TPU,
    with fewer chips than the cell asks for, or with a device the peak
    table does not know."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks()["devices"]:
        raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


def cpu_device() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def make_data(cfg: dict, config: str, rows: int, seed: int) -> dict:
    gen = load_module(os.path.join(HERE, "configs", config + ".py"),
                      "tpubench_config_" + config)
    arrays = gen.generate(cfg, rows, seed)
    arrays["id"] = np.arange(rows, dtype=np.int64)
    return arrays


def to_table(arrays: dict):
    """The generated columns as a store Table (``id`` is the store's)."""
    from repro.core import Table
    from repro.core.dtypes import DType
    from repro.core.table import Column
    cols = {}
    for name, a in arrays.items():
        if name == "id":
            continue
        if a.dtype.kind == "S":
            w = a.dtype.itemsize
            cols[name] = Column(DType.string(),
                                offsets=np.arange(len(a) + 1,
                                                  dtype=np.int64) * w,
                                blob=a.view(np.uint8))
        else:
            cols[name] = a
    return Table.from_pydict(cols)


class Compiles:
    """Counts programs compiled or loaded from the persistent cache."""

    def __init__(self):
        import jax
        self.n = 0

        def on_event(event: str, **_kw) -> None:
            if event.startswith("/jax/compilation_cache/cache_"):
                self.n += 1
        jax.monitoring.register_event_listener(on_event)


class Tracer:
    """The profiler around the window, when the run is traced."""

    def __init__(self, on: bool, workdir: str):
        self.on, self.dir = on, os.path.join(workdir, "trace")

    def __enter__(self):
        if self.on:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans only, no Python calls
            jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax
            jax.profiler.stop_trace()
        return False

    def reduce(self) -> Tuple[Optional[dict], Optional[dict]]:
        """``(device, spans)`` from the one trace file of the window:
        ``trace.py``'s reduction, and the ``layers_ms`` and ``queries`` of
        ``program_spans.py``'s; each None where it finds nothing."""
        if not self.on:
            return None, None
        from jax.profiler import ProfileData

        from . import program_spans, trace
        found = [os.path.join(d, f) for d, _, fs in os.walk(self.dir)
                 for f in fs if f.endswith(".xplane.pb")]
        if not found:
            return None, None
        data = ProfileData.from_file(found[0])
        spans = program_spans.reduce_planes(data.planes)
        return trace.reduce_planes(data.planes), spans and {
            k: spans[k] for k in ("layers_ms", "queries")}


def _span(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation("tpubench." + name)


# -- closed loop ---------------------------------------------------------------
def closed_window(db, traffic: dict, seed: int, seconds: float) -> dict:
    """Queries back to back until the first completion at or after
    ``seconds``.  Keeps every answer of a template whose ``keep`` is null,
    and a reservoir sample of ``keep`` answers from the seed otherwise;
    lists every query run."""
    from . import drive
    keep = traffic.get("keep") or [None] * len(traffic["queries"])
    names = [t["name"] for t in traffic["queries"]]
    rng = spec.seeded(seed, "keep")
    kept: List[List[tuple]] = [[] for _ in names]
    seen = [0] * len(names)
    stream = spec.closed_requests(traffic, seed)
    n, failed, run, took = 0, 0, [], []
    with _span("window"):
        t0 = last = time.perf_counter()
        while True:
            q = next(stream)
            t = q["template"]
            with _span(names[t]):
                try:
                    ans = drive.run(db, q)
                except Exception as e:  # noqa: BLE001 — counted, reported
                    failed += 1
                    ans = e
            now = time.perf_counter()
            took.append((names[t], now - last))
            last = now
            n += 1
            run.append(q)
            seen[t] += 1
            if keep[t] is None or len(kept[t]) < keep[t]:
                kept[t].append((q, ans))
            else:
                j = int(rng.integers(0, seen[t]))
                if j < keep[t]:
                    kept[t][j] = (q, ans)
            if now - t0 >= seconds:
                break
    return {"elapsed_s": now - t0, "attempted": n, "completed": n - failed,
            "failed": failed, "kept": [x for k in kept for x in k],
            "queries": run, "query_s": took}


# -- open loop -----------------------------------------------------------------
def _rpc(sock, req: dict) -> dict:
    from repro.serve.protocol import encode_frame, recv_frame
    sock.sendall(encode_frame(req))
    return recv_frame(sock)


def open_window(db, traffic: dict, n_rows: int, seed: int, seconds: float,
                workdir: str, arrays: dict, rate: Optional[float] = None,
                on_ready: Callable = lambda server: None) -> dict:
    """A DBServer with its default settings, fed by ``loadgen.py`` on the
    cell's schedule.  Returns each request with its record, the generation
    the window starts at and, where the schedule writes, every written
    row as the store reads it back after the window."""
    import socket

    from repro.serve.dbserver import DBServer
    if rate is not None:
        traffic = dict(traffic, rate_per_s=rate)
    sched = spec.open_schedule(traffic, n_rows, seconds, seed)
    path = os.path.join(workdir, "schedule.jsonl")
    with open(path, "w") as f:
        for q in sched:
            f.write(json.dumps({"due": q["due"], "req": wire(q)}) + "\n")
    server = DBServer(db)
    host, port = server.start()
    try:
        # warm-up: every template on keys from another stream, the first
        # and the last id among them (the last page is short); a write
        # puts back the values the row has, so the data stay as made
        warm = [0, n_rows - 1] + list(spec.seeded(seed, "warmup").integers(
            0, n_rows, WARMUP_LOOKUPS))
        with socket.create_connection((host, port), timeout=120) as s:
            for k in warm:
                for t in traffic["queries"]:
                    q = spec.instantiate(t, {**{p: int(k) for p in
                                                traffic["params"]},
                                             "_index": 0})
                    if q.get("set"):
                        q["set"] = {c: arrays[c][k].item() for c in q["set"]}
                    resp = _rpc(s, wire(q))
                    if resp.get("status") != 200:
                        raise RuntimeError(f"warm-up request failed: {resp}")
        server.result_cache.clear()
        generation = db._load_snapshot()[0].generation
        on_ready(server)
        before = server.stats.snapshot()
        out = os.path.join(workdir, "responses.jsonl")
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"),
             "--host", host, "--port", str(port), "--schedule", path,
             "--out", out], stdout=subprocess.PIPE, text=True)
        try:
            line = child.stdout.readline().strip()
            if line != "start":
                raise RuntimeError(f"load generator said {line!r}")
            with _span("window"):
                t0 = time.perf_counter()
                with _span("serving"):
                    line = child.stdout.readline().strip()
                elapsed = time.perf_counter() - t0
            if child.wait(timeout=120) != 0 or line != "end":
                raise RuntimeError("load generator failed")
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        after = server.stats.snapshot()
    finally:
        server.stop()
        db.wait_for_maintenance()
    with open(out) as f:
        recs = [json.loads(x) for x in f]
    written = sorted({q["where"][3] for q in sched if q.get("set")})
    readback = None
    if written:
        from . import drive
        from repro.core import field
        readback = drive.normalise(
            db.query().where(field("id").isin(written)).to_table())
    return {"elapsed_s": elapsed, "schedule": sched, "records": recs,
            "generation": generation, "readback": readback,
            "server": {k: after[k] - before[k] for k in
                       ("queries", "shed", "errors", "result_hits",
                        "result_misses")}}


def wire(q: dict) -> dict:
    """An instantiated query as a server request."""
    if q.get("op") == "update":
        return {"op": "update", "rows": [{"id": q["where"][3], **q["set"]}]}
    req = {"op": "query"}
    if q.get("where") is not None:
        req["where"] = q["where"]
    sel = q.get("select") or ["*"]
    if sel != ["*"]:
        req["select"] = sel
    return req


def p95(latencies: List[float]) -> float:
    """95th percentile by nearest rank."""
    s = sorted(latencies)
    return s[max(0, math.ceil(0.95 * len(s)) - 1)]


# -- the run -------------------------------------------------------------------
def measure(w: dict, seed: int, seconds: float, traced: bool,
            t_start: float, n_rows: int, arrays: dict, be) -> dict:
    """Set-up and window under the jax backend; the program's state is
    freed before this returns."""
    from repro.core import ParquetDB

    from . import drive
    cfg, traffic = w["config_file"], w["traffic_file"]
    compiles = Compiles()
    state: Dict[str, Any] = {}

    def ready(_server=None) -> None:
        state["setup_s"] = time.perf_counter() - t_start
        state["compiles"] = compiles.n
        state["pages"] = {k: Counter(getattr(be, k)) for k in PAGES}

    with tempfile.TemporaryDirectory(prefix="tpubench-") as workdir:
        db = ParquetDB(os.path.join(workdir, "db"), w["config"],
                       page_rows=int(cfg["page_rows"]),
                       row_group_rows=int(cfg["row_group_rows"]))
        db.create(to_table(arrays))
        generation = db._load_snapshot()[0].generation
        tracer = Tracer(traced, workdir)
        if traffic["loop"] == "closed":
            stream = spec.closed_requests(traffic, seed)
            for _ in traffic["queries"]:  # one query of each shape
                with _span("warmup"):
                    drive.run(db, next(stream))
            ready()
            with tracer:
                win = closed_window(db, traffic, seed, seconds)
        else:
            with tracer:
                win = open_window(db, traffic, n_rows, seed, seconds,
                                  workdir, arrays, on_ready=ready)
        win.setdefault("generation", generation)
        win["window_compiles"] = compiles.n - state["compiles"]
        win["setup_s"] = state["setup_s"]
        for k, before in state["pages"].items():
            c = Counter(getattr(be, k))
            c.subtract(before)
            win[k] = {f: v for f, v in c.items() if v}
        win["memory_peak_bytes"] = memory_peak()
        del db
        win["trace"], win["spans"] = tracer.reduce()
    return win


def check_closed(win: dict, arrays: dict, traced: bool) -> Dict[str, Any]:
    """The kept answers of a closed loop against the reference."""
    from . import drive
    wrong, gap = 0, 0.0
    for q, ans in win["kept"]:
        got = {} if isinstance(ans, Exception) else drive.normalise(ans)
        a, g = reference.compare(got, reference.evaluate(q, arrays),
                                 q.get("group_by"))
        wrong, gap = wrong + a, max(gap, g)
    if traced:
        win["required_values"] = sum(reference.device_values(q, arrays)
                                     for q in win["queries"])
    return {"wrong_values": wrong, "agg_rel_gap": gap,
            "failed": win["failed"], "answers_checked": len(win["kept"])}


def check_open(win: dict, arrays: dict) -> Dict[str, Any]:
    """Every request of the window against the reference.  A failed,
    shed or unanswered request's latency is the whole window from its due
    time; a shed (503) is a failure, any other error a wrong answer.

    Writes: a read of a row that requests wrote may show, in each written
    column, the value of any write to that row sent before the read's
    answer came, or the value made with the data while no such write had
    been answered before the read was sent.  A read's generation is the
    window's first where nothing writes, and no earlier one where requests
    write (writes and the compaction they set off commit new ones).  A
    write must update one row, and after the window the store
    must read back, for each written row, a value that one of its writes
    wrote, the data's own only where none was answered (``lost_writes``
    counts those it does not).  A shed write is never applied."""
    from . import drive
    sched, recs = win["schedule"], win["records"]
    gen0 = win["generation"]
    sent = [q["due"] + (r["late"] or 0.0) for q, r in zip(sched, recs)]
    done = [q["due"] + r["lat"] if r["lat"] is not None else math.inf
            for q, r in zip(sched, recs)]
    writes: Dict[int, list] = {}
    for i, (q, r) in enumerate(zip(sched, recs)):
        if q.get("set") and r["status"] in (200, None):  # maybe applied
            writes.setdefault(q["where"][3], []).append(
                (sent[i], done[i], q["set"], r["status"] == 200))
    wrong = unanswered = wrong_gen = 0
    lat, failed = [], 0
    for i, (q, r) in enumerate(zip(sched, recs)):
        if r["status"] != 200:
            failed += 1
            lat.append(win["elapsed_s"] - q["due"])
            unanswered += r["status"] is None
            wrong += r["status"] not in (None, 503)
            continue
        lat.append(r["lat"])
        if q.get("set"):
            wrong += r.get("updated") != 1
            continue
        wrong_gen += (r["generation"] < gen0 if writes
                      else r["generation"] != gen0)
        ref = reference.evaluate(q, arrays)
        ws = [w for w in writes.get(q["where"][3], ()) if w[0] < done[i]]
        if not ws:
            a, _ = reference.compare(drive.rows_to_table(r["rows"]), ref)
            wrong += a
            continue
        wrong += _check_written_row(r["rows"], ref["table"], ws, sent[i])
    lost = 0
    for k, ws in writes.items():
        rows = win["readback"]["table"]
        at = np.flatnonzero(np.asarray(rows["id"]) == k)
        for c in ws[0][2]:
            got = rows[c][at[0]] if len(at) == 1 else None
            allowed = {w[2][c] for w in ws}
            if not any(w[3] for w in ws):
                allowed.add(arrays[c][k].item())
            lost += got not in allowed
    win["latencies_s"] = lat
    win["attempted"], win["failed"] = len(sched), failed
    out = {"wrong_values": wrong, "unanswered": unanswered,
           "wrong_generation": wrong_gen}
    if win["readback"] is not None:
        out["lost_writes"] = lost
    return out


def _check_written_row(rows: list, ref: dict, ws: list, sent: float) -> int:
    """Wrong values in a read of a row that writes reached (see
    :func:`check_open`)."""
    if len(rows) != 1:
        return len(ref)
    wrong = 0
    for c, r in ref.items():
        got = rows[0].get(c)
        allowed = {w[2][c] for w in ws if c in w[2]}
        if not allowed:
            wrong += got != r[0]
            continue
        if not any(w[1] <= sent and w[3] and c in w[2] for w in ws):
            allowed.add(r[0].item())
        wrong += got not in allowed
    return wrong


def judge(checks: Dict[str, Any], limits: dict):
    """``(correct, compared)``: every number with a limit against it."""
    correct, compared = True, {}
    for k, lim in limits.items():
        v = checks[k]
        correct &= bool(v <= lim["max"] if "max" in lim else v >= lim["min"])
        compared[k] = {"value": v, **lim}
    for k in sorted(set(checks) - set(limits)):
        compared[k] = {"value": checks[k]}
    return correct, compared


def run_cell(name: str, seed: int, seconds: float, traced: bool,
             t_start: float, *, rows: Optional[int] = None,
             require_chip: bool = True) -> dict:
    """One run; returns the result object (the last stdout line)."""
    from repro.core.backend import get_backend, set_backend
    w = spec.cell(name)
    cfg, traffic = w["config_file"], w["traffic_file"]
    if require_chip:
        device = check_device(int(w["chips"]))
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
    else:
        device = cpu_device()
    set_backend("jax")
    try:
        be = get_backend("jax")
        if be.interpret and require_chip:
            raise NoChip("the jax decode backend is in interpret mode")
        n_rows = int(rows or cfg["rows"])
        arrays = make_data(cfg, w["config"], n_rows, seed)
        win = measure(w, seed, seconds, traced, t_start, n_rows, arrays, be)
    finally:
        set_backend(None)

    # the check: after the window, the peak read and the program freed
    if traffic["loop"] == "closed":
        checks = check_closed(win, arrays, traced)
    else:
        checks = check_open(win, arrays)
        lates = sorted(r["late"] for r in win["records"]
                       if r["late"] is not None)
        print(json.dumps({"load_generator": {
            "requests": win["attempted"],
            "late_p50_ms": 1e3 * lates[len(lates) // 2],
            "late_p99_ms": 1e3 * lates[int(0.99 * (len(lates) - 1))],
            "late_max_ms": 1e3 * lates[-1]}}), flush=True)
    checks["device_pages"] = sum(v for k, v in win["device_pages"].items()
                                 if k in DECODE_FAMILIES)
    correct, compared = judge(checks, traffic["limits"])

    record = dict(win, closed=traffic["loop"] == "closed",
                  decode_families=DECODE_FAMILIES,
                  peaks=peaks()["devices"].get(device["kind"]))
    side = {k: win[k] for k in ("window_compiles", "query_s",
                                "required_values", "spans") + PAGES
            if k in win}
    if win["trace"]:
        side["program_s"] = win["trace"]["program_s"]
    print(json.dumps(side), file=sys.stderr, flush=True)
    for k, c in compared.items():
        lim = ("<= %r" % c["max"] if "max" in c else
               ">= %r" % c["min"] if "min" in c else "(not compared)")
        print(f"check {k} = {c['value']!r} {lim}", file=sys.stderr,
              flush=True)

    device["memory_peak_bytes"] = win["memory_peak_bytes"]
    tr = win["trace"]
    if traced:
        device["busy_s"] = tr["busy_s"] if tr else 0.0
        device["window_s"] = tr["window_s"] if tr else win["elapsed_s"]
    out = {"correct": bool(correct), "attempted": win["attempted"],
           "failed": win["failed"], "metrics": read_metrics(w, record, traced),
           "device": device}
    if traced and tr:
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = compared
    return out


def read_metrics(w: dict, record: dict, traced: bool) -> dict:
    """End-to-end metrics without the trace, per-layer metrics with it;
    each read by ``metrics/<name>.py``, left out when it reads nothing."""
    bench = w["bench"]
    e2e = [m for m in bench["end_to_end"]
           if w["name"] in m.get("workloads", [w["name"]])]
    moved = {m["name"] for m in e2e}
    if traced:
        wanted = [m for m in bench["per_layer"]
                  if (w["name"] in m["workloads"] if "workloads" in m
                      else m["moves"] in moved)]
    else:
        wanted = e2e
    out = {}
    for m in wanted:
        mod = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                          "tpubench_metric_" + m["name"].replace(".", "_"))
        v = mod.read(record)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
