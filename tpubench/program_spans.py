"""Per-layer times of the store from its own profiler spans.

    python3 tpubench/program_spans.py <trace directory or .xplane.pb>

The store opens ``repro.*`` spans (``src/repro/spans.py``) at its layer
boundaries; inside a ``jax.profiler`` session they land in the same trace
as the device's operations.  This module reduces such a trace to:

- ``program_spans``: for each ``repro.*`` name, the wall time in seconds
  inside the window in which a span of that name was the innermost one
  open on its host line (its self time).  Where several lines have work
  open at once (a scan's worker threads) the instant is split evenly
  among them, and a bare ``repro.query`` root holds only instants in
  which no line has work open.  So the values add up to the time some
  ``repro.*`` span was open, and split each query's wall time between
  its layers; a self time summed over threads would count every waiting
  pool thread again.
- ``queries``: ``repro.query`` root spans that end inside the window.
- ``layers_ms``: ``program_spans`` summed by layer (``LAYERS``), in ms per
  query; every ``repro.*`` name belongs to exactly one layer.
- ``idle_gaps``: device idle time inside the window, summed by what the
  host was doing at the middle of each gap: the innermost ``tpubench.*``
  span, then the innermost ``repro.*`` span open on any host line, then
  the innermost other host event; a level with nothing open is left out.
  On a trace without ``repro.*`` spans the names are those of
  ``trace.reduce_planes``.

The window is the benchmark's ``tpubench.window`` span when the trace has
one, else from the first ``repro.query`` root's start to the last one's
end (a trace recorded around an application's own queries, as
``docs/ARCHITECTURE.md`` shows under "Tracing a query").
"""
from __future__ import annotations

import bisect
import json
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

if __package__:
    from . import trace
else:  # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))
    from tpubench import trace

PROGRAM = "repro."
ROOT = "repro.query"

# layer -> the span names whose time it holds (PERF.md, section 3)
LAYERS: Dict[str, Tuple[str, ...]] = {
    "plan_ms": ("repro.query.plan",),
    "query_host_ms": ("repro.query", "repro.query.compute"),
    "reader_ms": ("repro.scan.morsel", "repro.reader.filter",
                  "repro.reader.payload"),
    "stage_ms": ("repro.ops.stage",),
    "launch_ms": ("repro.ops.launch",),
    "fetch_ms": ("repro.ops.fetch",),
}

Segment = Tuple[int, int, str, int]  # (start, end, name, start of its span)


def _innermost(events: List[Tuple[int, int, str]]) -> List[Segment]:
    """The innermost span open on one host line, as non-overlapping
    segments in time order; a span never outlives the span it opened in."""
    out: List[Segment] = []
    stack: List[Tuple[int, str, int]] = []  # (end, name, start)
    t = 0

    def close_until(x: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= x:
            e, name, s0 = stack.pop()
            if e > t:
                out.append((t, e, name, s0))
                t = e

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        close_until(s)
        if stack:
            e = min(e, stack[-1][0])
            if s > t:
                out.append((t, s, stack[-1][1], stack[-1][2]))
        stack.append((e, name, s))
        t = s
    close_until(float("inf"))
    return out


def _shares(segments: List[Tuple[int, int, str]]) -> Dict[str, float]:
    """Wall time in ns held by each span name.  An instant goes to the
    innermost span of every line that has one open, split evenly among the
    lines whose innermost span is work, not a bare root; to the open roots
    only where no line has work open."""
    out: Dict[str, float] = defaultdict(float)
    if not segments:
        return out
    a = np.array([s for s, _, _ in segments], np.float64)
    b = np.array([e for _, e, _ in segments], np.float64)
    root = np.array([n == ROOT for _, _, n in segments])
    times = np.concatenate([a, b])
    order = np.argsort(times, kind="stable")
    t = times[order]
    sign = np.concatenate([np.ones(len(a), np.int64),
                           -np.ones(len(b), np.int64)])
    work = np.concatenate([~root, ~root]).astype(np.int64)
    roots = np.concatenate([root, root]).astype(np.int64)
    n_work = np.cumsum((sign * work)[order])[:-1]
    n_root = np.cumsum((sign * roots)[order])[:-1]
    dt = np.diff(t)
    w_work = np.where(n_work > 0, dt / np.maximum(n_work, 1), 0.0)
    w_root = np.where((n_work == 0) & (n_root > 0),
                      dt / np.maximum(n_root, 1), 0.0)
    f_work = np.concatenate([[0.0], np.cumsum(w_work)])
    f_root = np.concatenate([[0.0], np.cumsum(w_root)])
    ia, ib = np.searchsorted(t, a), np.searchsorted(t, b)
    held = np.where(root, f_root[ib] - f_root[ia], f_work[ib] - f_work[ia])
    for (_, _, name), v in zip(segments, held.tolist()):
        out[name] += v
    return out


class _Program:
    """The ``repro.*`` spans of every host line: the innermost span of
    each line over time, for 'innermost open at t on any line'."""

    def __init__(self, lines: List[List[Tuple[int, int, str]]]):
        self.lines = []
        for events in lines:
            segs = _innermost(events)
            self.lines.append(([s for s, _, _, _ in segs], segs))

    def held_ns(self, lo: int, hi: int) -> Dict[str, float]:
        return _shares([(max(s, lo), min(e, hi), name)
                        for _, segs in self.lines for s, e, name, _ in segs
                        if e > lo and s < hi])

    def innermost(self, t: int) -> Optional[str]:
        """The latest-starting span open at ``t`` on any line."""
        best: Optional[Tuple[int, str]] = None
        for starts, segs in self.lines:
            i = bisect.bisect_right(starts, t) - 1
            if i < 0:
                continue
            _, e, name, s0 = segs[i]
            if e > t and (best is None or s0 > best[0]):
                best = (s0, name)
        return best[1] if best else None


def layers_ms(spans: Dict[str, float], queries: int
              ) -> Optional[Dict[str, float]]:
    """``program_spans`` (seconds) by layer, in ms per query."""
    if not spans or not queries:
        return None
    return {layer: 1e3 * sum(spans.get(n, 0.0) for n in names) / queries
            for layer, names in LAYERS.items()}


def read_layer(record: dict, layer: str) -> Optional[float]:
    """One layer's ms per query from a run's record (its ``spans``, which
    the harness keeps from this reduction), or None where the traced
    window holds no ``repro.query`` root."""
    spans = record.get("spans")
    if not spans or not spans["layers_ms"]:
        return None
    return spans["layers_ms"][layer]


def reduce_planes(planes) -> Optional[Dict]:
    """The reduction over planes as ``ProfileData`` gives them.  None when
    the trace has neither a window span nor a ``repro.query`` root."""
    window: Optional[trace.Interval] = None
    bench, host, program, roots = [], [], [], []
    devices = []
    for plane in planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = []
            for s, e, name in trace._events(line):
                if name == trace.WINDOW:
                    window = (s, e)
                elif name.startswith(trace.PREFIX):
                    bench.append((s, e, name[len(trace.PREFIX):]))
                elif name.startswith(PROGRAM):
                    spans.append((s, e, name))
                    if name == ROOT:
                        roots.append((s, e))
                else:
                    host.append((s, e, name))
            if spans:
                program.append(spans)
    if window is None:
        if not roots:
            return None
        window = (min(s for s, _ in roots), max(e for _, e in roots))
    lo, hi = window
    own = _Program(program)
    bench_spans, host_spans = trace._Spans(bench), trace._Spans(host)
    idle: Dict[str, int] = defaultdict(int)
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get(trace.OPS_LINE) or lines.get(trace.MODULES_LINE)
        if ops is None:
            continue
        intervals = [(s, e) for s, e, _ in trace._events(ops)
                     if e > lo and s < hi]
        if not intervals:
            continue
        busy = trace.clip(trace.union(intervals), lo, hi)
        for s, e in trace.gaps(busy, lo, hi):
            mid = (s + e) // 2
            levels = [bench_spans.innermost(mid)
                      or ("outside a query" if bench else None),
                      own.innermost(mid), host_spans.innermost(mid)]
            idle[" / ".join(n for n in levels if n)] += e - s
    held = {n: t / 1e9 for n, t in sorted(own.held_ns(lo, hi).items())}
    queries = sum(1 for _, e in roots if lo <= e <= hi)
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:trace.TOP]
    return {
        "window_s": (hi - lo) / 1e9,
        "queries": queries,
        "program_spans": held,
        "layers_ms": layers_ms(held, queries),
        "idle_gaps": [[n, t / 1e9] for n, t in top_idle],
    }


def reduce_file(path: str) -> Optional[Dict]:
    """The reduction of one ``.xplane.pb`` file, or of the first one found
    under a directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(os.path.join(d, f) for d, _, fs in os.walk(path)
                       for f in fs if f.endswith(".xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb file under {path}")
        path = found[0]
    return reduce_planes(ProfileData.from_file(path).planes)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="a .xplane.pb file or a directory "
                    "jax.profiler.trace wrote")
    args = ap.parse_args(argv)
    got = reduce_file(args.trace)
    if got is None:
        print("program_spans: no window and no repro.query span in the "
              "trace", file=sys.stderr)
        return 1
    print(json.dumps(got, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
