"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports.

- ``window_s``: length of the benchmark's ``tpubench.window`` host span.
- ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside that span, averaged over the devices that ran any.
- ``launches``: programs started on the devices inside the span (events
  of the ``XLA Modules`` line).
- ``device_ops``: the operations that took most device time, named
  ``<program>:<operation>`` (the program from the ``XLA Modules`` line
  without its fingerprint, the operation's HLO name).
- ``program_s``: every program's device time, the time of its operations
  inside the span averaged over the devices as ``busy_s`` is, named as in
  ``device_ops``; so a kernel's time is found by its ``jit_`` name
  whatever its rank.
- ``idle_gaps``: device idle time inside the span, summed by what the
  host was doing: the innermost ``tpubench.*`` span open at the middle of
  the gap, then the innermost other host event open there, if any.

Only ``jax.profiler.ProfileData`` is used to read the file.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "tpubench.window"
PREFIX = "tpubench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

Interval = Tuple[int, int]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    """Idle intervals of ``[lo, hi]`` around merged ``busy`` intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class _Spans:
    """Host events of one kind, for 'innermost open at time t'."""

    def __init__(self, events: List[Tuple[int, int, str]]):
        self.ev = sorted(events)
        self.starts = [s for s, _, _ in self.ev]

    def innermost(self, t: int, depth: int = 256) -> Optional[str]:
        """The latest-starting event open at ``t``, looking back over at
        most ``depth`` events."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and depth:
            _, e, name = self.ev[i]
            if e > t:
                return name
            i -= 1
            depth -= 1
        return None


def _events(line):
    for ev in line.events:
        s = int(ev.start_ns)
        yield s, s + int(ev.duration_ns), ev.name


def _program(name: str) -> str:
    """``jit_bss_decode(1008...)`` -> ``jit_bss_decode``."""
    return name.split("(", 1)[0]


def _op(name: str) -> str:
    """``%fusion.1 = u32[8192]{...} fusion(...)`` -> ``fusion.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce_planes(planes) -> Optional[Dict]:
    """The reduction over planes as ``ProfileData`` gives them.  None when
    the trace has no window span or no device operation inside it."""
    window: Optional[Interval] = None
    bench, host = [], []
    devices = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for s, e, name in _events(line):
                if name == WINDOW:
                    window = (s, e)
                elif name.startswith(PREFIX):
                    bench.append((s, e, name[len(PREFIX):]))
                else:
                    host.append((s, e, name))
    if window is None:
        return None
    lo, hi = window
    busy_total, used, launches = 0, 0, 0
    op_time: Dict[str, int] = defaultdict(int)
    prog_time: Dict[str, int] = defaultdict(int)
    idle: Dict[str, int] = defaultdict(int)
    bench_spans, host_spans = _Spans(bench), _Spans(host)
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if ops is None:
            continue
        programs = _Spans([(s, e, _program(n)) for s, e, n in
                           _events(lines[MODULES_LINE])]
                          if MODULES_LINE in lines else [])
        launches += sum(1 for s in programs.starts if lo <= s < hi)
        intervals = []
        for s, e, name in _events(ops):
            if e > lo and s < hi:
                intervals.append((s, e))
                prog = programs.innermost(s)
                dt = min(e, hi) - max(s, lo)
                op_time[(prog + ":" if prog else "") + _op(name)] += dt
                if prog:
                    prog_time[prog] += dt
        if not intervals:
            continue
        used += 1
        busy = clip(union(intervals), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for s, e in gaps(busy, lo, hi):
            mid = (s + e) // 2
            name = bench_spans.innermost(mid) or "outside a query"
            inner = host_spans.innermost(mid)
            idle[name + (" / " + inner if inner else "")] += e - s
    if not used:
        return None
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / used / 1e9,
        "launches": launches,
        "devices": used,
        "device_ops": [[n, t / 1e9] for n, t in top],
        "idle_gaps": [[n, t / 1e9] for n, t in top_idle],
        "program_s": {n: t / used / 1e9 for n, t in sorted(prog_time.items())},
    }


def reduce_file(path: str) -> Optional[Dict]:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)
