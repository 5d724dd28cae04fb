"""The trace reduction, on synthetic planes and on a small trace recorded
on a TPU v5e (``testdata/filter_v5e.xplane.pb``: two filter queries at
40,000 rows inside a ``tpubench.window`` span)."""
import os
from types import SimpleNamespace as NS

import pytest

from tpubench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "testdata", "filter_v5e.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def test_union_and_gaps():
    busy = trace.union([(5, 8), (0, 2), (1, 3), (7, 10)])
    assert busy == [(0, 3), (5, 10)]
    assert trace.gaps(busy, 0, 12) == [(3, 5), (10, 12)]
    assert trace.clip(busy, 2, 6) == [(2, 3), (5, 6)]


def test_reduce_synthetic_planes():
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("tpubench.window", 100, 1000),
        ev("tpubench.q", 150, 400),
        ev("tpubench.q", 600, 450),
        ev("TransferFromDevice", 450, 100),
    ])])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_a(123)", 200, 50),
                                       ev("jit_b(456)", 700, 200),
                                       ev("jit_c(789)", 50, 10)]),
        NS(name="XLA Ops", events=[ev("fusion", 200, 50),
                                   ev("%copy = u32[8] copy(x)", 700, 100),
                                   ev("%fusion = u32[8] fusion(y)", 800, 100),
                                   ev("early", 50, 10)]),
    ])
    got = trace.reduce_planes([host, device])
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["busy_s"] == pytest.approx(250e-9)
    assert got["launches"] == 2  # jit_c started before the window
    assert dict(got["device_ops"]) == {
        "jit_a:fusion": pytest.approx(50e-9),
        "jit_b:copy": pytest.approx(100e-9),
        "jit_b:fusion": pytest.approx(100e-9)}
    # gaps 100-200 and 900-1100 fall in a query with no runtime event open
    # at their middles; 250-700 has the transfer open at 475
    assert dict(got["idle_gaps"]) == {
        "q": pytest.approx(300e-9),
        "q / TransferFromDevice": pytest.approx(450e-9)}


def test_reduce_without_window_or_device_is_none():
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("tpubench.q", 0, 10)])])
    assert trace.reduce_planes([host]) is None


def test_reduce_recorded_v5e_trace():
    pytest.importorskip("jax")
    got = trace.reduce_file(RECORDED)
    assert got is not None
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["launches"] > 0
    assert got["devices"] == 1
    assert got["launches"] == 50  # 2 queries x 5 pages x 5 programs
    names = [n for n, _ in got["idle_gaps"]]
    assert all(n.startswith("energy_range") for n in names)
    assert len(got["device_ops"]) == trace.TOP
    assert "jit_filter_range:filter_range.1" in dict(got["device_ops"])


def test_program_s_keeps_every_program():
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("tpubench.window", 100, 1000)])])

    def device(i, scale):
        return NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Modules", events=[ev("jit_a(1)", 200, 50),
                                           ev("jit_b(2)", 700, 500),
                                           ev("jit_c(3)", 50, 10)]),
            NS(name="XLA Ops", events=[
                ev("fusion", 200, 50 * scale),
                ev("%copy = u32[8] copy(x)", 700, 100 * scale),
                ev("%fusion = u32[8] fusion(y)", 1000, 300),
                ev("early", 50, 10)])])

    got = trace.reduce_planes([host, device(0, 1)])
    # jit_c ran before the window; jit_b's last op is cut at its end
    assert got["program_s"] == {"jit_a": pytest.approx(50e-9),
                                "jit_b": pytest.approx(200e-9)}
    # averaged over the devices, as busy_s is
    got = trace.reduce_planes([host, device(0, 1), device(1, 2)])
    assert got["program_s"] == {"jit_a": pytest.approx(75e-9),
                                "jit_b": pytest.approx(250e-9)}


def test_program_s_of_the_recorded_v5e_trace():
    pytest.importorskip("jax")
    from jax.profiler import ProfileData
    got = trace.reduce_file(RECORDED)
    prog = got["program_s"]
    for name, t in got["device_ops"]:
        assert prog[name.split(":", 1)[0]] >= t
    assert "jit_filter_range" in prog and "jit_bss_decode" in prog
    # every operation inside the window belongs to one program
    planes = list(ProfileData.from_file(RECORDED).planes)
    lo, hi = next((e.start_ns, e.start_ns + e.duration_ns)
                  for p in planes if p.name.startswith("/host:")
                  for ln in p.lines for e in ln.events
                  if e.name == trace.WINDOW)
    ops = [e for p in planes if p.name.startswith(trace.DEVICE_PREFIX)
           for ln in p.lines if ln.name == trace.OPS_LINE for e in ln.events]
    total = sum(min(e.start_ns + e.duration_ns, hi) - max(e.start_ns, lo)
                for e in ops if e.start_ns < hi and e.start_ns
                + e.duration_ns > lo)
    assert sum(prog.values()) == pytest.approx(total / 1e9, rel=1e-9)
    assert sum(prog.values()) >= got["busy_s"] * (1 - 1e-9)
