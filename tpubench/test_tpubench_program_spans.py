"""Per-layer times from the store's ``repro.*`` spans
(``tpubench/program_spans.py``): on synthetic planes, on the recorded v5e
trace (no ``repro.*`` spans) and on a trace recorded on the CPU."""
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from tpubench import program_spans as ps
from tpubench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "testdata", "filter_v5e.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _host(*lines):
    return NS(name="/host:CPU", lines=[NS(name="python", events=list(evs))
                                       for evs in lines])


DEVICE = NS(name="/device:TPU:0", lines=[
    NS(name="XLA Modules", events=[ev("jit_a(1)", 300, 100)]),
    NS(name="XLA Ops", events=[ev("fusion", 300, 100)])])


def test_program_spans_split_wall_time_between_layers():
    # main line: a root whose plan lies partly before the window, a
    # compute child, a morsel with a launch and a fetch; a worker line:
    # a morsel running beside the main line's morsel, past the window
    main = [ev("tpubench.window", 100, 1000), ev("tpubench.q", 100, 1000),
            ev("repro.query", 50, 900),
            ev("repro.query.plan", 50, 100),
            ev("repro.scan.morsel", 200, 400),
            ev("repro.ops.launch", 250, 50),
            ev("repro.ops.fetch", 300, 100),
            ev("repro.query.compute", 700, 100),
            ev("TransferFromDevice", 320, 20)]
    worker = [ev("repro.scan.morsel", 500, 700)]
    got = ps.reduce_planes([_host(main, worker), DEVICE])
    spans = got["program_spans"]
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["queries"] == 1
    assert spans["repro.query.plan"] == pytest.approx(50e-9)
    assert spans["repro.ops.launch"] == pytest.approx(50e-9)
    assert spans["repro.ops.fetch"] == pytest.approx(100e-9)
    # 500-600 and 700-800 are shared with the worker's morsel, which holds
    # 600-700 and 800-1100 alone (cut at the window's end)
    assert spans["repro.query.compute"] == pytest.approx(50e-9)
    assert spans["repro.scan.morsel"] == pytest.approx(
        (50 + 100 + 50) * 1e-9 + (50 + 100 + 50 + 300) * 1e-9)
    # the root holds only what no line has work open in: 150-200
    assert spans["repro.query"] == pytest.approx(50e-9)
    assert sum(spans.values()) == pytest.approx(1000e-9)  # the window
    assert got["layers_ms"]["reader_ms"] == pytest.approx(700e-6)
    assert got["layers_ms"]["query_host_ms"] == pytest.approx(100e-6)


def test_idle_gaps_name_the_program_span_between():
    main = [ev("tpubench.window", 100, 1000), ev("tpubench.q", 100, 1000),
            ev("repro.query", 100, 1000),
            ev("repro.ops.fetch", 250, 200),
            ev("np.asarray(jax.Array)", 260, 180)]
    worker = [ev("repro.reader.filter", 500, 550)]
    got = ps.reduce_planes([_host(main, worker), DEVICE])
    # gaps 100-300 (mid 200: the root only), 400-1100 (mid 750: the
    # worker's filter, which started after the root)
    assert dict(got["idle_gaps"]) == {
        "q / repro.query": pytest.approx(200e-9),
        "q / repro.reader.filter": pytest.approx(700e-9)}
    # a gap with the fetch and a runtime event open at its middle
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("fusion", 100, 200),
                                   ev("fusion", 400, 700)])])
    got = ps.reduce_planes([_host(main, worker), device])
    assert dict(got["idle_gaps"]) == {
        "q / repro.ops.fetch / np.asarray(jax.Array)": pytest.approx(
            100e-9)}


def test_a_trace_without_program_spans_keeps_its_names():
    host = _host([ev("tpubench.window", 100, 1000), ev("tpubench.q", 150, 400),
                  ev("TransferFromDevice", 450, 100)])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("fusion", 300, 100),
                                   ev("fusion", 600, 500)])])
    got = ps.reduce_planes([host, device])
    assert got["program_spans"] == {}
    assert got["queries"] == 0 and got["layers_ms"] is None
    # gaps 100-300 (mid 200) and 400-600 (mid 500, the transfer open)
    assert dict(got["idle_gaps"]) == {
        "q": pytest.approx(200e-9),
        "q / TransferFromDevice": pytest.approx(200e-9)}
    assert got["idle_gaps"] == trace.reduce_planes([host, device])[
        "idle_gaps"]


def test_a_trace_around_an_application_spans_its_queries():
    # no tpubench spans: the window runs from the first root to the last
    main = [ev("repro.query", 100, 200), ev("repro.ops.fetch", 150, 100),
            ev("repro.query", 500, 100), ev("repro.query.plan", 500, 40),
            ev("np.asarray(jax.Array)", 160, 80)]
    got = ps.reduce_planes([_host(main), DEVICE])
    assert got["window_s"] == pytest.approx(500e-9)
    assert got["queries"] == 2
    assert got["program_spans"] == {
        "repro.ops.fetch": pytest.approx(100e-9),
        "repro.query": pytest.approx(160e-9),
        "repro.query.plan": pytest.approx(40e-9)}
    # gaps 100-300 (mid 200, in the fetch) and 400-600 (mid 500, the
    # second root's plan), with no benchmark level in front
    assert dict(got["idle_gaps"]) == {
        "repro.ops.fetch / np.asarray(jax.Array)": pytest.approx(200e-9),
        "repro.query.plan": pytest.approx(200e-9)}
    assert ps.reduce_planes([_host([ev("other", 0, 10)]), DEVICE]) is None


EVERY = [n for names in ps.LAYERS.values() for n in names]


@pytest.mark.parametrize("layer", sorted(ps.LAYERS))
def test_a_layer_reads_its_spans(layer):
    spans = {n: 0.001 * (1 + i) for i, n in enumerate(EVERY)}
    want = 1e3 * sum(spans[n] for n in ps.LAYERS[layer]) / 4
    assert ps.layers_ms(spans, 4)[layer] == pytest.approx(want)
    assert ps.layers_ms({}, 4) is None
    assert ps.layers_ms(spans, 0) is None


@pytest.mark.parametrize("layer", sorted(ps.LAYERS))
def test_a_layer_metric_reads_the_record_or_nothing(layer):
    spans = {n: 0.001 * (1 + i) for i, n in enumerate(EVERY)}
    kept = {"layers_ms": ps.layers_ms(spans, 4), "queries": 4}
    assert ps.read_layer({"spans": kept}, layer) == kept["layers_ms"][layer]
    # an untraced run, a trace with no repro.query root in the window
    assert ps.read_layer({"spans": None}, layer) is None
    assert ps.read_layer({}, layer) is None
    none = {"layers_ms": ps.layers_ms(spans, 0), "queries": 0}
    assert ps.read_layer({"spans": none}, layer) is None


def test_the_layers_partition_the_program_spans():
    assert len(EVERY) == len(set(EVERY))
    spans = {n: 0.002 * (1 + i) for i, n in enumerate(EVERY)}
    total = sum(ps.layers_ms(spans, 2).values())
    assert total == pytest.approx(1e3 * sum(spans.values()) / 2)


def test_recorded_v5e_trace_has_no_program_spans():
    pytest.importorskip("jax")
    got = ps.reduce_file(RECORDED)
    before = trace.reduce_file(RECORDED)
    assert got["program_spans"] == {} and got["layers_ms"] is None
    assert got["window_s"] == before["window_s"]
    assert got["idle_gaps"] == before["idle_gaps"]


def test_a_cpu_trace_of_two_queries_splits_into_layers(tmp_path, capsys):
    jax = pytest.importorskip("jax")
    from repro.core import ParquetDB, Table, backend, field
    db = ParquetDB(str(tmp_path / "db"), page_rows=512, row_group_rows=2048)
    rng = np.random.default_rng(7)
    n = 6000
    db.create(Table.from_pydict({
        "energy": rng.normal(-30, 10, n).astype(np.float32),
        "spg": rng.integers(1, 20, n)}))
    flt = db.query().where((field("energy") >= -31) & (field("energy") <= -29))
    grp = db.query().group_by("spg").agg({"*": "count"})
    backend.set_backend("jax")
    try:
        flt.to_table()  # compiles outside the trace
        grp.to_table()
        with jax.profiler.trace(str(tmp_path / "trace")):
            flt.to_table()
            grp.to_table()
    finally:
        backend.set_backend(None)
    got = ps.reduce_file(str(tmp_path / "trace"))
    assert got["queries"] == 2
    layers = got["layers_ms"]
    assert set(layers) == set(ps.LAYERS)
    assert layers["reader_ms"] > 0 and layers["launch_ms"] > 0
    assert layers["fetch_ms"] > 0 and layers["stage_ms"] > 0
    # one thread opens every span here, so the layers hold the window
    # less the instants between the two queries
    assert 0 < 2e-3 * sum(layers.values()) <= got["window_s"] * (1 + 1e-9)
    assert ps.main([str(tmp_path / "trace")]) == 0
    assert '"layers_ms"' in capsys.readouterr().out
