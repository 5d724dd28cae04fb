"""The benchmark's cells on the CPU at a tiny size, Pallas in interpret
mode: the store's answers must equal the plain reference, the control
and planted faults must be caught, and a run without a TPU must refuse.
"""
import json
import os
import tempfile
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from tpubench import (control, drive, harness, program_spans,  # noqa: E402
                      reference, spec)

ROWS = 20_000
SEED = 3_000_000_019  # more than 32 bits: seeds may be that large

# The open-loop lookup cell ran correct on the chip but is not in
# BENCHMARK.json: two of twelve runs stalled for seconds (PERF.md §7).
# These entries put it back for the tests, so its path stays proven.
LOOKUP = {
    "workload": {"name": "alexandria.lookup", "config": "alexandria",
                 "traffic": "lookup", "chips": 1, "why": "open-loop lookups"},
    "end_to_end": {"name": "p95_ms", "unit": "ms", "better": "lower",
                   "bound": 0.25, "source": "host_clock",
                   "workloads": ["alexandria.lookup"]},
    "per_layer": [
        {"name": "device_idle.lookup", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device", "moves": "p95_ms",
         "workloads": ["alexandria.lookup"]},
        {"name": "result_hit_share.lookup", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "server", "moves": "p95_ms",
         "workloads": ["alexandria.lookup"]}],
}
CELLS = [w["name"] for w in spec.benchmark()["workloads"]] + [
    LOOKUP["workload"]["name"]]


@pytest.fixture(autouse=True)
def with_lookup_cell(monkeypatch):
    real = spec.benchmark

    def bench():
        b = real()
        b["workloads"].append(LOOKUP["workload"])
        b["end_to_end"].append(LOOKUP["end_to_end"])
        b["per_layer"].extend(LOOKUP["per_layer"])
        return b

    monkeypatch.setattr(spec, "benchmark", bench)


def _run(cell, seconds=0.3, traced=False):
    return harness.run_cell(cell, SEED, seconds, traced, time.perf_counter(),
                            rows=ROWS, require_chip=False)


def _sound(out):
    """Correct but for the float gap, whose limit is set at the cell's own
    size: at this tiny size float32 partial sums read up to about 1e-7."""
    checks = out["checks"]
    assert checks.get("agg_rel_gap", {"value": 0})["value"] < 1e-6
    return all(v["value"] <= v["max"] if "max" in v else v["value"] >= v["min"]
               for k, v in checks.items() if k != "agg_rel_gap" and
               ("max" in v or "min" in v))


@pytest.mark.parametrize("cell", CELLS)
def test_store_answers_equal_the_reference(cell, capsys):
    out = _run(cell)
    assert _sound(out), out["checks"]
    assert out["checks"]["wrong_values"]["value"] == 0
    assert out["checks"]["device_pages"]["value"] > 0
    assert out["attempted"] >= 1 and out["failed"] == 0
    e2e = {m["name"] for m in spec.benchmark()["end_to_end"]
           if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == e2e
    # the numbers compared come last, in the line and on stderr
    assert list(out)[-1] == "checks"
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check ")
    json.dumps(out)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_caught(cell):
    got = control.readings(cell, SEED, rows=ROWS, queries=4)
    assert got["correct"] is False
    assert got["checks"]["wrong_values"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced_is_caught(cell, monkeypatch):
    from repro.kernels import ops
    real = ops.decode_batch_on_device

    def altered(*a, **kw):
        return real(*a, **kw) + 1

    monkeypatch.setattr(ops, "decode_batch_on_device", altered)
    out = _run(cell)
    assert out["correct"] is False
    assert out["checks"]["wrong_values"]["value"] > 0


@pytest.mark.parametrize("cell", ["alexandria.filter", "alexandria.scan"])
def test_a_query_that_raises_is_not_correct(cell, monkeypatch):
    """A query that raises outside the answers kept for the check still
    makes the run not correct, through ``failed``."""
    real, calls = drive.run, []

    def flaky(db, q):
        calls.append(q)
        if len(calls) == len(spec.cell(cell)["traffic_file"]["queries"]) + 2:
            raise RuntimeError("planted")
        return real(db, q)

    monkeypatch.setattr(drive, "run", flaky)
    out = _run(cell, seconds=1.0)
    assert out["failed"] == 1
    assert out["checks"]["failed"]["value"] == 1
    assert out["correct"] is False


def test_rows_left_out_by_the_filter_are_caught(monkeypatch):
    from repro.core.backend import JaxDecodeBackend
    real = JaxDecodeBackend.range_mask

    def half(self, values, lo, hi):
        m = np.asarray(real(self, values, lo, hi)).copy()
        m[len(m) // 2:] = False
        return m

    monkeypatch.setattr(JaxDecodeBackend, "range_mask", half)
    out = _run("alexandria.filter")
    assert out["correct"] is False


def test_benchmark_names_its_metric_readers_and_traffic():
    b = spec.benchmark()
    for m in b["end_to_end"] + b["per_layer"]:
        assert os.path.exists(f"{spec.HERE}/metrics/{m['name']}.py")
    for w in b["workloads"]:
        assert os.path.exists(f"{spec.HERE}/traffic/{w['traffic']}.json")


def test_traced_run_reports_per_layer_metrics_only(capsys):
    out = _run("alexandria.scan", traced=True)
    assert _sound(out), out["checks"]
    per_layer = {m["name"] for m in spec.benchmark()["per_layer"]}
    assert set(out["metrics"]) <= per_layer
    # the CPU has no device trace; the backend's counters still read
    assert "host_page_share.query" in out["metrics"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    # the store's spans are on the host's lines of the same trace
    layers = {m: v["value"] for m, v in out["metrics"].items()
              if m.endswith("_ms.query")}
    assert set(layers) == {n + ".query" for n in program_spans.LAYERS}
    assert layers["reader_ms.query"] > 0 and layers["stage_ms.query"] > 0
    side = json.loads(next(x for x in capsys.readouterr().err.splitlines()
                           if x.startswith("{")))
    assert side["spans"]["queries"] == out["attempted"]
    assert side["required_values"] > 0


def test_run_refuses_without_a_tpu(capsys, monkeypatch):
    import sys

    from tpubench import run
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    assert run.main(["--workload", "alexandria.filter", "--seed", "1",
                     "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_unknown_device_kind_is_refused(monkeypatch):
    class FakeTPU:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda: [FakeTPU()])
    with pytest.raises(harness.NoChip, match="no peaks"):
        harness.check_device(1)


def test_q6_columns_follow_the_spec():
    w = spec.cell("tpch_lineitem_sf1.q6")
    cfg = w["config_file"]
    gen = harness.load_module(f"{spec.HERE}/configs/tpch_lineitem_sf1.py",
                              "tpch_gen")
    rows = 50_000
    orderdate, c = gen.orders_and_lines(cfg, rows, SEED)
    assert len(c["l_orderkey"]) == rows
    assert c["l_discount"].min() >= 0 and c["l_discount"].max() <= 10
    assert c["l_quantity"].min() >= 100 and c["l_quantity"].max() <= 5000
    assert (c["l_quantity"] % 100 == 0).all()
    gap = c["l_shipdate"] - orderdate
    assert gap.min() >= 1 and gap.max() <= 121
    assert c["l_tax"].min() >= 0 and c["l_tax"].max() <= 8
    assert (np.diff(c["l_orderkey"]) >= 0).all()
    assert ((c["l_orderkey"] & 31) < 8).all()  # dbgen's sparse keys
    _, per = np.unique(c["l_orderkey"], return_counts=True)
    assert per.min() >= 1 and per.max() <= 7
    price = c["l_extendedprice"] // (c["l_quantity"] // 100)
    assert price.min() >= 90000 and price.max() <= 90000 + 20000 + 99900
    # Q6 matches about 1.9% of the rows over its parameter sets
    sets = spec.param_sets(w["traffic_file"])
    arrays = dict(c, id=np.arange(rows))
    share = np.mean([reference.mask_of(
        spec.instantiate(w["traffic_file"]["queries"][0], p)["where"],
        arrays, rows).mean() for p in sets])
    assert 0.012 < share < 0.026


def test_every_seed_gets_the_same_work_in_another_order():
    w = spec.cell("alexandria.filter")
    t = w["traffic_file"]
    a = [q for q, _ in zip(spec.closed_requests(t, 1), range(80))]
    b = [q for q, _ in zip(spec.closed_requests(t, 2), range(80))]
    assert sorted(json.dumps(q) for q in a) == sorted(json.dumps(q)
                                                      for q in b)
    assert a != b
    lk = spec.cell("alexandria.lookup")["traffic_file"]
    s1 = spec.open_schedule(lk, 1000, 5.0, 1)
    s2 = spec.open_schedule(lk, 1000, 5.0, 2)
    assert sorted(q["where"][3] for q in s1) == sorted(q["where"][3]
                                                       for q in s2)
    gaps = [np.diff([q["due"] for q in s] + [5.0]) for s in (s1, s2)]
    assert np.allclose(sorted(gaps[0]), sorted(gaps[1]))
    assert all(0 <= q["due"] < 5.0 for q in s1)


def test_reference_compare_counts_each_wrong_value():
    ref = {"table": {"a": np.array([1, 2, 3]), "b": np.array([1., 2., 3.])}}
    same = {"table": {"a": np.array([1, 2, 3]), "b": np.array([1., 2., 3.])}}
    assert reference.compare(same, ref) == (0, 0.0)
    off = {"table": {"a": np.array([1, 9, 3]), "b": np.array([1., 2.])}}
    assert reference.compare(off, ref)[0] == 1 + 3
    assert reference.compare({"agg": {"x": {"sum": 5}}},
                             {"agg": {"x": {"sum": 6}}})[0] == 1
    assert reference.compare({"agg": {"x": {"mean": 1.5}}},
                             {"agg": {"x": {"mean": 1.0}}}) == (0, 0.5)
    assert drive.rows_to_table([{"a": 1}, {"a": 2}])["table"]["a"].tolist() \
        == [1, 2]


@pytest.mark.parametrize("traffic", ["lookup_fits", "ycsb_a"])
def test_a_listed_mix_is_added_as_files_only(traffic, monkeypatch):
    """Mixes that PERF.md §7 lists for later run from their traffic file
    and a workload entry, with no code of their own."""
    name = "alexandria." + traffic
    real = spec.benchmark

    def bench():
        b = real()
        b["workloads"].append({"name": name, "config": "alexandria",
                               "traffic": traffic, "chips": 1,
                               "why": "a mix listed for later"})
        return b

    monkeypatch.setattr(spec, "benchmark", bench)
    out = _run(name, seconds=1.0)
    assert _sound(out), out["checks"]
    assert out["correct"] is True
    t = spec.cell(name)["traffic_file"]
    sched = spec.open_schedule(t, ROWS, 10.0, SEED)
    zipf = t["params"]["key"]["zipf"]
    assert len({q["where"][3] for q in sched}) <= zipf.get("items", ROWS)


@pytest.mark.parametrize("template", [
    {"group_by": ["spg"], "agg": {"energy": ["sum", "mean", "min", "max"],
                                  "n_sites": ["sum", "mean", "min", "max"],
                                  "*": "count"}, "terminal": "table"},
    {"where": ["cmp", "energy", "<", {"sub": [-30.0, {"div": [1, 4]}]}],
     "computed": {"x": ["div", ["sub", 100, ["field", "n_sites"]],
                        ["field", "spg"]],
                  "y": ["mul", ["sub", ["field", "e_form"], 1.5], 2]},
     "select": ["id", "x", "y"], "terminal": "table"},
    {"where": ["cmp", "n_sites", ">=", {"add": [3, {"mul": [2, 2]}]}],
     "agg": {"e_form": ["min", "max", "sum", "mean"], "*": "count"},
     "terminal": "agg"},
], ids=["grouped", "arithmetic", "ungrouped"])
def test_query_forms_equal_the_store(template):
    """Each form a traffic file may use answers alike in the store and in
    the reference."""
    from repro.core import ParquetDB
    from repro.core.backend import set_backend
    w = spec.cell("alexandria.filter")
    arrays = harness.make_data(w["config_file"], "alexandria", ROWS, SEED)
    q = spec.instantiate(template, {})
    set_backend("jax")
    try:
        with tempfile.TemporaryDirectory() as d:
            db = ParquetDB(os.path.join(d, "db"), "a", page_rows=8192)
            db.create(harness.to_table(arrays))
            got = drive.normalise(drive.run(db, q))
    finally:
        set_backend(None)
    wrong, gap = reference.compare(got, reference.evaluate(q, arrays),
                                   q.get("group_by"))
    assert wrong == 0 and gap < 1e-6
    low = reference.evaluate(q, reference.lower_precision(arrays))
    assert reference.compare(low, reference.evaluate(q, arrays),
                             q.get("group_by")) != (0, 0.0)


def test_a_write_the_store_drops_is_caught(monkeypatch):
    """A write acknowledged but never applied: reads after its answer and
    the read-back after the window still show the data's own values."""
    from repro.core import ParquetDB
    real = spec.benchmark

    def bench():
        b = real()
        b["workloads"].append({"name": "alexandria.ycsb_a",
                               "config": "alexandria", "traffic": "ycsb_a",
                               "chips": 1, "why": "reads and writes"})
        return b

    monkeypatch.setattr(spec, "benchmark", bench)
    monkeypatch.setattr(ParquetDB, "update",
                        lambda self, rows, *a, **kw: len(rows))
    out = _run("alexandria.ycsb_a", seconds=1.0)
    assert out["correct"] is False
    assert out["checks"]["lost_writes"]["value"] > 0
