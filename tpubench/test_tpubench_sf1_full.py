"""The full-width TPC-H LINEITEM configuration and its Q1 cell on the CPU
at a tiny size: the generator keeps clause 4.2.3's domains and relations
and repeats ``tpch_lineitem_sf1``'s draws for the columns both hold; Q1
through the store equals the plain reference under both decode backends,
reads exactly its seven columns and reduces every morsel on the device;
and the cell's run is ``correct``."""
import os
import tempfile
import time

import numpy as np
import pytest

from tpubench import drive, harness, reference, spec

jax = pytest.importorskip("jax")

ROWS = 20_000
SEED = 3_000_000_019
CELL = "tpch_lineitem_sf1_full.q1"
SHARED = ["l_orderkey", "l_quantity", "l_extendedprice", "l_discount",
          "l_tax", "l_shipdate", "l_returnflag", "l_linestatus"]
Q1_COLUMNS = ["l_discount", "l_extendedprice", "l_linestatus",
              "l_quantity", "l_returnflag", "l_shipdate", "l_tax"]


@pytest.fixture(scope="module")
def full():
    w = spec.cell(CELL)
    return w, harness.make_data(w["config_file"], w["config"], ROWS, SEED)


def _q1(delta):
    t = spec.load_json(f"{spec.HERE}/traffic/q1.json")
    return spec.instantiate(t["queries"][0], {"delta": delta})


def test_the_shared_columns_are_tpch_lineitem_sf1s(full):
    _, a = full
    w = spec.cell("tpch_lineitem_sf1.q6")
    b = harness.make_data(w["config_file"], w["config"], ROWS, SEED)
    assert sorted(set(b) - {"id"}) == sorted(SHARED)
    for c in SHARED:
        assert a[c].dtype == b[c].dtype, c
        np.testing.assert_array_equal(a[c], b[c], err_msg=c)
    for delta in (60, 90, 120):
        q = _q1(delta)
        assert reference.compare(reference.evaluate(q, a),
                                 reference.evaluate(q, b),
                                 q["group_by"]) == (0, 0.0)


def test_the_columns_keep_the_specifications_domains(full):
    w, a = full
    cfg = w["config_file"]
    assert sorted(set(a) - {"id"}) == sorted(set(cfg["columns"]) - {"id"})
    now = (np.datetime64("-".join(f"{x:02d}" for x in cfg["current_date"]))
           - np.datetime64("1970-01-01")).astype(int)
    receipt, ship = a["l_receiptdate"], a["l_shipdate"]
    assert ((a["l_returnflag"] == b"N") == (receipt > now)).all()
    assert (1 <= receipt - ship).all() and (receipt - ship <= 30).all()
    # line numbers run 1 .. k within each order of k lines
    _, first, per = np.unique(a["l_orderkey"], return_index=True,
                              return_counts=True)
    lines = a["l_linenumber"]
    assert (lines[first] == 1).all()
    assert (np.diff(lines)[np.diff(a["l_orderkey"]) == 0] == 1).all()
    assert (lines[first + per - 1] == per).all()
    pk, sk = a["l_partkey"], a["l_suppkey"]
    assert pk.min() >= 1 and pk.max() <= cfg["parts"]
    s = cfg["suppliers"]
    assert sk.min() >= 1 and sk.max() <= s
    cands = [(pk + i * (s // 4 + (pk - 1) // s)) % s + 1 for i in range(4)]
    assert np.any(np.stack(cands) == sk, axis=0).all()
    retail = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
    assert (a["l_extendedprice"] == a["l_quantity"] // 100 * retail).all()
    assert set(a["l_shipinstruct"]) <= set(
        cfg["columns"]["l_shipinstruct"]["values"])
    assert set(a["l_shipmode"]) == set(cfg["columns"]["l_shipmode"]["values"])
    n = np.array([len(x) for x in a["l_comment"]])
    assert n.min() >= 10 and n.max() <= 43


def test_commit_dates_follow_the_order_date(full):
    """commit - orderdate in 30 .. 90 and ship - orderdate in 1 .. 121
    hold for one order date per order."""
    _, a = full
    _, first = np.unique(a["l_orderkey"], return_index=True)
    c, s = a["l_commitdate"].astype(int), a["l_shipdate"].astype(int)
    # some one date lies in [commit - 90, commit - 30] and in
    # [ship - 121, ship - 1] for every line of the order
    lo = np.maximum.reduceat(np.maximum(c - 90, s - 121), first)
    hi = np.minimum.reduceat(np.minimum(c - 30, s - 1), first)
    assert (lo <= hi).all()
    # and the spread of commit dates within an order is at most 60 days
    spread = np.maximum.reduceat(c, first) - np.minimum.reduceat(c, first)
    assert spread.max() <= 60


def test_strings_are_stored_with_their_own_lengths(full):
    _, a = full
    t = harness.to_table(a)
    col = t.column("l_comment")
    lens = np.diff(col.offsets)
    assert lens.tolist() == [len(x) for x in a["l_comment"]]
    assert b"\x00" not in col.blob.tobytes()
    assert t.column("l_shipmode").to_pylist() == list(a["l_shipmode"])


@pytest.mark.parametrize("be", ["numpy", "jax"])
def test_q1_through_the_store_equals_the_reference(full, be):
    from repro.core import ParquetDB
    from repro.core.backend import get_backend, set_backend
    w, a = full
    cfg = w["config_file"]
    set_backend(be)
    try:
        with tempfile.TemporaryDirectory() as d:
            db = ParquetDB(os.path.join(d, "db"), "q1",
                           page_rows=int(cfg["page_rows"]),
                           row_group_rows=8192)
            db.create(harness.to_table(a))
            for delta in (60, 97, 120):
                q = _q1(delta)
                got = drive.normalise(drive.run(db, q))
                assert reference.compare(got, reference.evaluate(q, a),
                                         q["group_by"]) == (0, 0.0), delta
            if be == "jax":
                be_ = get_backend("jax")
                before = dict(be_.host_pages)
                drive.run(db, _q1(90))
                assert be_.host_pages.get("group_rows", 0) == \
                    before.get("group_rows", 0)
    finally:
        set_backend(None)


def test_q1_reads_exactly_its_seven_columns(full):
    from repro.core import ParquetDB
    w, a = full
    with tempfile.TemporaryDirectory() as d:
        db = ParquetDB(os.path.join(d, "db"), "q1", page_rows=8192)
        db.create(harness.to_table(a))
        q = _q1(90)
        query = db.query().where(drive.expr(q["where"])).select(
            **{k: drive._arith(v) for k, v in q["computed"].items()})
        grouped = query.group_by(*q["group_by"]).agg(q["agg"])
        cp = grouped._compile()
        read = set(cp.scan_cols) | set(drive.expr(q["where"]).columns())
        assert sorted(read) == Q1_COLUMNS
        assert sorted(cp.plan._read_schema.names) == Q1_COLUMNS


def test_the_cell_is_correct_at_a_tiny_size():
    out = harness.run_cell(CELL, SEED, 0.5, False, time.perf_counter(),
                           rows=ROWS, require_chip=False)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["answers_checked"]["value"] == out["attempted"]


def test_a_traced_run_reports_the_device_group_share(capsys):
    out = harness.run_cell(CELL, SEED, 0.5, True, time.perf_counter(),
                           rows=ROWS, require_chip=False)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["device_group_share.query"]["value"] == 100.0
    assert out["metrics"]["host_page_share.query"]["value"] == 0.0
    # the CPU has no device trace, so no kernel time to divide by
    assert "group_reduce_roofline.query" not in out["metrics"]


def test_the_roofline_reads_the_kept_answers(full):
    """4 B a kept row for the group code and Q1's four data columns, at
    the peak bandwidth, over the kernel's device time; nothing unless
    every completed query was kept."""
    metric = harness.load_module(
        f"{spec.HERE}/metrics/group_reduce_roofline.query.py", "grr")
    from repro.core import Table
    ans = Table.from_pydict({"count": np.array([600, 400], np.int64)})
    r = {"trace": {"program_s": {"jit_group_reduce": 1e-6}},
         "peaks": {"hbm_bytes_per_s": 1e9}, "completed": 2,
         "kept": [(_q1(60), ans), (_q1(70), ans)]}
    assert metric.read(r) == pytest.approx(100.0 * 4 * 5 * 2000 / 1e9 / 1e-6)
    assert metric.read(dict(r, completed=3)) is None
    assert metric.read(dict(r, trace=None)) is None


@pytest.mark.parametrize("template", [
    {"where": ["cmp", "l_tax", ">=", 2],
     "computed": {"net": ["mul", ["field", "l_extendedprice"],
                          ["sub", 100, ["field", "l_discount"]]]},
     "group_by": ["l_shipmode", "l_returnflag"],
     "agg": {"net": ["sum", "mean"], "l_quantity": "sum", "*": "count"}},
    {"computed": {"share": ["div", ["field", "l_discount"], 100],
                  "late": ["sub", ["field", "l_receiptdate"],
                           ["field", "l_commitdate"]]},
     "group_by": ["l_linenumber"],
     "agg": {"share": ["sum", "mean"], "late": ["sum", "mean"],
             "*": "count"}},
    {"computed": {"w": ["mul", ["field", "l_suppkey"], ["field", "l_partkey"]]},
     "group_by": ["l_shipinstruct"],
     "agg": {"w": "sum", "l_tax": ["sum", "min", "max"]}},
], ids=["strings-integer", "numeric-float", "strings-minmax"])
@pytest.mark.parametrize("be", ["numpy", "jax"])
def test_grouped_computed_equals_the_reference(full, template, be):
    """Grouped aggregates over computed columns, integer and float, by
    the mixed-length string columns and a numeric one, against the plain
    reference."""
    from repro.core import ParquetDB
    from repro.core.backend import set_backend
    _, a = full
    q = spec.instantiate(dict(template, terminal="table"), {})
    set_backend(be)
    try:
        with tempfile.TemporaryDirectory() as d:
            db = ParquetDB(os.path.join(d, "db"), "g", page_rows=2048,
                           row_group_rows=8192)
            db.create(harness.to_table(a))
            got = drive.normalise(drive.run(db, q))
    finally:
        set_backend(None)
    wrong, gap = reference.compare(got, reference.evaluate(q, a),
                                   q["group_by"])
    assert wrong == 0 and gap < 1e-12
