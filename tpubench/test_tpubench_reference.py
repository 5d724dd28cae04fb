"""The plain reference on TPC-H Q1 (``traffic/q1.json``, no cell yet) and
the value counts behind ``device_roofline.query``: Q1's answers against
the specification's SQL run by ``sqlite3``, its control, and the counts of
the cells' own templates, pinned."""
import datetime
import sqlite3

import numpy as np
import pytest

from tpubench import harness, reference, spec

ROWS = 20_000
SEED = 3_000_000_019
Q1 = spec.load_json(f"{spec.HERE}/traffic/q1.json")

# reference.device_values at ROWS and SEED, summed over every parameter
# set of the template; a computed column counted as a data column would
# move them
PINNED = {
    ("alexandria.filter", "energy_range"): 1_708_588,
    ("tpch_lineitem_sf1.q6", "q6"): 4_831_230,
    ("alexandria.scan", "full_scan"): 100_000,
    ("alexandria.scan", "count_mean_by_spg"): 40_000,
}

# clause 2.4.1.1, with DELTA as a parameter
Q1_SQL = """
select l_returnflag, l_linestatus,
       sum(l_quantity) as sum_qty,
       sum(l_extendedprice) as sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
       avg(l_quantity) as avg_qty,
       avg(l_extendedprice) as avg_price,
       avg(l_discount) as avg_disc,
       count(*) as count_order
from lineitem
where l_shipdate <= date('1998-12-01', '-' || ? || ' days')
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""
# each SQL column: (the reference's column, its scale)
SQL_COLUMNS = [("l_returnflag", None), ("l_linestatus", None),
               ("l_quantity_sum", 100), ("l_extendedprice_sum", 100),
               ("disc_price_sum", 100 ** 2), ("charge_sum", 100 ** 3),
               ("l_quantity_mean", 100), ("l_extendedprice_mean", 100),
               ("l_discount_mean", 100), ("count", None)]


def _lineitem():
    w = spec.cell("tpch_lineitem_sf1.q6")
    return harness.make_data(w["config_file"], w["config"], ROWS, SEED)


def _q1(delta):
    return spec.instantiate(Q1["queries"][0], {"delta": delta})


@pytest.mark.parametrize("cell,template", sorted(PINNED))
def test_device_values_of_the_cells_are_pinned(cell, template):
    w = spec.cell(cell)
    t = w["traffic_file"]
    arrays = harness.make_data(w["config_file"], w["config"], ROWS, SEED)
    tmpl = next(x for x in t["queries"] if x["name"] == template)
    got = sum(reference.device_values(spec.instantiate(tmpl, p), arrays)
              for p in spec.param_sets(t) or [{}])
    assert got == PINNED[cell, template]


def test_q1_device_values_count_the_columns_its_expressions_read():
    arrays = _lineitem()
    q = _q1(90)
    matched = int(reference.mask_of(q["where"], arrays, ROWS).sum())
    assert 0.95 * ROWS < matched < ROWS
    # l_shipdate over every row; quantity, price, discount and tax over
    # the matched rows; the string flags and the computed names not at all
    assert reference.device_values(q, arrays) == ROWS + 4 * matched


def test_q1_spans_the_specification_parameter_range():
    assert [p["delta"] for p in spec.param_sets(Q1)] == list(range(60, 121))


@pytest.mark.parametrize("delta", [60, 90, 120])
def test_q1_reference_equals_the_specification_sql(delta):
    """The same rows in ``sqlite3`` as decimals and dates, queried with the
    specification's text: the reference's exact scaled sums, over their
    scale, and its means agree to float rounding; flags and counts are
    equal."""
    arrays = _lineitem()
    epoch = datetime.date(1970, 1, 1)
    con = sqlite3.connect(":memory:")
    con.execute("create table lineitem (l_quantity real, l_extendedprice "
                "real, l_discount real, l_tax real, l_returnflag text, "
                "l_linestatus text, l_shipdate text)")
    con.executemany("insert into lineitem values (?, ?, ?, ?, ?, ?, ?)", zip(
        (arrays["l_quantity"] / 100).tolist(),
        (arrays["l_extendedprice"] / 100).tolist(),
        (arrays["l_discount"] / 100).tolist(),
        (arrays["l_tax"] / 100).tolist(),
        np.char.decode(arrays["l_returnflag"]).tolist(),
        np.char.decode(arrays["l_linestatus"]).tolist(),
        [(epoch + datetime.timedelta(days=int(d))).isoformat()
         for d in arrays["l_shipdate"]]))
    rows = con.execute(Q1_SQL, (delta,)).fetchall()
    con.close()
    ref = reference.evaluate(_q1(delta), arrays)["table"]
    order = np.lexsort([ref["l_linestatus"].astype(str),
                        ref["l_returnflag"].astype(str)])
    assert len(rows) == len(order) == 4
    for (name, scale), got in zip(SQL_COLUMNS, zip(*rows)):
        want = ref[name][order]
        if scale is None:
            assert list(got) == want.tolist(), name
        else:
            assert np.asarray(got) == pytest.approx(
                want.astype(np.float64) / scale, rel=1e-9), name
    assert ref["charge_sum"].dtype == np.int64  # exact, not float


def test_q1_control_is_caught():
    arrays = _lineitem()
    low = reference.lower_precision(arrays)
    for delta in (60, 120):
        q = _q1(delta)
        wrong, _ = reference.compare(reference.evaluate(q, low),
                                     reference.evaluate(q, arrays),
                                     q["group_by"])
        assert wrong > 0
