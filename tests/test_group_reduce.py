"""Grouped aggregates over computed columns, and the exact device
reduction beneath them.

- ``group_reduce`` (Pallas, interpret mode) against int64 numpy at the
  values where 32-bit lanes would break: every row at TPC-H Q1's largest
  ``charge`` over a 131,072-row chunk, negative values, one group holding
  everything;
- the decode backend's gate: float inputs, nulls, many groups and
  arithmetic the host would wrap stay on the host;
- ``group_by().agg()`` over ``select(**computed)`` columns under both
  backends, against a plain numpy oracle, with string, numeric and null
  keys and integer and float inputs;
- string keys factorized from offsets and blob give the answers of the
  object path.
"""
import os

import numpy as np
import pytest

from repro.core import ParquetDB, Table, backend, field
from repro.core.expressions import FieldRef
from repro.core.query import _factorize, _partial_groups
from repro.core.table import Column

jax = pytest.importorskip("jax")

from repro.kernels.group_reduce import CHUNK  # noqa: E402
from repro.kernels.ops import group_sums_on_device  # noqa: E402

# Q1's computed inputs over (quantity, extendedprice, discount, tax)
DISC_PRICE = ("*", ("x", 1), ("-", ("c", 100), ("x", 2)))
CHARGE = ("*", DISC_PRICE, ("+", ("c", 100), ("x", 3)))
Q1_TERMS = ((("x", 0), 2), (("x", 1), 2), (DISC_PRICE, 3), (CHARGE, 3),
            (("x", 2), 1))


@pytest.fixture()
def jax_backend():
    be = backend.get_backend("jax")
    yield be
    backend.set_backend(None)


def _exact(codes, groups, leaves, fn):
    v = fn(*(np.asarray(x, np.int64) for x in leaves))
    return np.array([v[codes == g].sum() for g in range(groups)], np.int64)


def test_charge_at_its_largest_over_a_whole_chunk():
    """Every row at Q1's largest charge, all in one group: each row is
    about 2**37 and the sum about 2**54, far past 32-bit lanes."""
    n = CHUNK
    ep = np.full(n, 50 * 209_900, np.int64)  # 50 units at the top price
    disc, tax = np.zeros(n, np.int64), np.full(n, 8, np.int64)
    qty = np.full(n, 5000, np.int64)
    codes = np.zeros(n, np.int64)
    counts, sums = group_sums_on_device(codes, 1, [qty, ep, disc, tax],
                                        Q1_TERMS, interpret=True)
    assert counts.tolist() == [n]
    charge = ep * (100 - disc) * (100 + tax)
    assert sums[3].tolist() == [int(charge.sum())]
    assert sums[3][0] == n * 50 * 209_900 * 100 * 108
    assert sums[2].tolist() == [int((ep * 100).sum())]


def test_random_groups_negative_values_and_padding():
    rng = np.random.default_rng(5)
    n = CHUNK + 777  # two chunks, the second mostly padding
    codes = rng.integers(0, 5, n)
    a = rng.integers(-(1 << 31), 1 << 31, n)
    b = rng.integers(-(1 << 31), 1 << 31, n)
    c = rng.integers(-1000, 1000, n)
    terms = ((("x", 0), 3), (("-", ("x", 1), ("x", 0)), 3),
             (("*", ("x", 0), ("x", 1)), 5),
             (("*", ("+", ("x", 2), ("c", -7)), ("x", 0)), 4))
    counts, sums = group_sums_on_device(codes, 5, [a, b, c], terms,
                                        interpret=True)
    assert counts.tolist() == np.bincount(codes, minlength=5).tolist()
    fns = [lambda a, b, c: a, lambda a, b, c: b - a,
           lambda a, b, c: a * b, lambda a, b, c: (c - 7) * a]
    for got, fn in zip(sums, fns):
        # int64 wraps alike on both sides: the sums are exact mod 2**64
        with np.errstate(over="ignore"):
            want = _exact(codes, 5, [a, b, c], fn)
        np.testing.assert_array_equal(got, want)


def test_rows_of_no_group_count_nowhere():
    codes = np.array([0, 3, 3, 1, 0], np.int64)
    vals = np.array([-5, 7, -(1 << 31), (1 << 31) - 1, 2], np.int64)
    counts, (s,) = group_sums_on_device(codes, 4, [vals],
                                        ((("x", 0), 3),), interpret=True)
    assert counts.tolist() == [2, 1, 0, 2]
    assert s.tolist() == [-3, (1 << 31) - 1, 0, 7 - (1 << 31)]


def _table(**cols):
    return Table.from_pydict(cols)


def test_gate_sends_float_inputs_and_nulls_to_the_host(jax_backend):
    t = _table(f=np.arange(10, dtype=np.float32),
               i=np.arange(10, dtype=np.int64))
    codes = np.zeros(10, np.int64)
    before = jax_backend.host_pages["group_rows"]
    assert jax_backend.group_sums(codes, 1, [FieldRef("f")], t) is None
    assert jax_backend.group_sums(codes, 1, [field("i") * 0.5], t) is None
    assert jax_backend.group_sums(codes, 1, [None], t) is None
    assert jax_backend.group_sums(codes, 65, [FieldRef("i")], t) is None
    assert jax_backend.host_pages["group_rows"] == before + 40
    nulls = Table.from_pydict({"i": Column.numeric(
        np.arange(10, dtype=np.int64), validity=np.arange(10) % 2 == 0)})
    assert jax_backend.group_sums(codes, 1, [FieldRef("i")], nulls) is None


def test_gate_keeps_wrapping_and_wide_inputs_on_the_host(jax_backend):
    codes = np.zeros(4, np.int64)
    wide = _table(w=np.array([0, 1, 2, 1 << 40], np.int64))
    assert jax_backend.group_sums(codes, 1, [FieldRef("w")], wide) is None
    # int32 * int32 wraps in the host's own arithmetic: not exact to copy
    small = _table(s=np.array([1 << 20, 3, 4, 5], np.int32))
    assert jax_backend.group_sums(codes, 1, [field("s") * field("s")],
                                  small) is None
    before = jax_backend.device_pages["group_rows"]
    counts, (s,) = jax_backend.group_sums(codes, 1, [field("s") + 1], small)
    assert jax_backend.device_pages["group_rows"] == before + 4
    assert counts.tolist() == [4] and s.dtype == np.int32
    assert s.tolist() == [(1 << 20) + 16]


def test_limb_counts_do_not_follow_the_data_below_2_38():
    """One program for every morsel and parameter set: a term's limb
    count moves only for values past 2**38."""
    from repro.core.backend import _sum_plan
    small = _table(a=np.array([0, 3], np.int64), b=np.array([1, 2], np.int64))
    large = _table(a=np.array([0, 10**8], np.int64),
                   b=np.array([-(1 << 31), (1 << 31) - 1], np.int64))
    terms = [FieldRef("a"), field("a") * field("b") * 2]
    assert [k for _, k in _sum_plan(terms[:1], small)[0]] == [3]
    assert _sum_plan(terms[:1], small)[0] == _sum_plan(terms[:1], large)[0]
    assert [k for _, k in _sum_plan(terms, large)[0]] == [3, 5]


def test_partial_of_floats_is_the_host_partial(jax_backend):
    backend.set_backend("jax")
    t = _table(k=np.array([1, 2, 1, 2], np.int64),
               x=np.array([0.5, 1.5, 2.5, 3.5]))
    p = _partial_groups(t, ["k"], {"x": ["mean"]})
    assert p.cols["x"]["sum"].tolist() == [3.0, 5.0]
    assert p.cols["x"]["sum"].dtype == np.float64


# ---------------------------------------------------------------------------
# grouped aggregates over computed columns, through the store
# ---------------------------------------------------------------------------
N = 3000
RNG = np.random.default_rng(17)
DATA = {
    "flag": np.array(["A", "N", "R"], object)[RNG.integers(0, 3, N)],
    "status": np.array(["F", "O"], object)[RNG.integers(0, 2, N)],
    "num": RNG.integers(0, 4, N),
    "opt": [None if i % 7 == 0 else int(i % 3) for i in range(N)],
    "price": RNG.integers(90_000, 10_500_000, N),
    "disc": RNG.integers(0, 11, N),
    "tax": RNG.integers(0, 9, N),
    "f": RNG.standard_normal(N).astype(np.float32),
}
COMPUTED = {"dp": field("price") * (100 - field("disc")),
            "charge": field("price") * (100 - field("disc"))
            * (100 + field("tax")),
            "ff": field("f") * 2 - 1}


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    db = ParquetDB(os.path.join(str(tmp_path_factory.mktemp("g")), "db"),
                   row_group_rows=1024, page_rows=256)
    db.create(Table.from_pydict(dict(DATA)))
    return db


def _oracle(keys, spec, where):
    cols = {k: np.asarray(v, object) if k == "opt" else np.asarray(v)
            for k, v in DATA.items()}
    with np.errstate(all="ignore"):
        cols["dp"] = cols["price"] * (100 - cols["disc"])
        cols["charge"] = cols["dp"] * (100 + cols["tax"])
        cols["ff"] = cols["f"] * 2 - 1
    keep = cols["price"] >= where
    groups = {}
    for i in np.flatnonzero(keep):
        groups.setdefault(tuple(cols[k][i] for k in keys), []).append(i)
    out = {}
    for kv, rows in groups.items():
        ent = {"count": len(rows)}
        for c, ops in spec.items():
            if c == "*":
                continue
            vals = cols[c][rows]
            for op in ops:
                ent[f"{c}_{op}"] = (int(vals.sum()) if op == "sum"
                                    and vals.dtype.kind == "i"
                                    else vals.sum() if op == "sum"
                                    else vals.sum() / len(vals)
                                    if op == "mean" else len(vals))
        out[kv] = ent
    return out


@pytest.mark.parametrize("be", ["numpy", "jax"])
@pytest.mark.parametrize("keys", [["flag", "status"], ["num"], ["opt"],
                                  []], ids=["strings", "numeric", "nulls",
                                            "global"])
@pytest.mark.parametrize("spec", [
    {"price": ["sum", "mean"], "dp": "sum", "charge": ["sum", "count"],
     "disc": "mean", "*": "count"},
    {"ff": ["sum", "mean"], "charge": "sum", "*": "count"},
], ids=["integer", "float"])
def test_grouped_computed_equals_the_oracle(store, be, keys, spec):
    backend.set_backend(be)
    try:
        t = (store.query().where(field("price") >= 2_000_000)
             .select(**COMPUTED).group_by(*keys).agg(spec).to_table())
    finally:
        backend.set_backend(None)
    want = _oracle(keys, {c: [o] if isinstance(o, str) else o
                          for c, o in spec.items()}, 2_000_000)
    assert t.num_rows == len(want)
    for r in t.to_pylist():
        ent = want[tuple(r[k] for k in keys)]
        for name, v in ent.items():
            if isinstance(v, (float, np.floating)):
                assert r[name] == pytest.approx(float(v), rel=1e-6), name
            else:
                assert r[name] == v, name
    assert t.column("charge_sum").dtype.np == np.int64


def test_grouped_computed_runs_on_the_device_with_no_host_evaluation(
        store, jax_backend, monkeypatch):
    from repro.core.expressions import Arith
    backend.set_backend("jax")
    real = Arith.evaluate_column

    def refused(self, table):
        # typing the computed column evaluates it over no rows
        assert table.num_rows == 0, "a computed sum evaluated on the host"
        return real(self, table)

    monkeypatch.setattr(Arith, "evaluate_column", refused)
    before = jax_backend.device_pages["group_rows"]
    t = (store.query().select(**{k: COMPUTED[k] for k in ("dp", "charge")})
         .group_by("flag").agg({"dp": "sum", "charge": "sum"}).to_table())
    assert jax_backend.device_pages["group_rows"] == before + N
    assert sum(t.column("charge_sum").values.tolist()) == int(
        (np.asarray(DATA["price"]) * (100 - DATA["disc"])
         * (100 + DATA["tax"])).sum())


def test_grouped_query_reads_only_the_inputs(store):
    q = (store.query().where(field("price") >= 2_000_000)
         .select(**COMPUTED).group_by("flag", "status")
         .agg({"price": "sum", "charge": "sum", "*": "count"}))
    cp = q._compile()
    assert sorted(cp.scan_cols) == ["disc", "flag", "price", "status", "tax"]
    assert [n for n, _ in cp.computed] == ["charge"]


def test_unknown_agg_column_names_the_computed_ones(store):
    with pytest.raises(KeyError, match="nope"):
        store.query().select(**COMPUTED).group_by("flag").agg(
            {"nope": "sum"})
    with pytest.raises(TypeError):
        store.query().select(**COMPUTED).group_by("num").agg(
            {"flag": "sum"})


# ---------------------------------------------------------------------------
# string keys from offsets and blob
# ---------------------------------------------------------------------------
def _object_factorize(col):
    pl = col.to_pylist()
    valid = np.array([v is not None for v in pl], bool)
    present = np.array([v for v in pl if v is not None], dtype=object)
    u, inv = np.unique(present, return_inverse=True)
    codes = np.zeros(len(pl), np.int64)
    codes[valid] = inv
    keys = list(u)
    if not valid.all():
        codes[~valid] = len(keys)
        keys.append(None)
    return codes, keys


STRINGS = {
    "equal": ["N", "R", "A", "N", "A", "R", "R"],
    "equal_wide": ["abcdefghij", "abcdefghiz", "abcdefghij", "zzzzzzzzzz"],
    "equal_bytes": ["ab", "é", "ba", "ab", "zz", "a\x00"],
    "equal_nulls": ["N", None, "R", "N", None, "A"],
    "all_empty": ["", "", ""],
    "mixed": ["a", "", "ab", "a\x00", "b", "ab", "", "é", "aé", "a"],
    "mixed_long": ["TAKE BACK RETURN", "NONE", "COLLECT COD",
                   "DELIVER IN PERSON", "NONE", "TAKE BACK", "N", "",
                   "TAKE BACK RETURN\x00", "x" * 40],
    "nulls": ["x", None, "y", None, "x", "", None],
    "all_null": [None, None],
    "empty": [],
}


@pytest.mark.parametrize("name", list(STRINGS))
def test_string_factorize_equals_the_object_path(name):
    col = Column.strings(STRINGS[name])
    codes, keys = _factorize(col)
    want_codes, want_keys = _object_factorize(col)
    assert keys == want_keys
    assert codes.tolist() == want_codes.tolist()


@pytest.mark.parametrize("name", ["equal", "equal_bytes", "equal_nulls",
                                  "mixed", "mixed_long", "nulls"])
def test_string_minmax_per_group_equals_python(name):
    vals = STRINGS[name]
    n = len(vals)
    t = Table.from_pydict({"g": np.arange(n) % 3,
                           "s": Column.strings(vals)})
    p = _partial_groups(t, ["g"], {"s": ["min", "max", "count"]})
    for gi, (k,) in enumerate(p.keys):
        seg = [v for i, v in enumerate(vals) if i % 3 == k and v is not None]
        assert p.cols["s"]["min"][gi] == (min(seg) if seg else None)
        assert p.cols["s"]["max"][gi] == (max(seg) if seg else None)
        assert p.cols["s"]["count"][gi] == len(seg)


@pytest.mark.parametrize("vals", [
    np.array([5, -3, 5, 120, -128], np.int8),
    np.array([7, 7, 7], np.int64),
    np.array([2**63 + 5, 2**63 + 1, 2**63 + 5], np.uint64),
    np.array([0, 10**12, -10**12, 0], np.int64),  # too wide for a table
    np.array([], np.int32),
], ids=["int8", "one", "uint64", "wide", "empty"])
def test_integer_codes_equal_numpy_unique(vals):
    from repro.core.query import _sorted_codes
    u, inv = _sorted_codes(vals)
    want_u, want_inv = np.unique(vals, return_inverse=True)
    assert u.dtype == want_u.dtype
    assert u.tolist() == want_u.tolist()
    assert inv.tolist() == want_inv.tolist()
