"""Selection-vector late materialization: edge cases + counter reporting.

The two-phase reader turns the filter-column mask into a per-page selection
vector and materializes only the selected rows of payload columns.  These
tests pin the edge cases — empty selection, all-rows selection, all-null
pages, var-len/list/tensor payloads — and assert the result is always
row-identical to a full scan, with ``rows_skipped_late``/``bytes_saved_late``
reported by ``explain(execute=True)``.
"""
import os

import numpy as np
import pytest

from repro.core import (LoadConfig, NormalizeConfig, ParquetDB, Table,
                        TPQReader, backend, field, write_table)
from repro.core.fileformat import _page_stored_bytes
from repro.core.integrity import CorruptPageError
from repro.core.scan import ScanCounters


def norm(v):
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, dict):
        return {k: norm(x) for k, x in v.items()}
    if isinstance(v, list):
        return [norm(x) for x in v]
    return v


@pytest.fixture()
def mixed_file(tmp_path):
    """One file, 4 pages of 250 rows, every column kind as payload."""
    n = 1000
    rng = np.random.default_rng(5)
    t = Table.from_pydict({
        "k": np.arange(n),
        "f": rng.standard_normal(n),
        "s": [f"val_{i % 13}_{'x' * (i % 7)}" for i in range(n)],
        "t": rng.standard_normal((n, 2, 2)),
        "l": [[j for j in range(i % 4)] for i in range(n)],
        "ls": [[f"s{j}" for j in range(i % 3)] for i in range(n)],
    })
    p = str(tmp_path / "late.tpq")
    write_table(p, t, page_rows=250, row_group_rows=1000)
    return p, t


def _read(path, expr, **kw):
    c = ScanCounters()
    out = TPQReader(path).read(filter_expr=expr, counters=c, **kw)
    return out, c


class TestSelectionVector:
    def test_sparse_selection_all_kinds(self, mixed_file):
        p, t = mixed_file
        out, c = _read(p, (field("k") >= 100) & (field("k") < 103))
        assert out.num_rows == 3
        full = t.filter_mask(((np.arange(1000) >= 100) & (np.arange(1000) < 103)))
        assert norm(out.to_pylist()) == norm(full.to_pylist())
        assert c.rows_skipped_late > 0
        assert c.bytes_saved_late > 0

    def test_all_rows_selection_skips_nothing(self, mixed_file):
        p, t = mixed_file
        out, c = _read(p, field("k") >= 0)   # every row matches
        assert out.num_rows == 1000
        assert c.rows_skipped_late == 0
        assert c.bytes_saved_late == 0
        assert norm(out.to_pylist()) == norm(t.to_pylist())

    def test_empty_selection_yields_nothing(self, mixed_file):
        p, _ = mixed_file
        # explicit row-group selection is authoritative (no stats pruning)
        # and page pruning is off: every page reaches phase 1, every mask
        # comes back empty, no payload column is ever touched
        out, c = _read(p, field("k") < 0, row_groups=[0], prune_pages=False)
        assert out.num_rows == 0
        assert c.rows_skipped_late == 0   # nothing was kept to late-skip

    def test_single_row_per_page(self, mixed_file):
        p, t = mixed_file
        out, _ = _read(p, field("k").isin([10, 260, 510, 990]))
        assert sorted(out["k"].to_pylist()) == [10, 260, 510, 990]
        oracle = t.filter_mask(np.isin(np.arange(1000), [10, 260, 510, 990]))
        assert norm(out.to_pylist()) == norm(oracle.to_pylist())

    def test_all_null_payload_page(self, tmp_path):
        t = Table.from_pylist(
            [{"k": i, "v": None if i < 500 else float(i)} for i in range(1000)])
        p = str(tmp_path / "nulls.tpq")
        write_table(p, t, page_rows=250, row_group_rows=1000)
        out, c = _read(p, (field("k") >= 100) & (field("k") < 110))
        assert out["v"].to_pylist() == [None] * 10
        out2, _ = _read(p, (field("k") >= 700) & (field("k") < 705))
        assert out2["v"].to_pylist() == [700.0, 701.0, 702.0, 703.0, 704.0]

    def test_validity_respected_under_selection(self, tmp_path):
        t = Table.from_pylist(
            [{"k": i, "s": None if i % 3 == 0 else f"s{i}"} for i in range(500)])
        p = str(tmp_path / "vs.tpq")
        write_table(p, t, page_rows=100, row_group_rows=500)
        out, _ = _read(p, (field("k") >= 150) & (field("k") < 156))
        assert out["s"].to_pylist() == [None, "s151", "s152", None, "s154",
                                        "s155"]

    def test_multi_filter_columns(self, mixed_file):
        p, t = mixed_file
        expr = (field("k") < 300) & (field("s") == "val_5_")
        out, _ = _read(p, expr)
        ks = out["k"].to_pylist()
        assert ks and all(k < 300 and k % 13 == 5 and k % 7 == 0 for k in ks)


class TestFusedRangeMask:
    """The single-column range fast path (backend.range_mask) must be
    mask-identical to Expr.evaluate for every op and dtype mix."""

    @pytest.mark.parametrize("make_expr", [
        lambda f: f == 500, lambda f: f != 500,
        lambda f: f < 123, lambda f: f <= 123,
        lambda f: f > 877, lambda f: f >= 877,
        lambda f: (f >= 100) & (f < 200),
        lambda f: (f > 100) & (f <= 200),
    ], ids=["eq", "ne", "lt", "le", "gt", "ge", "range", "range-open"])
    @pytest.mark.parametrize("col,vals", [
        ("k", None),                       # int64
        ("f", None),                       # float64
    ])
    def test_ops_match_full_scan(self, tmp_path, make_expr, col, vals):
        n = 1000
        rng = np.random.default_rng(17)
        t = Table.from_pydict({
            "k": rng.integers(0, 1000, n),
            "f": rng.integers(0, 1000, n).astype(np.float64),
            "payload": [f"p{i}" for i in range(n)],
        })
        p = str(tmp_path / "rm.tpq")
        write_table(p, t, page_rows=250, row_group_rows=1000)
        expr = make_expr(field(col))
        out = TPQReader(p).read(filter_expr=expr, prune_pages=False)
        oracle = t.filter_mask(expr.evaluate(t))
        assert norm(out.to_pylist()) == norm(oracle.to_pylist())

    def test_float_strict_bounds_on_int_and_float(self, tmp_path):
        t = Table.from_pydict({"x": np.arange(10),
                               "y": np.arange(10) + 0.5,
                               "pay": ["z"] * 10})
        p = str(tmp_path / "fb.tpq")
        write_table(p, t, page_rows=5, row_group_rows=10)
        rd = TPQReader(p)
        out = rd.read(filter_expr=(field("x") > 2.5) & (field("x") < 5))
        assert out["x"].to_pylist() == [3, 4]
        out = rd.read(filter_expr=field("y") > 4.5)
        assert out["y"].to_pylist() == [4.5 + i for i in range(1, 6)]
        out = rd.read(filter_expr=field("x") == 2.5)
        assert out.num_rows == 0

    def test_projection_independent_near_2p53(self, tmp_path):
        # float bounds within one ulp of 2^53 must not take the exact-int
        # fused path while the residual path compares in rounded float64 —
        # results would depend on which columns were projected
        t = Table.from_pydict({"a": np.array([1, 2**53, 2**53 + 1], np.int64),
                               "pay": ["x", "y", "z"]})
        p = str(tmp_path / "p53.tpq")
        write_table(p, t, page_rows=3, row_group_rows=3)
        rd = TPQReader(p)
        expr = field("a") > float(2**53)
        two_phase = rd.read(filter_expr=expr)            # fused-eligible
        residual = rd.read(filter_expr=expr, columns=["a"])  # evaluate path
        assert two_phase["a"].to_pylist() == residual["a"].to_pylist()

    def test_as_range_shapes(self):
        assert (field("a") == 5).as_range() == ("a", 5, False, 5, False)
        assert ((field("a") >= 1) & (field("a") < 9)).as_range() == \
            ("a", 1, False, 9, True)
        assert ((field("a") > 1) & (field("b") < 9)).as_range() is None
        assert (field("a") != 5).as_range() is None
        assert (field("a") == "s").as_range() is None
        assert (field("a") == True).as_range() is None  # noqa: E712


def test_uint64_bloom_probe_full_domain():
    # bloom build hashes values mod 2^64; int and float probes in
    # [2^63, 2^64) must do the same — they used to overflow or byte-hash
    from repro.core.statistics import compute_stats
    from repro.core.table import Column
    col = Column.numeric(np.array([1, 2**63, 2**64 - 1], np.uint64))
    st = compute_stats(col)
    assert st.bloom is not None
    assert st.may_contain(2**63)
    assert st.may_contain(float(2**63))
    assert st.may_contain(2**64 - 1)


def test_float_literal_equality_not_bloom_pruned(tmp_path):
    # field('x') == 1.0 on an int column: the chunk bloom is built with the
    # integer hash, so the float literal must probe the same way — this
    # used to prune the whole file and return 0 rows
    from repro.core.statistics import compute_stats
    from repro.core.table import Column
    col = Column.numeric(np.arange(100, dtype=np.int64))
    st = compute_stats(col)
    assert st.bloom is not None
    assert st.may_contain(1.0)
    assert st.may_contain(np.float64(42.0))
    db = ParquetDB(os.path.join(str(tmp_path), "fb"))
    db.create([{"x": i, "y": i * 2} for i in range(100)])
    assert db.read(filters=[field("x") == 7.0]).num_rows == 1
    assert db.read(filters=[field("x") == 7]).num_rows == 1


class TestExplainReporting:
    def test_selective_scan_reports_late_savings(self, tmp_path):
        n = 20_000
        db = ParquetDB(os.path.join(str(tmp_path), "late"))
        db.create([{"a": i, "b": f"payload_{i}", "c": float(i)}
                   for i in range(n)])
        db.normalize(NormalizeConfig(max_rows_per_file=5_000,
                                     max_rows_per_group=2_048))
        rep = db.explain(filters=[field("a") == n // 2], execute=True)
        assert rep.counters.rows_matched == 1
        assert rep.counters.rows_skipped_late > 0
        assert rep.counters.bytes_saved_late > 0
        assert "late mat." in str(rep)
        # a full scan reports none
        rep = db.explain(execute=True)
        assert rep.counters.rows_skipped_late == 0
        assert rep.counters.bytes_saved_late == 0

    def test_to_dict_carries_new_counters(self, tmp_path):
        db = ParquetDB(os.path.join(str(tmp_path), "d"))
        db.create([{"a": i, "b": i} for i in range(10)])
        d = db.explain(execute=True).to_dict()
        assert "rows_skipped_late" in d["counters"]
        assert "bytes_saved_late" in d["counters"]

    def test_pruned_equals_unpruned_under_late_mat(self, tmp_path):
        """Oracle: late materialization never changes scan results."""
        rng = np.random.default_rng(9)
        n = 10_000
        db = ParquetDB(os.path.join(str(tmp_path), "oracle"))
        db.create(Table.from_pydict({
            "k": rng.integers(0, 500, n),
            "s": [f"r{i}" for i in range(n)],
            "v": rng.standard_normal(n),
        }))
        db.normalize(NormalizeConfig(max_rows_per_file=2_500,
                                     max_rows_per_group=512))
        expr = field("k") == 123
        pruned = db.read(filters=[expr])
        full = db.read()
        oracle = full.filter_mask(expr.evaluate(full))
        assert norm(pruned.to_pylist()) == norm(oracle.to_pylist())


# ---------------------------------------------------------------------------
# Row-group batches: the two-phase read decodes each column's surviving
# pages of a row group in one backend call, evaluates the predicate once
# over the row group and takes the concatenated selection.  The reference
# below is the same read page by page.
# ---------------------------------------------------------------------------
PAGE, GROUP = 64, 256
COUNTED = ("row_groups_scanned", "row_groups_skipped", "pages_scanned",
           "pages_skipped", "rows_scanned", "bytes_decoded",
           "rows_skipped_late", "bytes_saved_late")


def _per_page_read(path, expr):
    """The two-phase read one page at a time, on the numpy reference:
    filter columns decoded page by page, the predicate evaluated per page,
    payload pages materialized through their own selection vectors."""
    rd = TPQReader(path)
    c = ScanCounters()
    names = rd._project(None, expr)
    fnames = [n for n in dict.fromkeys(expr.columns()) if n in rd.schema]
    sub = rd.schema.select(names)
    parts = []
    for i, rg in enumerate(rd.row_groups):
        if not expr.prune(rd.row_group_stats(i)):
            c.row_groups_skipped += 1
            continue
        npages = len(rg["columns"][names[0]]["pages"])
        page_sel = (rd._select_pages(i, expr, npages) if npages > 1
                    else list(range(npages)))
        if not page_sel:
            c.row_groups_skipped += 1
            c.pages_skipped += npages
            continue
        c.row_groups_scanned += 1
        c.pages_scanned += len(page_sel)
        c.pages_skipped += npages - len(page_sel)
        for j in page_sel:
            page = {n: rg["columns"][n]["pages"][j] for n in names}
            c.rows_scanned += page[names[0]]["rows"]
            fcols = {n: rd._read_column_page(page[n], rd.schema[n].dtype)
                     for n in fnames}
            c.bytes_decoded += sum(_page_stored_bytes(page[n])
                                   for n in fnames)
            mask = expr.evaluate(Table(rd.schema.select(fnames), fcols))
            if not mask.any():
                continue
            sel = None if mask.all() else np.flatnonzero(mask)
            if sel is not None:
                c.rows_skipped_late += len(mask) - len(sel)
            cols = {}
            for n in names:
                if n in fcols:
                    cols[n] = fcols[n] if sel is None else fcols[n].take(sel)
                else:
                    c.bytes_decoded += _page_stored_bytes(page[n])
                    cols[n] = rd._read_column_page(
                        page[n], rd.schema[n].dtype, sel=sel, counters=c)
            parts.append(Table(sub, cols))
    return parts, c


def _assert_same_table(got, parts):
    assert got.num_rows == sum(p.num_rows for p in parts)
    assert got.column_names == parts[0].column_names
    assert norm(got.to_pylist()) == norm(
        [r for p in parts for r in p.to_pylist()])
    for name in got.column_names:
        col = got.column(name)
        if col.values is not None:  # fixed width: the same bytes
            ref = np.concatenate([p.column(name).values for p in parts])
            assert col.values.dtype == ref.dtype
            assert col.values.tobytes() == ref.tobytes(), name


def _numeric_table(n, seed=3):
    rng = np.random.default_rng(seed)
    return {
        "x": rng.normal(0.0, 1.0, n).astype(np.float32),
        "id": np.arange(n, dtype=np.int64) * 3 + 7,
        "k": rng.integers(0, 1000, n).astype(np.int64),
        "g": rng.choice(np.array([5, 11, 23, 42], np.int64), n),
    }


NUMERIC_ENC = {"x": "bss", "id": "delta", "k": "bitpack", "g": "dict"}


def _case_f32_range(tmp_path):
    t = Table.from_pydict(_numeric_table(4 * GROUP + 100))
    p = str(tmp_path / "f32.tpq")
    write_table(p, t, page_rows=PAGE, row_group_rows=GROUP,
                field_encodings=NUMERIC_ENC)
    return p, (field("x") >= np.float32(0.5)) & (field("x") <= np.float32(1.0))


def _case_int64_conjunction(tmp_path):
    t = Table.from_pydict(_numeric_table(3 * GROUP))
    p = str(tmp_path / "conj.tpq")
    write_table(p, t, page_rows=PAGE, row_group_rows=GROUP,
                field_encodings=NUMERIC_ENC)
    return p, (field("k") >= 100) & (field("k") < 160) & (field("g") != 23)


def _case_zero_match_pages_between(tmp_path):
    # pages 1 and 2 of each row group span the probe value in their stats
    # but hold none of it, so page pruning keeps them and phase 1 drops them
    n = 2 * GROUP
    k = np.tile(np.arange(PAGE, dtype=np.int64) % 7 * 2, n // PAGE)
    for rg in range(n // GROUP):
        for pg in (0, 3):
            k[rg * GROUP + pg * PAGE + 5] = 7
    d = _numeric_table(n)
    d["k"] = k
    p = str(tmp_path / "gaps.tpq")
    write_table(p, Table.from_pydict(d), page_rows=PAGE, row_group_rows=GROUP,
                field_encodings=NUMERIC_ENC)
    return p, field("k") == 7


def _case_all_match_pages(tmp_path):
    # ids grow, so pages past the bound match every row, one page partly
    t = Table.from_pydict(_numeric_table(2 * GROUP))
    p = str(tmp_path / "allmatch.tpq")
    write_table(p, t, page_rows=PAGE, row_group_rows=GROUP,
                field_encodings=NUMERIC_ENC)
    return p, field("id") >= 3 * (GROUP + 40) + 7


def _case_one_page_row_group(tmp_path):
    t = Table.from_pydict(_numeric_table(2 * GROUP + 40))  # last rg: 1 page
    p = str(tmp_path / "onepage.tpq")
    write_table(p, t, page_rows=PAGE, row_group_rows=GROUP,
                field_encodings=NUMERIC_ENC)
    return p, field("x") > np.float32(-0.25)


def _case_nullable_and_string_payload(tmp_path):
    n = 2 * GROUP
    d = _numeric_table(n)
    rows = [{"x": float(d["x"][i]), "k": int(d["k"][i]),
             "v": None if i % 5 == 0 else float(i),
             "s": f"s{i % 9}" * (i % 4)} for i in range(n)]
    t = Table.from_pylist(rows)
    p = str(tmp_path / "mixed.tpq")
    write_table(p, t, page_rows=PAGE, row_group_rows=GROUP)
    return p, field("k") < 300


def _case_all_null_page(tmp_path):
    n = 2 * GROUP
    d = _numeric_table(n)
    rows = [{"k": int(d["k"][i]), "id": int(d["id"][i]),
             "v": None if PAGE <= i % GROUP < 2 * PAGE else float(i)}
            for i in range(n)]
    p = str(tmp_path / "nullpage.tpq")
    write_table(p, Table.from_pylist(rows), page_rows=PAGE,
                row_group_rows=GROUP)
    return p, field("k") < 250


CASES = {
    "f32_range_bss": _case_f32_range,
    "int64_conjunction": _case_int64_conjunction,
    "zero_match_pages_between": _case_zero_match_pages_between,
    "all_match_pages": _case_all_match_pages,
    "one_page_row_group": _case_one_page_row_group,
    "nullable_and_string_payload": _case_nullable_and_string_payload,
    "all_null_page": _case_all_null_page,
}


@pytest.fixture(params=["numpy", "jax"])
def backend_name(request):
    if request.param == "jax":
        pytest.importorskip("jax")
    yield request.param
    backend.set_backend(None)


@pytest.mark.parametrize("case", list(CASES))
def test_batched_two_phase_equals_per_page(tmp_path, backend_name, case):
    path, expr = CASES[case](tmp_path)
    backend.set_backend("numpy")
    parts, want = _per_page_read(path, expr)
    assert parts, "the case must match some rows"
    backend.set_backend(backend_name)
    got, c = _read(path, expr)
    _assert_same_table(got, parts)
    for k in COUNTED:
        assert getattr(c, k) == getattr(want, k), k
    # every column page decoded is counted once, batched or alone: the
    # filter columns' surviving pages and the payload columns' kept pages
    # (the reference yields one part per kept page)
    fnames = set(expr.columns())
    payload = len(TPQReader(path).schema.names) - len(fnames)
    assert c.two_phase_pages_batched + c.two_phase_pages_single \
        == len(fnames) * c.pages_scanned + payload * len(parts)


def _count_device_calls(monkeypatch):
    from repro.kernels import ops
    calls = {"decode": 0, "range_mask": 0}

    def counting(name, real):
        def f(*a, **kw):
            calls[name] += 1
            return real(*a, **kw)
        return f

    monkeypatch.setattr(ops, "decode_batch_on_device",
                        counting("decode", ops.decode_batch_on_device))
    monkeypatch.setattr(ops, "range_mask_on_device",
                        counting("range_mask", ops.range_mask_on_device))
    return calls


def test_one_device_call_per_column_per_row_group(tmp_path, monkeypatch):
    pytest.importorskip("jax")
    groups = 3
    t = Table.from_pydict(_numeric_table(groups * GROUP))
    p = str(tmp_path / "calls.tpq")
    write_table(p, t, page_rows=PAGE, row_group_rows=GROUP,
                field_encodings=NUMERIC_ENC)
    backend.set_backend("jax")
    try:
        calls = _count_device_calls(monkeypatch)
        expr = (field("x") >= np.float32(-0.5)) & (field("x") <= np.float32(0.5))
        got, c = _read(p, expr)
        oracle = t.filter_mask(expr.evaluate(t))
        assert norm(got.to_pylist()) == norm(oracle.to_pylist())
        pages = GROUP // PAGE
        assert c.pages_scanned == groups * pages  # every page matches
        # one decode per eligible column per row group, one range mask
        assert calls == {"decode": groups * 4, "range_mask": groups}
        assert c.two_phase_pages_batched == groups * pages * 4
        assert c.two_phase_pages_single == 0

        # a point lookup after page pruning: one page, a batch of one
        calls.update(decode=0, range_mask=0)
        got, c = _read(p, field("id") == 3 * (GROUP + 70) + 7)
        assert got["id"].to_pylist() == [3 * (GROUP + 70) + 7]
        assert c.pages_scanned == 1
        assert calls == {"decode": 4, "range_mask": 1}
        assert (c.two_phase_pages_batched, c.two_phase_pages_single) == (0, 4)
        # the filter family counts the pages a range mask covers
        be = backend.get_backend("jax")
        before = be.device_pages["filter"]
        _read(p, expr)
        assert be.device_pages["filter"] - before == groups * pages
    finally:
        backend.set_backend(None)


def test_explain_shows_two_phase_split(tmp_path):
    n = 20_000
    db = ParquetDB(os.path.join(str(tmp_path), "tp"))
    db.create([{"a": i, "b": i * 2, "c": float(i)} for i in range(n)])
    rep = db.explain(filters=[field("a") >= 10], execute=True)
    assert rep.counters.two_phase_pages_batched > 0
    assert rep.counters.two_phase_pages_single == 0
    assert "two-phase:" in str(rep)
    assert "two_phase_pages_batched" in rep.to_dict()["counters"]
    assert "two-phase:" not in str(db.explain(execute=True))


@pytest.mark.parametrize("backend_name", ["numpy", "jax"], indirect=True)
def test_corrupt_payload_page_in_batch_keeps_coordinates(tmp_path,
                                                         backend_name):
    t = Table.from_pydict(_numeric_table(2 * GROUP))
    p = str(tmp_path / "corrupt.tpq")
    write_table(p, t, page_rows=PAGE, row_group_rows=GROUP,
                field_encodings=NUMERIC_ENC)
    target = next(buf for rg, col, page, key, buf
                  in TPQReader(p).iter_page_buffers()
                  if (rg, col, page, key) == (1, "id", 2, "values"))
    with open(p, "r+b") as fh:
        fh.seek(target["off"] + target["len"] // 2)
        b = fh.read(1)
        fh.seek(target["off"] + target["len"] // 2)
        fh.write(bytes([b[0] ^ 0x40]))
    backend.set_backend(backend_name)
    with pytest.raises(CorruptPageError) as ei:
        _read(p, field("x") > np.float32(-3.0))  # every page is kept
    e = ei.value
    assert (e.row_group, e.column, e.page) == (1, "id", 2)
