"""The read path's profiler spans (``repro.spans``): a no-op without jax,
and, in a real ``jax.profiler`` trace recorded on the CPU, one
``repro.query`` root per query with its layers nested under it."""
import glob
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import spans

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


def test_spans_stay_jax_free_under_the_numpy_backend(tmp_path):
    code = textwrap.dedent(f"""
        import sys
        import numpy as np
        from repro import spans
        from repro.core import ParquetDB, Table, field
        db = ParquetDB({str(tmp_path / "db")!r}, page_rows=256,
                       row_group_rows=1024)
        db.create(Table.from_pydict({{
            "x": np.arange(5000, dtype=np.float32),
            "k": np.arange(5000) % 7}}))
        q = db.query().where((field("x") >= 10) & (field("x") <= 900))
        assert q.to_table().num_rows == 891
        assert q.count() == 891
        assert q.agg({{"x": "max"}})["x"]["max"] == 900
        g = db.query().group_by("k").agg({{"*": "count"}}).to_table()
        assert g.num_rows == 7
        assert sum(t.num_rows for t in q.iter_batches(100)) == 891
        assert spans.span("query") is spans._NOOP
        assert "jax" not in sys.modules, "a span imported jax"
        print("ok")
        """)
    env = dict(os.environ, PYTHONPATH=SRC, REPRO_DECODE_BACKEND="numpy")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_nested_terminals_open_one_root(monkeypatch):
    opened = []

    def record(name, **args):
        opened.append((name, args))
        return spans._NOOP

    monkeypatch.setattr(spans, "span", record)
    with spans.query_span():
        with spans.query_span():
            pass
    with spans.query_span():
        pass
    roots = [a["qid"] for n, a in opened if n == "query"]
    assert len(roots) == 2 and roots[1] == roots[0] + 1
    assert list(spans.rooted(iter([1, 2]))) == [1, 2]
    assert len(opened) == 2 + 3  # one root per item, one for the end


def _recorded_spans(path):
    """``{line number: [(start, end, name, args)]}`` of the ``repro.*``
    events in a trace file."""
    from jax.profiler import ProfileData
    lines = {}
    k = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = []
            for ev in line.events:
                if ev.name.startswith("repro."):
                    s = int(ev.start_ns)
                    evs.append((s, s + int(ev.duration_ns), ev.name,
                                dict(ev.stats)))
            if evs:
                lines[k] = sorted(evs, key=lambda x: (x[0], -x[1]))
            k += 1
    return lines


def _parent(evs, i):
    """Index of the innermost span on the same line holding span ``i``."""
    s, e = evs[i][0], evs[i][1]
    best = None
    for j, (s2, e2, _, _) in enumerate(evs):
        if j != i and s2 <= s and e <= e2 and (s2, -e2) < (s, -e):
            if best is None or s2 >= evs[best][0]:
                best = j
    return best


def test_a_traced_query_nests_its_layers(tmp_path):
    jax = pytest.importorskip("jax")
    from repro.core import ParquetDB, Table, backend, field
    db = ParquetDB(str(tmp_path / "db"), page_rows=512, row_group_rows=2048)
    n = 6000
    rng = np.random.default_rng(5)
    db.create(Table.from_pydict({
        "energy": rng.normal(-30, 10, n).astype(np.float32),
        "spg": rng.integers(1, 20, n),
        "n_sites": rng.integers(1, 12, n)}))
    flt = db.query().where((field("energy") >= -31) & (field("energy") <= -29))
    grp = db.query().group_by("spg").agg({"*": "count", "energy": "mean"})
    backend.set_backend("jax")
    try:
        want = flt.to_table().num_rows  # compiles outside the trace
        grp.to_table()
        trace_dir = str(tmp_path / "trace")
        with jax.profiler.trace(trace_dir):
            got = flt.to_table().num_rows
            groups = grp.to_table().num_rows
    finally:
        backend.set_backend(None)
    assert got == want > 0 and groups == 19
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert found
    lines = _recorded_spans(found[0])
    everything = [x for evs in lines.values() for x in evs]
    roots = [x for x in everything if x[2] == "repro.query"]
    assert len(roots) == 2
    assert all(isinstance(r[3].get("qid"), int) for r in roots)
    assert roots[0][3]["qid"] != roots[1][3]["qid"]
    names = {x[2] for x in everything}
    assert {"repro.query.plan", "repro.query.compute", "repro.scan.morsel",
            "repro.reader.filter", "repro.reader.payload", "repro.ops.stage",
            "repro.ops.launch", "repro.ops.fetch"} <= names
    launches = 0
    for evs in lines.values():
        for i, (s, e, name, args) in enumerate(evs):
            p = _parent(evs, i)
            if p is not None:  # a child lies inside its parent
                assert evs[p][0] <= s and e <= evs[p][1]
            elif name != "repro.query":
                # a worker thread's outermost span lies inside a root
                assert any(r[0] <= s and e <= r[1] for r in roots), name
            if name.startswith("repro.ops."):
                assert args.get("kernel"), name
            if name == "repro.ops.launch":
                launches += 1
                after = [x for x in evs[i + 1:] if x[0] >= e
                         and x[2].startswith("repro.ops.")]
                assert after and after[0][2] in ("repro.ops.fetch",), \
                    "a launch without its copy back"
    assert launches > 0
