"""chip_smoke.py on the CPU, and the errors that keep the device path honest.

The smoke's phases run here at 50k rows with the Pallas kernels in
interpret mode (the jax backend's mode on the CPU platform) and must agree
with the numpy backend byte for byte; ``main`` itself refuses to run
without a TPU.  The remaining tests pin the two refusals the device path
relies on: a ``jax`` selection without jax, and the process executor under
the ``jax`` backend.
"""
import importlib.util
import os

import numpy as np
import pytest

from repro.core import LoadConfig, ParquetDB, Table, backend, scan

jax = pytest.importorskip("jax")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_match_numpy_on_cpu(tmp_path):
    smoke = _load_smoke()
    assert backend.get_backend("jax").interpret
    logged = []
    records = smoke.run_phases(50_000, 0, str(tmp_path), logged.append)
    assert [r["phase"] for r in logged] == [
        "kernels", "load", "full_scan", "range_filter", "filtered_agg",
        "group_by", "update", "filter_after_update", "server"]
    assert records["load"]["rows"] == 50_000
    for name in ("full_scan", "range_filter", "filtered_agg", "group_by",
                 "filter_after_update", "server"):
        assert records[name]["parity"] is True
    # the 40-bit fingerprint column is the one the 32-bit gate refuses
    assert records["full_scan"]["host_pages"] == {"bitpack": 7}
    assert set(records["full_scan"]["device_pages"]) == {
        "bitpack", "dict", "delta", "bss"}


def test_smoke_main_refuses_without_tpu(capsys):
    smoke = _load_smoke()
    assert smoke.main(["--rows", "1000"]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_jax_selected_without_jax_raises(monkeypatch):
    monkeypatch.setattr(backend, "_jax_probe", False)
    monkeypatch.setattr(backend, "_instances", {})
    monkeypatch.setenv(backend.ENV_VAR, "jax")
    with pytest.raises(RuntimeError, match="jax is not importable"):
        backend.active_backend()


def _gil_bound_db(tmp_path, files=4, n=2_000):
    db = ParquetDB(os.path.join(str(tmp_path), "db"), codec="none",
                   row_group_rows=256, auto_compact=False)
    for f in range(files):
        db.create(Table.from_pydict(
            {"x": np.arange(f * n, (f + 1) * n, dtype=np.int64) % 997}))
    return db


def test_process_executor_refused_under_jax(tmp_path):
    db = _gil_bound_db(tmp_path)
    backend.set_backend("jax")
    try:
        with pytest.raises(RuntimeError, match="device belongs"):
            db.read(load_config=LoadConfig(num_threads=2,
                                           executor="process"))
    finally:
        backend.set_backend(None)


def test_auto_executor_never_picks_process_under_jax(tmp_path, monkeypatch):
    db = _gil_bound_db(tmp_path)
    monkeypatch.setattr(scan, "PROCESS_MIN_ROWS", 1)
    plan = db._scan_plan(None, None, LoadConfig(num_threads=4))
    plan.fragments()
    morsels = plan._morsels()
    assert plan._choose_executor(morsels) == "process"  # numpy: GIL-bound

    def no_pool(_n):
        raise AssertionError("a worker pool was started under jax")

    monkeypatch.setattr(scan, "process_scan_pool", no_pool)
    backend.set_backend("jax")
    try:
        assert plan._choose_executor(morsels) != "process"
        got = db.read(load_config=LoadConfig(num_threads=4))
    finally:
        backend.set_backend(None)
    assert got["x"].to_pylist() == list(np.arange(8_000) % 997)


@pytest.mark.parametrize("env_dir", [None, "cache-from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    from repro import compile_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / env_dir))
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert got == os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path / env_dir)
            # JAX reads the variable itself; nothing is set in code
            assert jax.config.jax_compilation_cache_dir \
                == saved["jax_compilation_cache_dir"]
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
