"""Rate sweep of an open-loop cell, to find the highest rate it sustains.

    python3 tpubench/sweep.py --workload alexandria.lookup --seed S \\
        --seconds 10 --rates 50,100,200,400

Builds the cell's data once, then for each rate runs a fresh DBServer
window fed by ``loadgen.py`` and prints one JSON line: the rate offered,
answers per second, latency percentiles from the due time, sheds, the
generator's lateness and the result-cache hit share.  The knee is the
highest rate at which every request was answered in time to keep up (no
shed, no failure, the last requests no slower than four times the median)
and p95 stays within five times its value at the lowest rate.  The cell's
rate is set by hand from it (four fifths of the knee) in its traffic file;
the benchmark's runs never sweep.
"""
import argparse
import json
import os
import sys
import tempfile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from tpubench import harness, spec
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    import numpy as np

    from repro.core import ParquetDB
    from repro.core.backend import set_backend
    w = spec.cell(args.workload)
    cfg, traffic = w["config_file"], w["traffic_file"]
    if args.rows is None:
        harness.check_device(int(w["chips"]))
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
    set_backend("jax")
    n_rows = int(args.rows or cfg["rows"])
    arrays = harness.make_data(cfg, w["config"], n_rows, args.seed)
    results = []
    with tempfile.TemporaryDirectory(prefix="tpubench-") as workdir:
        db = ParquetDB(os.path.join(workdir, "db"), w["config"],
                       page_rows=int(cfg["page_rows"]),
                       row_group_rows=int(cfg["row_group_rows"]))
        db.create(harness.to_table(arrays))
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            win = harness.open_window(db, traffic, n_rows, args.seed + i,
                                      args.seconds, workdir, arrays,
                                      rate=rate)
            recs = win["records"]
            ok = [r for r in recs if r["status"] == 200]
            lat = np.array([r["lat"] if r["status"] == 200
                            else win["elapsed_s"] for r in recs])
            n = len(recs)
            tail = lat[int(0.9 * n):]
            s = win["server"]
            row = {"rate": rate, "requests": n, "answered": len(ok),
                   "answered_per_s": len(ok) / win["elapsed_s"],
                   "shed": s["shed"], "errors": s["errors"],
                   "p50_ms": 1e3 * float(np.percentile(lat, 50)),
                   "p95_ms": 1e3 * harness.p95(list(lat)),
                   "p99_ms": 1e3 * float(np.percentile(lat, 99)),
                   "last_tenth_p50_ms": 1e3 * float(np.median(tail)),
                   "late_p99_ms": 1e3 * float(np.percentile(
                       [r["late"] for r in recs if r["late"] is not None],
                       99)),
                   "hit_share": s["result_hits"] / max(
                       1, s["result_hits"] + s["result_misses"])}
            results.append(row)
            print(json.dumps(row), flush=True)
    set_backend(None)
    base = results[0]["p95_ms"]
    knee = None
    for r in results:
        if (r["answered"] == r["requests"] and not r["shed"]
                and r["last_tenth_p50_ms"] <= 4 * max(r["p50_ms"], 1e-3)
                and r["p95_ms"] <= 5 * base):
            knee = r["rate"]
    print(json.dumps({"knee_per_s": knee,
                      "four_fifths": None if knee is None else 0.8 * knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
