"""Benchmark harness — one module per paper table/figure.

``python -m benchmarks.run [--scale small|medium|paper] [--only fig5,...]``
prints ``name,us_per_call,derived`` CSV (paper protocol) and writes the rows
into a ParquetDB results store so they are queryable like everything else.

``--json [DIR]`` additionally writes one ``BENCH_<fig>.json`` artifact per
suite (median-of-k timings in the rows, plus rows/sec where applicable) —
the machine-readable trajectory that ``scripts/check_perf.py`` gates CI on.
The canonical artifact directory is ``bench/`` (the bare ``--json``
default); the committed engine artifacts CI gates on live there.  (The
root ``BENCH_baseline.json`` is different: it records the pre-engine
*seed* numbers as a trajectory record — see scripts/check_perf.py.)
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import platform
import sys
import time

from repro.compile_cache import enable_compile_cache

SUITES = ["fig5_create_read", "fig6_formats", "fig7_needle", "fig8_update",
          "fig9_alexandria", "fig10_ops", "fig11_aggregate", "fig12_serve",
          "pipeline_bench", "kernels_bench", "ckpt_bench"]


def _suite_tag(suite: str) -> str:
    """``fig5_create_read`` -> ``fig5``; non-figure suites keep their name."""
    head = suite.split("_", 1)[0]
    return head if head.startswith("fig") else suite


def write_json_artifact(directory: str, suite: str, scale: str,
                        rows: list) -> str:
    path = os.path.join(directory, f"BENCH_{_suite_tag(suite)}.json")
    doc = {
        "suite": suite,
        "scale": scale,
        "unit": "us_per_call (median-of-k for read/needle paths)",
        "machine": platform.machine(),
        "python": platform.python_version(),
        # scaling gates (check_perf SCALING_GATES) only make sense when
        # the recording box actually had the cores: stamp the count
        "cpus": os.cpu_count(),
        "generated_unix": int(time.time()),
        "rows": rows,
    }
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small",
                    choices=["quick", "small", "medium", "paper"])
    ap.add_argument("--quick", action="store_true",
                    help="shorthand for --scale quick: tiny-n smoke runs "
                         "of every suite, the CI regression signal")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite prefixes")
    ap.add_argument("--store", default=None,
                    help="optional ParquetDB dir for results")
    ap.add_argument("--json", nargs="?", const="bench", default=None,
                    metavar="DIR",
                    help="write BENCH_<fig>.json artifacts into DIR "
                         "(default: the canonical bench/ directory)")
    args = ap.parse_args(argv)
    if args.quick:
        args.scale = "quick"
    enable_compile_cache()

    only = args.only.split(",") if args.only else None
    all_rows = []
    errors = 0
    print("name,us_per_call,derived")
    for suite in SUITES:
        if only and not any(suite.startswith(o) for o in only):
            continue
        try:
            # import inside the guard: a suite with an unavailable
            # accelerator dep reports one ERROR row instead of killing
            # the whole run
            mod = importlib.import_module(f".{suite}", package=__package__)
            rows = mod.run(args.scale)
        except Exception as e:
            print(f"{suite}/ERROR,0,\"{e!r}\"")
            errors += 1
            continue
        for r in rows:
            derived = {k: v for k, v in r.items()
                       if k not in ("name", "us_per_call")}
            print(f"{r['name']},{r['us_per_call']:.1f},"
                  f"\"{json.dumps(derived)}\"")
        sys.stdout.flush()
        if args.json is not None:
            os.makedirs(args.json, exist_ok=True)
            path = write_json_artifact(args.json, suite, args.scale, rows)
            print(f"# wrote {path}", file=sys.stderr)
        all_rows.extend(rows)
    if args.store and all_rows:
        from repro.core import ParquetDB
        db = ParquetDB(args.store, "bench_results")
        db.create([{k: (float(v) if isinstance(v, (int, float)) else str(v))
                    for k, v in r.items()} for r in all_rows])
    # ERROR rows keep the other suites running but still fail the exit
    # code, so CI smoke runs catch a broken suite
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
