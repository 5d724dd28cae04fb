"""Run one cell of the benchmark once and print its result line.

    python3 tpubench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, with
``--trace 1``, ``breakdown``; ``checks`` comes last and gives each number
compared with its limit, as the last lines of standard error do too.
Without a TPU, with fewer chips than the cell asks for, with a device that
``peaks.json`` does not know, or with the decode backend in interpret
mode, it exits with code 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from tpubench import harness
    # the compile cache lives in the checkout at a fixed path, whatever
    # the machine's environment names
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"tpubench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
