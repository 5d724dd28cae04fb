"""The control of the correctness check: the plain reference, one step of
precision below what the configuration states, put in the program's
place.  A sound check calls a control run not correct.

    python3 tpubench/control.py --workload <name> --seeds 1,2,3 [--queries N]

For each seed it builds the cell's data, takes the requests a run would
(the first ``N`` of a closed loop, every request of an open loop over
``run_seconds``), answers them with the control, compares the answers
with the reference as a run compares the store's, and prints one JSON
line with each number, its limit and whether the control was caught.
The benchmark's own runs never run this.
"""
import argparse
import itertools
import json
import os
import sys


def readings(name: str, seed: int, rows=None, queries: int = 8) -> dict:
    from tpubench import harness, reference, spec
    w = spec.cell(name)
    cfg, traffic = w["config_file"], w["traffic_file"]
    n_rows = int(rows or cfg["rows"])
    arrays = harness.make_data(cfg, w["config"], n_rows, seed)
    low = reference.lower_precision(arrays)
    if traffic["loop"] == "closed":
        reqs = list(itertools.islice(spec.closed_requests(traffic, seed),
                                     queries))
    else:
        reqs = spec.open_schedule(traffic, n_rows,
                                  float(w["bench"]["run_seconds"]), seed)
    wrong, gap = 0, 0.0
    for q in (q for q in reqs if q.get("op", "query") == "query"):
        a, g = reference.compare(reference.evaluate(q, low),
                                 reference.evaluate(q, arrays),
                                 q.get("group_by"))
        wrong, gap = wrong + a, max(gap, g)
    checks = {"wrong_values": wrong, "agg_rel_gap": gap, "failed": 0,
              "unanswered": 0, "wrong_generation": 0, "device_pages": 1}
    limits = {k: v for k, v in traffic["limits"].items()
              if k != "device_pages"}
    correct, compared = harness.judge(
        {k: checks[k] for k in limits}, limits)
    return {"workload": name, "seed": seed, "requests": len(reqs),
            "correct": correct, "checks": compared}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--queries", type=int, default=8)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(args.workload, seed, args.rows,
                                  args.queries)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
