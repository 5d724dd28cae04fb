"""The grouped reduction kernel's share of its memory-bound floor.

Bound: HBM bandwidth.  The floor is 4 bytes for each row a completed
query's filter keeps, times one group code plus the distinct data
columns its aggregates and computed columns read, over the peak HBM
bandwidth of peaks.json.  The rows are the ``count`` column of the
answers, so every completed query must have been kept; nothing is read
otherwise.  The time is the device time of the ``jit_group_reduce``
program in the trace.  The work is fixed by the answers, not by what the
program did."""
import numpy as np

PROGRAM = "jit_group_reduce"


def _columns(e):
    if not isinstance(e, list) or not e:
        return set()
    if e[0] == "field":
        return {e[1]}
    return set().union(*(_columns(x) for x in e[1:]))


def _read_columns(q):
    computed = q.get("computed") or {}
    cols = set()
    for c in q.get("agg") or {}:
        if c in computed:
            cols |= _columns(computed[c])
        elif c != "*":
            cols.add(c)
    return len(cols)


def read(r):
    t, p = r["trace"], r["peaks"]
    prog = (t or {}).get("program_s", {}).get(PROGRAM)
    answers = [(q, a) for q, a in r.get("kept", ())
               if not isinstance(a, Exception)]
    if not prog or not p or len(answers) != r.get("completed"):
        return None
    floor_bytes = sum(
        4 * (1 + _read_columns(q)) * int(np.sum(a.column("count").values))
        for q, a in answers)
    return 100.0 * floor_bytes / p["hbm_bytes_per_s"] / prog
