"""The store's planning, in ms per query: `repro.query.plan` (compiling
the expression, the snapshot, footer opens, pruning), per `repro.query`
root ending in the traced window (program_spans.py's `layers_ms`)."""
from tpubench.program_spans import read_layer


def read(r):
    return read_layer(r, "plan_ms")
