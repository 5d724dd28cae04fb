"""The store's host execution, in ms per query: `repro.query.compute`
(computed columns, residual filters, group-by, reductions, sort) and the
bare `repro.query` root, per root ending in the traced window
(program_spans.py's `layers_ms`)."""
from tpubench.program_spans import read_layer


def read(r):
    return read_layer(r, "query_host_ms")
