"""Dispatch and upload in the kernel wrappers, in ms per query:
`repro.ops.launch`, per `repro.query` root ending in the traced window
(program_spans.py's `layers_ms`)."""
from tpubench.program_spans import read_layer


def read(r):
    return read_layer(r, "launch_ms")
