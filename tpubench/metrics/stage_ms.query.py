"""Host staging in the kernel wrappers, in ms per query:
`repro.ops.stage` (inputs packed for a device call), per `repro.query`
root ending in the traced window (program_spans.py's `layers_ms`)."""
from tpubench.program_spans import read_layer


def read(r):
    return read_layer(r, "stage_ms")
