"""The scan and the reader, in ms per query: `repro.scan.morsel`,
`repro.reader.filter` and `repro.reader.payload` (page walk, inflate,
checksums, selection takes), per `repro.query` root ending in the traced
window (program_spans.py's `layers_ms`)."""
from tpubench.program_spans import read_layer


def read(r):
    return read_layer(r, "reader_ms")
