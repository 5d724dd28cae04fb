"""Decode-kernel benchmarks: host numpy codecs vs the Pallas kernels.

The kernels run in the jax decode backend's mode — compiled on an
accelerator (rows ``kernels/pallas/*``), interpreted on the CPU platform
(rows ``kernels/pallas_interpret/*``, correctness and plumbing cost only).
The derived column reports the encoded:decoded byte ratio, which is the
PCIe/DMA win the kernels buy on real hardware."""
from __future__ import annotations

from typing import List

import numpy as np

from repro.core import encodings as enc
from repro.core.backend import get_backend
from repro.kernels import ops

from .common import row, timeit


def run(scale: str = "small") -> List[dict]:
    n = {"quick": 50_000, "small": 200_000, "medium": 1_000_000,
         "paper": 10_000_000}[scale]
    rng = np.random.default_rng(0)
    interpret = get_backend("jax").interpret
    kind = "pallas_interpret" if interpret else "pallas"
    out: List[dict] = []
    cases = [
        ("bitpack_tokens_v152k", rng.integers(0, 151_936, n).astype(np.int64),
         enc.BITPACK, np.int32),
        ("dict_lowcard", rng.integers(0, 30, n).astype(np.int64) * 7,
         enc.DICT, np.int64),
        ("delta_sorted_ids", np.cumsum(rng.integers(0, 5, n)).astype(np.int64),
         enc.DELTA, np.int32),
        ("bss_f32", rng.standard_normal(n).astype(np.float32),
         enc.BSS, np.float32),
    ]
    for name, arr, encoding, dev_dt in cases:
        chosen, meta, payload = enc.encode(arr, encoding)
        t_host = timeit(
            lambda: enc.decode(chosen, meta, payload, len(arr), arr.dtype),
            repeat=2)
        out.append(row(f"kernels/host_decode/{name}", t_host,
                       encoded_bytes=len(payload), raw_bytes=arr.nbytes,
                       compression=len(payload) / arr.nbytes))
        t_dev = timeit(lambda: np.asarray(ops.decode_on_device(
            chosen, meta, payload, len(arr), dev_dt, interpret=interpret)),
            repeat=2)
        out.append(row(f"kernels/{kind}/{name}", t_dev,
                       encoded_bytes=len(payload)))
    return out
