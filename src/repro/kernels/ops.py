"""Public jit'd wrappers: on-device decode of TPQ-encoded column buffers.

``decode_on_device`` is the bridge between the storage layer
(:mod:`repro.core.encodings`) and the TPU: the host hands over the *encoded*
payload (as uint8/uint32 arrays) and the matching footer metadata; decode runs
as Pallas kernels next to the consumer.  This is the beyond-paper
serialization-bottleneck fix for TPU.

BITPACK, DICT and DELTA decode through the segmented kernels
(:mod:`.segmented`), float32 BSS through ``bss_decode`` over the pages' byte
planes laid side by side — whether a morsel holds many pages or one: a
single page is a batch of one, so both entry points share one kernel per
encoding and one set of compiled shapes.  Kernels run compiled unless the
caller passes ``interpret=True`` (the decode backend does so on the CPU
platform).

Every device call the store makes goes through this module: the morsel
and page decodes, ``range_mask_on_device``, ``minmax_on_device`` and
``group_sums_on_device``.  Each opens three spans (:mod:`repro.spans`):
``repro.ops.stage`` around the host-side staging, ``repro.ops.launch``
around the jitted call with its implicit upload, and ``repro.ops.fetch``
around the blocking copy back, which includes waiting for the device.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import encodings as enc
from ..spans import span
from .bitunpack import bitunpack
from .bss_decode import bss_decode
from .delta_decode import delta_decode
from .dict_decode import dict_decode
from .filter_kernel import filter_range
from .group_reduce import (CHUNK, LIMB_BITS, MAX_GROUP_SLOTS, group_reduce,
                           group_slots, pad_groups)
from .segmented import (plan_segments, seg_bitunpack, seg_delta_decode,
                        seg_dict_decode)
from .stats_kernel import page_minmax

__all__ = ["bitunpack", "bss_decode", "delta_decode", "dict_decode",
           "filter_range", "page_minmax", "decode_on_device",
           "decode_batch_on_device", "range_mask_on_device",
           "minmax_on_device", "group_sums_on_device", "group_reduce",
           "group_slots", "pad_groups", "MAX_GROUP_SLOTS", "BATCHED",
           "plan_segments", "seg_bitunpack", "seg_dict_decode",
           "seg_delta_decode"]

# encodings with a fused multi-page device decode
BATCHED = frozenset([enc.BITPACK, enc.DICT, enc.DELTA, enc.BSS])


def decode_on_device(encoding: str, meta: dict, payload: bytes, n: int,
                     np_dtype, *, interpret: bool = False) -> jnp.ndarray:
    """Device-side equivalent of ``encodings.decode`` for one page of the
    kernelized encodings (BITPACK / DICT / DELTA at <= 31 bits, float32
    BSS).  Others fall back to host decode + transfer (PLAIN has nothing
    to decode anyway).  Values come back in the 32-bit device dtype."""
    dt = np.dtype(np_dtype)
    if encoding in BATCHED and (encoding != enc.BSS or dt == np.float32):
        vals = _batched(encoding, [(encoding, meta, payload, n)], dt,
                        interpret)
        return vals[:n].astype(jax.dtypes.canonicalize_dtype(dt))
    # fallback: host decode, then transfer
    return jnp.asarray(enc.decode(encoding, meta, payload, n, dt))


def decode_batch_on_device(encoding: str, specs, np_dtype, *,
                           interpret: bool = False) -> np.ndarray:
    """ONE fused device dispatch decoding a whole morsel's pages of a single
    encoding group.

    ``specs`` is ``[(encoding, meta, payload, n), ...]`` with at least one
    non-empty page, all the given ``encoding``; the caller
    (:meth:`JaxDecodeBackend.decode_batch`) has already proven every page
    32-bit exact.  Returns the concatenated value stream as a host array of
    ``np_dtype`` — byte-identical to per-page decode by construction.
    """
    dt = np.dtype(np_dtype)
    total = sum(n for _, _, _, n in specs)
    vals = _batched(encoding, specs, dt, interpret)
    with span("ops.fetch", kernel=_KERNEL[encoding][0]):
        return np.asarray(vals)[:total].astype(dt, copy=False)


def range_mask_on_device(values: np.ndarray, lo, hi, *,
                         interpret: bool = False) -> np.ndarray:
    """``lo <= values <= hi`` through ``filter_range``, as a host bool
    array; the caller has proven values and bounds exact in 32-bit lanes."""
    n = len(values)
    with span("ops.stage", kernel="filter_range"):
        padded = _pow2_pad(values)
    with span("ops.launch", kernel="filter_range"):
        mask, _ = filter_range(jnp.asarray(padded), lo, hi,
                               interpret=interpret)
    with span("ops.fetch", kernel="filter_range"):
        return np.asarray(mask)[:n]


def minmax_on_device(values: np.ndarray, *, interpret: bool = False):
    """(min, max) of a non-empty array through ``page_minmax``, as Python
    scalars; the caller has proven the dtype exact in 32-bit lanes."""
    with span("ops.stage", kernel="page_minmax"):
        padded = _pow2_pad(values)
    with span("ops.launch", kernel="page_minmax"):
        mins, maxs = page_minmax(jnp.asarray(padded),
                                 min(len(padded), 4096),
                                 interpret=interpret)
    with span("ops.fetch", kernel="page_minmax"):
        return (np.asarray(mins).min().item(),
                np.asarray(maxs).max().item())


def group_sums_on_device(codes: np.ndarray, groups: int, leaves, terms, *,
                         interpret: bool = False):
    """``(counts, sums)`` per group through ``group_reduce``: the row count
    of each of the ``groups`` groups and, for each term, the int64 sum of
    its values (exact modulo 2**64).  ``codes`` gives each row's group;
    the caller has proven every leaf exact in int32 and every term's limb
    count sufficient (``JaxDecodeBackend.group_sums``)."""
    g = pad_groups(groups)
    with span("ops.stage", kernel="group_reduce"):
        n = len(codes)
        size = max(1, -(-n // CHUNK)) * CHUNK
        c = np.full(size, -1, np.int32)
        c[:n] = codes
        xs = []
        for v in leaves:
            x = np.zeros(size, np.int32)
            x[:n] = v
            xs.append(x)
    with span("ops.launch", kernel="group_reduce"):
        parts = [group_reduce(c[s:s + CHUNK], *(x[s:s + CHUNK] for x in xs),
                              terms=terms, groups=g, interpret=interpret)
                 for s in range(0, size, CHUNK)]
    with span("ops.fetch", kernel="group_reduce"):
        tot = sum(np.asarray(p).astype(np.int64) for p in parts)[:groups]
    # the carries: each limb's sum times its weight, wrapping in int64
    sums, j = [], 1
    for _, k in terms:
        s = np.zeros(groups, np.int64)
        for i in range(k):
            s += tot[:, j + i] * np.int64(1 << (LIMB_BITS * i))
        sums.append(s)
        j += k
    return tot[:, 0], sums


def _pow2_pad(values: np.ndarray) -> np.ndarray:
    """Pad a non-empty array to a power-of-two length with copies of its
    last value, so kernels jit'd on shape compile once per size bucket;
    repeats change no min/max, and callers drop the padded mask slots."""
    n = len(values)
    size = 1 << max(n - 1, 0).bit_length()
    return values if size == n else np.pad(values, (0, size - n),
                                           mode="edge")


# the device kernel of each batched encoding, with the name its spans give
_KERNEL = {enc.BITPACK: ("seg_bitunpack", seg_bitunpack),
           enc.DICT: ("seg_dict_decode", seg_dict_decode),
           enc.DELTA: ("seg_delta_decode", seg_delta_decode),
           enc.BSS: ("bss_decode", bss_decode)}


def _batched(encoding: str, specs, dt: np.dtype,
             interpret: bool) -> jnp.ndarray:
    """Stage ``specs`` and run the device decode of ``encoding``; the
    device result is padded past the value count to a power of two."""
    if encoding not in _KERNEL:
        raise ValueError(f"no device decode for encoding {encoding!r}")
    name, kernel = _KERNEL[encoding]
    with span("ops.stage", kernel=name):
        args = _stage(encoding, specs, dt)
    with span("ops.launch", kernel=name):
        return kernel(*args, interpret=interpret)


def _stage(encoding: str, specs, dt: np.dtype) -> tuple:
    """The host-side inputs of ``encoding``'s device kernel for ``specs``:
    segment plans, byte planes, dictionaries, pow2-padded targets."""
    ns = np.array([n for _, _, _, n in specs], np.int64)
    total = int(ns.sum())
    if encoding == enc.BSS:
        # plane b of the morsel is plane b of every page, side by side
        planes = np.zeros((4, 1 << max(total - 1, 0).bit_length()),
                          np.uint8)
        pos = 0
        for _, _, p, n in specs:
            planes[:, pos:pos + n] = np.frombuffer(
                p, np.uint8, count=4 * n).reshape(4, n)
            pos += n
        return (planes,)
    ks = np.array([m["bits"] for _, m, _, _ in specs], np.int64)
    if encoding == enc.BITPACK:
        words, w0, sh, mask = plan_segments([p for _, _, p, _ in specs],
                                            ns, ks)
        refs = np.zeros(w0.shape[0], np.int32)
        if dt != np.bool_:
            refs[:total] = np.repeat(
                np.array([m["ref"] for _, m, _, _ in specs], np.int64), ns)
        return words, w0, sh, mask, refs
    if encoding == enc.DICT:
        le = dt.newbyteorder("<")
        dicts = [np.frombuffer(p[:m["dict_len"]], le)
                 for _, m, p, _ in specs]
        words, w0, sh, mask = plan_segments(
            [memoryview(p)[m["dict_len"]:] for _, m, p, _ in specs], ns, ks)
        off = np.zeros(len(dicts), np.int64)
        np.cumsum([len(d) for d in dicts[:-1]], out=off[1:])
        doff = np.zeros(w0.shape[0], np.int32)
        doff[:total] = np.repeat(off, ns)
        # the gather runs in 32-bit device lanes: the caller's gate proved
        # the dictionary VALUES fit, so the host-side narrow is lossless
        dcat = np.concatenate(dicts).astype(
            np.int32 if dt.kind in "iu" else dt)
        return words, w0, sh, mask, dcat, doff
    # DELTA: each page packs n-1 zigzag'd deltas; page-start slots are zero
    # in the scatter so one global cumsum recovers every page (wrap-exact)
    words, w0, sh, mask = plan_segments([p for _, _, p, _ in specs],
                                        ns - 1, ks)
    d_total = int((ns - 1).sum())
    starts = np.zeros(len(ns), np.int64)
    np.cumsum(ns[:-1], out=starts[1:])
    pad_out = 1 << max(total - 1, 0).bit_length()
    pid = np.zeros(pad_out, np.int32)
    pid[:total] = np.repeat(np.arange(len(ns), dtype=np.int32), ns)
    dmask = np.ones(total, bool)
    dmask[starts] = False
    # pad slots of dpos point at output slot 0 — a page start, whose
    # value is forced to zero anyway, so the padded scatter is harmless
    dpos = np.zeros(w0.shape[0], np.int32)
    dpos[:d_total] = np.nonzero(dmask)[0]
    firsts = np.array([m["first"] for _, m, _, _ in specs], np.int32)
    return (words, w0, sh, mask, dpos, starts.astype(np.int32), pid, firsts,
            np.array([d_total], np.int32))


def decode_and_filter(encoding: str, meta: dict, payload: bytes, n: int,
                      np_dtype, lo, hi, *, interpret: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused decode -> range predicate; returns (values, mask, block_counts)."""
    vals = decode_on_device(encoding, meta, payload, n, np_dtype,
                            interpret=interpret)
    mask, counts = filter_range(vals, lo, hi, interpret=interpret)
    return vals, mask, counts
