"""Segmented (multi-page) decode kernels: ONE device dispatch per morsel.

The per-page kernels in :mod:`bitunpack` / :mod:`dict_decode` /
:mod:`delta_decode` cost one Python-level ``pallas_call`` per page — which is
exactly the GIL convoy the parallel scan measures (bench/BENCH_fig11.json).
Here a whole morsel's pages of one column chunk are decoded by a single
fused dispatch:

- the host concatenates the packed page payloads 4-byte-aligned and
  precomputes, per output element, the 32-bit word index / shift / mask of
  its packed value (pure numpy index arithmetic, no data-dependent work);
- the device gathers the two straddling words (XLA gather — dynamic
  indexing is the one thing Pallas TPU blocks can't do), then a Pallas
  kernel fuses the shift/or/mask/reference-add combine over VPU lanes;
- DICT gathers one concatenated dictionary, DELTA recovers values with a
  single cumulative sum over all pages (page-start slots carry zero, so
  ``c[i] - c[start(p)] + first[p]`` is the page-local prefix sum — int32
  wrap commutes with the subtraction, and the backend's 32-bit gate proves
  every *final* value fits, so wrapped intermediates are still exact).

All functions take pre-staged host arrays from :func:`plan_segments` and are
jit'd on shape: inputs are padded to power-of-two lengths so repeated morsel
shapes hit the trace cache.  Kernels run compiled unless the caller asks
for ``interpret=True`` (the CPU backend does).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["plan_segments", "seg_bitunpack", "seg_dict_decode",
           "seg_delta_decode"]

LANE_VALUES = 1024  # outputs per Pallas grid step (matches bitunpack.py)


# ---------------------------------------------------------------------------
# host-side staging (numpy; no data-dependent work, just index arithmetic)
# ---------------------------------------------------------------------------
def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def plan_segments(payloads: Sequence, ns: np.ndarray, ks: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Stage a morsel's packed pages for one fused device dispatch.

    Returns ``(words, w0, sh, mask)``: the 4-byte-aligned concatenated
    uint32 word stream plus, per output element, the word index of its
    value's low word, the in-word bit shift and the k-bit mask.  Element
    *i* of page *p* (packed at ``ks[p] <= 31`` bits) lives at bit
    ``base[p] + i * ks[p]`` and spans at most two uint32 words.  Arrays
    are padded to power-of-two lengths (padding decodes word 0 harmlessly)
    so repeated morsel shapes reuse the jit trace.
    """
    total = int(ns.sum())
    needs = [(int(n) * int(k) + 7) // 8 for n, k in zip(ns, ks)]
    bases = np.zeros(len(payloads), np.int64)
    off = 0
    for p, nb in enumerate(needs):
        bases[p] = off
        off += (nb + 3) // 4 * 4
    buf = np.zeros(_pow2(off + 8), np.uint8)
    for base, pl_, nb in zip(bases, payloads, needs):
        if nb:
            buf[base:base + nb] = np.frombuffer(pl_, np.uint8, count=nb)
    words = buf.view("<u4")
    pid = np.repeat(np.arange(len(ns)), ns)
    starts = np.zeros(len(ns), np.int64)
    np.cumsum(ns[:-1], out=starts[1:])
    idx = np.arange(total, dtype=np.int64) - np.repeat(starts, ns)
    bit = bases[pid] * 8 + idx * ks[pid]
    pad = _pow2(total)
    w0 = np.zeros(pad, np.int32)
    sh = np.zeros(pad, np.uint32)
    mask = np.zeros(pad, np.uint32)
    w0[:total] = bit >> 5
    sh[:total] = bit & 31
    mask[:total] = ((np.uint32(1) << ks.astype(np.uint32))
                    - np.uint32(1))[pid]
    return words, w0, sh, mask


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------
def _combine_kernel(lo_ref, hi_ref, mask_ref, ref_ref, out_ref):
    """Fused shift-merge + mask + reference-add over one lane block."""
    v = (lo_ref[...] | hi_ref[...]) & mask_ref[...]
    out_ref[...] = v.astype(jnp.int32) + ref_ref[...]


def _combine(lo, hi, mask, refs, interpret: bool) -> jnp.ndarray:
    n = lo.shape[0]  # static under jit; already power-of-two padded
    # a morsel shorter than one lane block is one whole-array block: the
    # TPU admits a rank-1 block only as a multiple of its tiling or as
    # the full array, and a power of two below 1024 divides neither way
    block = min(n, LANE_VALUES)
    spec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        _combine_kernel,
        grid=(n // block,),
        in_specs=[spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
    )(lo, hi, mask, refs)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _seg_values(words, w0, sh, mask, refs, *, interpret: bool = False):
    """Gather + combine: the packed-value stream of a whole morsel."""
    w = words.astype(jnp.uint32)
    lo = w[w0] >> sh
    hi = jnp.where(sh == 0, jnp.uint32(0),
                   w[w0 + 1] << ((jnp.uint32(32) - sh) & jnp.uint32(31)))
    return _combine(lo, hi, mask, refs, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def seg_bitunpack(words, w0, sh, mask, refs, *, interpret: bool = False
                  ) -> jnp.ndarray:
    """BITPACK a whole morsel: unpack + frame-of-reference add, one dispatch.

    ``refs`` is the per-element reference (int32, page-constant).
    """
    return _seg_values(words, w0, sh, mask, refs, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def seg_dict_decode(words, w0, sh, mask, dictionary, doff, *,
                    interpret: bool = False) -> jnp.ndarray:
    """DICT a whole morsel: one index unpack + one gather of the
    concatenated per-page dictionaries (``doff`` = per-element dict base)."""
    idx = _seg_values(words, w0, sh, mask, jnp.zeros_like(w0),
                      interpret=interpret)
    return dictionary[idx + doff]


@functools.partial(jax.jit, static_argnames=("interpret",))
def seg_delta_decode(words, w0, sh, mask, dpos, starts, pid, firsts, n, *,
                     interpret: bool = False) -> jnp.ndarray:
    """DELTA a whole morsel: one zigzag unpack + ONE global cumsum.

    ``dpos`` scatters each decoded delta to its output slot (page-start
    slots stay zero), ``starts``/``pid``/``firsts`` recover page-local
    prefix sums from the global cumulative sum.  ``n`` is a length-1 array
    carrying the unpadded element count (kept as data, not a static arg,
    so shape buckets share one trace).
    """
    zz = _seg_values(words, w0, sh, mask, jnp.zeros_like(w0),
                     interpret=interpret)
    u = zz.astype(jnp.uint32)
    deltas = (u >> jnp.uint32(1)).astype(jnp.int32) ^ \
        -(u & jnp.uint32(1)).astype(jnp.int32)
    d_full = jnp.zeros(pid.shape[0], jnp.int32).at[dpos].set(
        jnp.where(jnp.arange(deltas.shape[0]) < n[0], deltas, 0))
    c = jnp.cumsum(d_full)
    return c - c[starts][pid] + firsts[pid]
