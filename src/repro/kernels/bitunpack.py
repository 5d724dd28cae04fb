"""Pallas TPU kernel: k-bit unpack (the BITPACK/DICT-index decode hot path).

Hardware adaptation: the paper decodes on the host CPU; here the host ships
the *packed* stream (k/32 of the decoded size) over PCIe and the chip widens
it in VMEM next to the consumer.

TPU-native formulation: a gather-free bit expansion.  Every 32 consecutive
k-bit values occupy exactly k uint32 words, and value j of such a group
starts at the *static* bit offset j*k.  The wrapper lays the stream out as a
(k, G) word matrix — word w of every group on one sublane row, groups along
the 128-wide lanes — so the kernel unrolls the 32 static (row, shift) pairs
into whole-row shift/or/mask ops and writes a (32, G) value matrix.  No
dynamic indexing and no reduction; the two transposes around the kernel are
plain XLA copies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Groups (of 32 values) per grid step, along the lane axis: a multiple of
# 128, so one step decodes 32 * GROUPS = 8192 values (one default page).
GROUPS = 256


def _bitunpack_kernel(words_ref, out_ref, *, k: int):
    mask = jnp.uint32(0xFFFFFFFF if k == 32 else (1 << k) - 1)
    for j in range(32):
        w, s = divmod(j * k, 32)
        v = words_ref[w:w + 1, :] >> jnp.uint32(s)
        if s + k > 32:
            v = v | (words_ref[w + 1:w + 2, :] << jnp.uint32(32 - s))
        out_ref[j:j + 1, :] = (v & mask).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _unpack(words: jnp.ndarray, *, k: int, interpret: bool) -> jnp.ndarray:
    g = words.shape[0] // k
    out = pl.pallas_call(
        functools.partial(_bitunpack_kernel, k=k),
        grid=(g // GROUPS,),
        in_specs=[pl.BlockSpec((k, GROUPS), lambda i: (0, i))],
        out_specs=pl.BlockSpec((32, GROUPS), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((32, g), jnp.int32),
        interpret=interpret,
    )(words.reshape(g, k).T)
    return out.T.reshape(-1)


def bitunpack(words: jnp.ndarray, n: int, k: int, *,
              interpret: bool = False) -> jnp.ndarray:
    """Decode ``n`` k-bit values from a packed little-endian uint32 stream.

    The kernel compiles once per (k, grid steps): every ``n`` up to one
    step's 8,192 values shares one program."""
    if k == 0:
        return jnp.zeros(n, jnp.int32)
    if k > 32:
        raise ValueError("device bitunpack supports k <= 32")
    size = max(-(-n // (32 * GROUPS)), 1) * GROUPS * k
    words = jnp.asarray(words, jnp.uint32)[:size]
    words = jnp.pad(words, (0, size - words.shape[0]))
    return _unpack(words, k=k, interpret=interpret)[:n]
