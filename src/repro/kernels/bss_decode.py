"""Pallas TPU kernel: BYTE_STREAM_SPLIT decode for float32 columns.

BSS stores the i-th byte of every value contiguously (great for compression);
decode recombines four byte planes into IEEE words.  On TPU this is four
widening loads + shifts + ors on the VPU and one bitcast — no transpose
through HBM: the four planes stream block-by-block into VMEM and recombine
in-register.
"""
from __future__ import annotations

import functools

import jax
import jax.lax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 2048


def _bss_kernel(planes_ref, out_ref):
    b = planes_ref[...].astype(jnp.uint32)         # (4, B)
    word = b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)
    out_ref[...] = jax.lax.bitcast_convert_type(word, jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bss_decode(byte_planes: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """byte_planes: (4, n) uint8 -> (n,) float32."""
    assert byte_planes.shape[0] == 4, "float32 has 4 byte planes"
    n = byte_planes.shape[1]
    if n == 0:
        return jnp.zeros(0, jnp.float32)
    blocks = -(-n // BLOCK)
    planes = jnp.pad(byte_planes, ((0, 0), (0, blocks * BLOCK - n)))
    out = pl.pallas_call(
        _bss_kernel,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((4, BLOCK), lambda i: (0, i))],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((blocks * BLOCK,), jnp.float32),
        interpret=interpret,
    )(planes)
    return out[:n]
