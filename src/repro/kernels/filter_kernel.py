"""Pallas TPU kernel: fused range-predicate evaluation + per-block match count.

The device-side half of predicate pushdown: after a column block is decoded in
VMEM, the predicate ``lo <= x <= hi`` is evaluated *in the same memory space*
and a per-block match count is emitted so the consumer can skip empty blocks
without reading the mask back — mirroring how the host-side reader skips pages
by their footer statistics.

Values run as (ROWS, 128) tiles of int32 or float32 lanes: the wrapper
widens narrower integers and maps uint32 order-preservingly onto int32 (a
sign-bit flip), and casts the bounds into the lane dtype, because Mosaic
converts floats to no unsigned type.  The mask leaves the kernel as int32 and
each block's count as one broadcast (8, 128) tile — the smallest output
block the TPU's tiling admits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROWS = 16
BLOCK = ROWS * LANES  # values per grid step


def _filter_kernel(bounds_ref, x_ref, mask_ref, count_ref):
    x = x_ref[...]
    m = ((x >= bounds_ref[0]) & (x <= bounds_ref[1])).astype(jnp.int32)
    mask_ref[...] = m
    count_ref[...] = jnp.broadcast_to(jnp.sum(m), count_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def filter_range(x: jnp.ndarray, lo, hi, *, interpret: bool = False):
    """Returns (mask: bool (n,), block_counts: int32 (blocks,))."""
    n = x.shape[0]
    if x.dtype.itemsize < 4:
        x = x.astype(jnp.int32)
    bounds = jnp.stack([jnp.asarray(lo, jnp.float32),
                        jnp.asarray(hi, jnp.float32)]).astype(x.dtype)
    if x.dtype == jnp.uint32:
        x, bounds = (jax.lax.bitcast_convert_type(v ^ jnp.uint32(1 << 31),
                                                  jnp.int32)
                     for v in (x, bounds))
    blocks = max(-(-n // BLOCK), 1)
    # padding must never match: -inf for floats, lo-1 for integers
    if jnp.issubdtype(x.dtype, jnp.floating):
        fill = jnp.array(-jnp.inf, x.dtype)
    else:
        fill = bounds[0] - 1
    xp = jnp.full((blocks * BLOCK,), fill, x.dtype).at[:n].set(x)
    mask, counts = pl.pallas_call(
        _filter_kernel,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # (2,) lo, hi
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((8, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((blocks * ROWS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((blocks * 8, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(bounds, xp.reshape(-1, LANES))
    return mask.reshape(-1)[:n].astype(jnp.bool_), counts[::8, 0]
