"""Pallas TPU kernel: fused per-page min/max statistics.

Reduces each page to (min, max) in one VMEM pass — the footer statistics
the reader prunes on, and the aggregate layer's partial-row-group min/max.

The kernel sees one page per grid step as a (rows, 128) tile of signed
32-bit lanes (Mosaic reduces neither unsigned integers nor rank-1 blocks).
The wrapper maps every supported dtype there order-preservingly — narrow
integers widen, uint32 flips its sign bit — and pads each page with copies
of its own last value up to a whole number of (8, 128) tiles, which changes
no page's min or max.  Each page's results leave as one broadcast (8, 128)
tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
TILE = 8 * LANES


def _stats_kernel(x_ref, min_ref, max_ref):
    x = x_ref[...]
    min_ref[...] = jnp.broadcast_to(jnp.min(x), min_ref.shape)
    max_ref[...] = jnp.broadcast_to(jnp.max(x), max_ref.shape)


@functools.partial(jax.jit, static_argnames=("page", "interpret"))
def page_minmax(x: jnp.ndarray, page: int, *, interpret: bool = False):
    """Per-page (min, max); a ragged last page is padded with ``x[-1]``."""
    dt = x.dtype
    n = x.shape[0]
    pages = -(-n // page)
    if pages * page != n:
        x = jnp.concatenate([x, jnp.full(pages * page - n, x[-1], dt)])
    if dt == jnp.uint32:
        x = jax.lax.bitcast_convert_type(x ^ jnp.uint32(1 << 31), jnp.int32)
    elif dt.itemsize < 4:
        x = x.astype(jnp.int32)
    width = -(-page // TILE) * TILE
    x = jnp.pad(x.reshape(pages, page), ((0, 0), (0, width - page)),
                mode="edge")
    rows = width // LANES
    mins, maxs = pl.pallas_call(
        _stats_kernel,
        grid=(pages,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((8, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((8, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((pages * 8, LANES), x.dtype),
                   jax.ShapeDtypeStruct((pages * 8, LANES), x.dtype)],
        interpret=interpret,
    )(x.reshape(-1, LANES))
    mins, maxs = mins[::8, 0], maxs[::8, 0]
    if dt == jnp.uint32:
        return tuple(jax.lax.bitcast_convert_type(m, jnp.uint32)
                     ^ jnp.uint32(1 << 31) for m in (mins, maxs))
    return mins.astype(dt), maxs.astype(dt)
