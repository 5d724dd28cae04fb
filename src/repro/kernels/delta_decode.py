"""Pallas TPU kernel: DELTA decode (zigzag + running prefix sum).

The sequential dependency (a cumulative sum over the whole column) maps onto
the TPU's *sequential grid*: each grid step computes the inclusive prefix
sum of its (ROWS, 128) block in VMEM and threads the running total to the
next step through an SMEM scratch cell — the same carry idiom TPU matmul
kernels use for accumulators.  No second pass and no host round-trip.

Mosaic has no cumulative-sum primitive, so the in-block scan is a
log-step (Hillis-Steele) scan built from lane and sublane rotations: seven
masked ``roll``-and-add steps along the 128 lanes give every row's prefix,
then four along the 16 sublanes carry each row's total into the rows below.
int32 addition wraps, so the scan is exact under the same overflow
semantics as ``jnp.cumsum``.

Input convention (matches ``repro.core.encodings._enc_delta``): ``zz`` holds
zigzag-encoded deltas with a leading 0 slot, so ``out = first + cumsum(deltas)``
has length n.
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


def _unzigzag(u: jnp.ndarray) -> jnp.ndarray:
    u = u.astype(jnp.uint32)
    neg = -(u & jnp.uint32(1)).astype(jnp.int32)
    return ((u >> jnp.uint32(1)) ^ neg.astype(jnp.uint32)).astype(jnp.int32)


def _scan(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inclusive prefix sum of a 2-D int32 tile along ``axis``."""
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    d = 1
    while d < x.shape[axis]:
        x = x + jnp.where(pos >= d, pltpu.roll(x, d, axis), 0)
        d *= 2
    return x


def _delta_kernel(zz_ref, first_ref, out_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[0] = first_ref[0]

    deltas = _unzigzag(zz_ref[...])                       # (ROWS, LANES)
    rows = _scan(deltas, 1)                               # per-row prefix
    tot = jnp.broadcast_to(rows[:, LANES - 1:], rows.shape)
    above = _scan(tot, 0) - tot                           # earlier rows' sum
    out_ref[...] = carry_ref[0] + rows + above
    carry_ref[0] = carry_ref[0] + jnp.sum(deltas)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_decode(zz: jnp.ndarray, first: jnp.ndarray, *,
                 interpret: bool = False) -> jnp.ndarray:
    n = zz.shape[0]
    if n == 0:
        return jnp.zeros(0, jnp.int32)
    blocks = -(-n // BLOCK)
    zzp = jnp.pad(zz.astype(jnp.uint32), (0, blocks * BLOCK - n))
    out = pl.pallas_call(
        _delta_kernel,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scalar `first`
        ],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks * ROWS, LANES), jnp.int32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(zzp.reshape(-1, LANES), first.astype(jnp.int32).reshape(1))
    return out.reshape(-1)[:n]
