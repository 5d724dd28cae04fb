"""Pallas TPU kernel: dictionary decode (DICT-encoded column hot path).

TPU adaptation: instead of a scalar gather (cheap on CPU, serialized on TPU),
small dictionaries are decoded as a *one-hot contraction*: the (L, D) match
matrix against the D dictionary entries is an MXU-shaped operation.  The full
dictionary lives in VMEM and is re-used by every grid step (its BlockSpec
index map pins block 0).  For D > MAX_ONEHOT_DICT the jit'd wrapper falls
back to ``jnp.take`` outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 1024
MAX_ONEHOT_DICT = 4096  # one-hot beyond this wastes FLOPs vs a gather


def _dict_kernel(idx_ref, dict_ref, out_ref):
    idx = idx_ref[...].astype(jnp.int32)                       # (L,)
    d = dict_ref[...]                                          # (D,)
    iota = jnp.arange(d.shape[0], dtype=jnp.int32)
    onehot = (idx[:, None] == iota[None, :])                   # (L, D)
    if jnp.issubdtype(d.dtype, jnp.floating):
        out = jnp.dot(onehot.astype(d.dtype), d)               # MXU path
    else:
        out = jnp.where(onehot, d[None, :], 0).sum(axis=1).astype(d.dtype)
    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("interpret",))
def dict_decode(indices: jnp.ndarray, dictionary: jnp.ndarray, *,
                interpret: bool = False) -> jnp.ndarray:
    """out[i] = dictionary[indices[i]]."""
    n, d = indices.shape[0], dictionary.shape[0]
    if d > MAX_ONEHOT_DICT or n == 0:
        return jnp.take(dictionary, indices.astype(jnp.int32), axis=0)
    blocks = -(-n // BLOCK)
    idx = jnp.pad(indices.astype(jnp.int32), (0, blocks * BLOCK - n))
    out = pl.pallas_call(
        _dict_kernel,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((d,), lambda i: (0,)),  # whole dict resident in VMEM
        ],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((blocks * BLOCK,), dictionary.dtype),
        interpret=interpret,
    )(idx, dictionary)
    return out[:n]
