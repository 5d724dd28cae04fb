"""Pallas TPU kernel: exact grouped integer sums in 32-bit lanes.

The device half of a grouped ``count``/``sum``/``mean`` over integer
inputs.  Each call takes one chunk of ``CHUNK`` rows: a group code per
row (``-1`` for padding, which matches no group) and the 32-bit leaf
columns.  Every aggregate input is a *term*, a small program over the
leaves built from ``+``, ``-``, ``*`` and integer constants, so a
computed input such as ``ep * (100 - d) * (100 + t)`` is formed here from
its 32-bit factors and its 64-bit value never exists on the host.

Exactness without 64-bit lanes: a term's value is carried as ``k`` limbs
of ``LIMB_BITS`` bits, ``v = sum(limb[i] << (LIMB_BITS * i))``, the low
limbs in ``[0, 2**13)`` and the top one signed in ``[-2**12, 2**12)``.
Sums and differences add limb by limb, products convolve the limbs
(terms past ``k`` limbs drop, as they are multiples of ``2**(13 k)``), and
a carry pass restores the ranges.  The caller sizes ``k`` so that every
value of every node of the term fits ``k`` signed limbs; then each
product of two limbs is below ``2**26`` and each convolution sum below
``2**29``.  A chunk's sum of one limb over its ``2**17`` rows stays below
``2**30``, so no partial overflows; the host resolves the carries in
int64.  No float is on the path.

Each grid step reduces a ``(BLOCK_ROWS, 128)`` block: for each group and
slot (the row count, then every limb of every term) the masked values
fold into one ``(8, 128)`` tile of the resident output, by adds across
vregs only; the jitted wrapper then sums each tile.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["group_reduce", "group_slots", "pad_groups", "LIMB_BITS",
           "MIN_LIMBS", "CHUNK", "MAX_GROUP_SLOTS"]

LANES = 128
LIMB_BITS = 13
# limbs a term takes at least: three hold any int32, so a term's limb
# count, and with it the compiled program, depends on the data only past
# 2**38 and does not change from one morsel or parameter set to the next
MIN_LIMBS = 3
_MASK = (1 << LIMB_BITS) - 1
_HALF = 1 << (LIMB_BITS - 1)
CHUNK = 1 << 17        # rows per call: 2**17 * 2**13 stays below 2**31
BLOCK_ROWS = 256       # (256, 128) values of each input per grid step
MAX_GROUP_SLOTS = 512  # padded groups x slots: a 2 MB resident output

# a term is (program, limbs); a program node is ("x", leaf index),
# ("c", int) or (op, node, node) with op "+", "-" or "*"
Term = Tuple[tuple, int]


def group_slots(terms: Sequence[Term]) -> int:
    """Output slots of each group: the row count, then every limb."""
    return 1 + sum(k for _, k in terms)


def pad_groups(groups: int) -> int:
    """Groups rounded up to a power of two, at least 4, so morsels with a
    few groups more or less share one compiled program."""
    return max(4, 1 << max(groups - 1, 0).bit_length())


def _carry(limbs: list) -> list:
    """Limbs back into range: low limbs in [0, 2**13), the top one
    sign-extended from 13 bits (the value fits, so this drops only
    multiples of 2**(13 k))."""
    out, carry = [], 0
    for v in limbs[:-1]:
        v = v + carry
        carry = v >> LIMB_BITS
        out.append(v & _MASK)
    top = limbs[-1] + carry
    out.append(((top + _HALF) & _MASK) - _HALF)
    return out


def _limbs(node: tuple, leaves: list, k: int) -> list:
    """The ``k`` limbs of a program node (arrays, or ints for constants)."""
    tag = node[0]
    if tag == "x":
        x = leaves[node[1]]
        # arithmetic shifts past bit 31 are the sign: cap them there
        return ([(x >> min(LIMB_BITS * i, 31)) & _MASK for i in range(k - 1)]
                + [x >> min(LIMB_BITS * (k - 1), 31)])
    if tag == "c":
        c = int(node[1])
        return [(c >> (LIMB_BITS * i)) & _MASK for i in range(k - 1)] \
            + [c >> (LIMB_BITS * (k - 1))]
    a, b = _limbs(node[1], leaves, k), _limbs(node[2], leaves, k)
    if tag == "+":
        return _carry([x + y for x, y in zip(a, b)])
    if tag == "-":
        return _carry([x - y for x, y in zip(a, b)])
    if tag == "*":
        return _carry([sum(a[i] * b[j - i] for i in range(j + 1))
                       for j in range(k)])
    raise ValueError(f"no device arithmetic {tag!r}")


def _kernel(codes_ref, *refs, terms: Tuple[Term, ...], groups: int):
    *x_refs, out_ref = refs

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...]
    leaves = [r[...] for r in x_refs]
    slots: List = [jnp.ones_like(codes)]
    for prog, k in terms:
        slots.extend(_limbs(prog, leaves, k))
    rows = codes.shape[0]
    for g in range(groups):
        hit = codes == g
        for j, v in enumerate(slots):
            part = jnp.where(hit, v, 0).reshape(rows // 8, 8, LANES)
            s = (g * len(slots) + j) * 8
            out_ref[s:s + 8, :] += part.sum(axis=0)


@functools.partial(jax.jit, static_argnames=("terms", "groups",
                                             "interpret"))
def group_reduce(codes: jnp.ndarray, *leaves: jnp.ndarray,
                 terms: Tuple[Term, ...], groups: int,
                 interpret: bool = False) -> jnp.ndarray:
    """Per-group slot sums of one chunk, int32 ``(groups, slots)``.

    ``codes`` and every leaf are int32 of one length, a multiple of 1,024
    and at most ``CHUNK``; a row's code is its group in ``[0, groups)``
    or ``-1``.  Slot 0 counts rows, then come the limbs of each term in
    order."""
    n = codes.shape[0]
    rows = n // LANES
    block = min(BLOCK_ROWS, rows)
    per = group_slots(terms)
    spec = pl.BlockSpec((block, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, terms=terms, groups=groups),
        grid=(rows // block,),
        in_specs=[spec] * (1 + len(leaves)),
        out_specs=pl.BlockSpec((groups * per * 8, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((groups * per * 8, LANES), jnp.int32),
        interpret=interpret,
    )(codes.reshape(rows, LANES), *(x.reshape(rows, LANES) for x in leaves))
    return out.reshape(groups, per, 8 * LANES).sum(axis=2)
