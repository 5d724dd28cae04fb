"""Pluggable decode backends: numpy reference vs. Pallas-kernel (jax) decode.

The TPQ reader decodes every page through :func:`active_backend`.  The
``numpy`` backend is the always-correct reference (it simply calls
:func:`repro.core.encodings.decode`); the ``jax`` backend routes the
kernelized encodings — BITPACK, DICT, DELTA, BSS — through the Pallas
kernels in :mod:`repro.kernels.ops` whenever the page is *provably safe*
to decode in 32-bit device arithmetic, and falls back to the numpy path
otherwise.  Both backends therefore produce byte-identical arrays on every
page (the parity sweep in ``tests/test_backend.py`` asserts this across
the full encoding matrix).

Selection:

- ``REPRO_DECODE_BACKEND=numpy|jax`` in the environment, or
- :func:`set_backend` at runtime (tests, benchmarks), or
- default: ``numpy``.

Selecting ``jax`` where jax does not import raises :class:`RuntimeError`;
no selection quietly decodes on the host instead.
"""
from __future__ import annotations

import contextlib
import os
import threading
from collections import Counter
from typing import Dict, List, Optional

import numpy as np

from . import encodings as enc
from .dtypes import KIND_NUMERIC
from .expressions import _ARITH_FNS, Arith, FieldRef

ENV_VAR = "REPRO_DECODE_BACKEND"

_INT32_MIN, _INT32_MAX = -(1 << 31), (1 << 31) - 1
# a morsel's grouped sums go to the device only with this many groups or
# fewer; more go to the host, which needs no slot per group
MAX_DEVICE_GROUPS = 64


class DecodeBackend:
    """Reference backend: the vectorized numpy decoders in ``encodings``."""

    name = "numpy"

    def decode(self, encoding: str, meta: dict, payload, n: int,
               np_dtype, out: Optional[np.ndarray] = None) -> np.ndarray:
        return enc.decode(encoding, meta, payload, n, np_dtype, out=out)

    def decode_batch(self, specs, np_dtype,
                     out: Optional[np.ndarray] = None) -> np.ndarray:
        """Fused decode of a whole morsel's pages of one column.

        ``specs`` is ``[(encoding, meta, payload, n), ...]`` in output order;
        returns the concatenated values — byte-identical to per-page
        :meth:`decode` + concatenate, but with one vectorized dispatch per
        encoding group instead of one Python-level decode per page (the GIL
        convoy fix: see ``enc.decode_batch``).
        """
        return enc.decode_batch(specs, np_dtype, out=out)

    def range_mask(self, values: np.ndarray, lo, hi) -> np.ndarray:
        """Boolean mask for ``lo <= values <= hi`` (fused on device backends)."""
        return (values >= lo) & (values <= hi)

    def covering(self, pages: int):
        """Context in which this thread's :meth:`range_mask` calls each
        cover ``pages`` pages (what a counting backend counts)."""
        return contextlib.nullcontext()

    def minmax(self, values: np.ndarray):
        """(min, max) of a non-empty 1-D numeric array.

        The aggregate layer's partial-row-group reduction; the jax backend
        routes it through the Pallas ``page_minmax`` kernel when the dtype
        is exactly representable in 32-bit device lanes.
        """
        return values.min(), values.max()

    def group_sums(self, codes: np.ndarray, groups: int, terms, table):
        """Per-group row counts and exact integer sums of ``terms`` over
        ``table``'s rows, where row *i* is in group ``codes[i]``: the
        grouped path's reduction.  ``terms`` are value expressions
        (``FieldRef`` or ``Arith``) over ``table``'s columns, ``None`` for
        one that only the host can reduce.  Returns ``(counts, sums)``,
        each sum in the dtype the host's own reduction gives, or None: the
        caller then reduces the whole morsel on the host."""
        return None


class _HostOnly(Exception):
    """A grouped sum the device cannot reproduce exactly."""


def _sbits(lo: int, hi: int) -> int:
    """Two's-complement bits that hold every integer in ``[lo, hi]``."""
    return 1 + max((v if v >= 0 else -v - 1).bit_length() for v in (lo, hi))


def _sum_plan(terms, table):
    """``(programs, leaves, dtypes)`` for ``group_reduce``, or None.

    Each leaf column must be integer, fully valid and inside int32 in this
    morsel.  Each term is walked with interval bounds from the leaves'
    minima and maxima, and its result dtype taken from numpy's own rules
    for the host's evaluation: every node must be integer and inside its
    dtype, so the host's arithmetic does not wrap and the device's exact
    value is the host's.  A term carries enough 13-bit limbs for the
    widest of its nodes, and at least ``MIN_LIMBS``.
    """
    from repro.kernels.group_reduce import LIMB_BITS, MIN_LIMBS
    index: dict = {}
    leaves: List[np.ndarray] = []
    bounds: List[tuple] = []

    def leaf(name: str):
        if name not in index:
            col = table.column(name)
            v = col.values
            if (col.dtype.kind != KIND_NUMERIC or v.dtype.kind not in "iu"
                    or (col.validity is not None and not col.validity.all())):
                raise _HostOnly
            lo, hi = int(v.min()), int(v.max())
            if not JaxDecodeBackend._fits_i32(lo, hi):
                raise _HostOnly
            index[name] = len(leaves)
            leaves.append(v)
            bounds.append((lo, hi))
        i = index[name]
        return ("x", i), bounds[i][0], bounds[i][1], leaves[i][:0], 0

    def walk(e):
        """``(program, lo, hi, empty array of the node's dtype or the
        constant itself, widest bits below)``."""
        if isinstance(e, FieldRef):
            return leaf(e.name)
        if isinstance(e, (int, np.integer)) and not isinstance(
                e, (bool, np.bool_)):
            return ("c", int(e)), int(e), int(e), e, 0
        if not isinstance(e, Arith) or e.op not in ("+", "-", "*"):
            raise _HostOnly
        pa, la, ha, va, ba = walk(e.a)
        pb, lb, hb, vb, bb = walk(e.b)
        if pa[0] == "c" and pb[0] == "c":
            raise _HostOnly  # a constant node: the host broadcasts it
        try:
            out = _ARITH_FNS[e.op](va, vb)
        except (OverflowError, TypeError):
            raise _HostOnly from None
        if e.op == "+":
            lo, hi = la + lb, ha + hb
        elif e.op == "-":
            lo, hi = la - hb, ha - lb
        else:
            ends = (la * lb, la * hb, ha * lb, ha * hb)
            lo, hi = min(ends), max(ends)
        if out.dtype.kind not in "iu":
            raise _HostOnly
        info = np.iinfo(out.dtype)
        if lo < info.min or hi > info.max:
            raise _HostOnly  # the host's own arithmetic would wrap
        bits = max(ba, bb, _sbits(la, ha), _sbits(lb, hb), _sbits(lo, hi))
        return (e.op, pa, pb), lo, hi, out, bits

    programs, dtypes = [], []
    try:
        for t in terms:
            if t is None:
                return None
            prog, lo, hi, empty, bits = walk(t)
            bits = max(bits, _sbits(lo, hi))
            programs.append((prog, max(MIN_LIMBS, -(-bits // LIMB_BITS))))
            dtypes.append(empty.dtype)
    except _HostOnly:
        return None
    return tuple(programs), leaves, dtypes


class JaxDecodeBackend(DecodeBackend):
    """Routes safe pages through the Pallas decode kernels.

    Safety gate: the device kernels compute in 32-bit lanes (jax's default
    x64-disabled mode), so a page is routed only when every decoded value is
    exactly representable there — otherwise the numpy reference runs.  The
    gate keeps the backend *bit-identical* to numpy by construction.

    The kernels run compiled on an accelerator and in Pallas interpret mode
    on the CPU platform (``interpret``).  Every page the backend sees is
    counted where it ran: ``device_pages`` and ``host_pages`` map an
    encoding name — or ``"filter"`` for the pages a :meth:`range_mask` call
    covers (one, or what :meth:`covering` says) and ``"minmax"`` for a
    :meth:`minmax` call (one decoded batch) — to a count, so a run can show
    that its work reached the device.  ``"group_rows"`` counts the rows a
    :meth:`group_sums` call reduced, on the device or on the host.
    """

    name = "jax"

    def __init__(self):
        import jax

        from repro.kernels import ops  # deferred: jax import is heavy
        self._ops = ops
        self.interpret = jax.default_backend() == "cpu"
        self.device_pages: Counter = Counter()
        self.host_pages: Counter = Counter()
        self._count_lock = threading.Lock()
        self._covered = threading.local()

    def _count(self, on_device: bool, family: str, pages: int = 1) -> None:
        with self._count_lock:
            (self.device_pages if on_device else self.host_pages)[family] \
                += pages

    # -- safety gate ---------------------------------------------------------
    @staticmethod
    def _fits_i32(*vals) -> bool:
        return all(_INT32_MIN <= int(v) <= _INT32_MAX for v in vals)

    def _routable(self, encoding: str, meta: dict, payload, n: int,
                  dt: np.dtype) -> bool:
        if n == 0:
            return False
        if encoding == enc.BITPACK:
            if dt == np.bool_:
                return True
            bits, ref = meta["bits"], meta["ref"]
            return (dt.kind in "iu" and bits <= 31
                    and self._fits_i32(ref, ref + (1 << bits) - 1))
        if encoding == enc.DICT:
            if meta["bits"] > 31:
                return False
            # the gather runs in the dictionary dtype on device, so the
            # dictionary's actual values must be 32-bit exact
            uniq = np.frombuffer(payload[:meta["dict_len"]],
                                 dt.newbyteorder("<"))
            if dt.kind in "iu":
                return not len(uniq) \
                    or self._fits_i32(uniq.min(), uniq.max())
            return dt == np.float32
        if encoding == enc.DELTA:
            bits, first = meta["bits"], meta["first"]
            if dt.kind not in "iu" or bits > 31:
                return False
            # worst-case partial sum: first ± n * max|delta|
            span = (n - 1) * (1 << max(bits - 1, 0))
            return self._fits_i32(first - span, first + span)
        if encoding == enc.BSS:
            return dt == np.float32
        return False

    def decode(self, encoding: str, meta: dict, payload, n: int,
               np_dtype, out: Optional[np.ndarray] = None) -> np.ndarray:
        dt = np.dtype(np_dtype)
        if not self._routable(encoding, meta, payload, n, dt):
            self._count(False, encoding)
            return enc.decode(encoding, meta, payload, n, np_dtype, out=out)
        # a single page is a batch of one
        vals = self._ops.decode_batch_on_device(
            encoding, [(encoding, meta, payload, n)], dt,
            interpret=self.interpret)
        self._count(True, encoding)
        if out is not None:
            out[:] = vals
            return out
        return vals

    def decode_batch(self, specs, np_dtype,
                     out: Optional[np.ndarray] = None) -> np.ndarray:
        """Morsel-fused decode: one device dispatch per encoding group.

        Routing is all-or-nothing *per encoding group*: a BITPACK / DICT /
        DELTA / BSS group goes to the device only when every page in it
        passes the 32-bit gate; any other group — and any group with an
        unroutable page — decodes through the numpy segmented reference,
        keeping the whole batch byte-identical to the numpy backend.
        """
        dt = np.dtype(np_dtype)
        starts = enc._spec_slices(specs)
        if out is None:
            out = np.empty(int(starts[-1]), dt)
        rest: List[int] = []
        for encoding, idxs in enc._batch_groups(specs).items():
            sub = [specs[i] for i in idxs]
            if encoding not in self._ops.BATCHED or not all(
                    self._routable(e, m, p, n, dt) for e, m, p, n in sub):
                self._count(False, encoding, len(idxs))
                rest.extend(idxs)
                continue
            vals = self._ops.decode_batch_on_device(
                encoding, sub, dt, interpret=self.interpret)
            self._count(True, encoding, len(idxs))
            pos = 0
            for i in idxs:
                n = specs[i][3]
                out[starts[i]:starts[i + 1]] = vals[pos:pos + n]
                pos += n
        if len(rest) == len(specs):
            return enc.decode_batch(specs, dt, out=out)
        if rest:
            rest.sort()
            tmp = enc.decode_batch([specs[i] for i in rest], dt)
            pos = 0
            for i in rest:
                n = specs[i][3]
                out[starts[i]:starts[i + 1]] = tmp[pos:pos + n]
                pos += n
        return out

    @contextlib.contextmanager
    def covering(self, pages: int):
        prev = getattr(self._covered, "pages", 1)
        self._covered.pages = pages
        try:
            yield
        finally:
            self._covered.pages = prev

    def range_mask(self, values: np.ndarray, lo, hi) -> np.ndarray:
        # the device sees 32-bit lanes and the kernel casts bounds through
        # float32, so both the column VALUES and the bounds must be exactly
        # representable there — otherwise jnp.asarray would silently
        # truncate (e.g. int64 2**32+50 -> 50) and the mask diverges from
        # the numpy reference
        dt = values.dtype
        if dt == np.float32:
            exact = bool(np.float32(lo) == lo and np.float32(hi) == hi)
        elif dt.kind in "iu":
            exact = (self._fits_i32(lo, hi)
                     and max(abs(int(lo)), abs(int(hi))) < (1 << 24))
            if exact and dt.itemsize > 4 and len(values):
                # wide columns route only when the actual values fit
                exact = self._fits_i32(values.min(), values.max())
        else:
            exact = False
        exact = exact and len(values) > 0
        self._count(exact, "filter", getattr(self._covered, "pages", 1))
        if not exact:
            return super().range_mask(values, lo, hi)
        return self._ops.range_mask_on_device(values, lo, hi,
                                              interpret=self.interpret)

    # min/max are pure comparisons — no arithmetic — so the only gate is
    # that jnp.asarray must not truncate the values: <=32-bit ints and
    # float32 round-trip exactly in x64-disabled mode, wider dtypes fall
    # back to the numpy reference
    _MINMAX_SAFE = frozenset(["i1", "i2", "i4", "u1", "u2", "u4", "f4"])

    def minmax(self, values: np.ndarray):
        dt = values.dtype
        routable = (dt.kind + str(dt.itemsize) in self._MINMAX_SAFE
                    and len(values) > 0)
        self._count(routable, "minmax")
        if not routable:
            return super().minmax(values)
        return self._ops.minmax_on_device(values, interpret=self.interpret)

    def group_sums(self, codes: np.ndarray, groups: int, terms, table):
        # the gate: few groups, and every term an exact integer program
        # over fully valid int32 leaves (see _sum_plan)
        plan = (_sum_plan(terms, table)
                if 0 < groups <= MAX_DEVICE_GROUPS and len(codes) else None)
        if plan is not None and (self._ops.pad_groups(groups)
                                 * self._ops.group_slots(plan[0])
                                 > self._ops.MAX_GROUP_SLOTS):
            plan = None
        self._count(plan is not None, "group_rows", len(codes))
        if plan is None:
            return None
        programs, leaves, dtypes = plan
        counts, sums = self._ops.group_sums_on_device(
            codes, groups, leaves, programs, interpret=self.interpret)
        # the host sums in each term's own dtype, wrapping alike
        return counts, [s.astype(dt) for s, dt in zip(sums, dtypes)]


_jax_probe: Optional[bool] = None


def jax_available() -> bool:
    """Cached probe: can the jax backend be constructed in this process?"""
    global _jax_probe
    if _jax_probe is None:
        try:
            import jax  # noqa: F401
            _jax_probe = True
        except Exception:
            _jax_probe = False
    return _jax_probe


_instances: Dict[str, DecodeBackend] = {}
_active: Optional[str] = None


def get_backend(name: str) -> DecodeBackend:
    """Backend instance by name (constructed once per process)."""
    if name not in ("numpy", "jax"):
        raise ValueError(f"unknown decode backend {name!r} "
                         "(expected 'numpy' or 'jax')")
    be = _instances.get(name)
    if be is None:
        if name == "jax":
            if not jax_available():
                raise RuntimeError("jax backend requested but jax is not "
                                   "importable; use 'numpy'")
            be = JaxDecodeBackend()
        else:
            be = DecodeBackend()
        _instances[name] = be
    return be


def set_backend(name: Optional[str]) -> None:
    """Select the process-wide decode backend (None = back to env/default)."""
    global _active
    if name is not None:
        get_backend(name)  # validate eagerly
    _active = name


def active_backend() -> DecodeBackend:
    """The backend the reader should decode through, honoring overrides.

    Precedence: :func:`set_backend` > ``REPRO_DECODE_BACKEND`` > numpy.
    """
    return get_backend(_active or os.environ.get(ENV_VAR, "numpy"))
