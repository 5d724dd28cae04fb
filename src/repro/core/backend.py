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

ENV_VAR = "REPRO_DECODE_BACKEND"

_INT32_MIN, _INT32_MAX = -(1 << 31), (1 << 31) - 1


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
    that its work reached the device.
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
