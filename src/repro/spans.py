"""Named host spans on the profiler's clock, for reading where a query's
time goes.

``span(name, **args)`` is a context manager around one piece of the read
path.  When ``jax`` is already imported it is a
``jax.profiler.TraceAnnotation`` named ``"repro." + name``: inside a
``jax.profiler.trace(...)`` session the span lands in the same trace as
the device's operations, on the host thread's own line, so host work and
device idle time can be laid side by side.  With no profiler session a
TraceAnnotation costs a fraction of a microsecond and formats nothing
(its arguments are encoded only while a session records).  Without jax
in the process the span is a shared no-op; this module never imports jax,
so the numpy backend and the server stay jax-free.

``query_span()`` opens the root span of one query, ``repro.query``, with
a per-process ``qid``.  A terminal called inside another one (``agg``
materialising through ``to_table``) opens no second root, so every span
of the query nests under one root on its thread.  A scan's worker and
readahead threads have lines of their own; their spans lie inside the
root in time.

The spans and the layer each marks are listed in ``docs/ARCHITECTURE.md``
("Tracing a query").
"""
from __future__ import annotations

import itertools
import sys
import threading
from typing import Any, Dict, Generator, Iterator, TypeVar

__all__ = ["span", "query_span", "rooted"]

PREFIX = "repro."

T = TypeVar("T")


class _NoSpan:
    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _NoSpan()
_annotation: Any = None
_full: Dict[str, str] = {}


def span(name: str, **args):
    """A ``repro.<name>`` profiler span, or a no-op without jax."""
    global _annotation
    make = _annotation
    if make is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None) if jax is not None else None
        if profiler is None:
            return _NOOP
        make = _annotation = profiler.TraceAnnotation
    full = _full.get(name)
    if full is None:
        full = _full.setdefault(name, PREFIX + name)
    return make(full, **args)


_qids = itertools.count(1)
_open = threading.local()


class query_span:
    """Root span of one query on this thread; nested roots are no-ops."""

    __slots__ = ("_inner",)

    def __enter__(self) -> None:
        if getattr(_open, "root", False):
            self._inner = None
            return None
        _open.root = True
        self._inner = span("query", qid=next(_qids))
        try:
            self._inner.__enter__()
        except BaseException:
            _open.root = False
            raise
        return None

    def __exit__(self, *exc) -> bool:
        inner = self._inner
        if inner is not None:
            self._inner = None
            try:
                inner.__exit__(*exc)
            finally:
                _open.root = False
        return False


def rooted(gen: Iterator[T]) -> Generator[T, None, None]:
    """Yield from ``gen``, each item produced inside its own
    :class:`query_span`; never holds a span open across a yield."""
    try:
        while True:
            with query_span():
                try:
                    item = next(gen)
                except StopIteration:
                    return
            yield item
    finally:
        close = getattr(gen, "close", None)
        if close is not None:
            close()
