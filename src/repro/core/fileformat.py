"""TPQ file format — the repo's Parquet analogue, from scratch.

Layout (paper §4.1 / SI §1), format v2:

    b"TPQ1"
    <data section: concatenated encoded buffers>
    <footer: zlib-compressed JSON>
    <uint32 LE crc32 of footer blob> <uint64 LE footer length> b"TPQ2"

Format v1 files (no checksums) end with ``<uint64 LE footer length> b"TPQ1"``
instead; the reader dispatches on the trailing magic and reads them as
"unchecksummed" (``TPQReader.checksummed`` is False).  v2 additionally
records a crc32 per stored buffer (``"crc"`` in each buffer dict, hashed
over the on-disk — possibly compressed — bytes, so verification is a single
pass before decompression).  Verification failures raise the typed errors
from :mod:`repro.core.integrity` (``TruncatedFileError`` /
``CorruptFooterError`` / ``CorruptPageError`` with file/row-group/page
coordinates) instead of cryptic ``struct``/``zlib``/``json`` errors.

A file holds *row groups* (horizontal partitions); each row group holds one
*column chunk* per field; each chunk is split into *pages* whose row boundaries
are aligned across columns (so page-level pruning on a filter column maps
directly to page slices of every projected column — our page-index
implementation of SI §1.3).  The footer carries the schema, table metadata and
per-chunk + per-page statistics (min/max/null-count/bloom) and buffer offsets,
enabling:

  - projection pushdown: only the byte ranges of requested columns are read;
  - predicate pushdown: row groups and then pages whose stats cannot match the
    filter are never read from disk.
"""
from __future__ import annotations

import json
import math
import mmap
import os
import struct
import zlib
from typing import Any, Dict, Iterator, List, Optional, Sequence

import numpy as np

from . import encodings as enc
from . import integrity
from ..spans import span
from .backend import active_backend
from .integrity import (CorruptFooterError, CorruptPageError,
                        TruncatedFileError)
from .dtypes import (DType, KIND_BINARY, KIND_LIST, KIND_NULL, KIND_NUMERIC,
                     KIND_STRING, KIND_TENSOR)
from .expressions import Expr
from .schema import Schema
from .statistics import (ColumnStats, compute_bloom, compute_stats,
                         merge_stat_maps, merge_stats)
from .table import (Column, Table, _ragged_gather_index, concat_columns,
                    null_column_of)


def _payload_nbytes(p) -> int:
    if isinstance(p, (bytes, bytearray)):
        return len(p)
    return memoryview(p).nbytes

MAGIC = b"TPQ1"
TRAILER_V2 = b"TPQ2"  # trailing magic of checksummed (v2) files
VERSION = 2
CREATED_BY = "repro-tpq 0.2"

DEFAULT_PAGE_ROWS = 8192
DEFAULT_ROW_GROUP_ROWS = 131072


# ---------------------------------------------------------------------------
# Writer
# ---------------------------------------------------------------------------
class TPQWriter:
    def __init__(self, path: str, *, codec: str = enc.CODEC_ZLIB, level: int = 1,
                 encoding: str = enc.AUTO, page_rows: int = DEFAULT_PAGE_ROWS,
                 row_group_rows: int = DEFAULT_ROW_GROUP_ROWS,
                 with_bloom: bool = True,
                 field_encodings: Optional[Dict[str, str]] = None,
                 field_codecs: Optional[Dict[str, str]] = None,
                 file_kind: str = "base",
                 checksums: bool = True):
        # file_kind: "base" | "upsert" | "tombstone" — a footer flag marking
        # merge-on-read delta files, so an orphaned .tpq is self-describing
        # even without the manifest (crash forensics, external tools).
        # checksums=False writes the exact legacy v1 layout (no crcs, TPQ1
        # trailer) — kept for back-compat tests and external v1 consumers.
        self.file_kind = file_kind
        self.path = path
        self.checksums = checksums
        self._fault(len(MAGIC))
        self._fh = open(path, "wb")
        self._fh.write(MAGIC)
        self._off = len(MAGIC)
        self.codec, self.level, self.encoding = codec, level, encoding
        self.page_rows, self.row_group_rows = page_rows, row_group_rows
        self.with_bloom = with_bloom
        self.field_encodings = field_encodings or {}
        self.field_codecs = field_codecs or {}
        self._row_groups: List[dict] = []
        self._schema: Optional[Schema] = None
        self._num_rows = 0
        self._closed = False

    def _fault(self, nbytes: int) -> None:
        # IO fault injection point (ENOSPC/EIO harness): called before every
        # disk write so tests can make the "disk" fill after K bytes
        if integrity.WRITE_FAULT_HOOK is not None:
            integrity.WRITE_FAULT_HOOK(self.path, nbytes)

    # -- buffers ---------------------------------------------------------------
    def _put(self, payload, encoding: str, meta: dict, codec: str,
             count: int) -> dict:
        # payload is any C-contiguous bytes-like (bytes, memoryview, uint8
        # ndarray): both zlib and the file write consume the buffer protocol,
        # so encoded pages reach disk without an intermediate .tobytes() copy
        nbytes = _payload_nbytes(payload)
        comp = enc.compress(payload, codec, self.level)
        if len(comp) >= nbytes:  # store raw when compression loses
            comp, codec, clen = payload, enc.CODEC_NONE, nbytes
        else:
            clen = len(comp)
        d = {"off": self._off, "len": clen, "enc": encoding,
             "codec": codec, "count": count}
        if self.checksums:
            # hash the *stored* bytes: verification is then one crc pass
            # over the raw page slice, before any decompression or decode
            d["crc"] = zlib.crc32(comp) & 0xFFFFFFFF
        if meta:
            d["meta"] = meta
        self._fault(clen)
        self._fh.write(comp)
        self._off += clen
        return d

    # encodings that already strip redundancy — compressing them again costs
    # CPU for ~no size win, so skip unless the user pinned a field codec
    _ENTROPY_CODED = frozenset({enc.BITPACK, enc.DICT, enc.DELTA, enc.RLE})

    def _write_values(self, arr: np.ndarray, name: str) -> dict:
        encoding = self.field_encodings.get(name, self.encoding)
        chosen, meta, payload = enc.encode(arr, encoding)
        if name in self.field_codecs:
            codec = self.field_codecs[name]
        elif chosen in self._ENTROPY_CODED:
            codec = enc.CODEC_NONE
        else:
            codec = self.codec
        return self._put(payload, chosen, meta, codec, len(arr))

    def _write_validity(self, validity: Optional[np.ndarray]) -> Optional[dict]:
        if validity is None or validity.all():
            return None
        payload = np.packbits(validity, bitorder="little")
        return self._put(payload, "bitmap", {}, self.codec, len(validity))

    def _write_column_page(self, col: Column, name: str) -> dict:
        page: Dict[str, Any] = {"rows": len(col)}
        vb = self._write_validity(col.validity)
        if vb is not None:
            page["validity"] = vb
        k = col.dtype.kind
        if k == KIND_NUMERIC:
            page["values"] = self._write_values(col.values, name)
        elif k == KIND_TENSOR:
            page["values"] = self._write_values(col.values.reshape(-1), name)
        elif k in (KIND_STRING, KIND_BINARY):
            lens = np.diff(col.offsets)
            page["lengths"] = self._write_values(lens, name)
            blob = np.ascontiguousarray(
                col.blob[col.offsets[0]:col.offsets[-1]])
            page["blob"] = self._put(blob, enc.PLAIN, {},
                                     self.field_codecs.get(name, self.codec),
                                     int(len(blob)))
        elif k == KIND_LIST:
            lens = np.diff(col.offsets)
            page["lengths"] = self._write_values(lens, name)
            child = col.child.slice(int(col.offsets[0]), int(col.offsets[-1]))
            page["child"] = self._write_column_page(child, name)
        # KIND_NULL: rows only
        return page

    # -- row groups --------------------------------------------------------------
    def write_table(self, table: Table) -> None:
        for start in range(0, max(table.num_rows, 1), self.row_group_rows):
            piece = table.slice(start, start + self.row_group_rows)
            if piece.num_rows == 0 and table.num_rows > 0:
                break
            self.write_row_group(piece)
            if table.num_rows == 0:
                break

    def write_row_group(self, table: Table) -> None:
        if self._schema is None:
            self._schema = table.schema
        elif not self._schema.equals_names_types(table.schema):
            raise ValueError("row group schema mismatch within one file")
        n = table.num_rows
        rg: Dict[str, Any] = {"num_rows": n, "columns": {}}
        for f in table.schema:
            col = table.column(f.name)
            pages, pstats = [], []
            for s in range(0, max(n, 1), self.page_rows):
                if s >= n and n > 0:
                    break
                piece = col.slice(s, min(s + self.page_rows, n))
                page = self._write_column_page(piece, f.name)
                # pages carry min/max/null stats only; the bloom fingerprint
                # lives at chunk level (like Parquet) — per-page blooms made
                # the footer JSON dominate file size and write time
                st = compute_stats(piece, with_bloom=False)
                page["stats"] = st.to_dict()
                pages.append(page)
                pstats.append(st)
                if n == 0:
                    break
            chunk_stats = merge_stats(pstats) if pstats else ColumnStats()
            if self.with_bloom and pstats:
                chunk_stats.bloom = compute_bloom(col)
            rg["columns"][f.name] = {
                "pages": pages,
                "stats": chunk_stats.to_dict(),
            }
        self._row_groups.append(rg)
        self._num_rows += n

    def close(self) -> None:
        if self._closed:
            return
        footer = {
            "version": VERSION if self.checksums else 1,
            "created_by": CREATED_BY,
            "num_rows": self._num_rows,
            "schema": (self._schema or Schema([])).to_dict(),
            "row_groups": self._row_groups,
        }
        if self.file_kind != "base":
            footer["kind"] = self.file_kind
        blob = zlib.compress(json.dumps(footer).encode("utf-8"), 6)
        if self.checksums:
            trailer = struct.pack("<I", zlib.crc32(blob) & 0xFFFFFFFF) \
                + struct.pack("<Q", len(blob)) + TRAILER_V2
        else:
            trailer = struct.pack("<Q", len(blob)) + MAGIC
        self._fault(len(blob) + len(trailer))
        self._fh.write(blob)
        self._fh.write(trailer)
        self._fh.flush()
        self._fh.close()
        self._closed = True

    def abort(self) -> None:
        """Close the handle *without* writing a footer.

        Used on write faults (ENOSPC/EIO mid-file): the partial file is left
        footer-less — structurally truncated, so any later open fails typed
        — and the caller unlinks it.  Idempotent with :meth:`close`.
        """
        if not self._closed:
            self._fh.close()
            self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, exc_type, *exc):
        # a failed write must NOT be sealed with a valid footer: the file
        # is incomplete, and a footer would make it open cleanly
        if exc_type is not None:
            self.abort()
        else:
            self.close()


def write_table(path: str, table: Table, **kw) -> None:
    with TPQWriter(path, **kw) as w:
        w.write_table(table)


# ---------------------------------------------------------------------------
# Reader
# ---------------------------------------------------------------------------
class TPQReader:
    def __init__(self, path: str):
        self.path = path
        with open(path, "rb") as fh:
            # map the whole file read-only: footer-described buffer ranges
            # become memoryview slices (no seek/read syscall per page, no
            # bytes copy for uncompressed buffers); falls back to one bulk
            # read where mmap is unavailable.  The fd can close immediately
            # — the mapping (and any ndarray viewing it) keeps the pages.
            # Windows cannot delete a mapped file, which would break the
            # orphan GC after compaction (cached readers hold maps for
            # their lifetime) — bulk-read there instead.
            self._mm = None
            if os.name != "nt":
                try:
                    self._mm = mmap.mmap(fh.fileno(), 0,
                                         access=mmap.ACCESS_READ)
                except (ValueError, OSError):
                    self._mm = None
            if self._mm is not None:
                self._buf = memoryview(self._mm)
            else:
                fh.seek(0)
                self._buf = memoryview(fh.read())
        buf = self._buf
        if len(buf) < 16:
            raise TruncatedFileError(
                path, f"file too short ({len(buf)} bytes) — torn write?")
        if bytes(buf[:4]) != MAGIC:
            raise CorruptFooterError(
                path, f"bad magic {bytes(buf[:4])!r} (not a TPQ file)")
        trailer = bytes(buf[-4:])
        if trailer == TRAILER_V2:
            # v2: ... <crc32 of blob> <footer len> TPQ2
            self.checksummed = True
            (flen,) = struct.unpack("<Q", buf[-12:-4])
            if len(buf) < 20 or flen > len(buf) - 20:
                raise TruncatedFileError(
                    path, f"footer length {flen} exceeds file size "
                    f"{len(buf)} — truncated")
            blob = buf[-(16 + flen):-16]
            (want,) = struct.unpack("<I", buf[-16:-12])
            got = zlib.crc32(blob) & 0xFFFFFFFF
            if got != want:
                raise CorruptFooterError(
                    path, f"footer checksum mismatch "
                    f"(crc32 {got:#010x} != recorded {want:#010x})")
        elif trailer == MAGIC:
            # legacy v1: no checksums anywhere in the file
            self.checksummed = False
            (flen,) = struct.unpack("<Q", buf[-12:-4])
            if flen > len(buf) - 16:
                raise TruncatedFileError(
                    path, f"footer length {flen} exceeds file size "
                    f"{len(buf)} — truncated")
            blob = buf[-(12 + flen):-12]
        else:
            raise TruncatedFileError(
                path, f"bad trailing magic {trailer!r} — truncated or "
                "torn footer")
        try:
            footer = json.loads(zlib.decompress(blob))
            self.footer = footer
            self.schema = Schema.from_dict(footer["schema"])
            self.file_kind: str = footer.get("kind", "base")
            self.num_rows: int = footer["num_rows"]
            self.row_groups: List[dict] = footer["row_groups"]
        except (zlib.error, ValueError, KeyError, TypeError) as e:
            # garbage blob, broken JSON, or parsed-but-wrong-shape footer
            raise CorruptFooterError(
                path, f"footer unreadable: {type(e).__name__}: {e}") from e
        self._file_stats: Optional[Dict[str, ColumnStats]] = None
        self._rg_stats: List[Optional[Dict[str, ColumnStats]]] = \
            [None] * len(self.row_groups)

    def dup(self) -> "TPQReader":
        """Per-thread handle over the same file mapping.

        Shares the mmap/buffer and the parsed footer (all read-only after
        construction) but gets private stats-memo slots, so scan workers on
        different threads never write the same memo cell.  Costs no I/O and
        no footer re-parse — this is what the per-thread reader cache in
        ``store.py`` hands to morsel workers.
        """
        other = object.__new__(TPQReader)
        other.path = self.path
        other._mm = self._mm          # mapping outlives both handles
        other._buf = self._buf
        other.footer = self.footer
        other.schema = self.schema
        other.checksummed = self.checksummed
        other.file_kind = self.file_kind
        other.num_rows = self.num_rows
        other.row_groups = self.row_groups
        other._file_stats = None
        other._rg_stats = [None] * len(self.row_groups)
        return other

    # -- stats access ------------------------------------------------------------
    # Everything here is served from the (already-parsed) footer: the scan
    # planner prunes fragments and row groups without touching a data page.
    @property
    def num_row_groups(self) -> int:
        return len(self.row_groups)

    def row_group_num_rows(self, i: int) -> int:
        return self.row_groups[i]["num_rows"]

    def row_group_stats(self, i: int) -> Dict[str, ColumnStats]:
        # memoized: the planner, the reader, and write-path pruning all
        # consult the same stats — rebuild the ColumnStats objects once
        st = self._rg_stats[i]
        if st is None:
            st = {name: ColumnStats.from_dict(c["stats"])
                  for name, c in self.row_groups[i]["columns"].items()}
            self._rg_stats[i] = st
        return st

    def file_stats(self) -> Dict[str, ColumnStats]:
        """Whole-file per-column stats (row-group stats merged), cached."""
        if self._file_stats is None:
            self._file_stats = merge_stat_maps(
                [self.row_group_stats(i) for i in range(len(self.row_groups))])
        return self._file_stats

    def page_stats(self, rg: int, name: str) -> List[ColumnStats]:
        return [ColumnStats.from_dict(p["stats"])
                for p in self.row_groups[rg]["columns"][name]["pages"]]

    # -- page reads ----------------------------------------------------------------
    def _get(self, buf: dict, verify: bool = False, ctx: tuple = ()):
        """Raw (decompressed) buffer bytes — a zero-copy slice of the file
        mapping when the buffer is stored uncompressed.

        ``verify=True`` checks the buffer's recorded crc32 (hashed over the
        stored bytes, so this is one pass before decompression) and raises
        :class:`CorruptPageError` on mismatch; ``ctx`` is the
        ``(row_group, column, page)`` coordinates carried by the error.
        Legacy buffers without a ``"crc"`` key skip the check.
        """
        raw = self._buf[buf["off"]:buf["off"] + buf["len"]]
        if verify and "crc" in buf \
                and zlib.crc32(raw) & 0xFFFFFFFF != buf["crc"]:
            raise CorruptPageError(self.path, "page checksum mismatch",
                                   **_ctx_kw(ctx))
        if buf["codec"] == enc.CODEC_NONE:
            return raw
        try:
            return enc.decompress(raw, buf["codec"])
        except Exception as e:
            # without checksums a flipped bit usually lands here; with
            # them, only when verification was explicitly switched off
            raise CorruptPageError(
                self.path, f"page decompress failed: {e}",
                **_ctx_kw(ctx)) from e

    def _read_values(self, buf: dict, np_dtype, verify: bool = False,
                     ctx: tuple = ()) -> np.ndarray:
        payload = self._get(buf, verify=verify, ctx=ctx)
        return active_backend().decode(buf["enc"], buf.get("meta", {}),
                                       payload, buf["count"], np_dtype)

    def _read_lengths(self, buf: dict, verify: bool = False,
                      ctx: tuple = ()) -> np.ndarray:
        """A var-len page's value lengths, decoded here on the host: they
        only index the blob, which is read here too, and a device round
        trip for one page's few kilobytes of lengths costs more than the
        decode."""
        payload = self._get(buf, verify=verify, ctx=ctx)
        return enc.decode(buf["enc"], buf.get("meta", {}), payload,
                          buf["count"], np.int64)

    # -- scrubbing ---------------------------------------------------------------
    def iter_page_buffers(self) -> Iterator[tuple]:
        """Yield ``(row_group, column, page, key, buf)`` for every stored
        buffer — validity/values/lengths/blob plus list children.  Used by
        the scrubber (:meth:`verify_pages`) and the fault-injection harness
        (which needs every page's byte extent to corrupt)."""
        for i, rg in enumerate(self.row_groups):
            for name, chunk in rg["columns"].items():
                for j, page in enumerate(chunk["pages"]):
                    stack = [page]
                    while stack:
                        p = stack.pop()
                        for k in ("validity", "values", "lengths", "blob"):
                            if k in p:
                                yield (i, name, j, k, p[k])
                        if "child" in p:
                            stack.append(p["child"])

    def verify_pages(self) -> int:
        """Crc-check every stored buffer (no decompression, no decode).

        Returns the number of buffers verified; raises
        :class:`CorruptPageError` with coordinates at the first mismatch.
        Legacy (v1) buffers carry no crc and count as unverified.
        """
        n = 0
        for i, name, j, _k, buf in self.iter_page_buffers():
            if "crc" not in buf:
                continue
            raw = self._buf[buf["off"]:buf["off"] + buf["len"]]
            if zlib.crc32(raw) & 0xFFFFFFFF != buf["crc"]:
                raise CorruptPageError(self.path, "page checksum mismatch",
                                       row_group=i, column=name, page=j)
            n += 1
        return n

    def _read_column_page(self, page: dict, dtype: DType,
                          sel: Optional[np.ndarray] = None,
                          counters=None, verify: bool = False,
                          ctx: tuple = ()) -> Column:
        """Decode one column page, optionally late-materialized.

        ``sel`` is a selection vector (sorted row indices within the page,
        from the filter-column mask): only the selected rows are
        materialized — for var-len columns the page-slice and ``take`` are
        fused, so unselected blob bytes are never copied out of the page
        buffer.  ``None`` decodes the full page.  ``counters`` (a
        ``ScanCounters``) accumulates ``bytes_saved_late``.
        """
        rows = page["rows"]
        validity = None
        if "validity" in page:
            raw = self._get(page["validity"], verify=verify, ctx=ctx)
            validity = np.unpackbits(np.frombuffer(raw, np.uint8), count=rows,
                                     bitorder="little").astype(bool)
            if sel is not None:
                validity = validity[sel]
        k = dtype.kind
        if k == KIND_NUMERIC:
            vals = self._read_values(page["values"], dtype.np,
                                     verify=verify, ctx=ctx)
            if sel is not None:
                vals = vals[sel]
                _late_saved(counters, (rows - len(sel)) * vals.dtype.itemsize)
            return Column(dtype, values=vals, validity=validity)
        if k == KIND_TENSOR:
            flat = self._read_values(page["values"], dtype.np,
                                     verify=verify, ctx=ctx)
            vals = flat.reshape(rows, *dtype.shape)
            if sel is not None:
                vals = vals[sel]
                _late_saved(counters, (rows - len(sel)) * flat.dtype.itemsize
                            * int(np.prod(dtype.shape)))
            return Column(dtype, values=vals, validity=validity)
        if k in (KIND_STRING, KIND_BINARY):
            lens = self._read_lengths(page["lengths"], verify=verify,
                                      ctx=ctx)
            offsets = np.zeros(rows + 1, np.int64)
            np.cumsum(lens, out=offsets[1:])
            blob = np.frombuffer(
                self._get(page["blob"], verify=verify, ctx=ctx), np.uint8)
            if sel is not None:
                new_off, gather = _ragged_gather_index(offsets, sel)
                _late_saved(counters, int(offsets[-1]) - len(gather))
                return Column(dtype, offsets=new_off, blob=blob[gather],
                              validity=validity)
            return Column(dtype, offsets=offsets, blob=blob, validity=validity)
        if k == KIND_LIST:
            lens = self._read_lengths(page["lengths"], verify=verify,
                                      ctx=ctx)
            offsets = np.zeros(rows + 1, np.int64)
            np.cumsum(lens, out=offsets[1:])
            if sel is not None:
                new_off, child_sel = _ragged_gather_index(offsets, sel)
                child = self._read_column_page(page["child"], dtype.child,
                                               sel=child_sel,
                                               counters=counters,
                                               verify=verify, ctx=ctx)
                return Column(dtype, offsets=new_off, child=child,
                              validity=validity)
            child = self._read_column_page(page["child"], dtype.child,
                                           counters=counters,
                                           verify=verify, ctx=ctx)
            return Column(dtype, offsets=offsets, child=child,
                          validity=validity)
        return Column.nulls(rows if sel is None else len(sel))

    # -- table reads ------------------------------------------------------------
    def _project(self, columns: Optional[Sequence[str]],
                 filter_expr: Optional[Expr]) -> List[str]:
        names = list(columns) if columns is not None else self.schema.names
        for n in names:
            if n not in self.schema:
                raise KeyError(f"unknown column {n!r}; file has {self.schema.names}")
        if filter_expr is not None:
            for n in filter_expr.columns():
                if n in self.schema and n not in names:
                    names.append(n)
        return names

    def read(self, columns: Optional[Sequence[str]] = None,
             filter_expr: Optional[Expr] = None,
             row_groups: Optional[Sequence[int]] = None,
             prune_pages: bool = True, counters=None,
             verify: Optional[str] = None) -> Table:
        parts = list(self.iter_row_group_tables(
            columns, filter_expr, row_groups, prune_pages=prune_pages,
            counters=counters, verify=verify))
        names = self._project(columns, filter_expr)
        keep = list(columns) if columns is not None else names
        if not parts:
            sub = self.schema.select(keep)
            return Table(sub, {f.name: null_column_of(f.dtype, 0) for f in sub})
        out = _concat_same_schema(parts)
        return out.select(keep)

    def iter_row_group_tables(self, columns=None, filter_expr=None,
                              row_groups=None, prune_pages: bool = True,
                              counters=None,
                              verify: Optional[str] = None) -> Iterator[Table]:
        """Yield one (filtered, projected) Table per surviving row group.

        ``counters``, when given, is a duck-typed observer (in practice a
        :class:`repro.core.scan.ScanCounters`) whose ``row_groups_scanned``,
        ``row_groups_skipped``, ``pages_scanned``, ``pages_skipped``,
        ``rows_scanned`` and ``bytes_decoded`` attributes are incremented as
        the reader prunes and decodes, and whose late-materialization and
        ``two_phase_pages_*`` attributes the two-phase read fills.

        ``verify`` is ``"page"`` (default — crc-check every stored buffer
        before decoding it, raising :class:`CorruptPageError` with
        coordinates), or ``"footer"``/``"off"`` to skip the per-page check
        (the footer checksum was already validated at open).

        An explicit ``row_groups`` selection is treated as authoritative at
        row-group granularity (the caller — normally the scan planner — has
        already consulted the stats); page-level pruning still applies.
        """
        vp = verify is None or verify == "page"
        names = self._project(columns, filter_expr)
        sub_schema = self.schema.select(names)
        filter_cols = ([c for c in dict.fromkeys(filter_expr.columns())
                        if c in self.schema]
                       if filter_expr is not None else [])
        two_phase = bool(filter_cols) and len(filter_cols) < len(names)
        # a single-column contiguous range evaluates through the decode
        # backend's fused range_mask (Pallas filter_range on the jax
        # backend); any other predicate through Expr.evaluate
        rng = (filter_expr.as_range()
               if two_phase and len(filter_cols) == 1 else None)
        if rng is not None and rng[0] != filter_cols[0]:
            rng = None
        rg_sel = set(row_groups) if row_groups is not None else None
        for i, rg in enumerate(self.row_groups):
            if rg_sel is not None and i not in rg_sel:
                continue
            if (rg_sel is None and filter_expr is not None
                    and not filter_expr.prune(self.row_group_stats(i))):
                if counters is not None:
                    counters.row_groups_skipped += 1
                continue  # row-group pushdown: skip entirely
            first_chunk = (next(iter(rg["columns"].values()))
                           if rg["columns"] else None)
            npages = len(first_chunk["pages"]) if first_chunk else 0
            page_sel = list(range(npages))
            if prune_pages and filter_expr is not None and npages > 1:
                page_sel = self._select_pages(i, filter_expr, npages)
                if not page_sel:
                    if counters is not None:
                        counters.row_groups_skipped += 1
                        counters.pages_skipped += npages
                    continue
            if counters is not None:
                counters.row_groups_scanned += 1
                counters.pages_scanned += len(page_sel)
                counters.pages_skipped += npages - len(page_sel)
                counters.rows_scanned += sum(
                    first_chunk["pages"][j]["rows"] for j in page_sel) \
                    if first_chunk else 0

            def fusable(name: str, idxs) -> bool:
                # one batched decode covers these pages of the column
                pages = rg["columns"][name]["pages"]
                return (self.schema[name].dtype.kind == KIND_NUMERIC
                        and not any("validity" in pages[j] for j in idxs))

            def read_pages(name: str, idxs, sels=None) -> Column:
                pages = rg["columns"][name]["pages"]
                if counters is not None:
                    counters.bytes_decoded += sum(
                        _page_stored_bytes(pages[j]) for j in idxs)
                dtype = self.schema[name].dtype
                if sels is None and len(idxs) > 1 and fusable(name, idxs):
                    # fused morsel decode: ONE batched backend dispatch per
                    # encoding group instead of one Python-level decode per
                    # page — the GIL-convoy fix for parallel scans (and it
                    # still skips the per-page temporaries + concat copy)
                    total = sum(pages[j]["rows"] for j in idxs)
                    out = np.empty(total, dtype.np)
                    specs = []
                    for j in idxs:
                        b = pages[j]["values"]
                        specs.append((b["enc"], b.get("meta", {}),
                                      self._get(b, verify=vp,
                                                ctx=(i, name, j)),
                                      b["count"]))
                    active_backend().decode_batch(specs, dtype.np, out=out)
                    return Column(dtype, values=out)
                pieces = [self._read_column_page(
                    pages[j], dtype,
                    sel=None if sels is None else sels[jj],
                    counters=counters, verify=vp,
                    ctx=(i, name, j)) for jj, j in enumerate(idxs)]
                return (concat_columns(pieces) if len(pieces) != 1
                        else pieces[0])

            def note_batch(npages: int, batched: bool) -> None:
                if counters is None:
                    return
                if batched and npages > 1:
                    counters.two_phase_pages_batched += npages
                else:
                    counters.two_phase_pages_single += npages

            if two_phase:
                # phase 1: decode ONLY the filter columns, each in one batch
                # over the row group's surviving pages, and evaluate the
                # predicate once over the row group; a page with zero
                # matches never touches the other columns.  Each kept
                # page's share of the mask becomes a *selection vector*:
                # phase 2 materializes only the selected rows of the
                # payload columns (late materialization).
                with span("reader.filter", pages=len(page_sel)):
                    fcols = {}
                    for n in filter_cols:
                        fcols[n] = read_pages(n, page_sel)
                        note_batch(len(page_sel), fusable(n, page_sel))
                    mask = self._row_group_mask(filter_expr, filter_cols,
                                                fcols, rng, len(page_sel))
                    rows = [rg["columns"][filter_cols[0]]["pages"][j]["rows"]
                            for j in page_sel]
                    kept: List[int] = []
                    sels: List[Optional[np.ndarray]] = []
                    kept_rows: List[int] = []
                    pos = 0
                    for j, r in zip(page_sel, rows):
                        m = mask[pos:pos + r]
                        pos += r
                        if m.any():
                            kept.append(j)
                            kept_rows.append(r)
                            sels.append(None if m.all()
                                        else np.flatnonzero(m))
                if not kept:
                    continue
                with span("reader.payload", pages=len(kept)):
                    if counters is not None:
                        counters.rows_skipped_late += sum(
                            r - len(s) for r, s in zip(kept_rows, sels)
                            if s is not None)
                    # the selections laid end to end: over the phase-1
                    # batch for filter columns, over the kept pages' batch
                    # for batched payload columns
                    fsel = None if mask.all() else np.flatnonzero(mask)
                    ksel = None
                    if any(s is not None for s in sels):
                        starts = np.cumsum([0] + kept_rows[:-1])
                        ksel = np.concatenate([
                            np.arange(b, b + r) if s is None else s + b
                            for b, r, s in zip(starts, kept_rows, sels)])
                    cols: Dict[str, Column] = {}
                    for name in names:
                        if name in fcols:
                            c = fcols[name]
                            cols[name] = c if fsel is None else c.take(fsel)
                        elif fusable(name, kept):
                            c = read_pages(name, kept)
                            note_batch(len(kept), True)
                            if ksel is not None:
                                c = c.take(ksel)
                                for r, s in zip(kept_rows, sels):
                                    if s is not None:
                                        _late_saved(counters, (r - len(s))
                                                    * c.values.itemsize)
                            cols[name] = c
                        else:
                            # var-len, tensor, null and nullable columns:
                            # page by page, the take fused into the decode
                            cols[name] = read_pages(name, kept, sels)
                            note_batch(len(kept), False)
                    t = Table(sub_schema, cols)
            else:
                with span("reader.payload", pages=len(page_sel)):
                    cols = {name: read_pages(name, page_sel)
                            for name in names}
                    t = Table(sub_schema, cols)
                if filter_expr is not None:
                    with span("reader.filter", pages=len(page_sel)):
                        mask = filter_expr.evaluate(t)
                        if not mask.all():
                            t = t.filter_mask(mask)
            if t.num_rows:
                yield t

    def _row_group_mask(self, filter_expr: Expr, filter_cols: List[str],
                        fcols: Dict[str, Column], rng: Optional[tuple],
                        npages: int) -> np.ndarray:
        """The predicate's mask over a row group's decoded filter columns.

        ``rng`` is the predicate as one column's contiguous range (or
        None): on a fully-valid numeric column it goes through the decode
        backend's ``range_mask``, one call covering the ``npages`` pages.
        """
        if rng is not None:
            fc = fcols[filter_cols[0]]
            if fc.dtype.kind == KIND_NUMERIC and fc.validity is None:
                bounds = _inclusive_bounds(rng, fc.values.dtype)
                if bounds is not None:
                    be = active_backend()
                    with be.covering(npages):
                        return np.asarray(be.range_mask(
                            fc.values, bounds[0], bounds[1]), bool)
        return filter_expr.evaluate(
            Table(self.schema.select(filter_cols), fcols))

    def _select_pages(self, rg: int, expr: Expr, npages: int) -> List[int]:
        """Page-index pruning: keep pages whose aligned stats may match."""
        cols = {c for c in expr.columns() if c in self.schema}
        per_page_stats: List[Dict[str, ColumnStats]] = [
            {} for _ in range(npages)]
        for name in cols:
            for j, st in enumerate(self.page_stats(rg, name)):
                per_page_stats[j][name] = st
        return [j for j in range(npages) if expr.prune(per_page_stats[j])]

    def read_row_group_bytes(self, i: int, columns: Optional[Sequence[str]] = None) -> int:
        """Total stored bytes for a row group's (projected) chunks.

        Footer-only (no data pages touched) — used by the scan planner's
        ``bytes_total`` / ``bytes_selected`` accounting and by benchmarks.
        """
        total = 0
        rg = self.row_groups[i]
        for name, chunk in rg["columns"].items():
            if columns is not None and name not in columns:
                continue
            for p in chunk["pages"]:
                total += _page_stored_bytes(p)
        return total


def _inclusive_bounds(rng, np_dtype):
    """Convert an ``Expr.as_range`` 5-tuple to inclusive [lo, hi] in the
    column's dtype, or None when it cannot be done exactly.

    Integer columns snap open/fractional bounds to the next representable
    integer; float columns use ``nextafter`` for strict bounds.  The
    resulting inclusive mask is bit-identical to ``Expr.evaluate`` on a
    fully-valid column.
    """
    _, lo, lo_open, hi, hi_open = rng
    try:
        if np_dtype.kind in "iu":
            # a float bound >= 2^53-2 is within one ulp of int values that
            # numpy's evaluate compares in (rounded) float64; exact integer
            # arithmetic here would then *diverge* from evaluate, making
            # results projection-dependent — keep the residual path instead
            for b in (lo, hi):
                if isinstance(b, (float, np.floating)) \
                        and abs(float(b)) >= 2.0**53 - 2:
                    return None
            info = np.iinfo(np_dtype)
            lo_i = info.min if lo is None else \
                (math.floor(lo) + 1 if lo_open else math.ceil(lo))
            hi_i = info.max if hi is None else \
                (math.ceil(hi) - 1 if hi_open else math.floor(hi))
            if lo_i > info.max or hi_i < info.min:
                return int(info.max), int(info.min)  # provably empty
            return max(int(lo_i), info.min), min(int(hi_i), info.max)
        if np_dtype.kind == "f":
            lo_f = -np.inf if lo is None else \
                (np.nextafter(lo, np.inf) if lo_open else float(lo))
            hi_f = np.inf if hi is None else \
                (np.nextafter(hi, -np.inf) if hi_open else float(hi))
            return lo_f, hi_f
    except (OverflowError, ValueError):
        pass
    return None


def _ctx_kw(ctx: tuple) -> dict:
    """(row_group, column, page) coordinates → CorruptPageError kwargs."""
    if not ctx:
        return {}
    return {"row_group": ctx[0], "column": ctx[1], "page": ctx[2]}


def _late_saved(counters, nbytes: int) -> None:
    """Accumulate payload bytes that late materialization never copied."""
    if counters is not None and nbytes > 0:
        counters.bytes_saved_late += int(nbytes)


def _page_stored_bytes(page: dict) -> int:
    """Stored (compressed) bytes backing one column page, from footer metadata."""
    t = 0
    for k in ("validity", "values", "lengths", "blob"):
        if k in page:
            t += page[k]["len"]
    if "child" in page:
        t += _page_stored_bytes(page["child"])
    return t


def page_codec_split(page: dict) -> tuple:
    """(stored_bytes, codec_compressed_bytes) for one column page.

    Footer-only.  The scan planner's auto-threading heuristic uses the
    ratio: decompression releases the GIL, so pages that are mostly
    codec-compressed parallelize across morsel workers, while raw/
    entropy-coded pages decode under the GIL and do not.
    """
    stored = compressed = 0
    for k in ("validity", "values", "lengths", "blob"):
        if k in page:
            stored += page[k]["len"]
            if page[k].get("codec", enc.CODEC_NONE) != enc.CODEC_NONE:
                compressed += page[k]["len"]
    if "child" in page:
        s, c = page_codec_split(page["child"])
        stored += s
        compressed += c
    return stored, compressed


def _concat_same_schema(parts: List[Table]) -> Table:
    if len(parts) == 1:
        return parts[0]
    schema = parts[0].schema
    cols = {f.name: concat_columns([p.columns[f.name] for p in parts])
            for f in schema}
    return Table(schema, cols)


def read_table(path: str, columns=None, filter_expr=None) -> Table:
    return TPQReader(path).read(columns=columns, filter_expr=filter_expr)
