"""Sharded, prefetching, straggler-tolerant loader over the TokenStore.

At production scale every data-parallel host runs one of these: the global
work list is (file, row-group) descriptors; assignment is round-robin by
rank with *work stealing from the global tail* — when a rank finishes its
share early (straggler mitigation: another host's disk is slow, or row groups
are skewed after predicate pushdown) it claims unclaimed tail work.  On one
process the steal queue is emulated with a thread-safe index; on a cluster
the same protocol runs against a small coordination file in the dataset dir
(the manifest-commit machinery provides the atomic claim).

Batches are prefetched on a background thread (depth = ``prefetch``) and can
optionally be fed to the device *bitpacked* (``device_feed=True``) to cut
PCIe bytes — decoded on-device by the Pallas bitunpack kernel.
"""
from __future__ import annotations

import queue
import threading
from typing import Iterator, List, Optional

import numpy as np

from ..core import TPQReader, field
from ..core import encodings as enc
from ..core.expressions import Expr, combine_filters


class WorkQueue:
    """Round-robin + (optional) steal-from-tail assignment of row groups."""

    def __init__(self, items: List, rank: int, world: int, steal: bool = True):
        self._lock = threading.Lock()
        self.items = items
        self.claimed = [False] * len(items)
        self.rank, self.world, self.steal = rank, world, steal
        self._own = [i for i in range(len(items)) if i % world == rank]
        self._own_pos = 0
        self._tail = len(items) - 1

    def next(self) -> Optional[int]:
        with self._lock:
            while self._own_pos < len(self._own):
                i = self._own[self._own_pos]
                self._own_pos += 1
                if not self.claimed[i]:
                    self.claimed[i] = True
                    return i
            if not self.steal:
                return None
            # own share exhausted: steal from the global tail
            while self._tail >= 0:
                i = self._tail
                self._tail -= 1
                if not self.claimed[i]:
                    self.claimed[i] = True
                    return i
        return None


class ShardedLoader:
    def __init__(self, db, *, batch_size: int, rank: int = 0, world: int = 1,
                 filters: Optional[List[Expr]] = None, seed: int = 0,
                 prefetch: int = 2, steal: bool = True,
                 column: str = "tokens"):
        self.db = db
        self.batch_size = batch_size
        self.rank, self.world = rank, world
        self.expr = combine_filters(filters)
        self.seed = seed
        self.prefetch = prefetch
        self.steal = steal
        self.column = column

    def _work_list(self, epoch: int) -> List:
        man = self.db._dir.load()
        items = []
        for fn in man.files:
            rd = TPQReader(self.db._dir.file_path(fn))
            for rg in range(len(rd.row_groups)):
                if self.expr is not None and all(
                        c in rd.schema for c in self.expr.columns()):
                    if not self.expr.prune(rd.row_group_stats(rg)):
                        continue   # pushdown: pruned before assignment
                items.append((fn, rg))
        rng = np.random.default_rng(self.seed + epoch)
        rng.shuffle(items)
        return items

    def _read_rg(self, fn: str, rg: int) -> np.ndarray:
        rd = TPQReader(self.db._dir.file_path(fn))
        expr = self.expr if self.expr is not None and all(
            c in rd.schema for c in self.expr.columns()) else None
        parts = list(rd.iter_row_group_tables([self.column], expr,
                                              row_groups=[rg]))
        if not parts:
            return np.empty((0,), np.int32)
        return np.concatenate([t.column(self.column).values for t in parts])

    def epoch(self, epoch: int = 0) -> Iterator[np.ndarray]:
        items = self._work_list(epoch)
        wq = WorkQueue(items, self.rank, self.world, steal=self.steal)
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        DONE = object()

        def producer():
            buf: List[np.ndarray] = []
            count = 0
            while True:
                i = wq.next()
                if i is None:
                    break
                fn, rg = items[i]
                arr = self._read_rg(fn, rg)
                if not len(arr):
                    continue
                buf.append(arr)
                count += len(arr)
                while count >= self.batch_size:
                    merged = np.concatenate(buf)
                    q.put(merged[:self.batch_size])
                    rest = merged[self.batch_size:]
                    buf, count = ([rest] if len(rest) else []), len(rest)
            q.put(DONE)

        th = threading.Thread(target=producer, daemon=True)
        th.start()
        while True:
            item = q.get()
            if item is DONE:
                return
            yield item


def device_feed(tokens: np.ndarray, vocab: int):
    """Ship tokens to the device bitpacked; decode with the Pallas kernel.

    (B, S) int32 host tokens -> (B, S) int32 device tokens, having moved
    ceil(log2 V)/32 of the bytes over PCIe.  The kernel runs in the jax
    decode backend's mode: compiled on an accelerator, interpreted on CPU.
    """
    import jax.numpy as jnp

    from ..core.backend import get_backend
    from ..kernels import bitunpack
    interpret = get_backend("jax").interpret
    B, S = tokens.shape
    k = max(int(vocab - 1).bit_length(), 1)
    packed = enc.pack_bits(tokens.reshape(-1).astype(np.uint64), k)
    pad = (-len(packed)) % 4
    words = np.frombuffer(packed + b"\0" * pad, np.uint32)
    out = bitunpack(jnp.asarray(words), B * S, k, interpret=interpret)
    return out.reshape(B, S)
