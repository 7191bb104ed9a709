"""Array-native top-K result store with lazy materialization.

The reference terminates its result stream in a no-op sink
(``FlinkCooccurrences.java:169-171``) — results exist only as a stream of
``(item, topK)`` records. We keep results *consumable*, but the hot path
must not pay Python-per-row costs: device backends hand back whole windows
as packed ``[S, K]`` arrays (:class:`TopKBatch`), and :class:`LatestResults`
absorbs them with O(S) numpy scatters into a dense pointer table. The
per-item ``[(other, score), ...]`` lists the public API exposes are built
lazily, only for items actually read (CLI dump, tests, checkpoint).

All stored ids are *dense* vocab indices; external ids appear only at the
materialization boundary (``IdMap.to_external_batch``).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Iterator, List, Mapping, Optional, Tuple

import numpy as np


#: Packed ``[2, S, K]`` float32 result blocks carry the ids in lane 1 as
#: an int32 bit pattern, biased by 2^23 so that every id's pattern is a
#: normal float (for every id below 2^31 - 2^24). TPUs flush denormal
#: floats to zero even in pure data movement (stack, scatter, gather):
#: unbiased, every id below 2^23 came back as id 0 on a v5e (PR 21).
ID_BIAS = 1 << 23


def pack_ids(ids):
    """Traced: int32 ids -> float32 lanes of a packed result block."""
    import jax
    import jax.numpy as jnp

    return jax.lax.bitcast_convert_type(ids + ID_BIAS, jnp.float32)


def unpack_ids(lanes: np.ndarray) -> np.ndarray:
    """Host: float32 id lanes fetched from a packed block -> int32 ids."""
    return lanes.view(np.int32) - ID_BIAS


@dataclasses.dataclass
class TopKBatch:
    """One window's top-K results in packed array form (dense-id space).

    ``vals`` may contain ``-inf`` for rows with fewer than K co-occurring
    items; the matching ``idx`` entries are garbage and are filtered at
    materialization time.
    """

    rows: np.ndarray  # [S] int32 dense item ids
    idx: np.ndarray   # [S, K] int32 dense other-item ids
    vals: np.ndarray  # [S, K] float32 scores (descending)

    def __len__(self) -> int:
        return len(self.rows)

    @staticmethod
    def empty(top_k: int) -> "TopKBatch":
        return TopKBatch(np.zeros(0, np.int32),
                         np.zeros((0, top_k), np.int32),
                         np.zeros((0, top_k), np.float32))

    @staticmethod
    def concatenate(rows_l, idx_l, vals_l, top_k: int) -> "TopKBatch":
        """Assemble per-chunk host arrays into one batch ([] -> empty)."""
        if not rows_l:
            return TopKBatch.empty(top_k)
        return TopKBatch(np.concatenate(rows_l), np.concatenate(idx_l),
                         np.concatenate(vals_l))

    def truncated(self, k: int) -> "TopKBatch":
        """This batch narrowed to its first ``k`` result columns.

        Scores are stored descending, so column truncation IS top-k'
        selection — the degradation plane's result-side shedding knob
        (``robustness/degrade.py``, level SHED_K): an O(1) numpy slice,
        no device round-trip and no recompile. Identity when ``k``
        already covers the batch.
        """
        if k >= self.idx.shape[1]:
            return self
        return TopKBatch(self.rows, self.idx[:, :k], self.vals[:, :k])


def materialize_dense(window_out) -> List[Tuple[int, List[Tuple[int, float]]]]:
    """Expand a backend's window output to (dense item, [(dense, score)]).

    Accepts either the packed :class:`TopKBatch` (device/sharded backends)
    or an already-materialized list (host backends). Debug/test helper —
    the job's hot path absorbs batches without this expansion.
    """
    if not isinstance(window_out, TopKBatch):
        return list(window_out)
    out = []
    for r in range(len(window_out.rows)):
        vals = window_out.vals[r]
        keep = np.isfinite(vals)
        out.append((int(window_out.rows[r]),
                    list(zip(window_out.idx[r][keep].tolist(),
                             vals[keep].astype(float).tolist()))))
    return out


def pack_rows(rows_list: List[Tuple[int, List[Tuple[int, float]]]],
              k: Optional[int] = None) -> TopKBatch:
    """Materialized list rows -> one padded :class:`TopKBatch`.

    Pads to width ``k`` (or the widest row) with idx 0 / ``-inf`` score
    lanes — the one definition of the list-to-packed convention, shared
    by :meth:`ResultsSnapshot.packed` and the serving snapshot builder's
    absorb path (two paddings that drift apart would silently corrupt
    the restore-seeded serving table).
    """
    if not rows_list:
        return TopKBatch.empty(max(k or 1, 1))
    if k is None:
        k = max(1, max(len(top) for _, top in rows_list))
    rows = np.asarray([item for item, _ in rows_list], dtype=np.int32)
    idx = np.zeros((len(rows_list), k), dtype=np.int32)
    vals = np.full((len(rows_list), k), -np.inf, dtype=np.float32)
    for r, (_, top) in enumerate(rows_list):
        for c, (j, s) in enumerate(top):
            idx[r, c] = j
            vals[r, c] = s
    return TopKBatch(rows, idx, vals)


class _ListBatch:
    """Adapter for host backends that produce per-row Python lists."""

    def __init__(self) -> None:
        self.rows: List[List[Tuple[int, float]]] = []

    def append(self, top: List[Tuple[int, float]]) -> int:
        self.rows.append(top)
        return len(self.rows) - 1

    def __len__(self) -> int:
        return len(self.rows)


def _materialize_row(b, row: int, vocab) -> List[Tuple[int, float]]:
    """One stored row -> ``[(external other, score), ...]`` (shared by the
    live store and its snapshots)."""
    if isinstance(b, _ListBatch):
        return [(vocab.to_external(j), s) for j, s in b.rows[row]]
    vals = b.vals[row]
    keep = np.isfinite(vals)
    if not keep.any():
        return []
    ext = vocab.to_external_batch(b.idx[row][keep].astype(np.int64))
    return list(zip(ext.tolist(), vals[keep].astype(float).tolist()))


class ResultsSnapshot(Mapping):
    """Consistent point-in-time view of a :class:`LatestResults`.

    Constructed by :meth:`LatestResults.snapshot` *under the store's
    lock*: the pointer arrays are copied, the batch list is
    shallow-copied, and batch contents are immutable once absorbed
    (compaction builds new batches and a new list; list-batch appends
    never move existing rows) — so every read here is lock-free and
    cannot interleave with concurrent absorption. This is what the
    stdout emitters and the serving snapshot builder consume; iterating
    the live store mid-run reads a moving target.
    """

    def __init__(self, vocab, batches: list, ptr_batch: np.ndarray,
                 ptr_row: np.ndarray) -> None:
        self._vocab = vocab
        self.batches = batches
        self.ptr_batch = ptr_batch
        self.ptr_row = ptr_row
        self._n_vocab = len(vocab)  # vocab grows; pin the extent too

    def _live_dense(self) -> np.ndarray:
        n = min(len(self.ptr_batch), self._n_vocab)
        return np.nonzero(self.ptr_batch[:n] >= 0)[0]

    def __len__(self) -> int:
        return int(len(self._live_dense()))

    def __iter__(self) -> Iterator[int]:
        live = self._live_dense()
        if len(live) == 0:
            return iter(())
        return iter(self._vocab.to_external_batch(live).tolist())

    def __contains__(self, ext_item) -> bool:
        dense = self._vocab.to_dense(ext_item)
        return (dense is not None and dense < len(self.ptr_batch)
                and self.ptr_batch[dense] >= 0)

    def __getitem__(self, ext_item) -> List[Tuple[int, float]]:
        dense = self._vocab.to_dense(ext_item)
        if (dense is None or dense >= len(self.ptr_batch)
                or self.ptr_batch[dense] < 0):
            raise KeyError(ext_item)
        return _materialize_row(self.batches[self.ptr_batch[dense]],
                                int(self.ptr_row[dense]), self._vocab)

    def packed(self) -> TopKBatch:
        """Live rows as one packed dense-id batch (list-backed rows are
        padded in) — the serving builder's restore-seed input."""
        live = self._live_dense()
        if not len(live):
            return TopKBatch.empty(1)
        bids = self.ptr_batch[live]
        rows = self.ptr_row[live]
        k = 1
        for bid in np.unique(bids):
            b = self.batches[bid]
            if isinstance(b, _ListBatch):
                k = max(k, max((len(r) for r in b.rows), default=0))
            else:
                k = max(k, b.idx.shape[1])
        out_rows, out_idx, out_vals = [], [], []
        for bid in np.unique(bids):
            b = self.batches[bid]
            sel = bids == bid
            r = rows[sel]
            out_rows.append(live[sel].astype(np.int32))
            if isinstance(b, _ListBatch):
                sub = pack_rows(
                    [(int(d), b.rows[row])
                     for d, row in zip(live[sel].tolist(), r.tolist())],
                    k=k)
                idx, vals = sub.idx, sub.vals
            else:
                idx = np.zeros((len(r), k), dtype=np.int32)
                vals = np.full((len(r), k), -np.inf, dtype=np.float32)
                idx[:, : b.idx.shape[1]] = b.idx[r]
                vals[:, : b.vals.shape[1]] = b.vals[r]
            out_idx.append(idx)
            out_vals.append(vals)
        return TopKBatch(np.concatenate(out_rows),
                         np.concatenate(out_idx),
                         np.concatenate(out_vals))


class LatestResults(Mapping):
    """``{external item -> [(external other, score), ...]}`` view, array-backed.

    A dense pointer table maps each item to its most recent result row
    across all absorbed batches; superseded rows linger until
    :meth:`_compact` trims them (triggered when dead rows dominate).

    Absorption and reads are lock-serialized: in pipelined execution
    (``pipeline.py``) the scorer worker drains finished top-K tables into
    this store one step behind the device frontier while the caller
    thread may concurrently read (``--emit-updates`` consumers, progress
    probes). The lock is per-window/per-read scale, far off the hot path;
    serial mode pays only an uncontended acquire per window.
    """

    _COMPACT_MIN_ROWS = 1 << 20

    def __init__(self, vocab) -> None:
        self._vocab = vocab
        self._batches: list = []
        self._ptr_batch = np.full(1024, -1, dtype=np.int64)
        self._ptr_row = np.zeros(1024, dtype=np.int64)
        self._total_rows = 0
        # RLock: absorb paths call _compact (and _compact calls absorb/
        # set_row) while already holding it.
        self._lock = threading.RLock()

    # -- absorption (hot path) ------------------------------------------

    def _ensure(self, n: int) -> None:
        if n <= len(self._ptr_batch):
            return
        cap = len(self._ptr_batch)
        while cap < n:
            cap *= 2
        grown = np.full(cap, -1, dtype=np.int64)
        grown[: len(self._ptr_batch)] = self._ptr_batch
        grown_rows = np.zeros(cap, dtype=np.int64)
        grown_rows[: len(self._ptr_row)] = self._ptr_row
        self._ptr_batch = grown
        self._ptr_row = grown_rows

    def absorb_batch(self, batch: TopKBatch) -> None:
        if len(batch) == 0:
            return
        with self._lock:
            bid = len(self._batches)
            self._batches.append(batch)
            rows = batch.rows.astype(np.int64)
            self._ensure(int(rows.max()) + 1)
            self._ptr_batch[rows] = bid
            self._ptr_row[rows] = np.arange(len(rows), dtype=np.int64)
            self._total_rows += len(rows)
            if (self._total_rows >= self._COMPACT_MIN_ROWS
                    and self._total_rows > 2 * len(self)):
                self._compact()

    def set_row(self, dense_item: int, top: List[Tuple[int, float]]) -> None:
        """Single-row update from a host (list-producing) backend."""
        with self._lock:
            if (not self._batches
                    or not isinstance(self._batches[-1], _ListBatch)):
                self._batches.append(_ListBatch())
            bid = len(self._batches) - 1
            row = self._batches[bid].append(top)
            self._ensure(dense_item + 1)
            self._ptr_batch[dense_item] = bid
            self._ptr_row[dense_item] = row
            self._total_rows += 1
            if (self._total_rows >= self._COMPACT_MIN_ROWS
                    and self._total_rows > 2 * len(self)):
                self._compact()

    def _compact(self) -> None:
        """Drop superseded rows: rebuild live array rows into one batch."""
        live = np.nonzero(self._ptr_batch[: len(self._vocab)] >= 0)[0]
        bids = self._ptr_batch[live]
        rows = self._ptr_row[live]
        keep_lists = []  # list batches are kept as-is (host paths are small)
        arr_rows, arr_idx, arr_vals = [], [], []
        for bid in np.unique(bids):
            b = self._batches[bid]
            sel = bids == bid
            r = rows[sel]
            if isinstance(b, _ListBatch):
                keep_lists.append((bid, b, live[sel], r))
                continue
            arr_rows.append(b.rows[r])
            arr_idx.append(b.idx[r])
            arr_vals.append(b.vals[r])
        self._batches = []
        self._ptr_batch[:] = -1
        self._total_rows = 0
        if arr_rows:
            merged = TopKBatch(np.concatenate(arr_rows),
                               np.concatenate(arr_idx),
                               np.concatenate(arr_vals))
            self.absorb_batch(merged)
        for _, b, dense_ids, r in keep_lists:
            for d, row in zip(dense_ids.tolist(), r.tolist()):
                self.set_row(d, b.rows[row])

    # -- Mapping API (lazy, cold path) ----------------------------------

    def _live_dense(self) -> np.ndarray:
        n = min(len(self._ptr_batch), len(self._vocab))
        return np.nonzero(self._ptr_batch[:n] >= 0)[0]

    def __len__(self) -> int:
        with self._lock:
            return int(len(self._live_dense()))

    def __iter__(self) -> Iterator[int]:
        with self._lock:
            live = self._live_dense()
            if len(live) == 0:
                return iter(())
            return iter(self._vocab.to_external_batch(live).tolist())

    def __contains__(self, ext_item) -> bool:
        dense = self._vocab.to_dense(ext_item)
        with self._lock:
            return (dense is not None and dense < len(self._ptr_batch)
                    and self._ptr_batch[dense] >= 0)

    def __getitem__(self, ext_item) -> List[Tuple[int, float]]:
        dense = self._vocab.to_dense(ext_item)
        with self._lock:
            if (dense is None or dense >= len(self._ptr_batch)
                    or self._ptr_batch[dense] < 0):
                raise KeyError(ext_item)
            b = self._batches[self._ptr_batch[dense]]
            row = int(self._ptr_row[dense])
        return _materialize_row(b, row, self._vocab)

    def snapshot(self) -> ResultsSnapshot:
        """Consistent copy for lock-free reading (stdout emitters, the
        serving seed). Pointer arrays copy under the lock; batches are
        shared by reference (immutable once absorbed — see
        :class:`ResultsSnapshot`). O(vocab extent) memcpy, no row data
        copied."""
        with self._lock:
            return ResultsSnapshot(self._vocab, list(self._batches),
                                   self._ptr_batch.copy(),
                                   self._ptr_row.copy())

    # -- checkpoint helpers ---------------------------------------------

    def clear(self) -> None:
        with self._lock:
            self._batches = []
            self._ptr_batch[:] = -1
            self._total_rows = 0
