"""The benchmark's plain reference: a record-at-a-time float64 copy of the
co-occurrence job's semantics, kept with the benchmark so that no later PR
of the program can move the yardstick.

It is a copy of ``tpu_cooccurrence/oracle/reference.py`` (``OracleJob``,
tumbling windows, item cut with feedback, per-user reservoir with the
splitmix64 draw), ``tpu_cooccurrence/oracle/sliding.py``
(``SlidingOracleJob``, per-window caps and basket expansion) and
``oracle/heap.py`` (the top-K heap), and imports nothing of the program.

One change, for cost: only the rows in ``rows`` (None = every row) have
their pair deltas expanded and are scored. Every other quantity is kept
for all items, record at a time: the cuts, the reservoir and its draws,
the row sums, ``observed``, the rows rescored and the three exact
counters. A row's top-K is scored once, at the end, against the row sums
and ``observed`` of the window that last updated it (a snapshot per
window) -- which is what the record-at-a-time oracle's last rescoring of
that row computed. ``benchmark/tests/test_reference.py`` holds it to the
program's own oracles on small seeded slices of both configurations.

Ids: the program keys the reservoir draw by a user's dense id, assigned
in first-appearance order; the reference assigns the same ids itself.
"""

from __future__ import annotations

import heapq
from collections import defaultdict
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

#: The counters a run must reproduce exactly (the reference's names).
OBSERVED = "UserInteractionCounterObservedCooccurrences"
RESCORED = "ItemRowRescorerRescoredItems"
ROW_SUM = "RowSumProcessWindowRowSum"
EXACT_COUNTERS = (OBSERVED, RESCORED, ROW_SUM)

_MASK = 0xFFFFFFFFFFFFFFFF


def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & _MASK
    z = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _MASK
    return z ^ (z >> 31)


def reservoir_draw(seed: int, user: int, draw: int, total: int) -> int:
    """Uniform draw in ``[0, total)`` keyed by ``(seed, user, draw)``
    (copy of ``sampling/rng.reservoir_draw``, in Python ints)."""
    s = seed & _MASK
    h = _splitmix64((_splitmix64((s ^ (user * 0x9E3779B97F4A7C15)) & _MASK)
                     ^ draw) & _MASK)
    return h % total


def llr(k11, k12, k21, k22, dtype=np.float64) -> np.ndarray:
    """The reference's 9-log entropy-form LLR with its round-off clamp
    (``LogLikelihood.java:41-57``), vectorised, in ``dtype``."""
    k11, k12, k21, k22 = (np.asarray(k, dtype=dtype)
                          for k in (k11, k12, k21, k22))

    def xlogx(x):
        return np.where(x > 0, x * np.log(np.where(x > 0, x, 1)), 0
                        ).astype(dtype)

    row1, row2 = k11 + k12, k21 + k22
    all_ = xlogx(row1 + row2)
    row = all_ - xlogx(row1) - xlogx(row2)
    col = all_ - xlogx(k11 + k21) - xlogx(k12 + k22)
    matrix = all_ - xlogx(k11) - xlogx(k12) - xlogx(k21) - xlogx(k22)
    out = (2 * (row + col - matrix)).astype(dtype)
    return np.where(row + col < matrix, 0, out).astype(np.float64)


class TopKHeap:
    """Bounded min-heap keeping the K largest scores, with the
    reference's protocol (copy of ``oracle/heap.py``,
    ``IntDoublePriorityQueue.java``): fill to K, then replace the least
    only on a strictly greater score; among tied least entries the
    earliest inserted goes first."""

    def __init__(self, max_size: int) -> None:
        self.max_size = max_size
        self._heap: List[Tuple[float, int, int]] = []
        self._seq = 0

    def offer(self, value: int, score: float) -> None:
        self._seq += 1
        if len(self._heap) < self.max_size:
            heapq.heappush(self._heap, (score, self._seq, value))
        elif score > self._heap[0][0]:
            heapq.heapreplace(self._heap, (score, self._seq, value))

    def sorted_desc(self) -> List[Tuple[int, float]]:
        return [(v, s) for s, _, v in
                sorted(self._heap, key=lambda e: (-e[0], e[1]))]


def top_k(cols: np.ndarray, scores: np.ndarray, k: int):
    """The heap's top-K of a row offered in ascending column order.
    Entries scored below the K-th largest score never decide which
    entries stay, so only the others are offered."""
    if len(scores) > k:
        keep = scores >= np.partition(scores, len(scores) - k)[-k]
        cols, scores = cols[keep], scores[keep]
    heap = TopKHeap(k)
    for c, s in zip(cols.tolist(), scores.tolist()):
        heap.offer(c, s)
    return heap.sorted_desc()


def next_score(scores: np.ndarray, k: int) -> Optional[float]:
    """The (K+1)-th largest score: what the last slot may tie with."""
    if len(scores) <= k:
        return None
    return float(np.partition(scores, len(scores) - k - 1)[-k - 1])


class _Scoring:
    """Rows, row sums and ``observed`` shared by both window kinds."""

    def __init__(self, top_k: int, rows: Optional[Set[int]]) -> None:
        self.top_k = top_k
        self.rows = rows
        self.counters: Dict[str, int] = defaultdict(int)
        self.item_rows: Dict[int, Dict[int, int]] = defaultdict(dict)
        self.global_row_sums: Dict[int, int] = defaultdict(int)
        self.observed = 0
        self.rescored: Set[int] = set()  # every row ever rescored
        self.next_scores: Dict[int, Optional[float]] = {}
        # row -> (row sums, observed) of the window that last updated it.
        self._last: Dict[int, Tuple[Dict[int, int], int]] = {}

    def keep(self, item: int) -> bool:
        return self.rows is None or item in self.rows

    def apply(self, row_deltas: Dict[int, Dict[int, int]],
              row_sum_updates: Dict[int, int], rescored: Set[int]) -> None:
        """One window: row sums first (watermark order), then the rows."""
        for i, s in row_sum_updates.items():
            if s != 0:
                self.counters[ROW_SUM] += s
                self.global_row_sums[i] += s
                self.observed += s
        self.counters[RESCORED] += len(rescored)
        self.rescored |= rescored
        snap = None
        for item, delta in row_deltas.items():
            row = self.item_rows[item]
            for j, inc in delta.items():
                row[j] = row.get(j, 0) + inc
            if snap is None:
                snap = (dict(self.global_row_sums), self.observed)
            self._last[item] = snap

    def latest(self, dtype=np.float64) -> Dict[int, List[Tuple[int, float]]]:
        """Each expanded row's top-K as its last rescoring scored it;
        ``next_scores`` gets each row's (K+1)-th score."""
        out = {}
        for item, (sums, observed) in self._last.items():
            row = self.item_rows[item]
            cols = np.array(sorted(j for j, c in row.items() if c != 0),
                            dtype=np.int64)
            if not len(cols):
                out[item], self.next_scores[item] = [], None
                continue
            k11 = np.array([row[j] for j in cols], dtype=np.int64)
            row_sum = sums.get(item, 0)
            other = np.array([sums.get(int(j), 0) for j in cols],
                             dtype=np.int64)
            k12 = row_sum - k11
            k21 = other - k11
            k22 = observed + k11 - k12 - k21
            scores = llr(k11, k12, k21, k22, dtype)
            out[item] = top_k(cols, scores, self.top_k)
            self.next_scores[item] = next_score(scores, self.top_k)
        return out


class TumblingReference(_Scoring):
    """``OracleJob``: tumbling event-time windows, item cut with reject
    feedback, per-user reservoir with eviction deltas."""

    def __init__(self, window_ms: int, item_cut: int, user_cut: int,
                 top_k: int, seed: int,
                 rows: Optional[Set[int]] = None) -> None:
        super().__init__(top_k, rows)
        self.window_ms = window_ms
        self.item_cut = item_cut
        self.user_cut = user_cut
        self.seed = seed
        self.max_ts_seen: Optional[int] = None
        self.window_buffers: Dict[int, List[Tuple[int, int]]] = \
            defaultdict(list)
        self.item_interactions: Dict[int, int] = defaultdict(int)
        self.user_ids: Dict[int, int] = {}  # external -> dense (RNG key)
        self.user_history: Dict[int, List[int]] = defaultdict(list)
        self.user_interactions: Dict[int, int] = defaultdict(int)
        self.user_total: Dict[int, int] = defaultdict(int)
        self.user_draws: Dict[int, int] = defaultdict(int)

    def process(self, user: int, item: int, ts: int) -> None:
        uid = self.user_ids.setdefault(user, len(self.user_ids))
        wm = None if self.max_ts_seen is None else self.max_ts_seen - 1
        if wm is not None and ts <= wm:
            return  # late (the traffic never sends one)
        start = ts - ts % self.window_ms
        if self.max_ts_seen is None or ts > self.max_ts_seen:
            self.max_ts_seen = ts
            self.window_buffers[start].append((uid, item))
            self._advance(ts - 1)
        else:
            self.window_buffers[start].append((uid, item))

    def finish(self) -> None:
        self._advance(None)

    def _advance(self, watermark: Optional[int]) -> None:
        for start in sorted(s for s in self.window_buffers
                            if watermark is None
                            or s + self.window_ms - 1 <= watermark):
            self._fire(self.window_buffers.pop(start))

    def _fire(self, events: List[Tuple[int, int]]) -> None:
        tagged = []
        for uid, item in events:  # item cut, first fMax per item
            sample = self.item_interactions[item] < self.item_cut
            if sample:
                self.item_interactions[item] += 1
            tagged.append((uid, item, sample))
        row_deltas: Dict[int, Dict[int, int]] = defaultdict(dict)
        row_sums: Dict[int, int] = defaultdict(int)
        rescored: Set[int] = set()
        feedback: List[int] = []
        k_max = self.user_cut

        def pair(i: int, j: int, inc: int) -> None:
            rescored.add(i)
            if self.keep(i):
                row = row_deltas[i]
                row[j] = row.get(j, 0) + inc

        for uid, item, sample in tagged:
            self.user_total[uid] += 1
            if not sample:
                continue
            history = self.user_history[uid]
            if self.user_interactions[uid] < k_max:
                self.user_interactions[uid] += 1
                size = len(history)
                if size:
                    row_sums[item] += size
                    for other in history:
                        pair(item, other, 1)
                        pair(other, item, 1)
                        row_sums[other] += 1
                    self.counters[OBSERVED] += 2 * size
                history.append(item)
            else:
                draw = self.user_draws[uid]
                self.user_draws[uid] += 1
                k = reservoir_draw(self.seed, uid, draw, self.user_total[uid])
                if k < k_max:
                    previous = history[k]
                    row_sums[item] += k_max - 1
                    row_sums[previous] -= k_max - 1
                    for idx, other in enumerate(history):
                        if idx == k:
                            continue
                        pair(item, other, 1)
                        pair(previous, other, -1)
                        pair(other, item, 1)
                        pair(other, previous, -1)
                    history[k] = item
                else:
                    feedback.append(item)
        for item in feedback:  # reject feedback, before the next window
            self.item_interactions[item] -= 1
        self.apply(row_deltas, row_sums, rescored)


class SlidingReference(_Scoring):
    """``SlidingOracleJob``: every event in ``size/slide`` windows,
    per-window caps in arrival order, every ordered pair of distinct
    basket positions ``+1``."""

    def __init__(self, window_ms: int, slide_ms: int, item_cut: int,
                 user_cut: int, top_k: int,
                 rows: Optional[Set[int]] = None) -> None:
        super().__init__(top_k, rows)
        if window_ms % slide_ms:
            raise ValueError("window size must be a multiple of slide")
        self.size = window_ms
        self.slide = slide_ms
        self.item_cut = item_cut
        self.user_cut = user_cut
        self.max_ts_seen: Optional[int] = None
        self._buffers: Dict[int, List[Tuple[int, int]]] = defaultdict(list)
        self._slot: Optional[int] = None  # the slide of the newest event
        self._targets: List[List[Tuple[int, int]]] = []

    def process(self, user: int, item: int, ts: int) -> None:
        if self.max_ts_seen is not None and ts < self.max_ts_seen:
            return  # late (the traffic never sends one)
        self.max_ts_seen = ts
        last_start = ts - ts % self.slide
        if last_start != self._slot:
            # Windows end on multiples of the slide, so only the first
            # event of a slide can fire one; its buffers are the same
            # for every event of the slide.
            self._advance(ts - 1)
            self._slot = last_start
            self._targets = [
                self._buffers[start] for start in
                range(last_start - self.size + self.slide, last_start + 1,
                      self.slide)]
        event = (user, item)
        for buf in self._targets:
            buf.append(event)

    def finish(self) -> None:
        self._advance(None)

    def _advance(self, watermark: Optional[int]) -> None:
        for start in sorted(s for s in self._buffers
                            if watermark is None
                            or s + self.size - 1 <= watermark):
            self._fire(self._buffers.pop(start))

    def _fire(self, events: List[Tuple[int, int]]) -> None:
        item_seen: Dict[int, int] = {}
        user_seen: Dict[int, int] = {}
        baskets: Dict[int, List[int]] = {}
        item_cut, user_cut = self.item_cut, self.user_cut
        for user, item in events:
            n_item = item_seen.get(item, 0)
            n_user = user_seen.get(user, 0)
            if n_item < item_cut and n_user < user_cut:
                basket = baskets.get(user)
                if basket is None:
                    baskets[user] = [item]
                else:
                    basket.append(item)
            item_seen[item] = n_item + 1
            user_seen[user] = n_user + 1
        row_deltas: Dict[int, Dict[int, int]] = defaultdict(dict)
        row_sums: Dict[int, int] = defaultdict(int)
        rescored: Set[int] = set()
        keep = self.rows
        for basket in baskets.values():
            b = len(basket)
            if b < 2:
                continue
            self.counters[OBSERVED] += b * (b - 1)
            rescored.update(basket)
            for a, src in enumerate(basket):
                row_sums[src] += b - 1
                if keep is None or src in keep:
                    row = row_deltas[src]
                    for c, dst in enumerate(basket):
                        if c != a:
                            row[dst] = row.get(dst, 0) + 1
        self.apply(row_deltas, row_sums, rescored)
