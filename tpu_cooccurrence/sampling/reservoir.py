"""Vectorized per-user reservoir sampling with eviction deltas.

Replaces the reference's keyed user-counter operator — the algorithmic core
(``UserInteractionCounterOneInputStreamOperator.java:145-257``) — with a
batch formulation that emits NumPy COO pair-delta blocks per window instead
of record-at-a-time tuples.

Key vectorization facts (proved against the reference semantics; tested
directly in ``tests/test_sampler_equivalence.py`` and end-to-end in
``tests/test_pipeline.py``):

  1. Within a window, a user's reservoir length never decreases, so *all
     appends precede all draws*: the first ``kMax - len_before`` sampled
     interactions append, the rest draw. Append targets are distinct slots,
     so all appends can be written first and each append's pair partners are
     then exactly ``history[:slot]`` of the post-write array.
  2. The reservoir denominator counts *every* interaction (sampled or not):
     ``total_at_event = total_before + rank_within_window + 1``
     (reference :158 increments before the ``sample`` check).
  3. Row-sum deltas are exactly the per-source segment-sum of pair deltas
     (append: ``(item, size)`` + ``(other, +1)`` each, :183-192; replace:
     ``+/-(kMax-1)`` with partner sums cancelling, :218-236), so they are
     not emitted separately — the scorer derives them.
  4. ``observedCooccurrences`` counts only append-path emissions
     (``2 * size``, :195); the replace path does not touch it.

Draws use the order-independent ``(seed, user, draw_index)`` hash RNG
(``sampling/rng.py``); the draw index is a per-user monotone counter.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from ..metrics import Counters, OBSERVED_COOCCURRENCES
from .item_cut import grouped_rank
from .rng import reservoir_draw


@dataclasses.dataclass
class PairDeltaBatch:
    """COO pair deltas for one window: ``C[src, dst] += delta``."""

    src: np.ndarray  # int64
    dst: np.ndarray  # int64
    delta: np.ndarray  # int32

    @staticmethod
    def concat(batches: List["PairDeltaBatch"]) -> "PairDeltaBatch":
        if not batches:
            z = np.zeros(0, dtype=np.int64)
            return PairDeltaBatch(z, z, np.zeros(0, dtype=np.int32))
        return PairDeltaBatch(
            np.concatenate([b.src for b in batches]),
            np.concatenate([b.dst for b in batches]),
            np.concatenate([b.delta for b in batches]),
        )

    def __len__(self) -> int:
        return len(self.src)


@dataclasses.dataclass
class BasketBatch:
    """One window's pair deltas in un-expanded *star-op* form.

    The fused-window uplink format (``--fused-window``,
    ``ops/device_scorer``): each row is one expansion op — a new/star
    item against a basket of partner items — and the device performs
    the expansion into COO deltas on chip
    (``ops/device_scorer._basket_lanes``). One append event is
    one op (basket = the user's history prefix, ``skip = -1``); one
    replacement is two ops over the same pre-write reservoir row
    (``(+1, new item)`` and ``(-1, previous item)``, both with
    ``skip = slot``). The logical pair stream is identical to the
    expanded :class:`PairDeltaBatch` — ``len(self)`` counts logical
    pairs, and :meth:`to_pairs` materializes them host-side for
    consumers that need COO (the chained-path fallback, the scorer
    circuit breaker's host-oracle fallback).

    ``baskets`` cells at ``j >= lens[i]`` are UNSPECIFIED (they come
    straight from the reservoir storage, which grows with ``np.empty``)
    and must be masked by every consumer.
    """

    new_items: np.ndarray  # [N] int32 star item per op
    baskets: np.ndarray    # [N, W] int32 partner rows
    lens: np.ndarray       # [N] int32 valid cells per row
    skips: np.ndarray      # [N] int32 excluded column (-1 = none)
    signs: np.ndarray      # [N] int32 delta sign (+1 / -1)

    @property
    def n_ops(self) -> int:
        return len(self.new_items)

    def _valid(self) -> np.ndarray:
        # Cached: len(), the scorer's routing prep, and the host
        # expansion all need the same mask (instances are per-window,
        # built once and consumed once).
        if not hasattr(self, "_valid_mask"):
            w = self.baskets.shape[1] if self.baskets.ndim == 2 else 0
            j = np.arange(w, dtype=np.int64)[None, :]
            self._valid_mask = ((j < self.lens[:, None])
                                & (j != self.skips[:, None]))
        return self._valid_mask

    def pairs_per_op(self) -> np.ndarray:
        """Directed pairs each op emits per direction (= valid cells)."""
        if not hasattr(self, "_per_op"):
            self._per_op = self._valid().sum(axis=1)
        return self._per_op

    def __len__(self) -> int:
        # Logical expanded pair count — identical to the equivalent
        # PairDeltaBatch's len (both directions), so journal/stat
        # fields agree between the fused and chained configurations.
        return int(2 * self.pairs_per_op().sum())

    def to_pairs(self) -> "PairDeltaBatch":
        """Host-side expansion to COO (the chained-path equivalent).

        Cell-for-cell the same multiset of (src, dst, delta) entries
        the sampler's expanded path emits (entry order differs; every
        consumer folds or segment-sums, so order is immaterial).
        """
        valid = self._valid()
        per_op = valid.sum(axis=1)
        partners = self.baskets[valid].astype(np.int64)
        news = np.repeat(self.new_items.astype(np.int64), per_op)
        deltas = np.repeat(self.signs.astype(np.int32), per_op)
        return PairDeltaBatch(
            np.concatenate([news, partners]),
            np.concatenate([partners, news]),
            np.concatenate([deltas, deltas]),
        )

    # Duck-typing for PairDeltaBatch consumers (the breaker's
    # host-oracle fallback reads .src/.dst/.delta directly): expand
    # lazily, once.
    def _expanded(self) -> "PairDeltaBatch":
        if not hasattr(self, "_pairs"):
            self._pairs = self.to_pairs()
        return self._pairs

    @property
    def src(self) -> np.ndarray:
        return self._expanded().src

    @property
    def dst(self) -> np.ndarray:
        return self._expanded().dst

    @property
    def delta(self) -> np.ndarray:
        return self._expanded().delta

    @staticmethod
    def empty() -> "BasketBatch":
        z = np.zeros(0, dtype=np.int32)
        return BasketBatch(z, np.zeros((0, 0), dtype=np.int32), z.copy(),
                           z.copy(), z.copy())


def _ragged_arange(sizes: np.ndarray) -> np.ndarray:
    """``[0..s0), [0..s1), ...`` concatenated."""
    total = int(sizes.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    ends = np.cumsum(sizes)
    starts = ends - sizes
    return np.arange(total, dtype=np.int64) - np.repeat(starts, sizes)


class UserReservoirSampler:
    """Reservoir state over dense user ids, with 2D history storage.

    In sampled mode histories are bounded by ``kMax`` → a flat
    ``[capacity, kMax]`` int64 array. In skip-cuts mode histories are
    unbounded → the column dimension grows by doubling.
    """

    def __init__(self, user_cut: int, seed: int, skip_cuts: bool,
                 capacity: int = 1024, counters: Optional[Counters] = None) -> None:
        self.user_cut = user_cut
        self.seed = seed
        self.skip_cuts = skip_cuts
        self.counters = counters if counters is not None else Counters()
        init_cols = 8 if skip_cuts else user_cut
        # int32 storage: histories hold dense item ids (< 2^31 by the
        # job's vocab mapping); at 100k+ users x kMax columns the growth
        # memcpys and cache footprint are the sampler's dominant cost.
        self.hist = np.zeros((capacity, init_cols), dtype=np.int32)
        self.hist_len = np.zeros(capacity, dtype=np.int64)
        self.total = np.zeros(capacity, dtype=np.int64)
        self.draws = np.zeros(capacity, dtype=np.int64)
        # Fused-window mode (--fused-window, ops/device_scorer): emit
        # un-expanded star ops (BasketBatch) instead of host-expanded
        # COO — the expansion then happens on chip. Set by the job when
        # the scorer resolved the fused path on; every sampling decision
        # (cuts, draws, reservoir writes, feedback) is identical in
        # either mode, only the output encoding differs.
        self.emit_baskets = False

    # -- storage growth --------------------------------------------------

    def _ensure_rows(self, max_user: int) -> None:
        # ``hist`` grows with np.empty, NOT np.zeros: zeroing the grown
        # region is a 100+ MB memset at benchmark user counts (measured
        # 0.19 s of a 0.44 s host window pass — the single biggest host
        # cost), and cells at column >= hist_len[u] are never read (the
        # append path writes slot then reads [0, slot); the draw path
        # reads [0, kMax) of full reservoirs). Contract: hist content
        # beyond each row's hist_len is UNSPECIFIED. The count vectors
        # stay zero-initialized — their zeros are semantic.
        if max_user >= self.hist.shape[0]:
            # Pow-2 target, not max_user+1: with uniform user ids the
            # first window's max lands a hair under the true user count,
            # and an exact-fit growth forces a second full-array copy one
            # window later (measured: 200 MB of memcpy on config 4).
            new_rows = max(2 * self.hist.shape[0],
                           1 << int(max_user + 1).bit_length())
            for name in ("hist_len", "total", "draws"):
                old = getattr(self, name)
                grown = np.zeros(new_rows, dtype=old.dtype)
                grown[: len(old)] = old
                setattr(self, name, grown)
            grown = np.empty((new_rows, self.hist.shape[1]),
                             dtype=self.hist.dtype)
            grown[: self.hist.shape[0]] = self.hist
            self.hist = grown

    def _ensure_cols(self, max_len: int) -> None:
        if max_len > self.hist.shape[1]:
            new_cols = max(2 * self.hist.shape[1], max_len)
            grown = np.empty((self.hist.shape[0], new_cols),
                             dtype=self.hist.dtype)
            grown[:, : self.hist.shape[1]] = self.hist
            self.hist = grown

    # -- the window fire -------------------------------------------------

    def fire(
        self,
        users: np.ndarray,
        items: np.ndarray,
        sampled: np.ndarray,
        rng_users: Optional[np.ndarray] = None,
    ) -> Tuple[PairDeltaBatch, np.ndarray]:
        """Process one window's tagged interactions (arrival order).

        Returns ``(pair_deltas, feedback_items)`` where ``feedback_items``
        are the rejected interactions' items (each implies a ``-1`` item-cut
        decrement, reference :246-248).

        ``rng_users`` (default: ``users``) supplies the ids hashed by the
        draw RNG. The partitioned sampler indexes state by *part-local*
        compact ids but must draw with the *global* dense ids so its
        decisions are bit-identical to the serial sampler's.
        """
        if rng_users is None:
            rng_users = users
        empty = (BasketBatch.empty() if self.emit_baskets
                 else PairDeltaBatch.concat([]))
        if len(users) == 0:
            return empty, np.zeros(0, dtype=np.int64)
        self._ensure_rows(int(users.max()))

        # Reservoir denominators (fact 2): per-event totals.
        rank_all = grouped_rank(users)
        total_at_event = self.total[users] + rank_all + 1
        np.add.at(self.total, users, 1)

        if not np.any(sampled):
            return empty, np.zeros(0, dtype=np.int64)

        s_users = users[sampled]
        s_items = items[sampled]
        s_rng = rng_users[sampled]
        s_total = total_at_event[sampled]
        s_rank = grouped_rank(s_users)  # rank among *sampled* events per user

        len_before = self.hist_len[s_users]
        if self.skip_cuts:
            is_append = np.ones(len(s_users), dtype=bool)
        else:
            is_append = (len_before + s_rank) < self.user_cut

        blocks: List[PairDeltaBatch] = []
        ap_baskets: Optional[np.ndarray] = None

        # ---- Append path (vectorized; fact 1) ----
        a_users = s_users[is_append]
        a_items = s_items[is_append]
        a_slot = (len_before + s_rank)[is_append]  # the slot each append writes
        if len(a_users):
            self._ensure_cols(int(a_slot.max()) + 1)
            # Write all appends first; partners of event e are hist[u, :slot_e],
            # which equals the state at e's processing time (earlier appends of
            # the same user occupy earlier slots; other users don't interfere).
            self.hist[a_users, a_slot] = a_items
            # Unbuffered scatter-add: exact with duplicate users, and
            # ~6x cheaper than the np.unique sort it replaces.
            np.add.at(self.hist_len, a_users, 1)

            sizes = a_slot  # number of partners per append event
            total_partners = int(sizes.sum())
            if self.emit_baskets:
                # Capture the partner prefixes NOW, not at assembly: the
                # draw path below mutates reservoir rows of users that
                # cross the kMax boundary inside this same window.
                # Advanced indexing copies; cells at j >= slot_e are the
                # storage's unspecified tail, masked by every consumer.
                wa = int(a_slot.max()) if len(a_slot) else 0
                ap_baskets = (self.hist[a_users, :wa] if wa else
                              np.zeros((len(a_users), 0), dtype=np.int32))
                if total_partners > 0:
                    self.counters.add(OBSERVED_COOCCURRENCES,
                                      2 * total_partners)
            elif total_partners > 0:
                # Hot path: native C++ expansion; fallback: vectorized numpy.
                from .. import native

                expanded = native.expand_appends(
                    self.hist, a_users, a_items, a_slot)
                if expanded is not None:
                    blocks.append(PairDeltaBatch(*expanded))
                else:
                    col = _ragged_arange(sizes)
                    row_u = np.repeat(a_users, sizes)
                    partners = self.hist[row_u, col].astype(np.int64)
                    new_rep = np.repeat(a_items, sizes)
                    ones = np.ones(len(partners), dtype=np.int32)
                    # Both directions (reference :180-193).
                    blocks.append(PairDeltaBatch(new_rep, partners, ones))
                    blocks.append(PairDeltaBatch(partners, new_rep, ones))
                self.counters.add(OBSERVED_COOCCURRENCES, 2 * total_partners)

        # ---- Draw path ----
        d_mask = ~is_append
        rep_ops = None
        if np.any(d_mask):
            d_users = s_users[d_mask]
            d_items = s_items[d_mask]
            d_total = s_total[d_mask]
            # Per-user draw indices: draws_before + rank among draw events.
            d_rank = grouped_rank(d_users)
            d_idx = self.draws[d_users] + d_rank
            np.add.at(self.draws, d_users, 1)
            k = reservoir_draw(self.seed, s_rng[d_mask], d_idx, d_total)
            replace = k < self.user_cut
            feedback_items = d_items[~replace]

            # Replacements mutate slots sequentially (same slot can be hit
            # twice in one window). Hot path: native C++ expansion
            # (native/reservoir_expand.cpp); fallback: per-event loop with
            # O(kMax) numpy ops each. Basket mode skips expansion
            # entirely: each replacement becomes two star ops over the
            # pre-write row, expanded on chip.
            kc = self.user_cut
            r_users = d_users[replace]
            r_items = d_items[replace]
            r_slots = k[replace]
            if self.emit_baskets:
                rep_ops = self._replacement_ops(r_users, r_items, r_slots,
                                                kc)
            else:
                if len(r_users) and self.hist.shape[1] == kc:
                    from .. import native

                    expanded = native.expand_replacements(
                        self.hist, r_users, r_items, r_slots)
                    if expanded is not None:
                        src, dst, delta = expanded
                        blocks.append(PairDeltaBatch(src, dst, delta))
                        return PairDeltaBatch.concat(blocks), feedback_items
                for u, item, slot in zip(r_users.tolist(), r_items.tolist(),
                                         r_slots.tolist()):
                    hist_row = self.hist[u, :kc]
                    previous = int(hist_row[slot])
                    # kMax-1 partners (skip slot)
                    others = np.delete(hist_row, slot).astype(np.int64)
                    new_rep = np.full(kc - 1, item, dtype=np.int64)
                    prev_rep = np.full(kc - 1, previous, dtype=np.int64)
                    plus = np.ones(kc - 1, dtype=np.int32)
                    minus = -plus
                    # (item -> others, +1), (previous -> others, -1),
                    # (others -> item, +1), (others -> previous, -1)
                    # (reference :215-243).
                    blocks.append(PairDeltaBatch(new_rep, others, plus))
                    blocks.append(PairDeltaBatch(prev_rep, others.copy(),
                                                 minus))
                    blocks.append(PairDeltaBatch(others.copy(), new_rep,
                                                 plus))
                    blocks.append(PairDeltaBatch(others.copy(), prev_rep,
                                                 minus))
                    self.hist[u, slot] = item
        else:
            feedback_items = np.zeros(0, dtype=np.int64)

        if self.emit_baskets:
            return (self._assemble_baskets(a_items, a_slot, ap_baskets,
                                           rep_ops), feedback_items)
        return PairDeltaBatch.concat(blocks), feedback_items

    def _replacement_ops(self, r_users, r_items, r_slots, kc: int):
        """Replacement events as star ops: per event, two ops over the
        PRE-write reservoir row — ``(+1, new item)`` and ``(-1, previous
        occupant)``, both excluding ``slot`` — then the slot write.

        Event semantics are sequential (the same user's row may be hit
        twice in one window and each op must see the row state at its
        own event time), but the overwhelmingly common window has every
        replacement user distinct — no intra-window row interference —
        and takes the fully vectorized path: one advanced-indexing
        gather of the pre-write rows, one scatter of the writes (the
        basket-mode analogue of the native ``expand_replacements`` fast
        path; this loop runs on the producer hot path in fused mode).
        """
        m = len(r_users)
        new = np.empty(2 * m, dtype=np.int32)
        skips = np.empty(2 * m, dtype=np.int32)
        signs = np.empty(2 * m, dtype=np.int32)
        if m:
            skips[0::2] = skips[1::2] = r_slots
        signs[0::2] = 1
        signs[1::2] = -1
        if m and len(np.unique(r_users)) == m:
            rows = self.hist[r_users, :kc]            # copies (advanced)
            baskets = np.repeat(rows, 2, axis=0)
            new[0::2] = r_items
            new[1::2] = self.hist[r_users, r_slots]   # previous occupants
            self.hist[r_users, r_slots] = r_items
            return new, baskets, skips, signs
        baskets = np.empty((2 * m, kc if m else 0), dtype=np.int32)
        for e, (u, item, slot) in enumerate(zip(
                r_users.tolist(), r_items.tolist(), r_slots.tolist())):
            row = self.hist[u, :kc]
            baskets[2 * e] = row
            baskets[2 * e + 1] = row
            new[2 * e] = item
            new[2 * e + 1] = row[slot]  # previous occupant
            self.hist[u, slot] = item
        return new, baskets, skips, signs

    def _assemble_baskets(self, a_items, a_slot, ap_baskets,
                          rep_ops) -> BasketBatch:
        """Stack the window's append and replacement ops into one
        :class:`BasketBatch` (basket width = the window's widest op)."""
        n_app = len(a_items)
        wa = ap_baskets.shape[1] if ap_baskets is not None else 0
        if rep_ops is not None:
            r_new, r_baskets, r_skips, r_signs = rep_ops
        else:
            r_new = np.zeros(0, dtype=np.int32)
            r_baskets = np.zeros((0, 0), dtype=np.int32)
            r_skips = r_signs = np.zeros(0, dtype=np.int32)
        n_rep = len(r_new)
        n = n_app + n_rep
        if n == 0:
            return BasketBatch.empty()
        w = max(wa, r_baskets.shape[1])
        baskets = np.zeros((n, w), dtype=np.int32)
        new_items = np.empty(n, dtype=np.int32)
        lens = np.empty(n, dtype=np.int32)
        skips = np.full(n, -1, dtype=np.int32)
        signs = np.ones(n, dtype=np.int32)
        if n_app:
            baskets[:n_app, :wa] = ap_baskets
            new_items[:n_app] = a_items
            lens[:n_app] = a_slot
        if n_rep:
            baskets[n_app:, :r_baskets.shape[1]] = r_baskets
            new_items[n_app:] = r_new
            lens[n_app:] = r_baskets.shape[1]
            skips[n_app:] = r_skips
            signs[n_app:] = r_signs
        return BasketBatch(new_items, baskets, lens, skips, signs)

    # -- checkpoint -------------------------------------------------------

    def clean_hist(self, n_users: int) -> np.ndarray:
        """``hist[:n_users]`` with the unspecified cells beyond each
        row's ``hist_len`` zeroed — the deterministic persistence view.
        Growth allocates with np.empty (see ``_ensure_rows``), so the raw
        array may hold stale heap bytes that must not reach disk: a
        checkpoint has to be byte-reproducible (and compressible)."""
        h = self.hist[:n_users].copy()
        cols = np.arange(h.shape[1], dtype=np.int64)[None, :]
        h[cols >= self.hist_len[:n_users, None]] = 0
        return h

    def checkpoint_state(self, n_users: int) -> dict:
        """Reservoir state for the first ``n_users`` dense users.

        The vocab can be ahead of the sampler (users whose events are
        still buffered in unfired windows, or late-dropped) — size the
        state arrays up before slicing, or the slice comes up short."""
        self._ensure_rows(max(n_users - 1, 0))
        return {
            "hist": self.clean_hist(n_users),
            "hist_len": self.hist_len[:n_users],
            "total": self.total[:n_users],
            "draws": self.draws[:n_users],
        }

    def restore_state(self, st: dict, n_users: int) -> None:
        self._ensure_rows(max(n_users - 1, 0))
        self._ensure_cols(st["hist"].shape[1])
        self.hist[:n_users, : st["hist"].shape[1]] = st["hist"]
        self.hist_len[:n_users] = st["hist_len"]
        self.total[:n_users] = st["total"]
        self.draws[:n_users] = st["draws"]
