"""Elastic state: the :class:`StateStore` interface + tiered spill cache.

The reference gets elastic state for free from Flink — savepoints can be
rescaled onto a different parallelism, and RocksDB tiers cold state out
of memory (SURVEY L0). This backend's sparse slab had neither: every
live row held HBM cells for the whole run, on a topology fixed at
launch. This module closes both gaps behind one interface:

* **StateStore** — the contract over today's canonical checkpoint blobs
  (``rows_key`` / ``rows_cnt`` / ``row_sums`` / ``observed``, the format
  every sparse-family backend has shared since round 3). A scorer
  delegates ``checkpoint_state`` / ``restore_state`` to its store; the
  store decides *placement* (device slab, host arena, shard bucket)
  while the blob stays backend- and topology-neutral. Checkpoints
  therefore remain interchangeable across stores: any store restores
  any store's blob.

* **DirectSlabStore** — today's behavior: every row device-resident,
  checkpoint/restore pass through to the scorer's device snapshot.

* **TieredSlabStore** — HBM as a managed hot cache over host memory.
  A window-granularity recency clock (one vectorized stamp per window,
  zero per-touch device cost) drives an LRU spill of cold rows into a
  host-side packed arena (:class:`SpillArena`); their index keys are
  *really freed* (``SlabIndex.free_rows`` → the PR-7 registry drops
  them, compaction reclaims the slab region), so hot rows reuse the
  capacity and the device slab stops growing with the long tail.
  A spilled row touched again is **re-promoted before the window's
  deltas apply**: its cells re-enter the index with their within-row
  slab order preserved (``SlabIndex.adopt_rows`` — top-K tie-breaking
  is slot-ordered, so order is part of bit-identity) and the cell
  values ride the window's existing update upload as extra
  new-cell + delta section entries — steady state stays ONE dispatch
  per window (PR 6). Spill/promote is exact movement, never
  approximation: a spill-enabled run is bit-identical to spill-off,
  and its checkpoints are byte-identical (the arena merges back into
  the canonical blob at save).

* **ShardedRescaleStore** — rescale-on-restore for the sharded-sparse
  backend (Flink savepoint semantics): the single-process checkpoint
  blob is written in the GLOBAL key space, so ``restore`` re-buckets
  every cell key onto the *current* mesh via :func:`rebucket_cells`
  (``row % D``) — a checkpoint taken at ``--num-shards N`` restores
  onto M shards bit-identically, N→M in both directions. Multi-host
  (per-process) snapshots still require the writing layout — they
  shard the slab *values* across files, not just the keys.

Residency rules the tiered store shares with the narrow-cell side-table
(``state/wire.cell_promote_threshold``): a spilled row re-promotes to
the wide int32 table when it was wide at spill time OR its
(already-updated) row sum has crossed the promotion bound — exactly the
residency an unspilled run would have (once wide, always wide), so
placement can never diverge from the spill-off run.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from ..observability import LEDGER


class StateStore:
    """Placement-policy interface over the canonical checkpoint blob.

    ``checkpoint_state`` / ``restore_state`` own the scorer's matrix
    state round trip; ``tick`` / ``promote_touched`` are the per-window
    hooks a tiering policy uses (no-ops for non-tiered stores, so the
    steady-state hot path pays nothing for the indirection).
    """

    kind = "abstract"
    #: True when the store may hold rows outside the device slab.
    tiered = False
    #: Checkpoint dirty-row log (state/delta.DirtyRowLog), lazily
    #: created by :meth:`enable_ckpt_dirty` — class-level ``None``
    #: default so subclasses need no ``__init__`` cooperation and a
    #: run without ``--checkpoint-incremental`` pays nothing.
    _ckpt_log = None

    def checkpoint_state(self) -> dict:
        raise NotImplementedError

    def restore_state(self, st: dict) -> None:
        raise NotImplementedError

    # -- incremental-checkpoint dirty feed ------------------------------
    #
    # One dirty source, two consumers (ISSUE 12): the scorer calls
    # note_touched with the SAME per-window touched-rows set the tiered
    # store's recency clock stamps; the checkpoint writer drains it per
    # generation (state/checkpoint.save) to emit delta files whose
    # bytes scale with churn, not vocab.

    def enable_ckpt_dirty(self):
        """Arm dirty-row tracking (``--checkpoint-incremental``).
        Returns the log."""
        if self._ckpt_log is None:
            from .delta import DirtyRowLog

            self._ckpt_log = DirtyRowLog()
        return self._ckpt_log

    @property
    def ckpt_dirty(self):
        """The dirty log, or ``None`` when incremental checkpoints are
        off."""
        return self._ckpt_log

    def note_touched(self, rows: np.ndarray) -> None:
        """Record this window's touched rows for the checkpoint delta
        (no-op unless :meth:`enable_ckpt_dirty` armed the log)."""
        if self._ckpt_log is not None:
            self._ckpt_log.note(rows)

    def tick(self) -> None:
        """Advance the window clock; spill whatever went cold."""

    def promote_touched(self, rows: np.ndarray):
        """Re-promote spilled rows among ``rows`` (sorted unique dense
        ids, row sums already updated for this window). Returns
        ``(promo_narrow, promo_wide)`` — per-slab extra update-section
        triples ``(cell_keys, dst_vals, cnt_vals)`` or ``None``; the
        scorer resolves keys to slots AFTER the window's ``apply`` (it
        may relocate a just-adopted row)."""
        return None, None

    def record_gauges(self) -> None:
        """Refresh the store's registry gauges (tiering counters)."""


class DirectSlabStore(StateStore):
    """Every row device-resident — the pre-elastic behavior, unchanged.

    Round-trip evidence: ``tests/test_state_store.py`` pins blob
    equivalence against :class:`TieredSlabStore` and the existing
    checkpoint suite exercises it on every sparse resume test.
    """

    kind = "direct"

    def __init__(self, scorer) -> None:
        self.scorer = scorer

    def checkpoint_state(self) -> dict:
        return self.scorer._device_checkpoint_state()

    def restore_state(self, st: dict) -> None:
        self.scorer._device_restore_state(st)


class SpillArena:
    """Host-side packed arena for spilled rows' cells.

    One append-only (keys, counts) array pair plus a ``row -> (offset,
    length, was_wide)`` directory; cells are stored in their within-row
    SLAB order (the order ``adopt_rows`` must reproduce). Popped rows
    leave garbage that a ratio-triggered compaction sweeps — same
    1/3-garbage rule as the device slab's heap.
    """

    def __init__(self) -> None:
        self.keys = np.zeros(0, dtype=np.int64)
        self.cnt = np.zeros(0, dtype=np.int32)
        self.tail = 0
        self.garbage = 0
        self.dir: Dict[int, Tuple[int, int, bool]] = {}

    def __contains__(self, row: int) -> bool:
        return row in self.dir

    def __len__(self) -> int:
        return len(self.dir)

    @property
    def live_cells(self) -> int:
        return self.tail - self.garbage

    @property
    def nbytes(self) -> int:
        return self.keys.nbytes + self.cnt.nbytes + 48 * len(self.dir)

    def _ensure(self, need: int) -> None:
        if need <= len(self.keys):
            return
        cap = max(len(self.keys), 1024)
        while cap < need:
            cap *= 2
        keys = np.zeros(cap, dtype=np.int64)
        cnt = np.zeros(cap, dtype=np.int32)
        keys[: self.tail] = self.keys[: self.tail]
        cnt[: self.tail] = self.cnt[: self.tail]
        self.keys, self.cnt = keys, cnt

    def put_rows(self, rows: np.ndarray, lens: np.ndarray,
                 keys: np.ndarray, cnt: np.ndarray,
                 was_wide: np.ndarray) -> None:
        """Append ``rows`` (cells concatenated in slab order)."""
        n = len(keys)
        self._ensure(self.tail + n)
        self.keys[self.tail: self.tail + n] = keys
        self.cnt[self.tail: self.tail + n] = cnt
        off = self.tail + np.concatenate(
            [[0], np.cumsum(lens)[:-1]]).astype(np.int64)
        for r, o, ln, w in zip(rows.tolist(), off.tolist(), lens.tolist(),
                               was_wide.tolist()):
            self.dir[int(r)] = (int(o), int(ln), bool(w))
        self.tail += n

    def pop_rows(self, rows: np.ndarray):
        """Remove ``rows`` and return ``(lens, keys, cnt, was_wide)``
        with cells concatenated in ``rows`` order (slab order within
        each row)."""
        lens = np.empty(len(rows), dtype=np.int64)
        wide = np.empty(len(rows), dtype=bool)
        keys_l, cnt_l = [], []
        for i, r in enumerate(rows.tolist()):
            off, ln, w = self.dir.pop(int(r))
            lens[i] = ln
            wide[i] = w
            keys_l.append(self.keys[off: off + ln])
            cnt_l.append(self.cnt[off: off + ln])
            self.garbage += ln
        # np.concatenate always allocates (even for one input), so the
        # returned arrays are already detached from the backing store
        # the compaction below may replace — no defensive copy needed.
        keys = (np.concatenate(keys_l) if keys_l
                else np.zeros(0, dtype=np.int64))
        cnt = (np.concatenate(cnt_l) if cnt_l
               else np.zeros(0, dtype=np.int32))
        if self.garbage * 3 > self.tail and self.tail > 4096:
            self._compact()
        return lens, keys, cnt, wide

    def _compact(self) -> None:
        live = sum(ln for _o, ln, _w in self.dir.values())
        keys = np.zeros(max(live, 1024), dtype=np.int64)
        cnt = np.zeros(max(live, 1024), dtype=np.int32)
        pos = 0
        for r in sorted(self.dir):
            off, ln, w = self.dir[r]
            keys[pos: pos + ln] = self.keys[off: off + ln]
            cnt[pos: pos + ln] = self.cnt[off: off + ln]
            self.dir[r] = (pos, ln, w)
            pos += ln
        self.keys, self.cnt = keys, cnt
        self.tail = pos
        self.garbage = 0

    def all_cells(self):
        """Every spilled cell as ``(keys, counts)``, row order by id —
        the checkpoint merge input."""
        keys_l, cnt_l = [], []
        for r in sorted(self.dir):
            off, ln, _w = self.dir[r]
            keys_l.append(self.keys[off: off + ln])
            cnt_l.append(self.cnt[off: off + ln])
        if not keys_l:
            return (np.zeros(0, dtype=np.int64),
                    np.zeros(0, dtype=np.int32))
        return np.concatenate(keys_l), np.concatenate(cnt_l)

    def reset(self) -> None:
        self.keys = np.zeros(0, dtype=np.int64)
        self.cnt = np.zeros(0, dtype=np.int32)
        self.tail = 0
        self.garbage = 0
        self.dir.clear()


class TieredSlabStore(StateStore):
    """LRU cold-row spill over :class:`SpillArena` + exact re-promotion.

    ``threshold_windows`` — rows untouched for this many fired windows
    become spill-eligible. ``target_hbm_frac`` — spilling engages only
    while live device cells exceed this fraction of the allocated slab
    capacity (0.0 = spill every eligible row unconditionally; 1.0 =
    only under a full slab). Eligible rows spill coldest-bucket-first.

    Bit-identity contract (pinned by ``tests/test_state_store.py`` and
    the spill arm of the chaos suite): scores, emitted top-K and
    checkpoint blobs are identical to a spill-off run — the store only
    ever moves exact cell values between tiers, preserves within-row
    slab order across the round trip, and re-promotes *before* the
    window's deltas apply.
    """

    kind = "tiered"
    tiered = True

    def __init__(self, scorer, threshold_windows: int,
                 target_hbm_frac: float = 0.5) -> None:
        if threshold_windows < 1:
            raise ValueError(
                f"spill threshold must be >= 1 window, got "
                f"{threshold_windows}")
        if not (0.0 <= target_hbm_frac <= 1.0):
            raise ValueError(
                f"spill target HBM fraction must be in [0, 1], got "
                f"{target_hbm_frac}")
        self.scorer = scorer
        self.threshold = int(threshold_windows)
        self.frac = float(target_hbm_frac)
        self.clock = 0
        self.last_touch = np.full(scorer.items_cap, -1, dtype=np.int64)
        # Arena residency as a flat bool array (kept in lockstep with
        # arena.dir): the per-window touched-rows membership test must
        # be one vectorized index, not a Python loop over the window.
        self._resident = np.zeros(scorer.items_cap, dtype=bool)
        # clock -> rows stamped then (stale entries — rows re-touched
        # later — are filtered by last_touch equality at spill time).
        self._buckets: Dict[int, np.ndarray] = {}
        self.arena = SpillArena()
        self.evictions = 0
        self.promotions = 0
        self.touches = 0

    # -- bookkeeping ----------------------------------------------------

    def _ensure(self, n: int) -> None:
        if n <= len(self.last_touch):
            return
        grown = np.full(n, -1, dtype=np.int64)
        grown[: len(self.last_touch)] = self.last_touch
        self.last_touch = grown
        res = np.zeros(n, dtype=bool)
        res[: len(self._resident)] = self._resident
        self._resident = res

    def _over_target(self) -> bool:
        sc = self.scorer
        cap = sc.capacity + (sc.capacity_w if sc.index_w is not None else 0)
        return sc.live_cells > self.frac * cap

    # -- the spill step (between windows) -------------------------------

    def tick(self) -> None:
        self.clock += 1
        self._ensure(self.scorer.items_cap)
        limit = self.clock - self.threshold
        if (not self._over_target()
                and len(self._buckets) <= max(4 * self.threshold, 64)):
            # Under the HBM target with a small bucket directory:
            # nothing to spill and nothing worth consolidating — the
            # steady-state tick stays O(1).
            return
        sc = self.scorer
        cap = sc.capacity + (sc.capacity_w if sc.index_w is not None else 0)
        projected = sc.live_cells
        spill_parts = []
        for c in sorted(k for k in self._buckets if k <= limit):
            rows = self._buckets.pop(c)
            rows = rows[self.last_touch[rows] == c]
            if not len(rows):
                continue
            if projected > self.frac * cap:
                # Coldest-bucket-first selection against a host-side
                # projection of live cells; the actual movement is
                # batched into ONE _spill below so the index pays one
                # free_rows (a full table rebuild under the hash
                # layout) per tick, not one per bucket.
                rows = np.unique(rows)
                projected -= self._cells_held(rows)
                spill_parts.append(rows)
                continue
            # Under the HBM target: keep the rows eligible but
            # consolidate them into one bucket at the eligibility
            # horizon, so the bucket directory stays bounded (~threshold
            # entries) on arbitrarily long streams instead of growing
            # one entry per window. Relative coldness among
            # already-eligible rows is deliberately collapsed — they
            # are all past the threshold.
            self.last_touch[rows] = limit
            b = self._buckets.get(limit)
            self._buckets[limit] = (rows if b is None
                                    else np.concatenate([b, rows]))
        if spill_parts:
            # Buckets are disjoint (a row has exactly one last_touch
            # stamp), so unique == merge-sort of the parts.
            self._spill(np.unique(np.concatenate(spill_parts)))

    def _cells_held(self, rows: np.ndarray) -> int:
        """Device cells currently held by ``rows`` across both slabs —
        the spill-selection projection (host registry reads only,
        matches exactly what :meth:`_spill` will remove)."""
        sc = self.scorer
        wmask = (sc.wide_rows[rows] if sc.index_w is not None
                 else np.zeros(len(rows), dtype=bool))
        total = 0
        for wide in (False, True):
            r = rows[wmask] if wide else rows[~wmask]
            if len(r):
                index = sc.index_w if wide else sc.index
                total += int(index.rows.get(r)[1].sum())
        return total

    def _spill(self, rows: np.ndarray) -> None:
        """Move ``rows`` (sorted unique, device-resident) to the arena:
        fetch their cells in slab order, record residency, free the
        index keys (the slab region becomes compactible garbage)."""
        import jax.numpy as jnp

        sc = self.scorer
        wmask = (sc.wide_rows[rows] if sc.index_w is not None
                 else np.zeros(len(rows), dtype=bool))
        for wide in (False, True):
            r = rows[wmask] if wide else rows[~wmask]
            if not len(r):
                continue
            index = sc.index_w if wide else sc.index
            cnt_dev = sc.cnt_w if wide else sc.cnt
            keys, slots = index.row_cells(r)
            _s, lens, _c = index.rows.get(r)
            if len(keys):
                # Slab (slot) order within each row: tie-breaking among
                # equal scores is slot-ordered, so the arena must
                # preserve it for the promotion to be exact.
                seg = np.repeat(np.arange(len(r)), lens)
                order = np.lexsort((slots, seg))
                keys_o = keys[order]
                slots_o = np.ascontiguousarray(slots[order])
                LEDGER.up("spill-slots", slots_o)
                sc.stage_clock.add("launches")
                fetched = np.asarray(cnt_dev[jnp.asarray(slots_o)])
                LEDGER.down("spill-cells", fetched)
                vals = fetched.astype(np.int32)
            else:
                keys_o = np.zeros(0, dtype=np.int64)
                vals = np.zeros(0, dtype=np.int32)
            self.arena.put_rows(r, lens, keys_o, vals,
                                np.full(len(r), wide, dtype=bool))
            self._resident[r] = True
            index.free_rows(r)
            sc.live_cells -= len(keys_o)
            if wide:
                sc.wide_rows[r] = False
            self.evictions += len(r)

    # -- the promote step (inside the window, before deltas) ------------

    def promote_touched(self, rows: np.ndarray):
        sc = self.scorer
        self._ensure(sc.items_cap)
        self.touches += len(rows)
        promo = (None, None)
        if len(self.arena.dir) and len(rows):
            spilled = np.asarray(rows, dtype=np.int64)
            spilled = spilled[self._resident[spilled]]
            if len(spilled):
                promo = self._promote(spilled)
        if len(rows):
            r64 = np.asarray(rows, dtype=np.int64)
            self.last_touch[r64] = self.clock
            b = self._buckets.get(self.clock)
            self._buckets[self.clock] = (
                r64.copy() if b is None else np.concatenate([b, r64]))
        return promo

    def _promote(self, spilled: np.ndarray):
        """Re-insert ``spilled`` rows' cells (slab order preserved) and
        return per-slab update-section extras. Residency: wide iff the
        row was wide at spill time or its updated sum crossed the
        promotion bound — identical to the unspilled run's once-wide-
        always-wide rule, so placement never diverges."""
        sc = self.scorer
        lens, keys, vals, was_wide = self.arena.pop_rows(spilled)
        self._resident[spilled] = False
        if sc.index_w is not None:
            wmask = was_wide | (
                sc.row_sums_host[spilled] >= sc.promote_threshold)
        else:
            wmask = np.zeros(len(spilled), dtype=bool)
        seg = np.repeat(np.arange(len(spilled)), lens)
        out = [None, None]
        for wide in (False, True):
            sel = wmask if wide else ~wmask
            if not sel.any():
                continue
            r = spilled[sel]
            cell_sel = sel[seg]
            k = keys[cell_sel]
            v = vals[cell_sel]
            ln = lens[sel].astype(np.int32)
            if wide:
                crossing = ~was_wide[sel]
                if crossing.any():
                    # A row crossing the wide bound ON its promotion
                    # window must adopt in KEY order, not arena (narrow
                    # slab) order: the spill-off reference path is
                    # _promote_rows, whose wide insert is key-sorted —
                    # arena order here would flip slot-ordered tie
                    # breaks against it. Rows already wide at spill
                    # keep their preserved slab order (identity key).
                    seg_w = np.repeat(np.arange(len(r)), ln)
                    order = np.lexsort((
                        np.where(np.repeat(crossing, ln), k,
                                 np.arange(len(k), dtype=np.int64)),
                        seg_w))
                    k, v = k[order], v[order]
            index = sc.index_w if wide else sc.index
            index.adopt_rows(r, k, ln)
            if wide:
                sc.wide_rows[r] = True
            sc.live_cells += len(k)
            # Keys, not slots: the window's apply may still relocate a
            # just-adopted row, so the scorer re-resolves slots after it
            # (SlabIndex.lookup).
            out[int(wide)] = (k,
                              (k & 0xFFFFFFFF).astype(np.int32),
                              v.astype(np.int32))
        self.promotions += len(spilled)
        return out[0], out[1]

    # -- checkpoint blobs ------------------------------------------------

    def checkpoint_state(self) -> dict:
        """The canonical blob, arena cells merged back in — the CELL
        arrays stay byte-identical to a spill-off run's (placement is
        not a checkpoint concern). The spill clock rides alongside as
        supplemental ``tier_*`` arrays (ages relative to the clock, so
        the values are resume-position-free): a restore resumes the
        same residency trajectory instead of starting every row hot and
        waiting ``threshold`` windows to re-spill the cold tail. Other
        stores ignore the keys — blobs stay interchangeable."""
        st = self.scorer._device_checkpoint_state()
        keys_a, cnt_a = self.arena.all_cells()
        if len(keys_a):
            keys = np.concatenate([st["rows_key"], keys_a])
            vals = np.concatenate([st["rows_cnt"],
                                   cnt_a.astype(np.int64)])
            order = np.argsort(keys, kind="stable")
            keys, vals = keys[order], vals[order]
            nz = vals != 0
            st["rows_key"] = keys[nz]
            st["rows_cnt"] = vals[nz]
        stamped = np.flatnonzero(self.last_touch >= 0).astype(np.int64)
        st["tier_clock"] = np.asarray([self.clock], dtype=np.int64)
        st["tier_rows"] = stamped
        # Ages clipped at the eligibility threshold: relative coldness
        # among already-eligible rows is deliberately collapsed — the
        # exact collapse the tick's bucket consolidation applies — so
        # the rider stays a tiny-alphabet array (deflates to almost
        # nothing at vocab scale) while eligibility round-trips
        # exactly.
        st["tier_ages"] = np.minimum(
            self.clock - self.last_touch[stamped],
            self.threshold).astype(np.int32)
        return st

    def restore_state(self, st: dict) -> None:
        """Restore everything hot. With ``tier_*`` arrays in the blob
        the recency clock resumes where the writer left it (same
        residency trajectory — untouched cold rows re-spill at the next
        tick, pinned by the spill-parity-across-restore test); a legacy
        blob without them restores with every row freshly stamped and
        the cold tail re-spills ``threshold`` windows in."""
        self.scorer._device_restore_state(st)
        self.arena.reset()
        self._buckets.clear()
        self.last_touch = np.full(self.scorer.items_cap, -1,
                                  dtype=np.int64)
        self._resident = np.zeros(self.scorer.items_cap, dtype=bool)
        if "tier_rows" in st:
            self.clock = int(np.asarray(st["tier_clock"]).reshape(-1)[0])
            rows = np.asarray(st["tier_rows"], dtype=np.int64)
            ages = np.asarray(st["tier_ages"], dtype=np.int64)
            # A stamped row whose cells all decayed to zero may sit past
            # the restored capacity (restore sizes from cell keys).
            ok = rows < self.scorer.items_cap
            rows, ages = rows[ok], ages[ok]
            stamps = self.clock - ages
            self.last_touch[rows] = stamps
            # One argsort + split (not a per-stamp scan: distinct
            # stamps x rows would be quadratic-ish on long runs).
            order = np.argsort(stamps, kind="stable")
            uniq, starts = np.unique(stamps[order], return_index=True)
            for s, part in zip(uniq.tolist(),
                               np.split(rows[order], starts[1:])):
                self._buckets[int(s)] = part
            return
        self.clock = 0
        rows = np.unique(
            (np.asarray(st["rows_key"]) >> 32).astype(np.int64))
        if len(rows):
            self.last_touch[rows] = 0
            self._buckets[0] = rows

    # -- observability ---------------------------------------------------

    def record_gauges(self) -> None:
        from ..observability.registry import REGISTRY

        REGISTRY.gauge(
            "cooc_spill_evictions_total",
            help="rows spilled from the HBM slab to the host arena"
        ).set(self.evictions)
        REGISTRY.gauge(
            "cooc_spill_promotions_total",
            help="spilled rows re-promoted to the HBM slab on touch"
        ).set(self.promotions)
        REGISTRY.gauge(
            "cooc_spill_resident_rows",
            help="rows currently held in the host spill arena"
        ).set(len(self.arena))
        REGISTRY.gauge(
            "cooc_spill_arena_bytes",
            help="host spill-arena footprint (packed cells + directory)"
        ).set(self.arena.nbytes)
        REGISTRY.gauge(
            "cooc_spill_row_touches_total",
            help="row touches observed by the tiered store (hit rate = "
                 "1 - promotions/touches)").set(self.touches)


def rebucket_cells(keys: np.ndarray, vals: Optional[np.ndarray],
                   n_shards: int):
    """Re-partition a GLOBAL-key-space cell blob onto ``n_shards``.

    The rescale-on-restore core: global row ``r`` owns shard ``r % D``
    and shard-local row ``r // D`` (the modulo sharding rule), so a
    checkpoint taken at any shard count re-buckets exactly onto any
    other. Returns a list of per-shard ``(local_keys, vals, dst)``
    with local keys sorted (global keys are sorted and ``r // D`` is
    monotone within a residue class). ``vals=None`` (a keys-only
    caller, e.g. the multihost index restore) yields ``None`` in the
    vals slot instead of partitioning a throwaway array.
    """
    src = (keys >> 32).astype(np.int64)
    dst = (keys & 0xFFFFFFFF).astype(np.int64)
    owner = (src % n_shards).astype(np.int64)
    out = []
    for d in range(n_shards):
        sel = owner == d
        lk = ((src[sel] // n_shards) << 32) | dst[sel]
        out.append((lk, vals[sel] if vals is not None else None,
                    dst[sel]))
    return out


def merge_mh_cells(blobs: "list[dict]") -> dict:
    """Merge the per-process multi-host slab blobs of ONE generation
    back into the canonical GLOBAL key-space blob — the gang rescale's
    N→M bridge (``checkpoint.restore_rescaled``).

    Every per-process file carries the identical host-replicated key
    union (``mh_rows_key``, sorted global keys) and the counts of the
    shards its chips owned (``mh_local_cnt``, laid out per shard in
    ascending ``mh_local_shards`` order, within a shard in sorted
    local-key order — which is the same relative order as the sorted
    global union restricted to that shard, because the global key
    ``(local_row * D + d) << 32 | dst`` is monotone in the local key
    within a residue class). So each file's count segments scatter
    straight into the union by ownership mask. Zero-count cells are
    KEPT, exactly like the same-topology mh restore keeps them: a
    zeroed cell still owns its slot, and dropping it would shift the
    slot-ordered top-K tie-breaks of every later re-insertion — the
    cross-topology restore must canonicalize to the same within-row
    layout a fixed-topology recovery at the same boundary would. The
    result restores through the ordinary ``rebucket_cells`` path onto
    ANY shard count.
    """
    if not blobs:
        raise ValueError("merge_mh_cells needs at least one blob")
    keys = np.asarray(blobs[0]["mh_rows_key"], dtype=np.int64)
    shard_ids = sorted({int(s) for b in blobs
                        for s in np.asarray(b["mh_local_shards"]).tolist()})
    d_old = (shard_ids[-1] + 1) if shard_ids else 1
    if shard_ids != list(range(d_old)):
        raise ValueError(
            f"multi-host blobs cover shards {shard_ids}, expected the "
            f"full range 0..{d_old - 1} — a writer's file is missing")
    owner = ((keys >> 32) % d_old).astype(np.int64)
    cnt = np.zeros(len(keys), dtype=np.int64)
    for b in blobs:
        if len(np.asarray(b["mh_rows_key"])) != len(keys):
            raise ValueError(
                "multi-host blobs disagree on the replicated key union "
                "— files from different generations?")
        local_cnt = np.asarray(b["mh_local_cnt"], dtype=np.int64)
        lo = 0
        for d in np.asarray(b["mh_local_shards"]).tolist():
            sel = owner == int(d)
            n = int(sel.sum())
            cnt[sel] = local_cnt[lo: lo + n]
            lo += n
        if lo != len(local_cnt):
            raise ValueError(
                "multi-host blob count segments do not cover its "
                "declared shards")
    return {
        "rows_key": keys.copy(),
        "rows_cnt": cnt,
        "row_sums": np.asarray(blobs[0]["row_sums"], dtype=np.int64),
        "observed": np.asarray(blobs[0]["observed"], dtype=np.int64),
    }


class ShardedRescaleStore(StateStore):
    """Rescale-on-restore for the sharded-sparse backend.

    Single-process checkpoints are written in the global key space
    (the scorer's ``_global_key``), so ``restore_state`` re-buckets
    through :func:`rebucket_cells` onto however many shards THIS run
    has — N→M in both directions, proven bit-identical by the rescale
    chaos test. Multi-host per-process snapshots shard the slab values
    across files and still require the writing layout (the scorer's
    ``_restore_multihost`` path, reached through here).
    """

    kind = "rescale"

    def __init__(self, scorer) -> None:
        self.scorer = scorer

    def checkpoint_state(self) -> dict:
        return self.scorer._device_checkpoint_state()

    def restore_state(self, st: dict) -> None:
        self.scorer._device_restore_state(st)


def make_store(scorer, spill_threshold_windows: int = 0,
               spill_target_hbm_frac: float = 0.5) -> StateStore:
    """Store factory for the single-device sparse scorer: tiered when a
    spill threshold is set, direct otherwise."""
    if spill_threshold_windows > 0:
        return TieredSlabStore(scorer, spill_threshold_windows,
                               spill_target_hbm_frac)
    return DirectSlabStore(scorer)
