"""SlabIndex row registries (dense vs SMASH-style bitmap) and the
allocator/compaction edge cases the move plan can hit.

Covers the PR-7 satellite checklist explicitly: row relocation across a
registry capacity doubling, zero-length rows, re-insertion of a key
whose row was freed (promotion) and its region reclaimed by compaction —
plus the bitmap registry's RSS claim at a 1M-row space and dense/bitmap
behavioral equivalence under fuzz.
"""

import numpy as np
import pytest

from tpu_cooccurrence.state.sparse_scorer import (
    BitmapRowRegistry, DenseRowRegistry, SlabCapacityError, SlabIndex,
    _pow2ceil, make_row_registry)

REGS = ["dense", "bitmap"]


@pytest.mark.parametrize("kind", REGS)
def test_registry_get_update_clear(kind):
    reg = make_row_registry(64, kind)
    rows = np.asarray([3, 70, 1000], np.int64)
    s0, l0, c0 = reg.get(rows)  # absent rows read as zeros
    assert not s0.any() and not l0.any() and not c0.any()
    reg.update(rows, start=np.asarray([10, 20, 30], np.int32),
               length=np.asarray([1, 2, 3], np.int32),
               cap=np.asarray([4, 4, 4], np.int32))
    s, ln, c = reg.get(rows)
    np.testing.assert_array_equal(s, [10, 20, 30])
    np.testing.assert_array_equal(ln, [1, 2, 3])
    np.testing.assert_array_equal(c, [4, 4, 4])
    np.testing.assert_array_equal(reg.occupied(), rows)
    # Scattered single-field update keeps the others.
    reg.update(np.asarray([70]), length=np.asarray([9], np.int32))
    s, ln, c = reg.get(np.asarray([70]))
    assert (int(s[0]), int(ln[0]), int(c[0])) == (20, 9, 4)
    reg.clear(np.asarray([70]))
    assert 70 not in reg.occupied().tolist()
    s, ln, c = reg.get(rows)
    np.testing.assert_array_equal(ln, [1, 0, 3])


def test_bitmap_registry_matches_dense_under_fuzz():
    rng = np.random.default_rng(0xBEE)
    a = make_row_registry(64, "dense")
    b = make_row_registry(64, "bitmap")
    universe = 5000
    for step in range(200):
        rows = np.unique(rng.integers(0, universe, rng.integers(1, 40)))
        field = rng.integers(0, 3)
        vals = rng.integers(1, 1000, len(rows)).astype(np.int32)
        kw = [{"start": vals}, {"length": vals}, {"cap": vals}][field]
        a.ensure(int(rows.max()))
        a.update(rows, **kw)
        b.update(rows, **kw)
        probe = np.unique(rng.integers(0, universe, 64))
        for x, y in zip(a.get(probe), b.get(probe)):
            np.testing.assert_array_equal(x, y)
        if step % 17 == 0:
            victims = np.unique(rng.integers(0, universe, 5))
            a.clear(victims)
            b.clear(victims)
    np.testing.assert_array_equal(a.occupied(), b.occupied())


def test_bitmap_registry_rss_claim():
    """The tentpole's memory claim, pinned: at a 1M-row space with a
    sparse occupancy the bitmap+rank layout is at least 4x smaller than
    the dense triple."""
    n_rows = 1 << 20
    occupied = np.arange(0, n_rows, 11, dtype=np.int64)[:100_000]
    dense = DenseRowRegistry(n_rows)
    bitmap = BitmapRowRegistry(n_rows)
    vals = np.ones(len(occupied), np.int32)
    for reg in (dense, bitmap):
        reg.update(occupied, start=vals, length=vals, cap=vals)
    assert dense.nbytes >= 12 * n_rows
    assert bitmap.nbytes * 4 < dense.nbytes
    # Same answers, an order of magnitude less host RSS.
    probe = np.asarray([0, 11, 5, n_rows - 1], np.int64)
    for x, y in zip(dense.get(probe), bitmap.get(probe)):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("kind", REGS)
def test_relocation_across_registry_capacity_doubling(kind):
    """Satellite: a row relocated in the same apply() that doubles the
    row-registry capacity (high row id arrives together with growth of a
    low row) must keep slots/moves consistent."""
    idx = SlabIndex(rows_capacity=64, row_index=kind)
    base = np.asarray([(0 << 32) | d for d in range(4)], np.int64)
    p0 = idx.apply(base)
    assert p0.mv is None  # fresh row: nothing to move
    # Row 0 outgrows cap 4 in the same window that first touches a row
    # beyond the registry capacity (forces ensure/doubling mid-apply).
    big_row = 70_000
    batch = np.unique(np.concatenate([
        (0 << 32) + np.arange(4, 9),
        ((big_row << 32) + np.arange(3)).astype(np.int64)]))
    p1 = idx.apply(batch)
    assert idx.rows_cap > 64
    assert p1.mv is not None  # row 0 relocated
    old0, new0, len0 = (int(p1.mv[0, 0]), int(p1.mv[1, 0]),
                        int(p1.mv[2, 0]))
    assert (old0, len0) == (int(p0.slots[0]), 4)
    # Index agrees with the relocated layout for ALL of row 0's cells.
    keys, slots = idx.row_cells(np.asarray([0], np.int64))
    assert len(keys) == 9
    assert slots.min() >= new0
    s, ln, c = idx.rows.get(np.asarray([0, big_row], np.int64))
    assert int(ln[0]) == 9 and int(ln[1]) == 3
    assert int(c[0]) >= 9


@pytest.mark.parametrize("kind", REGS)
def test_zero_length_rows_ignored_everywhere(kind):
    """Satellite: rows that exist in the row space but never held a cell
    read as (0, 0, 0), never enter compaction, and never appear in
    row_cells output."""
    idx = SlabIndex(rows_capacity=64, row_index=kind)
    idx.apply(np.asarray([(5 << 32) | 1, (9 << 32) | 2], np.int64))
    ghost = np.asarray([0, 4, 63], np.int64)
    s, ln, c = idx.rows.get(ghost)
    assert not s.any() and not ln.any() and not c.any()
    keys, slots = idx.row_cells(ghost)
    assert len(keys) == 0 and len(slots) == 0
    assert sorted(idx.rows.occupied().tolist()) == [5, 9]
    gmap = idx.compact()
    assert idx.heap_end == len(gmap)


@pytest.mark.parametrize("kind", REGS)
def test_reinsert_key_freed_by_compaction(kind):
    """Satellite: free a row (promotion), let compaction reclaim its
    region, then re-insert the SAME key — it must allocate a fresh slot
    and the index must treat it as new."""
    idx = SlabIndex(rows_capacity=64, row_index=kind)
    key_a = np.asarray([(1 << 32) | 7, (1 << 32) | 8], np.int64)
    key_b = np.asarray([(2 << 32) | d for d in range(6)], np.int64)
    idx.apply(key_a)
    idx.apply(key_b)
    idx.free_rows(np.asarray([1], np.int64))
    assert idx.garbage > 0
    gmap = idx.compact()  # reclaims row 1's region
    assert idx.garbage == 0
    assert 1 not in idx.rows.occupied().tolist()
    # Row 2 survived compaction intact.
    s2, l2, _ = idx.rows.get(np.asarray([2], np.int64))
    assert int(l2[0]) == 6
    assert len(gmap) == idx.heap_end
    # Re-insert the freed key: allocated as NEW, fresh slot, correct len.
    plan = idx.apply(key_a[:1].copy())
    assert plan.new_sel.all()
    s1, l1, c1 = idx.rows.get(np.asarray([1], np.int64))
    assert int(l1[0]) == 1 and int(c1[0]) >= 1
    assert int(plan.slots[0]) == int(s1[0])


@pytest.mark.parametrize("kind", REGS)
def test_registry_choice_is_behavior_invariant_for_allocator(kind):
    """Whole-allocator fuzz under each registry: same plans as the
    reference (dense) run, window for window."""
    rng = np.random.default_rng(0xF00D)
    ref = SlabIndex(rows_capacity=8, row_index="dense")
    alt = SlabIndex(rows_capacity=8, row_index=kind)
    for _ in range(40):
        n = int(rng.integers(1, 100))
        rows = rng.integers(0, 60, n).astype(np.int64)
        dsts = rng.integers(0, 200, n)
        d_key = np.unique((rows << 32) | dsts)
        pa, pb = ref.apply(d_key.copy()), alt.apply(d_key.copy())
        np.testing.assert_array_equal(pa.slots, pb.slots)
        np.testing.assert_array_equal(pa.new_sel, pb.new_sel)
        if pa.mv is not None or pb.mv is not None:
            np.testing.assert_array_equal(pa.mv, pb.mv)
        if ref.needs_compaction(64):
            np.testing.assert_array_equal(ref.compact(), alt.compact())


def test_pow2ceil_overflow_guard():
    """Satellite: capacity growth crossing 2^31 cells fails loudly with
    the permanent-exit config error instead of wrapping to a negative
    int32 capacity."""
    assert int(_pow2ceil(np.asarray([3]), 4)[0]) == 4
    with pytest.raises(SlabCapacityError, match="int32"):
        _pow2ceil(np.asarray([2**31 - 5]), 4)


def test_allocate_heap_overflow_guard():
    idx = SlabIndex(rows_capacity=64)
    idx.heap_end = 2**31 - 2
    with pytest.raises(SlabCapacityError, match="heap growth"):
        idx.apply(np.asarray([(3 << 32) | 1], np.int64))


def test_make_row_registry_env():
    """The layout comes from the ``kind`` argument alone (bitmap by
    default); the environment has no say."""
    assert make_row_registry(64).kind == "bitmap"
    assert make_row_registry(64, "dense").kind == "dense"
    assert make_row_registry(64, "bitmap").kind == "bitmap"
    with pytest.raises(ValueError, match="bitmap or dense"):
        make_row_registry(64, "nope")
