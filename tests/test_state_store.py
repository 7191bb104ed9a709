"""Elastic state (state/store.py): tiered spill cache + rescale-on-restore.

The contracts under test:

* **Spill transparency** — a spill-enabled run is bit-identical to
  spill-off on the same stream: same rows, same scores, same tie order
  (within-row slab order is preserved across the spill/promote round
  trip), and its checkpoint blobs are byte-identical (the arena merges
  back into the canonical format at save).
* **Store interchange** — :class:`DirectSlabStore` and
  :class:`TieredSlabStore` round-trip the same canonical blob; a
  checkpoint written by either restores under the other.
* **Rescale-on-restore** — :class:`ShardedRescaleStore` re-buckets a
  ``--num-shards N`` checkpoint onto M shards, N→M in both directions,
  bit-identical to resuming at N (the same-topology resume is the
  reference: any restore rebuilds rows in key order, so THAT is the
  canonical post-restore state).
* **Pre-codec compatibility** — a PR-7 ``ckpt_codec``-less checkpoint
  (``--wire-format raw``) restores bit-identically under the bitmap
  row index and under the tiered store.
"""

import os

import numpy as np
import pytest

from tpu_cooccurrence.config import Backend, Config
from tpu_cooccurrence.job import CooccurrenceJob
from tpu_cooccurrence.state.sparse_scorer import (HashSlabIndex, SlabIndex,
                                                  SparseDeviceScorer)
from tpu_cooccurrence.state.store import (DirectSlabStore, ShardedRescaleStore,
                                          SpillArena, TieredSlabStore,
                                          make_store, rebucket_cells)

from test_pipeline import random_stream


def assert_latest_identical(a, b):
    """EXACT equality, tie order included — the spill-transparency bar
    (stricter than test_pipeline.assert_latest_equal, which canonicalizes
    tie order away)."""
    sa, sb = a.snapshot(), b.snapshot()
    assert set(sa) == set(sb)
    for item in sa:
        assert sa[item] == sb[item], (item, sa[item], sb[item])


def sparse_cfg(tmp_path=None, **kw):
    kw.setdefault("backend", Backend.SPARSE)
    kw.setdefault("window_size", 10)
    kw.setdefault("seed", 0xABCD)
    kw.setdefault("item_cut", 5)
    kw.setdefault("user_cut", 3)
    kw.setdefault("development_mode", True)
    if tmp_path is not None:
        kw.setdefault("checkpoint_dir", str(tmp_path / "ckpt"))
    return Config(**kw)


def run_job(cfg, users, items, ts, chunk=97):
    job = CooccurrenceJob(cfg)
    for lo in range(0, len(users), chunk):
        job.add_batch(users[lo:lo + chunk], items[lo:lo + chunk],
                      ts[lo:lo + chunk])
    job.finish()
    return job


SPILL = dict(spill_threshold_windows=2, spill_target_hbm_frac=0.0)


# -- spill transparency ------------------------------------------------


@pytest.mark.parametrize("depth", [0, 2])
def test_spill_bit_identical_to_off(depth):
    users, items, ts = random_stream(77, n=800, n_items=60, n_users=25)
    # dev-mode off: the row-sum invariant is separately covered, and
    # the point here is exact OUTPUT equality, cheap enough for tier-1.
    off = run_job(sparse_cfg(pipeline_depth=depth,
                             development_mode=False), users, items, ts)
    on = run_job(sparse_cfg(pipeline_depth=depth, development_mode=False,
                            **SPILL), users, items, ts)
    assert_latest_identical(off.latest, on.latest)
    assert off.counters.as_dict() == on.counters.as_dict()
    store = on.scorer.store
    assert isinstance(store, TieredSlabStore)
    assert store.evictions > 0, "stream never spilled — test is vacuous"
    assert store.promotions > 0, "nothing re-promoted — test is vacuous"
    assert isinstance(off.scorer.store, DirectSlabStore)


def test_spill_checkpoint_blob_byte_identical():
    """The CANONICAL blob arrays stay byte-identical to spill-off;
    the tiered store adds only the supplemental ``tier_*`` recency
    arrays (ISSUE 12 satellite — restore resumes the same residency
    trajectory), which every other store ignores."""
    users, items, ts = random_stream(78, n=700, n_items=60, n_users=25)
    off = run_job(sparse_cfg(), users, items, ts)
    on = run_job(sparse_cfg(**SPILL), users, items, ts)
    assert len(on.scorer.store.arena) > 0, "nothing left spilled at end"
    a = off.scorer.checkpoint_state()
    b = on.scorer.checkpoint_state()
    extra = set(b) - set(a)
    assert extra == {"tier_clock", "tier_rows", "tier_ages"}
    for key in a:
        assert np.array_equal(a[key], b[key]), key
    # The persisted clock matches the run's fired-window count and the
    # stamp arrays are consistent.
    assert int(b["tier_clock"][0]) == on.scorer.store.clock
    assert len(b["tier_rows"]) == len(b["tier_ages"])
    assert (b["tier_ages"] >= 0).all()


def test_spill_resume_bit_identical(tmp_path):
    users, items, ts = random_stream(79, n=800, n_items=60, n_users=25)
    half = 390
    ref = run_job(sparse_cfg(tmp_path / "ref", **SPILL), users, items, ts)

    a = CooccurrenceJob(sparse_cfg(tmp_path, **SPILL))
    a.add_batch(users[:half], items[:half], ts[:half])
    a.checkpoint()
    assert len(a.scorer.store.arena) > 0  # the blob really merged spill

    # Resume under the OTHER store kind too: blobs are interchangeable.
    for resume_kw in (SPILL, {}):
        b = CooccurrenceJob(sparse_cfg(tmp_path, **resume_kw))
        b.restore()
        b.add_batch(users[half:], items[half:], ts[half:])
        b.finish()
        # Reference: a spill-on run RESTORED at the same point (restore
        # canonicalizes within-row order, so the uninterrupted run is
        # not the bit-exact comparator — the restored one is).
        c = CooccurrenceJob(sparse_cfg(tmp_path, **SPILL))
        c.restore()
        c.add_batch(users[half:], items[half:], ts[half:])
        c.finish()
        assert_latest_identical(c.latest, b.latest)
    assert set(ref.latest.snapshot()) == set(b.latest.snapshot())


def test_spill_parity_across_restore(tmp_path):
    """Recency is checkpointed (ISSUE 12 satellite): a restored tiered
    run resumes the writer's spill clock, so residency converges to the
    uninterrupted run's at the first post-restore tick instead of every
    row sitting hot for ``threshold`` more windows."""
    users, items, ts = random_stream(83, n=900, n_items=70, n_users=26)
    half = 430
    a = CooccurrenceJob(sparse_cfg(tmp_path, **SPILL))
    a.add_batch(users[:half], items[:half], ts[:half])
    a.checkpoint()
    store_a = a.scorer.store
    assert store_a.clock > 0 and len(store_a.arena), "vacuous setup"
    b = CooccurrenceJob(sparse_cfg(tmp_path, **SPILL))
    b.restore()
    store_b = b.scorer.store

    def eligibility(store):
        # Ages are persisted clipped at the threshold (the same
        # collapse the tick's bucket consolidation applies), so the
        # restored trajectory is compared in eligibility space.
        lt = store.last_touch
        return np.where(lt >= 0,
                        np.minimum(store.clock - lt, store.threshold),
                        -1)

    # The clock resumed (not reset to 0) and the stamps match the
    # writer's exactly up to the documented eligible-age collapse.
    assert store_b.clock == store_a.clock
    np.testing.assert_array_equal(eligibility(store_b),
                                  eligibility(store_a))
    # Continue both; the arena's resident set re-converges and stays in
    # lockstep (with frac=0.0 every eligible row spills each tick).
    a.add_batch(users[half:], items[half:], ts[half:])
    a.finish()
    b.add_batch(users[half:], items[half:], ts[half:])
    b.finish()
    assert sorted(store_b.arena.dir) == sorted(store_a.arena.dir)
    assert store_b.clock == store_a.clock
    assert set(a.latest.snapshot()) == set(b.latest.snapshot())


def _phased_stream():
    """Three phases over disjoint-ish item sets so rows genuinely go
    cold: (1) a tiny hot set driven past the int8 promotion bound,
    (2) several windows of fresh users on OTHER items (phase-1 rows —
    including WIDE ones — idle long enough to spill), (3) phase-1 items
    re-touched (wide rows re-promote out of the arena)."""
    rng = np.random.default_rng(82)
    us, its, tss = [], [], []
    t0 = 0

    def phase(user_base, item_lo, item_hi, windows, per):
        nonlocal t0
        for _w in range(windows):
            us.append(user_base + rng.integers(0, 4, per))
            its.append(rng.integers(item_lo, item_hi, per))
            tss.append(np.full(per, t0, dtype=np.int64) + np.arange(per) % 10)
            t0 += 10
    phase(0, 0, 6, 10, 80)       # hot head, counts pile past 127
    phase(100, 6, 30, 8, 40)     # fresh users, other items: head goes cold
    phase(200, 0, 6, 3, 40)      # head re-touched: promote from arena
    return (np.concatenate(us), np.concatenate(its),
            np.concatenate(tss))


def test_spill_narrow_wide_residency_and_gauges():
    """Rows pushed past the int8 promotion bound spill out of and
    re-promote into the wide table; spill-on stays bit-identical and
    the registry gauges move."""
    from tpu_cooccurrence.observability.registry import REGISTRY

    users, items, ts = _phased_stream()
    kw = dict(cell_dtype="int8", skip_cuts=True)
    off = run_job(sparse_cfg(**kw), users, items, ts)
    REGISTRY.reset()
    on = run_job(sparse_cfg(**kw, **SPILL), users, items, ts)
    assert_latest_identical(off.latest, on.latest)
    assert on.scorer.wide_rows.any(), "nothing promoted wide — vacuous"
    assert on.scorer.store.evictions > 0
    assert on.scorer.store.promotions > 0
    assert REGISTRY.gauge("cooc_spill_evictions_total").get() > 0
    assert REGISTRY.gauge("cooc_spill_row_touches_total").get() > 0
    assert (REGISTRY.gauge("cooc_spill_promotions_total").get()
            == on.scorer.store.promotions)


def test_spill_cross_promotion_window_tie_order_identical():
    """A spilled NARROW row whose sum crosses the wide bound on its
    re-promotion window must adopt its cells in KEY order — the
    spill-off reference for that window is ``_promote_rows``, whose
    wide insert is key-sorted. Arena (narrow slab) order would flip
    slot-ordered tie-breaks (regression: tied partners emitted [9, 2]
    vs spill-off's [2, 9])."""
    from tpu_cooccurrence.sampling.reservoir import PairDeltaBatch

    def scorer(**kw):
        return SparseDeviceScorer(
            5, cell_dtype="int8", wire_format="raw",
            development_mode=True, capacity=64, items_capacity=8,
            compact_min_heap=256, **kw)

    def feed(sc):
        # Row 5 collects tied partners 9 then 2 (slab order [9, 2], key
        # order [2, 9]), idles two windows (spills under threshold 1),
        # then re-touches with a delta crossing the int8 bound (128) —
        # promotion to wide happens ON the re-promotion window.
        windows = [
            ([5, 9, 20, 21], [9, 5, 21, 20], [1, 1, 1, 1]),
            ([5, 2, 20, 21], [2, 5, 21, 20], [1, 1, 1, 1]),
            ([20, 21], [21, 20], [1, 1]),
            ([20, 21], [21, 20], [1, 1]),
            ([5, 60, 20, 21], [60, 5, 21, 20], [126, 126, 1, 1]),
        ]
        outs = []
        for w, (s, d, v) in enumerate(windows):
            outs.append(sc.process_window(
                w * 10, PairDeltaBatch(np.asarray(s, np.int64),
                                       np.asarray(d, np.int64),
                                       np.asarray(v, np.int32))))
        outs.append(sc.flush())
        return outs

    off = feed(scorer())
    on_sc = scorer(spill_threshold_windows=1, spill_target_hbm_frac=0.0)
    on = feed(on_sc)
    assert on_sc.store.promotions > 0, "row 5 never spilled — vacuous"
    assert bool(on_sc.wide_rows[5]), "row 5 never crossed the bound"
    for a, b in zip(off, on):
        oa, ob = np.argsort(a.rows), np.argsort(b.rows)
        np.testing.assert_array_equal(a.rows[oa], b.rows[ob])
        np.testing.assert_array_equal(a.idx[oa], b.idx[ob])
        np.testing.assert_array_equal(a.vals[oa], b.vals[ob])


# -- adopt_rows: the order-preservation core ---------------------------


@pytest.mark.parametrize("index_cls", [SlabIndex, HashSlabIndex])
def test_adopt_rows_preserves_slab_order(index_cls):
    try:
        ix = index_cls()
    except RuntimeError:
        pytest.skip("native library unavailable")
    # Insert a row's cells over two windows so within-row slab order is
    # chronological, NOT key order.
    k = lambda r, d: (r << 32) | d
    ix.apply(np.asarray(sorted([k(5, 9), k(5, 30)]), dtype=np.int64))
    ix.apply(np.asarray(sorted([k(5, 2), k(5, 11)]), dtype=np.int64))
    rows = np.asarray([5], dtype=np.int64)
    keys, slots = ix.row_cells(rows)
    order = np.argsort(slots, kind="stable")
    slab_order_keys = keys[order].copy()
    assert list(slab_order_keys & 0xFFFFFFFF) == [9, 30, 2, 11]
    ix.free_rows(rows)
    slots2 = ix.adopt_rows(rows, slab_order_keys,
                           np.asarray([4], dtype=np.int32))
    # Slots ascend in the given order: slab layout reproduced exactly.
    assert list(np.diff(slots2)) == [1, 1, 1]
    assert np.array_equal(ix.lookup(slab_order_keys), slots2)
    keys3, slots3 = ix.row_cells(rows)
    assert np.array_equal(keys3[np.argsort(slots3, kind="stable")],
                          slab_order_keys)


def test_lookup_rejects_absent_keys():
    ix = SlabIndex()
    ix.apply(np.asarray([(1 << 32) | 3], dtype=np.int64))
    with pytest.raises(KeyError):
        ix.lookup(np.asarray([(9 << 32) | 1], dtype=np.int64))


# -- the arena ---------------------------------------------------------


def test_spill_arena_round_trip_and_compaction():
    arena = SpillArena()
    rng = np.random.default_rng(5)
    expect = {}
    for r in range(200):
        n = int(rng.integers(1, 9))
        keys = (np.int64(r) << 32) | rng.integers(0, 1000, n).astype(np.int64)
        cnt = rng.integers(1, 100, n).astype(np.int32)
        arena.put_rows(np.asarray([r]), np.asarray([n]), keys, cnt,
                       np.asarray([r % 3 == 0]))
        expect[r] = (keys.copy(), cnt.copy(), r % 3 == 0)
    # Pop half (forces compaction), verify payloads, re-add some.
    for r in range(0, 200, 2):
        lens, keys, cnt, wide = arena.pop_rows(np.asarray([r]))
        ek, ec, ew = expect.pop(r)
        assert np.array_equal(keys, ek) and np.array_equal(cnt, ec)
        assert wide[0] == ew and lens[0] == len(ek)
        assert r not in arena
    assert len(arena) == len(expect)
    keys_all, cnt_all = arena.all_cells()
    assert len(keys_all) == sum(len(k) for k, _c, _w in expect.values())
    arena.reset()
    assert len(arena) == 0 and arena.live_cells == 0


def test_tiered_bucket_directory_stays_bounded():
    """Long under-target streams must not grow one recency bucket per
    window: once the directory crosses the amortization bound the
    eligible tail consolidates at the eligibility horizon."""
    scorer = SparseDeviceScorer(top_k=5)
    store = TieredSlabStore(scorer, 2, 1.0)  # frac 1.0: never over target
    rows = np.arange(4, dtype=np.int64)
    for w in range(300):
        store.tick()
        # Touch a rotating single row so older stamps go stale slowly.
        store.promote_touched(rows[w % 4: w % 4 + 1])
    assert len(store._buckets) <= max(4 * store.threshold, 64) + 2
    assert store.evictions == 0  # never over target -> never spilled


# -- store interface / blob interchange --------------------------------


def test_make_store_kinds():
    scorer = SparseDeviceScorer(top_k=5)
    assert isinstance(make_store(scorer, 0, 0.5), DirectSlabStore)
    tiered = make_store(scorer, 3, 0.25)
    assert isinstance(tiered, TieredSlabStore)
    assert tiered.tiered and not DirectSlabStore(scorer).tiered
    with pytest.raises(ValueError):
        TieredSlabStore(scorer, 0)
    with pytest.raises(ValueError):
        TieredSlabStore(scorer, 2, 1.5)


def test_direct_store_round_trip_matches_scorer():
    users, items, ts = random_stream(83, n=500, n_items=40, n_users=20)
    job = run_job(sparse_cfg(), users, items, ts)
    blob = job.scorer.store.checkpoint_state()
    fresh = SparseDeviceScorer(top_k=job.config.top_k,
                               cell_dtype=job.scorer.cell_dtype)
    fresh.store.restore_state(blob)
    blob2 = fresh.store.checkpoint_state()
    for key in blob:
        assert np.array_equal(blob[key], blob2[key]), key


# -- rescale-on-restore ------------------------------------------------


def test_rebucket_cells_partitions_exactly():
    rng = np.random.default_rng(9)
    rows = rng.integers(0, 500, 300).astype(np.int64)
    dst = rng.integers(0, 500, 300).astype(np.int64)
    keys = np.unique((rows << 32) | dst)
    vals = rng.integers(1, 50, len(keys)).astype(np.int64)
    for d_count in (1, 2, 4, 8):
        parts = rebucket_cells(keys, vals, d_count)
        assert len(parts) == d_count
        total = 0
        for d, (lk, v, dst_d) in enumerate(parts):
            assert np.all(np.diff(lk) > 0)  # sorted unique local keys
            recon = ((((lk >> 32) * d_count + d) << 32)
                     | (lk & 0xFFFFFFFF))
            assert np.all(np.isin(recon, keys))
            assert np.array_equal(dst_d, recon & 0xFFFFFFFF)
            total += len(lk)
        assert total == len(keys)


@pytest.mark.parametrize("n_from,n_to", [
    (2, 4), (4, 2),
    # Non-divisible topology: the modulo re-bucket owes nothing to
    # divisibility (the load-driven autoscaler may land on any size
    # inside its min/max bounds).
    (2, 3), (3, 2),
    # Degenerate single-shard ends: a 1-shard checkpoint is the
    # single-device SparseDeviceScorer's global blob (interchangeable
    # by design), restored onto a mesh — and back down to one shard.
    (1, 4), (4, 1),
])
def test_sharded_rescale_restore_bit_identical(tmp_path, n_from, n_to):
    """A checkpoint taken at N shards resumes at M bit-identically to
    resuming at N — the ShardedRescaleStore re-bucket is pure topology,
    zero content change."""
    import shutil

    users, items, ts = random_stream(31, n=500, n_items=60, n_users=25)
    half = 240

    def cfg(path, shards):
        return Config(window_size=10, seed=0xBEEF, item_cut=5, user_cut=3,
                      backend=Backend.SPARSE, num_shards=shards,
                      checkpoint_dir=str(path))

    a = CooccurrenceJob(cfg(tmp_path / "ck", n_from))
    if n_from > 1:
        assert isinstance(a.scorer.store, ShardedRescaleStore)
    a.add_batch(users[:half], items[:half], ts[:half])
    a.checkpoint()
    shutil.copytree(tmp_path / "ck", tmp_path / "ck2")

    same = CooccurrenceJob(cfg(tmp_path / "ck2", n_from))
    same.restore()
    same.add_batch(users[half:], items[half:], ts[half:])
    same.finish()

    rescaled = CooccurrenceJob(cfg(tmp_path / "ck", n_to))
    rescaled.restore()
    rescaled.add_batch(users[half:], items[half:], ts[half:])
    rescaled.finish()

    assert_latest_identical(same.latest, rescaled.latest)
    assert same.counters.as_dict() == rescaled.counters.as_dict()


# -- pre-codec checkpoints under the new store / index ------------------


@pytest.mark.parametrize("resume_kw", [
    {},                 # bitmap row index (the default), direct store
    SPILL,              # TieredSlabStore
], ids=["bitmap", "tiered"])
def test_precodec_checkpoint_restores_bit_identical(tmp_path, resume_kw):
    """A PR-7 pre-codec checkpoint (--wire-format raw writes the
    ckpt_codec-less layout) restores under the bitmap row index and
    under the TieredSlabStore, resuming bit-identically either way."""
    users, items, ts = random_stream(84, n=700, n_items=60, n_users=25)
    half = 330

    a = CooccurrenceJob(sparse_cfg(tmp_path, wire_format="raw"))
    a.add_batch(users[:half], items[:half], ts[:half])
    a.checkpoint()
    # Really pre-codec: no packed blobs, no codec record in the meta.
    import json

    gen = tmp_path / "ckpt" / "state.1.npz"
    with np.load(gen) as data:
        names = set(data.files)
        meta = json.loads(bytes(data["meta_json"]).decode())
    assert not any(n.endswith("__packed") for n in names)
    assert "ckpt_codec" not in meta

    b = CooccurrenceJob(sparse_cfg(tmp_path, wire_format="raw",
                                   **resume_kw))
    b.restore()
    b.add_batch(users[half:], items[half:], ts[half:])
    b.finish()

    # Reference: the same restore WITHOUT the new machinery (direct
    # store, same raw format) — the new store/index must change nothing.
    c = CooccurrenceJob(sparse_cfg(tmp_path, wire_format="raw"))
    c.restore()
    c.add_batch(users[half:], items[half:], ts[half:])
    c.finish()
    assert_latest_identical(c.latest, b.latest)
    assert c.counters.as_dict() == b.counters.as_dict()


# -- config gating -----------------------------------------------------


def test_spill_flags_config_gating():
    with pytest.raises(ValueError):
        Config(window_size=10, spill_threshold_windows=-1)
    with pytest.raises(ValueError):
        Config(window_size=10, spill_target_hbm_frac=1.5)
    with pytest.raises(ValueError):  # device backend cannot spill
        Config(window_size=10, backend=Backend.DEVICE,
               spill_threshold_windows=3)
    with pytest.raises(ValueError):  # sharded sparse cannot spill
        Config(window_size=10, backend=Backend.SPARSE, num_shards=4,
               spill_threshold_windows=3)
    cfg = Config(window_size=10, backend=Backend.SPARSE,
                 spill_threshold_windows=3, spill_target_hbm_frac=0.25)
    assert cfg.spill_threshold_windows == 3


def test_checkpoint_retain_sweeps_aged_corrupt_files(tmp_path):
    """--checkpoint-retain ages out orphan *.corrupt quarantine files
    beyond the retain window (they previously accumulated forever);
    a corrupt file still inside the window is kept for forensics."""
    users, items, ts = random_stream(85, n=600, n_items=40, n_users=20)
    cfg = sparse_cfg(tmp_path, backend=Backend.ORACLE,
                     checkpoint_retain=2)
    cfg.backend = Backend.ORACLE
    job = CooccurrenceJob(cfg)
    ck = tmp_path / "ckpt"
    half = len(users) // 2
    job.add_batch(users[:half], items[:half], ts[:half])
    job.checkpoint()   # gen 1
    # Simulate old quarantined generations (gen 0 = legacy name).
    (ck / "state.0.npz.corrupt").write_bytes(b"x")
    (ck / "state.npz.corrupt").write_bytes(b"x")
    job.checkpoint()   # gen 2
    job.checkpoint()   # gen 3: retain=2 keeps {2, 3}; corrupt 0 aged out
    names = set(os.listdir(ck))
    assert "state.2.npz" in names and "state.3.npz" in names
    assert "state.1.npz" not in names
    assert "state.0.npz.corrupt" not in names
    assert "state.npz.corrupt" not in names
    # A corrupt generation INSIDE the window survives the sweep.
    (ck / "state.3.npz.corrupt").write_bytes(b"x")
    job.checkpoint()   # gen 4: window = {3, 4}; 3.corrupt stays
    names = set(os.listdir(ck))
    assert "state.3.npz.corrupt" in names
    job.finish()
