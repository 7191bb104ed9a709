"""Sparse device backend (HBM slab + host index) tests.

Tiny initial capacities force every structural path — heap doubling, row
relocation, compaction, items-capacity growth — on small test streams.
"""

import numpy as np
import pytest

from tpu_cooccurrence.config import Backend, Config
from tpu_cooccurrence.metrics import (
    OBSERVED_COOCCURRENCES,
    RESCORED_ITEMS,
    ROW_SUM_PROCESS_WINDOW,
)
from tpu_cooccurrence.state.sparse_scorer import SparseDeviceScorer

from test_pipeline import (
    assert_latest_close,
    random_stream,
    relabel_first_appearance,
    run_production,
)


def tiny_scorer_factory(cfg):
    from tpu_cooccurrence.job import CooccurrenceJob

    scorer = SparseDeviceScorer(cfg.top_k, development_mode=True,
                                capacity=64, items_capacity=8,
                                compact_min_heap=256,
                                score_ladder=cfg.score_ladder)
    job = CooccurrenceJob(cfg, scorer=scorer)
    scorer.counters = job.counters
    return job


@pytest.mark.parametrize("overrides", [
    dict(skip_cuts=True),
    dict(item_cut=5, user_cut=4),
    dict(item_cut=3, user_cut=2, window_size=25),
])
def test_sparse_matches_oracle_backend(overrides):
    kw = dict(window_size=10, seed=0xBEEF, development_mode=True)
    kw.update(overrides)
    users, items, ts = random_stream(31)
    a = run_production(Config(**kw, backend=Backend.ORACLE), users, items, ts)
    b = run_production(Config(**kw, backend=Backend.SPARSE), users, items, ts)
    assert_latest_close(a.latest, b.latest)
    for name in (OBSERVED_COOCCURRENCES, ROW_SUM_PROCESS_WINDOW,
                 RESCORED_ITEMS):
        assert a.counters.get(name) == b.counters.get(name), name


def test_sparse_growth_and_compaction_paths():
    """Tiny capacities force heap doubling, relocations, and compaction
    while matching the oracle end to end."""
    from tpu_cooccurrence.job import CooccurrenceJob

    kw = dict(window_size=20, seed=0xD1, skip_cuts=True,
              development_mode=True)
    rng = np.random.default_rng(11)
    n = 3000
    users = relabel_first_appearance(rng.integers(0, 10, n))
    items = relabel_first_appearance(rng.integers(0, 150, n))
    ts = np.cumsum(rng.integers(0, 2, n)).astype(np.int64)

    a = run_production(Config(**kw, backend=Backend.ORACLE), users, items, ts)
    cfg = Config(**kw, backend=Backend.SPARSE)
    b = tiny_scorer_factory(cfg)
    for lo in range(0, n, 97):
        b.add_batch(users[lo:lo + 97], items[lo:lo + 97], ts[lo:lo + 97])
    b.finish()
    sc = b.scorer
    assert sc.capacity > 64          # heap doubled
    assert sc.items_cap > 8          # item registry grew
    assert sc.compactions > 0        # defragmentation actually ran
    assert_latest_close(a.latest, b.latest)


def test_sparse_index_invariants():
    """Host index/registry invariants after a mixed stream: sorted keys,
    in-range slots, per-row segments exactly [start, start+len)."""
    users, items, ts = random_stream(77, n=900, n_items=40)
    cfg = Config(window_size=15, seed=3, item_cut=6, user_cut=4,
                 backend=Backend.SPARSE, development_mode=True)
    job = tiny_scorer_factory(cfg)
    job.add_batch(users, items, ts)
    job.finish()
    sc = job.scorer
    idx = sc.index
    g_key, g_slot = idx.keys_and_slots()
    assert len(g_key) > 0              # the invariants below must bite
    assert np.all(np.diff(g_key) > 0)  # strictly sorted, unique
    assert len(g_slot) == len(g_key)
    rows = (g_key >> 32).astype(np.int64)
    for r in np.unique(rows):
        slots = np.sort(g_slot[rows == r])
        start, ln = idx.row_start[r], idx.row_len[r]
        assert ln == len(slots)
        np.testing.assert_array_equal(slots, np.arange(start, start + ln))
        assert ln <= idx.row_cap[r]
    assert idx.heap_end <= sc.capacity


def test_sparse_checkpoint_roundtrip(tmp_path):
    from tpu_cooccurrence.job import CooccurrenceJob

    kw = dict(window_size=10, seed=4, item_cut=5, user_cut=3,
              backend=Backend.SPARSE, checkpoint_dir=str(tmp_path / "ck"),
              development_mode=True)
    users, items, ts = random_stream(33, n=400)
    half = 180

    ref = CooccurrenceJob(Config(**kw))
    ref.add_batch(users, items, ts)
    ref.finish()

    a = CooccurrenceJob(Config(**kw))
    a.add_batch(users[:half], items[:half], ts[:half])
    a.checkpoint()
    b = CooccurrenceJob(Config(**kw))
    b.restore()
    b.add_batch(users[half:], items[half:], ts[half:])
    b.finish()

    assert set(ref.latest) == set(b.latest)
    for item in ref.latest:
        np.testing.assert_allclose(
            np.array([s for _, s in b.latest[item]]),
            np.array([s for _, s in ref.latest[item]]), rtol=1e-6, atol=1e-6)


def test_sparse_hybrid_checkpoint_interchange(tmp_path):
    """The migration path for the retired hybrid backend: a job configured
    with ``--backend hybrid`` (now the sparse alias) writes/restores the
    same canonical sparse-matrix checkpoint, in both directions."""
    from tpu_cooccurrence.job import CooccurrenceJob

    users, items, ts = random_stream(35, n=400)
    half = 200
    for first, second in [(Backend.HYBRID, Backend.SPARSE),
                          (Backend.SPARSE, Backend.HYBRID)]:
        kw = dict(window_size=10, seed=9, item_cut=5, user_cut=3,
                  checkpoint_dir=str(tmp_path / f"ck-{first.value}"),
                  development_mode=True)
        ref = CooccurrenceJob(Config(**kw, backend=second))
        ref.add_batch(users, items, ts)
        ref.finish()

        a = CooccurrenceJob(Config(**kw, backend=first))
        a.add_batch(users[:half], items[:half], ts[:half])
        a.checkpoint()
        b = CooccurrenceJob(Config(**kw, backend=second))
        b.restore()
        b.add_batch(users[half:], items[half:], ts[half:])
        b.finish()
        assert_latest_close(ref.latest, b.latest, rtol=1e-5, atol=1e-5)


def test_sparse_coordinator_requires_shards():
    import pytest

    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.job import CooccurrenceJob

    cfg = Config(window_size=10, seed=1, backend=Backend.SPARSE,
                 coordinator="127.0.0.1:1", num_processes=2, process_id=0)
    with pytest.raises(ValueError, match="num-shards"):
        CooccurrenceJob(cfg)


def test_slab_index_fuzz_against_slab_simulation():
    """Model-based fuzz: simulate the device slab (key per slot) on host
    through many windows of random cell batches, applying the exact move /
    new-cell / compaction protocol the real cnt/dst arrays get. Catches
    allocator, relocation, and compaction bugs that single-window
    invariant checks can't."""
    from tpu_cooccurrence.state.sparse_scorer import SlabIndex

    rng = np.random.default_rng(0xF00D)
    idx = SlabIndex(rows_capacity=8)
    slab = np.full(64, -1, dtype=np.int64)  # key living in each slot
    seen = set()
    for window in range(60):
        n = int(rng.integers(1, 120))
        rows = rng.integers(0, 50, n).astype(np.int64)
        # Zipf-ish partner ids; duplicates collapse via unique.
        dsts = rng.integers(0, 1 + int(rng.integers(1, 200)), n)
        d_key = np.unique((rows << 32) | dsts)
        plan = idx.apply(d_key)
        if idx.heap_end > len(slab):
            grown = np.full(max(2 * len(slab), idx.heap_end), -1,
                            dtype=np.int64)
            grown[: len(slab)] = slab
            slab = grown
        if plan.mv is not None:
            old_s, new_s, ln = plan.mv[0], plan.mv[1], plan.mv[2]
            for o, w, m in zip(old_s.tolist(), new_s.tolist(), ln.tolist()):
                if m:
                    slab[w: w + m] = slab[o: o + m]
        slab[plan.slots[plan.new_sel]] = d_key[plan.new_sel]
        seen.update(d_key.tolist())
        # Every applied key must be found at the slot the index returned...
        np.testing.assert_array_equal(slab[plan.slots], d_key)
        # ...and the whole index must agree with the simulated slab.
        np.testing.assert_array_equal(slab[idx.g_slot], idx.g_key)
        assert len(idx.g_key) == len(seen)
        if idx.needs_compaction(min_heap=64):
            gmap = idx.compact()
            new_slab = np.full(len(slab), -1, dtype=np.int64)
            new_slab[: len(gmap)] = slab[gmap]
            slab = new_slab
            np.testing.assert_array_equal(slab[idx.g_slot], idx.g_key)
    assert idx.compactions > 0, "fuzz never hit the compaction path"


@pytest.mark.parametrize("ladder", [2, 4, 16])
def test_sparse_score_ladder_equivalence(ladder):
    """Every bucket-ladder base scores identically (padding is compute
    only); coarser ladders exist to cut dispatches on high-latency links."""
    users, items, ts = random_stream(5, n=1200, n_items=80)
    cfg = Config(window_size=20, seed=9, item_cut=8, user_cut=5,
                 backend=Backend.SPARSE, development_mode=True,
                 score_ladder=ladder)
    job = tiny_scorer_factory(cfg)
    job.add_batch(users, items, ts)
    job.finish()
    assert job.scorer.score_ladder == ladder
    ref_cfg = Config(window_size=20, seed=9, item_cut=8, user_cut=5,
                     backend=Backend.ORACLE, development_mode=True)
    from tpu_cooccurrence.job import CooccurrenceJob

    ref = CooccurrenceJob(ref_cfg)
    ref.add_batch(users, items, ts)
    ref.finish()
    assert job.counters.as_dict() == ref.counters.as_dict()
    assert set(job.latest) == set(ref.latest)
    for item in ref.latest:
        np.testing.assert_allclose(
            [s for _, s in job.latest[item]],
            [s for _, s in ref.latest[item]], rtol=2e-4, atol=2e-4)


def test_sparse_deferred_matches_pipelined():
    """defer_results keeps results in the device table and fetches once:
    final state must equal the per-window pipelined mode's, and no
    per-window results may be emitted before the flush."""
    from tpu_cooccurrence.job import CooccurrenceJob

    kw = dict(window_size=10, seed=0xA1, item_cut=5, user_cut=4,
              development_mode=True)
    users, items, ts = random_stream(41, n=1200)

    def run(defer):
        cfg = Config(**kw, backend=Backend.SPARSE)
        scorer = SparseDeviceScorer(cfg.top_k, development_mode=True,
                                    capacity=64, items_capacity=8,
                                    compact_min_heap=256,
                                    defer_results=defer)
        job = CooccurrenceJob(cfg, scorer=scorer)
        scorer.counters = job.counters
        mid_stream_emissions = []
        job.on_update = lambda batch: mid_stream_emissions.append(len(batch))
        job.add_batch(users, items, ts)
        mid = list(mid_stream_emissions)
        job.finish()
        return job, mid

    piped, mid_p = run(False)
    deferred, mid_d = run(True)
    assert sum(mid_p) > 0          # pipelined mode streams mid-run
    assert mid_d == []             # deferred mode holds everything on device
    assert_latest_close(piped.latest, deferred.latest)
    # Structural growth paths ran under deferral too (table re-allocation).
    assert deferred.scorer.items_cap > 8


def test_sparse_deferred_flush_idempotent_and_checkpoint(tmp_path):
    """Periodic checkpoints flush the deferred table (idempotently); a
    restore repopulates results from the saved LatestResults and the
    post-restore windows, matching an uninterrupted run."""
    from tpu_cooccurrence.job import CooccurrenceJob

    kw = dict(window_size=10, seed=7, item_cut=5, user_cut=3,
              backend=Backend.SPARSE, checkpoint_dir=str(tmp_path / "ck"),
              development_mode=True)
    users, items, ts = random_stream(53, n=500)
    half = 230

    ref = CooccurrenceJob(Config(**kw))
    assert ref.scorer.defer_results   # job default: no --emit-updates
    ref.add_batch(users, items, ts)
    ref.finish()

    a = CooccurrenceJob(Config(**kw))
    a.add_batch(users[:half], items[:half], ts[:half])
    f1 = a.scorer.flush()
    assert len(f1) > 0       # first flush drains everything scored so far
    a._absorb(f1)            # flushed rows belong to the caller (the job
    # absorbs every flush; dropping one would lose results)
    f2 = a.scorer.flush()
    assert len(f2) == 0      # incremental: nothing new since -> no refetch
    a.checkpoint()
    b = CooccurrenceJob(Config(**kw))
    b.restore()
    b.add_batch(users[half:], items[half:], ts[half:])
    b.finish()
    assert_latest_close(ref.latest, b.latest, rtol=1e-6, atol=1e-6)


def test_sparse_fixed_shapes_matches_variable():
    """Fixed-shape scoring (constant per-bucket rectangles, TPU default)
    produces identical results to the variable pow-4 ladder."""
    from tpu_cooccurrence.job import CooccurrenceJob

    kw = dict(window_size=10, seed=0xF5, item_cut=5, user_cut=4,
              development_mode=True)
    users, items, ts = random_stream(59, n=1200)

    def run(fixed):
        cfg = Config(**kw, backend=Backend.SPARSE)
        scorer = SparseDeviceScorer(cfg.top_k, development_mode=True,
                                    capacity=64, items_capacity=8,
                                    compact_min_heap=256,
                                    defer_results=True,
                                    fixed_score="on" if fixed else "off")
        if fixed:
            # Small fixed rectangles so the CPU test stays quick; the
            # shape-constancy property is what is under test.
            scorer.FIXED_BUDGET = 1 << 12
            scorer.FIXED_ROW_CAP = 64
        job = CooccurrenceJob(cfg, scorer=scorer)
        scorer.counters = job.counters
        job.add_batch(users, items, ts)
        job.finish()
        return job

    var = run(False)
    fix = run(True)
    assert_latest_close(var.latest, fix.latest, rtol=1e-6, atol=1e-6)
    for name in (OBSERVED_COOCCURRENCES, ROW_SUM_PROCESS_WINDOW,
                 RESCORED_ITEMS):
        assert var.counters.get(name) == fix.counters.get(name), name


def test_sparse_fixed_shapes_dispatch_signature_constant():
    """Fixed mode scores a whole window in ONE dispatch whose static plan
    gives each bucket R a single constant S — the whole point (a handful
    of programs, one scoring dispatch per window)."""
    import tpu_cooccurrence.state.sparse_scorer as sp
    from tpu_cooccurrence.job import CooccurrenceJob

    plans = []
    calls = {"window": 0, "per_bucket": 0}
    orig_window = sp._score_window_into_table
    orig_bucket = sp._score_into_table

    def spy_window(tbl, cnt, dst, row_sums, meta_all, observed, *,
                   top_k, plan, interpret=False):
        calls["window"] += 1
        plans.append(plan)
        return orig_window(tbl, cnt, dst, row_sums, meta_all, observed,
                           top_k=top_k, plan=plan, interpret=interpret)

    def spy_bucket(*a, **k):
        calls["per_bucket"] += 1
        return orig_bucket(*a, **k)

    cfg = Config(window_size=10, seed=0xF6, item_cut=5, user_cut=4,
                 backend=Backend.SPARSE, development_mode=True)
    users, items, ts = random_stream(61, n=1500)
    scorer = SparseDeviceScorer(cfg.top_k, development_mode=True,
                                defer_results=True, fixed_score="on")
    scorer.FIXED_BUDGET = 1 << 12
    scorer.FIXED_ROW_CAP = 64
    job = CooccurrenceJob(cfg, scorer=scorer)
    scorer.counters = job.counters
    sp._score_window_into_table = spy_window
    sp._score_into_table = spy_bucket
    try:
        job.add_batch(users, items, ts)
        job.finish()
    finally:
        sp._score_window_into_table = orig_window
        sp._score_into_table = orig_bucket
    assert calls["window"] > 0       # fixed mode used the fused dispatch
    assert calls["per_bucket"] == 0  # never the per-bucket path
    # S is a pure function of R across EVERY dispatch of the stream
    # (constant rectangles — the invariant that bounds program count).
    s_by_r = {}
    for plan in plans:
        for r, s, _o, _pl in plan:
            assert s_by_r.setdefault(r, s) == s, (r, s, s_by_r)
    # The monotone high-water plan only ever grows: each plan's
    # (R -> chunk count) multiset extends its predecessor's.
    seen = {}
    for plan in plans:
        counts = {}
        for r, _s, _o, _pl in plan:
            counts[r] = counts.get(r, 0) + 1
        for r, n in seen.items():
            assert counts.get(r, 0) >= n, (seen, counts)
        seen = counts
    # Program count bounded by the final plan count, not window count.
    assert len(set(plans)) <= sum(seen.values())


def test_sparse_fixed_shapes_chunk_overflow_plan_persists():
    """A bucket overflowing its per-dispatch row cap adds chunk-rank
    entries to the plan; later smaller windows RETAIN them (all-padding)
    so the fused program never retraces."""
    import tpu_cooccurrence.state.sparse_scorer as sp
    from tpu_cooccurrence.job import CooccurrenceJob

    plans = []
    orig = sp._score_window_into_table

    def spy(*a, **k):
        plans.append(k["plan"])
        return orig(*a, **k)

    cfg = Config(window_size=10, seed=2, skip_cuts=True,
                 development_mode=True)
    sc = sp.SparseDeviceScorer(cfg.top_k, development_mode=True,
                               defer_results=True, fixed_score="on")
    sc.FIXED_BUDGET = 1 << 10
    sc.FIXED_ROW_CAP = 16   # force chunk overflow on the busy window
    job = CooccurrenceJob(cfg, scorer=sc)
    sc.counters = job.counters
    u1 = np.zeros(40, np.int64)
    i1 = np.arange(40, dtype=np.int64)
    u2 = np.zeros(5, np.int64)
    i2 = np.arange(5, dtype=np.int64)
    sp._score_window_into_table = spy
    try:
        job.add_batch(np.concatenate([u1, u2]),
                      np.concatenate([i1, i2]),
                      np.concatenate([np.full(40, 5, np.int64),
                                      np.full(5, 15, np.int64)]))
        job.finish()
    finally:
        sp._score_window_into_table = orig
    assert len(plans) >= 2
    assert len(plans[0]) >= 3          # the busy window overflowed
    assert len(set(plans)) == 1        # one program for the whole stream


def test_hash_index_matches_sorted_index():
    """The native hash index and the sorted fallback must be plan-for-plan
    identical across appends, relocations, compactions, and rebuilds."""
    import pytest

    from tpu_cooccurrence.native import get_lib
    from tpu_cooccurrence.state.sparse_scorer import (HashSlabIndex,
                                                      SlabIndex)

    if get_lib() is None:
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(0xF00D)
    a = SlabIndex(rows_capacity=8)
    b = HashSlabIndex(rows_capacity=8)
    for window in range(120):
        n = int(rng.integers(1, 120))
        rows = rng.integers(0, 50, n).astype(np.int64)
        dsts = rng.integers(0, 1 + int(rng.integers(1, 200)), n)
        d_key = np.unique((rows << 32) | dsts)
        pa = a.apply(d_key.copy())
        pb = b.apply(d_key.copy())
        np.testing.assert_array_equal(pa.new_sel, pb.new_sel)
        np.testing.assert_array_equal(pa.slots, pb.slots)
        assert a.heap_end == b.heap_end
        if pa.mv is not None or pb.mv is not None:
            np.testing.assert_array_equal(pa.mv, pb.mv)
        if a.needs_compaction(256):
            np.testing.assert_array_equal(a.compact(), b.compact())
    ka, sa = a.keys_and_slots()
    kb, sb = b.keys_and_slots()
    np.testing.assert_array_equal(ka, kb)
    np.testing.assert_array_equal(sa, sb)
    # Restore path: both rebuild to the same layout and keep agreeing.
    np.testing.assert_array_equal(a.rebuild_from_keys(ka.copy()),
                                  b.rebuild_from_keys(ka.copy()))
    pa = a.apply(ka[:7].copy())
    pb = b.apply(ka[:7].copy())
    np.testing.assert_array_equal(pa.slots, pb.slots)
