"""Sharded-sparse backend tests (virtual 8-device CPU mesh)."""

import numpy as np
import pytest

from tpu_cooccurrence.config import Backend, Config
from tpu_cooccurrence.job import CooccurrenceJob
from tpu_cooccurrence.metrics import (
    OBSERVED_COOCCURRENCES,
    RESCORED_ITEMS,
    ROW_SUM_PROCESS_WINDOW,
)

from test_pipeline import (
    assert_latest_close,
    random_stream,
    relabel_first_appearance,
    run_production,
)


@pytest.mark.parametrize("overrides", [
    dict(skip_cuts=True),
    dict(item_cut=5, user_cut=4),
])
@pytest.mark.parametrize("shards", [2, 8])
def test_sharded_sparse_matches_oracle(shards, overrides):
    kw = dict(window_size=10, seed=0xBEEF, development_mode=True)
    kw.update(overrides)
    users, items, ts = random_stream(31)
    a = run_production(Config(**kw, backend=Backend.ORACLE), users, items, ts)
    b = run_production(Config(**kw, backend=Backend.SPARSE,
                              num_shards=shards), users, items, ts)
    assert_latest_close(a.latest, b.latest)
    for name in (OBSERVED_COOCCURRENCES, ROW_SUM_PROCESS_WINDOW,
                 RESCORED_ITEMS):
        assert a.counters.get(name) == b.counters.get(name), name


def test_sharded_sparse_matches_single_device_sparse():
    """Shard count must not change results at all (same f32 math, same
    insertion-order tie-breaking within each row)."""
    kw = dict(window_size=20, seed=0xD2, item_cut=6, user_cut=4)
    rng = np.random.default_rng(13)
    n = 2000
    users = relabel_first_appearance(rng.integers(0, 12, n))
    items = relabel_first_appearance(rng.integers(0, 120, n))
    ts = np.cumsum(rng.integers(0, 2, n)).astype(np.int64)
    a = run_production(Config(**kw, backend=Backend.SPARSE),
                       users, items, ts)
    b = run_production(Config(**kw, backend=Backend.SPARSE, num_shards=8),
                       users, items, ts)
    assert set(a.latest) == set(b.latest)
    for item in a.latest:
        assert a.latest[item] == b.latest[item], f"row {item}"
    assert a.counters.as_dict() == b.counters.as_dict()


def test_sharded_sparse_growth_and_compaction():
    from tpu_cooccurrence.parallel.sharded_sparse import ShardedSparseScorer

    kw = dict(window_size=20, seed=0xD3, skip_cuts=True,
              development_mode=True)
    rng = np.random.default_rng(17)
    n = 2500
    users = relabel_first_appearance(rng.integers(0, 8, n))
    items = relabel_first_appearance(rng.integers(0, 150, n))
    ts = np.cumsum(rng.integers(0, 2, n)).astype(np.int64)
    a = run_production(Config(**kw, backend=Backend.ORACLE), users, items, ts)
    cfg = Config(**kw, backend=Backend.SPARSE, num_shards=4)
    scorer = ShardedSparseScorer(cfg.top_k, num_shards=4,
                                 development_mode=True, capacity=64,
                                 items_capacity=32, compact_min_heap=128)
    job = CooccurrenceJob(cfg, scorer=scorer)
    scorer.counters = job.counters
    for lo in range(0, n, 97):
        job.add_batch(users[lo:lo + 97], items[lo:lo + 97], ts[lo:lo + 97])
    job.finish()
    assert scorer.capacity > 64
    assert scorer.items_cap > 32
    assert sum(ix.compactions for ix in scorer.indexes) > 0
    assert_latest_close(a.latest, job.latest)


def test_sharded_sparse_checkpoint_interchange(tmp_path):
    """Canonical format: 1-shard checkpoint restores onto 8 shards and an
    8-shard checkpoint restores onto the single-device sparse backend."""
    users, items, ts = random_stream(35, n=400)
    half = 200
    for first_shards, second_shards in [(1, 8), (8, 1)]:
        kw = dict(window_size=10, seed=9, item_cut=5, user_cut=3,
                  development_mode=True,
                  checkpoint_dir=str(tmp_path / f"ck-{first_shards}"))
        ref = CooccurrenceJob(Config(**kw, backend=Backend.SPARSE,
                                     num_shards=second_shards))
        ref.add_batch(users, items, ts)
        ref.finish()

        a = CooccurrenceJob(Config(**kw, backend=Backend.SPARSE,
                                   num_shards=first_shards))
        a.add_batch(users[:half], items[:half], ts[:half])
        a.checkpoint()
        b = CooccurrenceJob(Config(**kw, backend=Backend.SPARSE,
                                   num_shards=second_shards))
        b.restore()
        b.add_batch(users[half:], items[half:], ts[half:])
        b.finish()
        assert_latest_close(ref.latest, b.latest, rtol=1e-5, atol=1e-5)


def test_sharded_sparse_deferred_matches_pipelined():
    """Deferred results (job default) == per-window pipeline (the
    --emit-updates path) on the virtual mesh, and no mid-stream
    emissions under deferral."""
    kw = dict(window_size=10, seed=0xA7, item_cut=5, user_cut=4,
              development_mode=True)
    users, items, ts = random_stream(67, n=1500)

    def run(emit):
        cfg = Config(**kw, backend=Backend.SPARSE, num_shards=8,
                     emit_updates=emit)
        job = CooccurrenceJob(cfg)
        mid = []
        job.on_update = lambda batch: mid.append(len(batch))
        job.add_batch(users, items, ts)
        n_mid = sum(mid)
        job.finish()
        return job, n_mid

    piped, mid_p = run(True)
    assert not piped.scorer.defer_results
    deferred, mid_d = run(False)
    assert deferred.scorer.defer_results
    assert mid_p > 0
    assert mid_d == 0
    assert_latest_close(piped.latest, deferred.latest,
                        rtol=1e-6, atol=1e-6)


def test_sharded_sparse_deferred_growth_and_checkpoint(tmp_path):
    """Deferred table survives items-capacity growth; periodic checkpoint
    + restore matches an uninterrupted run."""
    kw = dict(window_size=10, seed=0xA8, item_cut=5, user_cut=3,
              backend=Backend.SPARSE, num_shards=4,
              checkpoint_dir=str(tmp_path / "ck"), development_mode=True)
    rng = np.random.default_rng(17)
    n = 2600
    users = relabel_first_appearance(rng.integers(0, 15, n))
    items = relabel_first_appearance(rng.integers(0, 6000, n))
    ts = np.cumsum(rng.integers(0, 2, n)).astype(np.int64)
    half = 1300

    ref = CooccurrenceJob(Config(**kw))
    # Tiny capacity so the stream forces table growth mid-run.
    from tpu_cooccurrence.parallel.sharded_sparse import ShardedSparseScorer

    def tiny(cfg):
        sc = ShardedSparseScorer(cfg.top_k, num_shards=4,
                                 development_mode=True,
                                 items_capacity=1024,
                                 defer_results=True)
        job = CooccurrenceJob(cfg, scorer=sc)
        sc.counters = job.counters
        return job

    ref2 = tiny(Config(**kw))
    ref2.add_batch(users, items, ts)
    ref2.finish()
    assert ref2.scorer.items_cap > 1024  # growth actually happened
    ref.add_batch(users, items, ts)
    ref.finish()
    assert_latest_close(ref.latest, ref2.latest, rtol=1e-6, atol=1e-6)

    a = CooccurrenceJob(Config(**kw))
    a.add_batch(users[:half], items[:half], ts[:half])
    a.checkpoint()
    b = CooccurrenceJob(Config(**kw))
    b.restore()
    b.add_batch(users[half:], items[half:], ts[half:])
    b.finish()
    assert_latest_close(ref.latest, b.latest, rtol=1e-6, atol=1e-6)


def test_sharded_sparse_fixed_shapes_matches_variable():
    """Sharded fixed-shape scoring (one fused shard_map dispatch per
    window over a shard-uniform monotone plan) == the variable ladder."""
    from tpu_cooccurrence.parallel.sharded_sparse import ShardedSparseScorer

    kw = dict(window_size=10, seed=0xF7, item_cut=5, user_cut=4,
              development_mode=True)
    users, items, ts = random_stream(73, n=1500)

    def run(fixed):
        cfg = Config(**kw, backend=Backend.SPARSE, num_shards=8)
        scorer = ShardedSparseScorer(cfg.top_k, num_shards=8,
                                     development_mode=True,
                                     defer_results=True,
                                     fixed_score="on" if fixed else "off")
        if fixed:
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
