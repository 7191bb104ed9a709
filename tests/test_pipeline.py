"""End-to-end equivalence tests.

The production pipeline (vectorized window engine + item cut + reservoir +
backend scorer) must reproduce the record-at-a-time OracleJob exactly on the
oracle backend (same float64 math, same hash-RNG), and to float32 tolerance
on the device backend.

User RNG keys: OracleJob draws with raw user ids, the production job with
dense first-appearance indices — test streams are relabeled so they
coincide.
"""

import numpy as np
import pytest

from tpu_cooccurrence.config import Backend, Config
from tpu_cooccurrence.job import CooccurrenceJob
from tpu_cooccurrence.metrics import (
    ITEM_LATE_ELEMENTS,
    OBSERVED_COOCCURRENCES,
    RESCORED_ITEMS,
    ROW_SUM_PROCESS_WINDOW,
)
from tpu_cooccurrence.oracle import OracleJob


def relabel_first_appearance(ids):
    mapping = {}
    out = []
    for x in ids:
        out.append(mapping.setdefault(x, len(mapping)))
    return np.asarray(out, dtype=np.int64)


def random_stream(seed, n=600, n_users=12, n_items=25, max_dt=3):
    rng = np.random.default_rng(seed)
    users = relabel_first_appearance(rng.integers(0, n_users, n))
    items = relabel_first_appearance(rng.integers(0, n_items, n))
    ts = np.cumsum(rng.integers(0, max_dt, n)).astype(np.int64)
    return users, items, ts


def run_oracle(cfg, users, items, ts):
    job = OracleJob(cfg)
    for u, i, t in zip(users.tolist(), items.tolist(), ts.tolist()):
        job.process(u, i, t)
    job.finish()
    return job


def run_production(cfg, users, items, ts, chunk=97):
    job = CooccurrenceJob(cfg)
    for lo in range(0, len(users), chunk):
        job.add_batch(users[lo:lo + chunk], items[lo:lo + chunk], ts[lo:lo + chunk])
    job.finish()
    return job


def assert_latest_equal(oracle_latest, prod_latest, tol=None):
    assert set(oracle_latest) == set(prod_latest)
    for item in oracle_latest:
        o = oracle_latest[item]
        p = prod_latest[item]
        assert len(o) == len(p), f"row {item}: {o} vs {p}"
        o_scores = np.array([s for _, s in o])
        p_scores = np.array([s for _, s in p])
        if tol is None:
            np.testing.assert_allclose(p_scores, o_scores, rtol=1e-12, atol=1e-12)
            # Tie order among equal scores is implementation-defined (the
            # reference depends on hashmap iteration order); compare
            # canonicalized by (score desc, item).
            assert sorted(o, key=lambda e: (-e[1], e[0])) == \
                sorted(p, key=lambda e: (-e[1], e[0]))
        else:
            np.testing.assert_allclose(p_scores, o_scores, **tol)


def assert_latest_close(a_latest, b_latest, rtol=1e-4, atol=1e-3, gap=1e-2):
    """Tolerance comparison for f32-vs-f64 backends: scores to (rtol, atol),
    and the recommended item ids exactly whenever every score gap in the row
    exceeds ``gap`` (near-ties may legitimately reorder across precisions)."""
    assert set(a_latest) == set(b_latest)
    for item in a_latest:
        o = a_latest[item]
        p = b_latest[item]
        assert len(o) == len(p), f"row {item}: {o} vs {p}"
        o_scores = np.array([s for _, s in o])
        p_scores = np.array([s for _, s in p])
        np.testing.assert_allclose(p_scores, o_scores, rtol=rtol, atol=atol)
        if len(o_scores) > 1 and np.min(np.abs(np.diff(o_scores))) > gap:
            # The final rank stays uncertain even with clean in-list gaps:
            # the unseen K+1'th score may near-tie it across precisions.
            assert [j for j, _ in o][:-1] == [j for j, _ in p][:-1], \
                f"row {item}"


CONFIGS = [
    dict(skip_cuts=True),
    dict(item_cut=5, user_cut=4),
    dict(item_cut=3, user_cut=2, window_size=25),
    dict(item_cut=500, user_cut=3),
]


@pytest.mark.parametrize("overrides", CONFIGS)
def test_production_oracle_backend_matches_oracle_job(overrides):
    kw = dict(window_size=10, seed=0xBEEF, development_mode=True,
              backend=Backend.ORACLE)
    kw.update(overrides)
    cfg = Config(**kw)
    users, items, ts = random_stream(1)
    oracle = run_oracle(cfg, users, items, ts)
    prod = run_production(cfg, users, items, ts)
    assert_latest_equal({i: t for i, t in oracle.latest.items()}, prod.latest)
    for name in (OBSERVED_COOCCURRENCES, ROW_SUM_PROCESS_WINDOW,
                 RESCORED_ITEMS, ITEM_LATE_ELEMENTS):
        assert oracle.counters.get(name) == prod.counters.get(name), name


@pytest.mark.parametrize("overrides", CONFIGS)
def test_device_backend_matches_oracle_job(overrides):
    kw = dict(window_size=10, seed=0xBEEF, development_mode=True,
              backend=Backend.DEVICE, num_items=32)
    kw.update(overrides)
    cfg = Config(**kw)
    users, items, ts = random_stream(2)
    oracle_cfg = Config(**{**kw, "backend": Backend.ORACLE})
    oracle = run_oracle(oracle_cfg, users, items, ts)
    prod = run_production(cfg, users, items, ts)
    # float32 device scores vs float64 oracle: compare score vectors.
    assert set(oracle.latest) == set(prod.latest)
    for item in oracle.latest:
        o_scores = np.array([s for _, s in oracle.latest[item]])
        p_scores = np.array([s for _, s in prod.latest[item]])
        assert len(o_scores) == len(p_scores)
        np.testing.assert_allclose(p_scores, o_scores, rtol=1e-4, atol=1e-3)
        # Top-K member sets may differ only among near-tied scores; require
        # equality when all gaps exceed the tolerance.
        o_items = [j for j, _ in oracle.latest[item]]
        p_items = [j for j, _ in prod.latest[item]]
        if len(o_scores) > 1 and np.min(np.abs(np.diff(o_scores))) > 1e-2:
            assert o_items == p_items


def test_negative_timestamps_end_to_end():
    """Pre-epoch event times (legal raw longs in the reference CSV)
    flow through windowing, cuts, and scoring identically on the
    oracle and sparse backends — window floors must not truncate
    toward zero when ts < 0."""
    rng = np.random.default_rng(0xAB)
    n = 800
    users = relabel_first_appearance(rng.integers(0, 10, n))
    items = relabel_first_appearance(rng.integers(0, 20, n))
    ts = (np.cumsum(rng.integers(0, 3, n)) - 600).astype(np.int64)
    assert ts[0] < 0 < ts[-1]
    kw = dict(window_size=10, seed=0xBEEF, item_cut=5, user_cut=4,
              development_mode=True)
    a = run_production(Config(**kw, backend=Backend.ORACLE),
                       users, items, ts)
    b = run_production(Config(**kw, backend=Backend.SPARSE),
                       users, items, ts)
    assert a.latest, "negative-ts stream must produce results"
    assert_latest_close(a.latest, b.latest)
    assert a.counters.as_dict() == b.counters.as_dict()


def test_device_backend_counters_match_oracle_backend():
    cfg_o = Config(window_size=10, seed=3, item_cut=4, user_cut=3,
                   backend=Backend.ORACLE)
    cfg_d = Config(window_size=10, seed=3, item_cut=4, user_cut=3,
                   backend=Backend.DEVICE, num_items=32)
    users, items, ts = random_stream(7)
    a = run_production(cfg_o, users, items, ts)
    b = run_production(cfg_d, users, items, ts)
    for name in (OBSERVED_COOCCURRENCES, ROW_SUM_PROCESS_WINDOW, RESCORED_ITEMS):
        assert a.counters.get(name) == b.counters.get(name), name


def test_batch_boundaries_do_not_matter():
    cfg = Config(window_size=10, seed=5, item_cut=4, user_cut=3,
                 backend=Backend.ORACLE)
    users, items, ts = random_stream(9)
    a = run_production(cfg, users, items, ts, chunk=1)
    cfg2 = Config(window_size=10, seed=5, item_cut=4, user_cut=3,
                  backend=Backend.ORACLE)
    b = run_production(cfg2, users, items, ts, chunk=600)
    assert_latest_equal(a.latest, b.latest)


def test_device_backend_auto_derives_vocab():
    """num_items == 0: the dense backend grows C from the data (the
    config.py promise) and matches the oracle across growth events."""
    from tpu_cooccurrence.ops.device_scorer import DeviceScorer

    users, items, ts = random_stream(51, n=900, n_items=60)
    kw = dict(window_size=10, seed=0xA0, item_cut=6, user_cut=4,
              development_mode=True)
    a = run_production(Config(**kw, backend=Backend.ORACLE), users, items, ts)
    cfg = Config(**kw, backend=Backend.DEVICE)  # num_items defaults to 0
    job = CooccurrenceJob(cfg)
    # Start tiny so the stream forces several doublings.
    job.scorer = DeviceScorer(0, cfg.top_k, job.counters)
    job.scorer.num_items = job.scorer.num_items_logical = 16
    job.scorer.C = job.scorer.C[:16, :16]
    job.scorer.row_sums = job.scorer.row_sums[:16]
    for lo in range(0, len(users), 97):
        job.add_batch(users[lo:lo + 97], items[lo:lo + 97], ts[lo:lo + 97])
    job.finish()
    assert job.scorer.num_items >= 60  # grew past the stream's vocab
    assert_latest_close(a.latest, job.latest)


def test_device_backend_auto_derive_checkpoint_roundtrip(tmp_path):
    kw = dict(window_size=10, seed=7, item_cut=5, user_cut=3,
              backend=Backend.DEVICE, checkpoint_dir=str(tmp_path / "ck"))
    users, items, ts = random_stream(52, n=400)
    half = 200
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
    assert_latest_close(ref.latest, b.latest, rtol=1e-6, atol=1e-6)


def test_device_int16_counts_match_oracle():
    """--count-dtype int16 (reference-style short counts) is exact while
    counts stay within int16 range."""
    users, items, ts = random_stream(41)
    kw = dict(window_size=10, seed=0xD0D0, item_cut=6, user_cut=4)
    a = run_production(Config(**kw, backend=Backend.ORACLE), users, items, ts)
    b = run_production(Config(**kw, backend=Backend.DEVICE, num_items=32,
                              count_dtype="int16"), users, items, ts)
    assert_latest_equal(a.latest, b.latest, tol=dict(rtol=1e-4, atol=1e-4))
    assert a.counters.as_dict() == b.counters.as_dict()


def test_sharded_int16_counts_match_oracle():
    users, items, ts = random_stream(42)
    kw = dict(window_size=10, seed=0xD0D1, skip_cuts=True)
    a = run_production(Config(**kw, backend=Backend.ORACLE), users, items, ts)
    b = run_production(Config(**kw, backend=Backend.SHARDED, num_items=32,
                              num_shards=8, count_dtype="int16"),
                       users, items, ts)
    assert_latest_equal(a.latest, b.latest, tol=dict(rtol=1e-4, atol=1e-4))


def test_int16_counts_wrap_like_reference_shorts():
    """--count-dtype int16 reproduces the reference's silent short
    overflow (ItemRowAggregator.java:16 accumulates Java shorts): a cell
    pushed past 32767 wraps negative instead of raising, and the run
    keeps going."""
    from tpu_cooccurrence.ops.device_scorer import DeviceScorer
    from tpu_cooccurrence.sampling.reservoir import PairDeltaBatch

    sc = DeviceScorer(8, top_k=2, count_dtype="int16")
    # 3 windows x 20k on one cell: crosses 32767 -> wraps.
    n = 20_000
    batch = PairDeltaBatch(np.zeros(n, np.int64), np.ones(n, np.int64),
                           np.ones(n, np.int32))
    for ts in range(3):
        sc.process_window(ts, batch)
    sc.flush()
    c = sc.checkpoint_state()["C"]
    assert c.dtype == np.int16
    assert c[0, 1] == 60_000 - 65_536  # wrapped into the negative range
    assert c[0, 1] < 0


def test_device_deferred_matches_pipelined():
    """Dense-backend deferred-results mode (job default without
    --emit-updates) matches the per-window pipeline's final state, for
    both count dtypes and the pallas-on path."""

    from tpu_cooccurrence.job import CooccurrenceJob
    from tpu_cooccurrence.ops.device_scorer import DeviceScorer

    kw = dict(window_size=10, seed=0xD3, item_cut=5, user_cut=4,
              num_items=40, development_mode=True)
    users, items, ts = random_stream(43, n=1200)

    def run(defer, **scorer_kw):
        cfg = Config(**kw, backend=Backend.DEVICE)
        scorer = DeviceScorer(cfg.num_items, cfg.top_k,
                              defer_results=defer, **scorer_kw)
        job = CooccurrenceJob(cfg, scorer=scorer)
        scorer.counters = job.counters
        emitted = []
        job.on_update = lambda batch: emitted.append(len(batch))
        job.add_batch(users, items, ts)
        mid = list(emitted)
        job.finish()
        return job, mid

    piped, mid_p = run(False)
    assert sum(mid_p) > 0
    for scorer_kw in (dict(), dict(count_dtype="int16"),
                      dict(count_dtype="int16", use_pallas="on")):
        deferred, mid_d = run(True, **scorer_kw)
        assert mid_d == []
        assert_latest_close(piped.latest, deferred.latest,
                            rtol=1e-4, atol=1e-4)


def test_device_deferred_auto_capacity_growth():
    """Deferred table survives dense auto-capacity re-allocation
    (--num-items omitted): rows scored before the growth keep their
    entries. Window size 60 (not 10): the growth claim needs the vocab
    to cross the dense capacity MID-stream with scored rows on both
    sides, which ~60 windows prove as well as ~375 did at a sixth of
    the wall time (tier-1 budget)."""
    from tpu_cooccurrence.job import CooccurrenceJob

    kw = dict(window_size=60, seed=0xD4, skip_cuts=True,
              development_mode=True)
    users, items, ts = random_stream(47, n=2500, n_items=1500)
    a = run_production(Config(**kw, backend=Backend.ORACLE),
                       users, items, ts)
    cfg = Config(**kw, backend=Backend.DEVICE)  # num_items=0: derive
    b = CooccurrenceJob(cfg)
    assert b.scorer.defer_results
    for lo in range(0, len(users), 500):
        b.add_batch(users[lo:lo + 500], items[lo:lo + 500],
                    ts[lo:lo + 500])
    b.finish()
    assert b.scorer.num_items > 1024  # growth actually happened
    assert_latest_close(a.latest, b.latest)


def test_vocab_smaller_than_top_k():
    """A vocabulary smaller than K must not crash the dense backends
    (lax.top_k rejects k > axis size; the reference's heap simply holds
    fewer entries). Found by the extended randomized sweep."""
    rng = np.random.default_rng(0x26)
    n = 600
    users = rng.integers(0, 20, n).astype(np.int64)
    items = rng.integers(0, 5, n).astype(np.int64)
    ts = np.cumsum(rng.integers(0, 3, n)).astype(np.int64)
    kw = dict(window_size=20, seed=9, item_cut=8, user_cut=4, top_k=10)
    oracle = run_production(Config(backend=Backend.ORACLE,
                                   development_mode=True, **kw),
                            users, items, ts)
    ref = {i: oracle.latest[i] for i in oracle.latest}
    for backend, extra in (("device", {"num_items": 5}),
                           ("sharded", {"num_items": 5, "num_shards": 4}),
                           ("sparse", {})):
        job = run_production(Config(backend=Backend(backend),
                                    development_mode=True,
                                    **dict(kw, **extra)),
                             users, items, ts)
        assert job.counters.as_dict() == oracle.counters.as_dict(), backend
        assert_latest_close(ref, {i: job.latest[i] for i in job.latest},
                            rtol=2e-4, atol=2e-4)
