"""The benchmark's copied reference against the program's own oracles,
on small seeded slices of both configurations' streams."""

import math

import numpy as np
import pytest

from benchmark.reference import oracle
from benchmark.traffic import stream

from tpu_cooccurrence.config import Config
from tpu_cooccurrence.oracle.reference import OracleJob
from tpu_cooccurrence.oracle.sliding import SlidingOracleJob

ML = {"generator": "calibrated", "n_users": 300, "n_items": 400,
      "item_s": 1.335659, "item_q": 116.337, "user_mu": 4.2627,
      "user_sigma": 1.1346, "user_lo": 20.0, "user_hi": 2000.0,
      "sizes_seed": 25}
ZIPF = {"generator": "zipf", "n_items": 3000, "n_users": 400, "alpha": 1.1,
        "sizes_seed": 4}


def first_appearance(users):
    ids = {}
    return [ids.setdefault(u, len(ids)) for u in users]


def assert_same_rows(mine, theirs, rows):
    for r in rows:
        a, b = mine[r], theirs[r]
        assert [i for i, _ in a] == [i for i, _ in b], r
        for (_, x), (_, y) in zip(a, b):
            assert math.isclose(x, y, rel_tol=1e-9, abs_tol=1e-9), r


def sliding(seed):
    users, items = stream.generate(ML, 12_000, seed)
    ts = stream.timestamps(12_000, 1_000)
    return users.tolist(), items.tolist(), ts.tolist()


def tumbling(seed):
    users, items = stream.generate(ZIPF, 12_000, seed)
    ts = stream.timestamps(12_000, 2_000)
    return users.tolist(), items.tolist(), ts.tolist()


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_sliding_matches_sliding_oracle(seed):
    users, items, ts = sliding(seed)
    cfg = Config(window_size=4000, window_slide=1000, item_cut=30,
                 user_cut=12, top_k=10)
    theirs = SlidingOracleJob(cfg)
    mine = oracle.SlidingReference(4000, 1000, 30, 12, 10)
    for u, i, t in zip(users, items, ts):
        theirs.process(u, i, t)
        mine.process(u, i, t)
    theirs.finish()
    mine.finish()
    for c in oracle.EXACT_COUNTERS:
        assert mine.counters[c] == theirs.counters.get(c), c
    latest = mine.latest()
    assert set(latest) == set(theirs.latest) == mine.rescored
    assert_same_rows(latest, theirs.latest, theirs.latest)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_tumbling_matches_oracle(seed):
    users, items, ts = tumbling(seed)
    cfg = Config(window_size=100, item_cut=25, user_cut=4, top_k=10,
                 seed=4)
    theirs = OracleJob(cfg)  # keyed by the dense ids the job assigns
    for u, i, t in zip(first_appearance(users), items, ts):
        theirs.process(u, i, t)
    theirs.finish()
    mine = oracle.TumblingReference(100, 25, 4, 10, 4)
    for u, i, t in zip(users, items, ts):
        mine.process(u, i, t)
    mine.finish()
    for c in oracle.EXACT_COUNTERS:
        assert mine.counters[c] == theirs.counters.get(c), c
    latest = mine.latest()
    assert set(latest) == set(theirs.latest) == mine.rescored
    assert_same_rows(latest, theirs.latest, theirs.latest)


@pytest.mark.parametrize("kind", ["sliding", "tumbling"])
def test_sampled_rows_match_all_rows(kind):
    if kind == "sliding":
        users, items, ts = sliding(19)

        def make(rows):
            return oracle.SlidingReference(4000, 1000, 30, 12, 10, rows)
    else:
        users, items, ts = tumbling(19)

        def make(rows):
            return oracle.TumblingReference(100, 25, 4, 10, 4, rows)
    full = make(None)
    for u, i, t in zip(users, items, ts):
        full.process(u, i, t)
    full.finish()
    rows = set(sorted(full.rescored)[::7])
    part = make(rows)
    for u, i, t in zip(users, items, ts):
        part.process(u, i, t)
    part.finish()
    assert dict(part.counters) == dict(full.counters)
    assert part.rescored == full.rescored
    assert set(part.latest()) == rows
    assert_same_rows(part.latest(), full.latest(), rows)


def test_lower_precision_scores_differ():
    s64 = oracle.llr([50], [5000], [4000], [10**8])
    s32 = oracle.llr([50], [5000], [4000], [10**8], np.float32)
    assert abs(s32[0] - s64[0]) > 1e-3 * s64[0]


def test_reservoir_draw_matches_program():
    from tpu_cooccurrence.sampling.rng import reservoir_draw_scalar

    for seed, user, draw, total in [(4, 0, 0, 501), (4, 99, 7, 10**6),
                                    (2**40 + 3, 12345, 2, 777)]:
        assert (oracle.reservoir_draw(seed, user, draw, total)
                == reservoir_draw_scalar(seed, user, draw, total))
