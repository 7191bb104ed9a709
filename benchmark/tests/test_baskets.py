"""The Instacart basket cell at its rehearsal size on the CPU, and its
basket stream: whole runs read correct and the control does not, the
work is fixed, and the generator keeps the configuration's shapes."""

import numpy as np
import pytest

from benchmark import run as bench
from benchmark.tests.test_harness import one_run
from benchmark.traffic import baskets

CELL = "instacart-baskets.replay"


@pytest.fixture(scope="module")
def stream():
    return bench.Spec(CELL).config["stream"]


def test_sound_run_is_correct(capsys):
    out = one_run(capsys, CELL, 2**31 + 25)
    assert out["correct"], out["checks"]
    assert {"pairs_per_s", "setup_s", "hbm_peak_gb"} <= set(out["metrics"])
    for m in out["metrics"].values():
        assert m["value"] is None  # no CPU number under a device name


def test_control_is_not_correct_by_score_gap(capsys):
    out = one_run(capsys, CELL, 2**31 + 26, "--control", "1")
    assert not out["correct"], out["checks"]
    checks = out["checks"]
    assert checks["score_gap"]["value"] > checks["score_gap"]["limit"]


def test_fixed_work_is_the_same_on_every_seed(capsys):
    """round(--seconds x batches_per_s) windows (5 x 2) after the
    rehearsal's warm-up, whatever the seed."""
    runs = [one_run(capsys, CELL, s) for s in (7, 2**33 + 7)]
    assert [r["attempted"] for r in runs] == [10, 10]


def test_every_basket_is_one_user_at_one_time(stream):
    users, items, ts, sizes = baskets.generate(stream, 11)
    assert len(users) == len(items) == len(ts) == sizes.sum()
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    basket = np.repeat(np.arange(len(sizes)), sizes)
    assert (users == users[starts][basket]).all()
    assert (ts == ts[starts][basket]).all()
    assert (np.diff(ts[starts]) == stream["ms_per_basket"]).all()


def test_windows_are_cut_at_basket_boundaries(stream):
    _users, _items, ts, sizes = baskets.generate(stream, 12)
    per_window = 1000 // stream["ms_per_basket"]
    bounds = baskets.window_bounds(sizes, per_window)
    assert bounds[0] == 0 and bounds[-1] == len(ts)
    assert len(bounds) - 1 == -(-len(sizes) // per_window)
    # Each batch is one whole 1000 ms tumbling window.
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        assert len(set((ts[lo:hi] // 1000).tolist())) == 1


def test_sizes_do_not_move_with_the_seed(stream):
    users_a, items_a, ts_a, sizes_a = baskets.generate(stream, 13)
    users_b, items_b, ts_b, sizes_b = baskets.generate(stream, 2**33 + 13)
    np.testing.assert_array_equal(users_a, users_b)
    np.testing.assert_array_equal(ts_a, ts_b)
    np.testing.assert_array_equal(sizes_a, sizes_b)
    assert (items_a != items_b).mean() > 0.9  # the products are the seed's


def test_marginals_are_the_configurations(stream):
    """Orders per user, basket sizes and the product spectrum, each
    within sampling error of the calibrated law
    (io/synthetic.INSTACART_CALIBRATION)."""
    users, items, _ts, sizes = baskets.generate(stream, 14)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    orders = np.bincount(users[starts], minlength=stream["n_users"])
    assert orders.sum() == stream["n_orders"]
    assert orders.min() >= stream["orders_lo"]
    assert orders.mean() == pytest.approx(16.59, abs=0.01)
    # Basket size: mean about 10.1, median 8 (the published anchors).
    assert sizes.mean() == pytest.approx(10.1, abs=0.15)
    assert np.median(sizes) == 8
    assert sizes.min() >= 1 and sizes.max() <= stream["basket_hi"]
    # Products: the head of the Zipf-Mandelbrot law, within 4 sigma.
    r = np.arange(1, stream["n_products"] + 1, dtype=np.float64)
    w = (r + stream["item_q"]) ** (-stream["item_s"])
    expected = len(items) * w / w.sum()
    got = np.bincount(items, minlength=stream["n_products"])
    head = slice(0, 20)
    assert (np.abs(got[head] - expected[head])
            <= 4 * np.sqrt(expected[head])).all()
    assert items.max() < stream["n_products"]
    # Top product's share as published: 491,291 of 33,819,106.
    assert got[0] / len(items) == pytest.approx(491_291 / 33_819_106,
                                                rel=0.06)
