"""Fused one-dispatch window path (--fused-window): parity + routing.

The contract under test (ISSUE 6): with the fused path forced on, every
routable window runs expansion + count update + row sums + LLR + top-K
as ONE device program fed by the basket uplink, and the results are
BIT-identical to the chained path (and match the host oracle to the
usual f32/f64 tolerance with the tie exemption) at pipeline depths 0
and 2 — including the ladder edges: empty windows, single-pair windows,
windows exactly at an ops-bucket boundary, and windows overflowing into
the next bucket. Non-routable windows (oversized for the chunk budget)
must fall back to the chained path with identical results, and the
PR-5 scorer circuit breaker must fail over to the host oracle
identically whether the fused path is on or off.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import tpu_cooccurrence.ops.device_scorer as ds
from tpu_cooccurrence.config import Backend, Config
from tpu_cooccurrence.job import CooccurrenceJob
from tpu_cooccurrence.observability.registry import REGISTRY
from tpu_cooccurrence.ops.aggregate import aggregate_window_coo
from tpu_cooccurrence.sampling.reservoir import (BasketBatch,
                                                 PairDeltaBatch,
                                                 UserReservoirSampler)

from test_pipeline import assert_latest_close, relabel_first_appearance

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run_job(users, items, ts, chunk=97, **overrides):
    kw = dict(window_size=10, seed=0xBEEF, backend=Backend.DEVICE,
              development_mode=True)
    kw.update(overrides)
    job = CooccurrenceJob(Config(**kw))
    for lo in range(0, len(users), chunk):
        job.add_batch(users[lo:lo + chunk], items[lo:lo + chunk],
                      ts[lo:lo + chunk])
    job.finish()
    return job


def _table(job):
    return {k: job.latest[k] for k in job.latest}


def _fold(src, dst, delta):
    s, d, v = aggregate_window_coo(np.asarray(src, dtype=np.int64),
                                   np.asarray(dst, dtype=np.int64),
                                   np.asarray(delta, dtype=np.int64))
    keep = v != 0
    return list(zip(s[keep].tolist(), d[keep].tolist(), v[keep].tolist()))


def _ladder_edge_stream():
    """A stream whose windows hit the ops-bucket ladder edges.

    Window 1 (ts 5): first-ever items only — every op has len 0, so the
    window fires with events but ZERO pairs (the empty edge). Window 2
    (ts 15): one user's second item — a single op of len 1 (the
    single-pair edge). Window 3 (ts 25): exactly 64 append ops (the
    minimum ops bucket, exactly-at-boundary). Window 4 (ts 35): 65 ops
    — overflow into the 128 bucket. Window 5 (ts 45): draws against
    full reservoirs (user_cut=4) — the replacement two-op ±1 form.
    """
    users, items, ts = [], [], []

    def ev(u, i, t):
        users.append(u)
        items.append(i)
        ts.append(t)

    for u in range(70):                      # window 1: all first items
        ev(u, 1000 + u, 5)
    ev(0, 100, 15)                           # window 2: one len-1 op
    for u in range(64):                      # window 3: exactly 64 ops
        ev(u, 200 + u, 25)
    for u in range(65):                      # window 4: 65 ops
        ev(u, 300 + u, 35)
    for k in range(30):                      # window 5: replacements
        ev(k % 4, 400 + k, 45)
    ev(0, 999, 65)                           # flush window 5
    users = relabel_first_appearance(np.asarray(users))
    items = relabel_first_appearance(np.asarray(items))
    return users, np.asarray(items), np.asarray(ts, dtype=np.int64)


# -- the device-side expansion ------------------------------------------


def _expand(b, step=0, pairs=None):
    """One scatter step's lanes of ``b`` (pairs past ``pairs`` dropped)."""
    n_cap, l_cap = ds.pad_pow2(b.n_ops, minimum=8), 128
    blk = np.zeros((n_cap, l_cap + 4), np.int32)
    blk[:b.n_ops, :b.baskets.shape[1]] = b.baskets
    blk[:, l_cap + 2] = -1
    blk[:b.n_ops, l_cap:] = np.stack([b.new_items, b.lens, b.skips,
                                      b.signs], axis=1)
    n = len(b) // 2 if pairs is None else pairs
    lanes = ds._basket_lanes(blk, np.int32(step), np.int32(n),
                             num_items=1000, basket_width=l_cap)
    src, dst, delta = (np.asarray(x) for x in lanes)
    keep = src < 1000
    return src[keep], dst[keep], delta[keep]


def test_basket_lanes_match_host_expansion():
    """The device expansion's lanes fold to the host expansion's
    (BasketBatch.to_pairs) across append ops (skip=-1), replacement op
    pairs (skip=slot, +-1), zero-length ops and a skip past the length;
    exactly the window's pairs are live, each once per direction."""
    rng = np.random.default_rng(42)
    n_ops, w = 16, 100
    baskets = rng.integers(1, 50, size=(n_ops, w)).astype(np.int32)
    lens = np.array([0, 1, 5, 7, 100, 0, 3, 64] * 2, dtype=np.int32)
    skips = np.full(n_ops, -1, dtype=np.int32)
    skips[2::4] = 3                       # replacement-style exclusions
    skips[6] = 9                          # past the op's length
    signs = np.ones(n_ops, dtype=np.int32)
    signs[3::4] = -1
    new = rng.integers(50, 60, size=n_ops).astype(np.int32)
    b = BasketBatch(new, baskets, lens, skips, signs)
    src, dst, delta = _expand(b)
    assert len(src) == len(b)
    p = b.to_pairs()
    assert _fold(src, dst, delta) == _fold(p.src, p.dst, p.delta)
    # A window of more pairs than one step is the union of its steps.
    steps = [_expand(b, t, pairs=len(b) // 2) for t in range(2)]
    assert sum(len(x[0]) for x in steps) == len(b)


def test_basket_lanes_split_across_steps(monkeypatch):
    """Pairs past one scatter step continue in the next, op boundaries
    and skips included, and the lanes past the window's pairs drop."""
    monkeypatch.setattr(ds, "SCATTER_STEP", 16)
    rng = np.random.default_rng(7)
    n_ops, w = 9, 40
    baskets = rng.integers(1, 90, size=(n_ops, w)).astype(np.int32)
    lens = np.array([11, 0, 30, 1, 17, 0, 40, 5, 2], dtype=np.int32)
    skips = np.array([-1, -1, 12, -1, 0, -1, 39, 4, -1], dtype=np.int32)
    signs = np.array([1, 1, 1, -1, 1, 1, -1, 1, 1], dtype=np.int32)
    new = rng.integers(90, 99, size=n_ops).astype(np.int32)
    b = BasketBatch(new, baskets, lens, skips, signs)
    n = len(b) // 2
    parts = [_expand(b, t, pairs=n) for t in range(-(-n // 16))]
    src, dst, delta = (np.concatenate(x) for x in zip(*parts))
    assert [len(x[0]) for x in parts[:-1]] == [32] * (len(parts) - 1)
    p = b.to_pairs()
    assert _fold(src, dst, delta) == _fold(p.src, p.dst, p.delta)


# -- sampler encoding ---------------------------------------------------


def test_sampler_basket_mode_matches_expanded_pairs():
    """Twin samplers over the same stream: the basket encoding's
    expanded pair multiset equals the COO path's, window by window,
    including replacement windows (the two-op ±1 form) and the
    feedback stream."""
    rng = np.random.default_rng(7)
    a = UserReservoirSampler(user_cut=4, seed=123, skip_cuts=False)
    b = UserReservoirSampler(user_cut=4, seed=123, skip_cuts=False)
    b.emit_baskets = True
    for _ in range(12):
        n = int(rng.integers(5, 40))
        users = rng.integers(0, 6, n)
        items = rng.integers(0, 30, n)
        sampled = rng.random(n) < 0.9
        pa, fa = a.fire(users, items, sampled)
        pb, fb = b.fire(users, items, sampled)
        assert isinstance(pb, BasketBatch)
        assert len(pa) == len(pb)
        assert _fold(pa.src, pa.dst, pa.delta) == \
            _fold(pb.src, pb.dst, pb.delta)
        np.testing.assert_array_equal(fa, fb)
    # Reservoir state is identical too: the encoding is output-only.
    np.testing.assert_array_equal(a.hist_len, b.hist_len)
    np.testing.assert_array_equal(a.clean_hist(6), b.clean_hist(6))


# -- end-to-end parity: ladder edges, both backends, depths 0 + 2 ------


@pytest.mark.parametrize("depth", [0, 2])
def test_fused_bit_identical_to_chained_at_ladder_edges(depth):
    users, items, ts = _ladder_edge_stream()
    kw = dict(user_cut=4, item_cut=500, pipeline_depth=depth)
    chained = _run_job(users, items, ts, fused_window="off", **kw)
    fused = _run_job(users, items, ts, fused_window="on", **kw)
    # Bit-identical: same rows, same ids, same float32 scores.
    assert _table(chained) == _table(fused)
    assert chained.counters.as_dict() == fused.counters.as_dict()
    assert chained.windows_fired == fused.windows_fired


@pytest.mark.parametrize("depth", [0, 2])
def test_fused_matches_host_oracle(depth):
    users, items, ts = _ladder_edge_stream()
    kw = dict(user_cut=4, item_cut=500, pipeline_depth=depth)
    oracle = _run_job(users, items, ts, backend=Backend.ORACLE, **kw)
    fused = _run_job(users, items, ts, fused_window="on", **kw)
    # f32 device vs f64 oracle: scores to tolerance, ids exact wherever
    # the row's score gaps exceed it (the lo>0-style tie exemption).
    assert_latest_close(_table(oracle), _table(fused))


def test_fused_bit_identical_with_pallas_score_and_int16():
    users, items, ts = _ladder_edge_stream()
    for extra in (dict(pallas="on"), dict(count_dtype="int16")):
        kw = dict(user_cut=4, item_cut=500, **extra)
        chained = _run_job(users, items, ts, fused_window="off", **kw)
        fused = _run_job(users, items, ts, fused_window="on", **kw)
        assert _table(chained) == _table(fused), extra


def _basket_stream(seed, windows=8, users=12, items=120, per_window=6,
                   window=10):
    """Seeded baskets, as an order history streams: each basket is one
    user's products under one timestamp, several baskets of a window
    share a timestamp, and each user orders often enough that a small
    ``user_cut`` fills the reservoir (replacements, -1 deltas)."""
    rng = np.random.default_rng(seed)
    users_out, items_out, ts_out = [], [], []
    for w in range(windows):
        stamps = np.sort(rng.integers(0, 3, per_window)) + w * window
        for t in stamps:
            u = int(rng.integers(users))
            for _ in range(int(rng.integers(1, 15))):
                users_out.append(u)
                items_out.append(int(rng.integers(items)))
                ts_out.append(int(t))
    users_out.append(0)                   # flush the last window
    items_out.append(0)
    ts_out.append(windows * window + window)
    return (relabel_first_appearance(np.asarray(users_out)),
            relabel_first_appearance(np.asarray(items_out)),
            np.asarray(ts_out, dtype=np.int64))


def _spy_windows(job):
    """Record what reaches the scorer each window: the logical pairs,
    whether any carries a -1 delta, the lanes of its basket rectangle
    and of its scatter steps."""
    seen = []
    real = job.scorer.process_window

    def spy(ts, pairs):
        rec = {"pairs": len(pairs), "minus": bool(len(pairs))
               and bool((np.asarray(pairs.delta) < 0).any())}
        if isinstance(pairs, BasketBatch) and pairs.n_ops:
            rec["rect"] = (ds.pad_pow2(pairs.n_ops, minimum=64) * 2
                           * ds.pad_pow2(pairs.baskets.shape[1],
                                         minimum=128))
            step = ds.SCATTER_STEP
            rec["lanes"] = 2 * step * -(-(len(pairs) // 2) // step)
        seen.append(rec)
        return real(ts, pairs)

    job.scorer.process_window = spy
    return seen


def _shrink_score_chunk(job):
    job.scorer.max_score_rows = 64


#: Basket windows the fused path must serve bitwise like the chained
#: one: reservoir evictions, a rescoring set over one chained score
#: chunk (the Pallas kernel scores it in one program, skipping the
#: padding blocks), and an expansion over the old lane budget
#: (``max_pairs_per_step``, now only the chained COO chunk).
BASKET_CASES = {
    "evictions": (dict(), None),
    "evictions-pallas-int16": (dict(pallas="on", count_dtype="int16"),
                               None),
    "score-chunk-pallas": (dict(pallas="on"), _shrink_score_chunk),
    "lane-budget": (dict(max_pairs_per_step=1 << 13), None),
}


@pytest.mark.parametrize("case", sorted(BASKET_CASES))
def test_fused_basket_windows_bit_identical_to_chained(case):
    extra, tweak = BASKET_CASES[case]
    users, items, ts = _basket_stream(5, items=400, per_window=12)
    kw = dict(user_cut=10, item_cut=40, **extra)
    jobs, seen = {}, {}
    for mode in ("off", "on"):
        job = CooccurrenceJob(Config(window_size=10, seed=0xBEEF,
                                     backend=Backend.DEVICE,
                                     development_mode=True,
                                     fused_window=mode, **kw))
        if tweak is not None:
            tweak(job)
        seen[mode] = _spy_windows(job)
        for lo in range(0, len(users), 97):
            job.add_batch(users[lo:lo + 97], items[lo:lo + 97],
                          ts[lo:lo + 97])
        job.finish()
        jobs[mode] = job
    chained, fused = jobs["off"], jobs["on"]
    assert _table(chained) == _table(fused)
    assert chained.counters.as_dict() == fused.counters.as_dict()
    np.testing.assert_array_equal(np.asarray(chained.scorer.C),
                                  np.asarray(fused.scorer.C))
    np.testing.assert_array_equal(np.asarray(chained.scorer.row_sums),
                                  np.asarray(fused.scorer.row_sums))
    # And both as the host oracle, to the f32/f64 tolerance.
    oracle = _run_job(users, items, ts, backend=Backend.ORACLE,
                      user_cut=10, item_cut=40)
    assert_latest_close(_table(oracle), _table(fused))
    assert any(w["minus"] for w in seen["off"]), "no reservoir eviction"
    # Every window with pairs went fused, and its counts say what the
    # expansion was shaped for and what of it was live.
    records = list(fused.step_timer.windows)
    carrying = [w for w in seen["on"] if w["pairs"]]
    counts = [r.counts for r in records if r.counts.get("fused_windows")]
    assert len(counts) == len(carrying)
    assert sum(c["expand_live"] for c in counts) == \
        sum(w["pairs"] for w in carrying)
    assert [c["expand_lanes"] for c in counts] == \
        [w["lanes"] for w in carrying]
    if case == "lane-budget":
        assert max(w["rect"] for w in carrying) > 1 << 13
    if case == "score-chunk-pallas":
        # Rescoring sets over one chained chunk, scored in one program
        # whose last pow4 blocks are padding the kernel skips.
        width = fused.scorer.num_items
        rows = [c["live_cells"] // width for c in counts]
        assert max(rows) > 64
        assert any(ds.pad_pow4(r, minimum=64) - r >= 64 for r in rows)
        assert [c["score_cells"] // width for c in counts] == \
            [-(-r // 64) * 64 for r in rows]


def test_deferred_fused_windows_in_flight_are_bounded(monkeypatch):
    """Once ``FUSED_IN_FLIGHT`` deferred fused windows are on the device,
    the next waits for the oldest before it uplinks its block, so the
    blocks the device holds stay bounded however far the host runs
    ahead; results are unchanged."""
    users, items, ts = _basket_stream(5, items=400, per_window=12)
    kw = dict(user_cut=10, item_cut=40)
    chained = _run_job(users, items, ts, fused_window="off", **kw)
    waited, queued = [], []
    real = ds._fused_window_defer

    class Done:
        def __init__(self, window):
            self.window = window

        def block_until_ready(self):
            waited.append(self.window)
            return self

    def spy(*args, **kwargs):
        queued.append(len(job.scorer._fused_queue))
        *out, _done = real(*args, **kwargs)
        return (*out, Done(len(queued)))

    monkeypatch.setattr(ds, "_fused_window_defer", spy)
    job = CooccurrenceJob(Config(window_size=10, seed=0xBEEF,
                                 backend=Backend.DEVICE,
                                 development_mode=True, fused_window="on",
                                 **kw))
    for lo in range(0, len(users), 97):
        job.add_batch(users[lo:lo + 97], items[lo:lo + 97], ts[lo:lo + 97])
    job.finish()
    assert len(queued) > ds.FUSED_IN_FLIGHT
    assert max(queued) == ds.FUSED_IN_FLIGHT - 1
    assert waited == list(range(1, len(queued) - ds.FUSED_IN_FLIGHT + 1))
    assert _table(chained) == _table(job)


def test_fused_emit_updates_mode_bit_identical():
    users, items, ts = _ladder_edge_stream()
    kw = dict(user_cut=4, item_cut=500, emit_updates=True)
    chained = _run_job(users, items, ts, fused_window="off", **kw)
    fused = _run_job(users, items, ts, fused_window="on", **kw)
    assert _table(chained) == _table(fused)


# -- routing and dispatch counts ---------------------------------------


class _FusedCounter:
    """Counting shims around the device scorer's jitted entry points."""

    TRACKED = ("_fused_window_emit", "_fused_window_defer", "_update_coo",
               "_update_coo_u16", "_update_coo_chunked",
               "_update_coo_u16_chunked", "_score")

    def __init__(self, monkeypatch):
        self.counts = {name: 0 for name in self.TRACKED}
        for name in self.TRACKED:
            monkeypatch.setattr(ds, name, self._wrap(name,
                                                     getattr(ds, name)))

    def _wrap(self, name, fn):
        def counted(*args, **kwargs):
            self.counts[name] += 1
            return fn(*args, **kwargs)
        return counted

    @property
    def fused(self):
        return (self.counts["_fused_window_emit"]
                + self.counts["_fused_window_defer"])

    @property
    def chained(self):
        return sum(self.counts[n] for n in self.TRACKED
                   if n.startswith("_update")) + self.counts["_score"]


def test_fused_window_is_one_dispatch(monkeypatch):
    """Every fused-routable window is exactly ONE jitted call — no
    separate update or score dispatch ever runs on the fused path."""
    counter = _FusedCounter(monkeypatch)
    users, items, ts = _ladder_edge_stream()
    job = _run_job(users, items, ts, user_cut=4, fused_window="on")
    assert counter.chained == 0, counter.counts
    # Windows 2-5 carry pairs (window 1 is the all-first-items empty
    # edge): one fused dispatch each.
    assert counter.fused == 4, counter.counts
    assert job.windows_fired >= 5


def test_chained_dispatch_path_unchanged_with_fused_off(monkeypatch):
    """--fused-window off (the default) keeps the seed's compiled-shape
    ladder: the exact chained entry points run, and the fused program
    is never compiled or dispatched — the dispatch/compile-count
    contract for existing configurations."""
    counter = _FusedCounter(monkeypatch)
    users, items, ts = _ladder_edge_stream()
    _run_job(users, items, ts, user_cut=4, fused_window="off")
    assert counter.fused == 0, counter.counts
    updates = sum(counter.counts[n] for n in counter.TRACKED
                  if n.startswith("_update"))
    assert updates >= 4, counter.counts
    assert counter.counts["_score"] >= 4, counter.counts


def test_fused_oversize_window_falls_back_chained(monkeypatch):
    """A window whose padded expansion lanes exceed FUSED_MAX_LANES
    routes chained (per-window, results identical); the lane bound is
    honored rather than silently inflated."""
    users, items, ts = _ladder_edge_stream()
    kw = dict(user_cut=4, item_cut=500)
    chained = _run_job(users, items, ts, fused_window="off", **kw)
    monkeypatch.setattr(ds, "FUSED_MAX_LANES", 1 << 14)
    counter = _FusedCounter(monkeypatch)
    fused = _run_job(users, items, ts, fused_window="on", **kw)
    # 2 * n_cap * l_cap = 16384 lanes at the minimum buckets fits the
    # budget exactly, so the <=64-op windows stay fused; the 65-op
    # window (128-op bucket, 32768 lanes) falls back to chained.
    assert counter.fused == 3, counter.counts
    assert counter.chained >= 2, counter.counts
    assert _table(chained) == _table(fused)


def test_fused_registry_counters_and_journal(tmp_path):
    REGISTRY.reset()
    users, items, ts = _ladder_edge_stream()
    jpath = tmp_path / "journal.jsonl"
    _run_job(users, items, ts, user_cut=4, fused_window="on",
             journal=str(jpath))
    assert REGISTRY.gauge("cooc_fused_dispatches_total").get() == 4
    assert REGISTRY.gauge("cooc_chained_dispatches_total").get() == 0
    from tpu_cooccurrence.observability.journal import (read_records,
                                                        validate_record)

    recs = [r for r in read_records(str(jpath)) if "seq" in r]
    for r in recs:
        validate_record(r)
    flags = [r["fused"] for r in recs]
    assert flags.count(1) == 4            # the four pair-carrying windows
    assert set(flags) <= {0, 1}
    # Each fused window's record carries its scorer seconds and counts:
    # one program launched (the first window also allocates the deferred
    # results table), shaped for at least the rows it scored.
    fused = [r for r in recs if r["fused"]]
    assert [r["counts"]["launches"] for r in fused] == [2, 1, 1, 1]
    for r in fused:
        assert r["score_seconds"] > 0
        assert r["counts"]["score_cells"] >= r["counts"]["live_cells"] > 0


# -- config validation --------------------------------------------------


def test_fused_window_config_validation():
    with pytest.raises(ValueError, match="device or sparse"):
        Config(window_size=10, backend=Backend.ORACLE, fused_window="on")
    with pytest.raises(ValueError, match="tumbling"):
        Config(window_size=10, window_slide=5, fused_window="on")
    with pytest.raises(ValueError, match="auto"):
        Config(window_size=10, fused_window="sometimes")
    # Single-process sparse accepts a forced 'on' since the fused sparse
    # window landed (its own validation lives in test_fused_sparse.py);
    # auto still rides along anywhere.
    Config(window_size=10, backend=Backend.SPARSE, fused_window="on")
    Config(window_size=10, backend=Backend.SHARDED, fused_window="auto")


# -- satellite: COO chunk pad-slot guard --------------------------------


def test_check_coo_chunk_guard():
    coo = np.zeros((3, 8), dtype=np.int32)
    coo[:, :5] = 1
    ds.check_coo_chunk(coo, 5)            # clean chunk passes
    with pytest.raises(AssertionError, match="silently truncated"):
        ds.check_coo_chunk(coo, 9)
    coo[2, 6] = 1                          # nonzero pad slot
    with pytest.raises(AssertionError, match="pad slots"):
        ds.check_coo_chunk(coo, 5)


# -- chaos: breaker failover with the fused path on ---------------------


def test_fused_breaker_failover_identical(tmp_path):
    """An injected dispatch failure (the scorer_breaker site inside the
    device scorer — where an injected `scorer_dispatch`-class fault
    lands once the window reaches the scorer) trips the PR-5 circuit
    breaker mid-run with --fused-window on; the run completes on the
    host-oracle fallback and its stdout is IDENTICAL to the same
    faulted run on the chained path — the fallback consumes the basket
    payload through the same pair stream."""
    from test_cli import write_stream

    f = tmp_path / "in.csv"
    write_stream(f, n=600)

    def run(fused, journal):
        proc = subprocess.run(
            [sys.executable, "-m", "tpu_cooccurrence.cli", "-i", str(f),
             "-ws", "40", "-ic", "8", "-uc", "5", "-s", "0xC0FFEE",
             "--backend", "device", "--fused-window", fused,
             "--journal", journal,
             "--scorer-breaker-threshold", "1",
             "--scorer-breaker-probe-windows", "3",
             "--inject-fault", "scorer_breaker:3:exception"],
            capture_output=True, text=True, env=ENV, cwd=REPO,
            timeout=600)
        assert proc.returncode == 0, proc.stderr[-800:]
        return proc.stdout

    out_fused = run("on", str(tmp_path / "j_fused.jsonl"))
    out_chained = run("off", str(tmp_path / "j_chained.jsonl"))
    assert out_fused, "run completed but emitted no results"
    assert out_fused == out_chained
    from tpu_cooccurrence.observability.journal import read_records

    recs = [r for r in read_records(str(tmp_path / "j_fused.jsonl"))
            if "breaker_state" in r]
    states = [r["breaker_state"] for r in recs]
    assert "open" in states, states       # the trip is journaled
    assert states[-1] == "closed", states  # half-open probe recovered
    # A fallback-scored window is never a fused dispatch — the breaker
    # wrapper shadows the primary's stale flag.
    for r in recs:
        if r["breaker_state"] == "open" and r.get("rows_scored"):
            assert r.get("fused") == 0, r
