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
from tpu_cooccurrence.ops.pallas_score import pallas_expand_baskets
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


# -- kernel-level parity (the registered parity test for
#    pallas_expand_baskets, pinned by cooclint pallas-kernel-registry) --


def test_pallas_expand_baskets_matches_host_expansion():
    """The expansion kernel's folded COO output equals the host
    expansion (BasketBatch.to_pairs) fold, across append ops (skip=-1),
    replacement op pairs (skip=slot, ±1), zero-length ops, and pad
    rows; pad/invalid lanes carry the (0, 0, 0) scatter no-op."""
    rng = np.random.default_rng(42)
    n_ops, w = 16, 128
    baskets = rng.integers(1, 50, size=(n_ops, w)).astype(np.int32)
    lens = np.array([0, 1, 5, 7] * 4, dtype=np.int32)
    skips = np.full(n_ops, -1, dtype=np.int32)
    skips[2::4] = 3                       # replacement-style exclusions
    signs = np.ones(n_ops, dtype=np.int32)
    signs[3::4] = -1
    new = rng.integers(50, 60, size=n_ops).astype(np.int32)
    b = BasketBatch(new, baskets, lens, skips, signs)

    src, dst, delta = pallas_expand_baskets(
        baskets, new.reshape(-1, 1), lens.reshape(-1, 1),
        skips.reshape(-1, 1), signs.reshape(-1, 1), interpret=True)
    src, dst, delta = (np.asarray(src).ravel(), np.asarray(dst).ravel(),
                      np.asarray(delta).ravel())
    lanes_used = (delta != 0).sum()
    assert lanes_used == len(b) == len(b.to_pairs())
    # Every zero-delta lane is the full no-op triple.
    idle = delta == 0
    assert not src[idle].any() and not dst[idle].any()
    p = b.to_pairs()
    assert _fold(src, dst, delta) == _fold(p.src, p.dst, p.delta)


def test_pallas_expand_baskets_rejects_bad_shapes():
    ok = np.zeros((8, 128), np.int32)
    meta = np.zeros((8, 1), np.int32)
    with pytest.raises(ValueError, match="multiple of 8"):
        pallas_expand_baskets(ok[:6], meta[:6], meta[:6], meta[:6],
                              meta[:6], interpret=True)
    with pytest.raises(ValueError, match="multiple of 128"):
        pallas_expand_baskets(np.zeros((8, 64), np.int32), meta, meta,
                              meta, meta, interpret=True)


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
    """A window whose padded expansion lanes exceed max_pairs_per_step
    routes chained (per-window, results identical); the chunk budget is
    honored rather than silently inflated."""
    users, items, ts = _ladder_edge_stream()
    kw = dict(user_cut=4, item_cut=500, max_pairs_per_step=1 << 14)
    chained = _run_job(users, items, ts, fused_window="off", **kw)
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
