"""Fused SHARDED window (--fused-window on, --num-shards > 1): parity,
fallback routing, the rescale seam, and the observability split.

The contract under test (ISSUE 16): with the fused path forced on, every
steady-state sharded sparse window runs ownership-partitioned decode +
slab update scatter + row-sum psum + per-shard registry-mirror sync +
rescore + results-table scatter as ONE jit(shard_map) launch per worker,
BIT-identical to the chained sharded path — across shard counts, cell
dtypes, raw and packed wire, checkpoint/restore (all-dirty mirror
resync), and the 2→4 autoscale seam (plans rebuild cold, the first
post-seam window routes chained, the second re-enters fused with one new
bucket compilation). Non-routable windows fall back chained per window
under the reason taxonomy the cooclint ``fused-fallback-registry`` rule
pins: ``plan-rebuild``, ``relocation``, ``upload-split``, ``promotion``.
"""

import json

import jax
import numpy as np
import pytest

from tpu_cooccurrence.config import Backend
from tpu_cooccurrence.observability.registry import REGISTRY
from tpu_cooccurrence.parallel.sharded_sparse import ShardedSparseScorer
from tpu_cooccurrence.sampling.reservoir import PairDeltaBatch

from test_fused_window import _run_job, _table

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="sharded fused tests need >= 4 (virtual) devices")


# -- scorer-level harness -----------------------------------------------


def _steady_windows(seed=0, n_win=8, n_items=40):
    """A fixed pair population, then per-window subsets of it: after the
    first window every cell exists, so no row ever relocates — the
    zero-relocation steady state the fused path requires."""
    rng = np.random.default_rng(seed)
    src0 = rng.integers(0, n_items, 200).astype(np.int64)
    dst0 = rng.integers(0, n_items, 200).astype(np.int64)
    keep = src0 != dst0
    src0, dst0 = src0[keep], dst0[keep]
    out = [(src0, dst0, np.ones(len(src0), np.int64))]
    for _ in range(n_win - 1):
        sel = rng.random(len(src0)) < 0.6
        out.append((src0[sel], dst0[sel],
                    rng.integers(1, 4, int(sel.sum())).astype(np.int64)))
    return out


def _mk(num_shards, fused, **kw):
    return ShardedSparseScorer(
        5, num_shards=num_shards, defer_results=True,
        development_mode=True, fused_window=fused, **kw)


def _drive(scorer, windows, start=0):
    """Process windows, returning the (fused?, fallback-reason) trace."""
    trace = []
    for i, (src, dst, delta) in enumerate(windows, start=start):
        scorer.process_window(
            i, PairDeltaBatch(src=src, dst=dst, delta=delta))
        trace.append((scorer.last_dispatch_fused,
                      scorer.last_fallback_reason))
    return trace


def _assert_batches_equal(a, b, ctx=""):
    assert np.array_equal(a.rows, b.rows), ctx
    assert np.array_equal(a.vals, b.vals), ctx
    assert np.array_equal(a.idx, b.idx), ctx


# -- steady-state parity matrix -----------------------------------------


@pytest.mark.parametrize("cell_dtype", ["int32", "int16"])
@pytest.mark.parametrize("wire_format", ["raw", "packed"])
def test_fused_sharded_steady_state_bit_identical(cell_dtype, wire_format):
    for num_shards in (2, 3):
        wins = _steady_windows()
        kw = dict(cell_dtype=cell_dtype, wire_format=wire_format)
        chained = _mk(num_shards, "off", **kw)
        _drive(chained, wins)
        fused = _mk(num_shards, "on", **kw)
        trace = _drive(fused, wins)
        ctx = f"shards={num_shards} cell={cell_dtype} wire={wire_format}"
        _assert_batches_equal(chained.flush(), fused.flush(), ctx)
        # First non-empty window is the cold plan-rebuild; every later
        # window of the fixed population re-enters the ONE-launch path.
        assert trace[0] == (False, "plan-rebuild"), (ctx, trace)
        assert all(f for f, _ in trace[1:]), (ctx, trace)
        # One pow2 bucket tuple serves the whole steady stream.
        assert fused.fused_compilations == 1, (ctx, trace)


# -- job-level parity: depths 0 and 2 -----------------------------------


def _steady_job_stream(n_win=6):
    """Per-window repeats of the same event set: user histories saturate
    after window 1, so the pair population stabilizes and later windows
    can fuse."""
    users, items, ts = [], [], []
    for w in range(n_win):
        for j in range(60):
            users.append(j % 6)
            items.append((j * 7) % 30)
            ts.append(w * 10 + 5)
    users.append(0)
    items.append(999)
    ts.append(n_win * 10 + 5)
    return (np.asarray(users), np.asarray(items),
            np.asarray(ts, dtype=np.int64))


@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("num_shards", [2, 3])
def test_fused_sharded_job_parity(depth, num_shards):
    users, items, ts = _steady_job_stream()
    kw = dict(backend=Backend.SPARSE, num_shards=num_shards,
              pipeline_depth=depth)
    chained = _run_job(users, items, ts, fused_window="off", **kw)
    fused = _run_job(users, items, ts, fused_window="on", **kw)
    assert _table(chained) == _table(fused)
    assert chained.counters.as_dict() == fused.counters.as_dict()
    assert chained.windows_fired == fused.windows_fired


# -- checkpoint/restore: all-dirty mirror resync ------------------------


@pytest.mark.parametrize("cell_dtype", ["int32", "int16"])
def test_fused_sharded_restore_resyncs_mirrors(cell_dtype):
    """A restore rebuilds the per-shard registries (all-dirty), so the
    first post-restore window must route chained while plans rebuild and
    the device mirrors resync — and the resumed fused run must stay
    bit-identical to a chained resume over the same schedule."""
    wins = _steady_windows()

    def resume(fused):
        s = _mk(2, fused, cell_dtype=cell_dtype)
        _drive(s, wins[:4])
        state = s.checkpoint_state()
        s.flush()
        s2 = _mk(2, fused, cell_dtype=cell_dtype)
        s2.restore_state(state)
        trace = _drive(s2, wins[4:], start=4)
        return s2.flush(), trace

    b_fused, trace = resume("on")
    b_chained, _ = resume("off")
    _assert_batches_equal(b_fused, b_chained, cell_dtype)
    assert trace[0] == (False, "plan-rebuild"), trace
    assert all(f for f, _ in trace[1:]), trace


# -- the autoscale seam: 2 -> 4 rescale ---------------------------------


def test_fused_sharded_rescale_seam_rebuilds_plans():
    """A 2→4 rescale invalidates every shard's bucket plan: plans must
    rebuild from the post-restore registry state, the first post-seam
    window must fall back chained cleanly, the second must re-enter
    fused with exactly one fresh bucket compilation (no stale-plan
    dispatch, no compile storm) — and stdout stays bit-identical to both
    the chained seam run and a fixed-topology fused run."""
    wins = _steady_windows()
    REGISTRY.reset()

    def seam(fused):
        s = _mk(2, fused)
        trace = _drive(s, wins[:4])
        state = s.checkpoint_state()
        s.flush()
        s2 = _mk(4, fused)
        s2.restore_state(state)
        assert s2._plan_buckets == {}, "stale bucket plan across seam"
        trace += _drive(s2, wins[4:], start=4)
        return s2.flush(), trace, s2

    b_fused, trace, s2 = seam("on")
    # Pre-seam: cold window then fused; post-seam: one chained
    # plan-rebuild window, then fused again.
    assert trace[0] == (False, "plan-rebuild"), trace
    assert all(f for f, _ in trace[1:4]), trace
    assert trace[4] == (False, "plan-rebuild"), trace
    assert all(f for f, _ in trace[5:]), trace
    # One compile before the seam, one after — counted on the gauge.
    assert s2.fused_compilations == 1, trace
    assert (REGISTRY.gauge("cooc_fused_bucket_compilations_total").get()
            == 1)

    b_chained, _, _ = seam("off")
    _assert_batches_equal(b_fused, b_chained, "seam fused-vs-chained")

    # Fixed-topology D=4 fused run over the same windows: the post-seam
    # flush only drains rows touched after the seam, so compare those.
    s4 = _mk(4, "on")
    _drive(s4, wins)
    b_fixed = s4.flush()
    sel = np.isin(b_fixed.rows, b_fused.rows)
    assert np.array_equal(b_fixed.rows[sel], b_fused.rows)
    assert np.array_equal(b_fixed.vals[sel], b_fused.vals)
    assert np.array_equal(b_fixed.idx[sel], b_fused.idx)


# -- fallback taxonomy: relocation, promotion, upload-split -------------


def test_fused_sharded_relocation_falls_back_and_recovers():
    n = 40
    w_small = (np.zeros(10, np.int64), np.arange(1, 11, dtype=np.int64),
               np.ones(10, np.int64))
    w_big = (np.zeros(n, np.int64), np.arange(1, n + 1, dtype=np.int64),
             np.ones(n, np.int64))

    def run(fused):
        s = _mk(2, fused)
        trace = _drive(s, [w_small, w_small, w_big, w_big])
        return s.flush(), trace

    b_fused, trace = run("on")
    b_chained, _ = run("off")
    _assert_batches_equal(b_fused, b_chained, "relocation parity")
    assert trace[0] == (False, "plan-rebuild"), trace
    assert trace[1][0] is True, trace
    # Row 0 outgrows its pow2 cap: moves ride the chained update.
    assert trace[2] == (False, "relocation"), trace
    # The repeated population recovers the one-launch path.
    assert trace[3][0] is True, trace


def test_fused_sharded_promotion_falls_back_chained():
    """int8 cells: the hub row crosses the promote threshold (128) and
    moves to the wide side-table — every window touching it must route
    chained (reason ``promotion``), bit-identical to the chained run."""
    w = (np.zeros(20, np.int64), np.arange(1, 21, dtype=np.int64),
         np.full(20, 3, np.int64))

    def run(fused):
        s = _mk(2, fused, cell_dtype="int8")
        trace = _drive(s, [w, w, w, w])
        return s.flush(), trace

    b_fused, trace = run("on")
    b_chained, _ = run("off")
    _assert_batches_equal(b_fused, b_chained, "promotion parity")
    assert trace[0] == (False, "plan-rebuild"), trace
    assert trace[1][0] is True, trace
    reasons = [r for _, r in trace]
    assert "promotion" in reasons, trace
    # Once wide, the hub row keeps the window chained.
    assert trace[3] == (False, "promotion"), trace


def test_fused_sharded_upload_split_pins_chained(monkeypatch):
    """An explicit TPU_COOC_UPLOAD_CHUNKS request is a measurement
    lever: the chunking A/B must not silently measure the fused program,
    so every window routes chained (reason ``upload-split``)."""
    monkeypatch.setenv("TPU_COOC_UPLOAD_CHUNKS", "2")
    wins = _steady_windows(n_win=3)
    s = _mk(2, "on")
    trace = _drive(s, wins)
    assert trace[0] == (False, "plan-rebuild"), trace
    assert all(t == (False, "upload-split") for t in trace[1:]), trace


# -- observability: gauges, journal, packed-uplink ledger ---------------


def test_fused_sharded_gauges_and_journal(tmp_path):
    REGISTRY.reset()
    users, items, ts = _steady_job_stream()
    jpath = tmp_path / "journal.jsonl"
    _run_job(users, items, ts, backend=Backend.SPARSE, num_shards=2,
             fused_window="on", journal=str(jpath))
    fused_total = REGISTRY.gauge("cooc_fused_dispatches_total").get()
    chained_total = REGISTRY.gauge("cooc_chained_dispatches_total").get()
    assert fused_total > 0, "no window ever took the fused sharded path"
    # The per-shard split sits beside the process-level pair: each
    # worker dispatches once per window, so every shard's gauge equals
    # the process total.
    for d in range(2):
        assert (REGISTRY.gauge(
            f"cooc_fused_dispatches_total_shard{d}").get() == fused_total)
        assert (REGISTRY.gauge(
            f"cooc_chained_dispatches_total_shard{d}").get()
            == chained_total)
    from tpu_cooccurrence.observability.journal import (read_records,
                                                        validate_record)
    recs = [r for r in read_records(str(jpath)) if "seq" in r]
    for r in recs:
        validate_record(r)
    flags = [r["fused"] for r in recs]
    assert set(flags) <= {0, 1}
    assert flags.count(1) == fused_total
    # Chained windows name their fallback reason for the operator —
    # the first (cold-plan) window is always a "plan-rebuild".
    assert recs[0]["fused"] == 0
    assert recs[0]["fallback_reason"] == "plan-rebuild"
    assert all("fallback_reason" not in r for r in recs if r["fused"])
    # The bucket-compile counter rides the journal per window.
    compiles = [r["fused_compiles"] for r in recs if "fused_compiles" in r]
    assert compiles and compiles[-1] == REGISTRY.gauge(
        "cooc_fused_bucket_compilations_total").get()
    # Each fused window's record carries its scorer seconds.
    assert sum(1 for r in recs
               if r["fused"] and r["score_seconds"] > 0) == fused_total


def test_fused_sharded_packed_uplink_is_ledger_booked(tmp_path):
    """The sharded packed uplink books encoded vs raw bytes exactly as
    the single-process PR-7 path: per fused window the encoded pair is
    accounted and never exceeds the raw equivalent."""
    users, items, ts = _steady_job_stream()
    jpath = tmp_path / "journal.jsonl"
    _run_job(users, items, ts, backend=Backend.SPARSE, num_shards=2,
             fused_window="on", wire_format="packed", journal=str(jpath))
    recs = [json.loads(line) for line in open(jpath)]
    fused_recs = [r for r in recs if r.get("fused") == 1 and r.get("pairs")]
    assert fused_recs, "no fused window with pairs to account"
    for r in fused_recs:
        assert r["wire"]["h2d_bytes"] > 0
        assert r["wire"]["uplink_enc_bytes"] > 0
        assert (r["wire"]["uplink_raw_bytes"]
                >= r["wire"]["uplink_enc_bytes"])
