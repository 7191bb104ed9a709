"""Fused one-dispatch SPARSE window path (--fused-window): parity + routing.

The contract under test (ISSUE 11): with the fused path forced on, every
steady-state sparse window runs packed-wire decode + slab update scatter
+ device registry sync + LLR rescore + results-table scatter as ONE
device program, BIT-identical to the chained sparse path (and matching
the host oracle to tolerance) at pipeline depths 0 and 2 — across the
edges: empty windows, single-pair windows, score-bucket boundaries,
narrow cell dtypes, packed and raw wire. Non-routable windows — slab
relocation, narrow→wide promotion, spill re-promotion — must fall back
to the chained path per window with identical results, and the journal
/ metrics split must record which path each window took.
"""

import json

import numpy as np
import pytest

from tpu_cooccurrence.config import Backend, Config
from tpu_cooccurrence.observability.registry import REGISTRY

from test_fused_window import _ladder_edge_stream, _run_job, _table
from test_pipeline import assert_latest_close


def _run_sparse(users, items, ts, **overrides):
    kw = dict(backend=Backend.SPARSE)
    kw.update(overrides)
    return _run_job(users, items, ts, **kw)


def _wide_row_stream():
    """One hub item co-occurring with ~300 partners (row len crosses the
    16 → 64 → 1024 score-bucket ladder), then repeat touches of the SAME
    cells (the zero-relocation steady state), then a fresh growth spurt.
    """
    users, items, ts = [], [], []

    def ev(u, i, t):
        users.append(u)
        items.append(i)
        ts.append(t)

    for j in range(120):                     # window 1: hub grows wide
        ev(j % 6, 0, 5)
        ev(j % 6, 1 + j, 5)
    for w in range(2, 6):                    # windows 2-5: same cells
        for j in range(40):
            ev(j % 6, 1 + j, w * 10 + 5)
    for j in range(150):                     # window 6: growth again
        ev(j % 6, 200 + j, 65)
    ev(0, 999, 85)                           # flush
    return (np.asarray(users), np.asarray(items),
            np.asarray(ts, dtype=np.int64))


# -- end-to-end parity: edges, depths 0 + 2, oracle ---------------------


@pytest.mark.parametrize("depth", [0, 2])
def test_fused_sparse_bit_identical_to_chained_at_edges(depth):
    users, items, ts = _ladder_edge_stream()
    kw = dict(user_cut=4, item_cut=500, pipeline_depth=depth)
    chained = _run_sparse(users, items, ts, fused_window="off", **kw)
    fused = _run_sparse(users, items, ts, fused_window="on", **kw)
    # Bit-identical: same rows, same ids, same float32 scores — the
    # fused program shares _update_body and _score_rect with chained.
    assert _table(chained) == _table(fused)
    assert chained.counters.as_dict() == fused.counters.as_dict()
    assert chained.windows_fired == fused.windows_fired


def test_fused_sparse_matches_host_oracle():
    # Depth 2 is covered transitively: fused == chained bit-for-bit at
    # both depths above, and chained-vs-oracle is pinned by the
    # existing sparse parity suite.
    users, items, ts = _ladder_edge_stream()
    kw = dict(user_cut=4, item_cut=500)
    oracle = _run_job(users, items, ts, backend=Backend.ORACLE, **kw)
    fused = _run_sparse(users, items, ts, fused_window="on", **kw)
    assert_latest_close(_table(oracle), _table(fused))


@pytest.mark.parametrize("wire", ["raw", "packed"])
def test_fused_sparse_wire_formats_bit_identical(wire):
    """Wire compression and fusion compose: the packed form decodes in
    the fused program's prologue, the raw form ships the buffer — both
    bit-identical to the chained path under the same wire setting. The
    wide-row stream also drives rows across score-bucket widths (16 →
    64 → 1024) with steady repeat windows in between, so plan growth
    and all-padding top-up rectangles are exercised too."""
    users, items, ts = _wide_row_stream()
    kw = dict(user_cut=6, item_cut=500, wire_format=wire)
    chained = _run_sparse(users, items, ts, fused_window="off", **kw)
    fused = _run_sparse(users, items, ts, fused_window="on", **kw)
    assert _table(chained) == _table(fused)
    assert chained.counters.as_dict() == fused.counters.as_dict()


def test_fused_sparse_pallas_rectangles_bit_identical():
    """--pallas on routes kernel-carriable buckets (R >= 256) through
    pallas_score_rect INSIDE the fused program; results stay
    bit-identical to the chained path with the same kernel routing."""
    users, items, ts = _wide_row_stream()
    kw = dict(user_cut=6, item_cut=500, pallas="on")
    chained = _run_sparse(users, items, ts, fused_window="off", **kw)
    fused = _run_sparse(users, items, ts, fused_window="on", **kw)
    assert _table(chained) == _table(fused)


@pytest.mark.parametrize("cell_dtype", ["int16", "int8"])
def test_fused_sparse_narrow_cells_promotion_forces_chained(cell_dtype):
    """Narrow cell dtypes: a hot row crossing the promote threshold
    moves to the wide side-table — that window (and every later window
    touching the wide row) routes chained; output stays bit-identical
    and the promotion really happened."""
    rng = np.random.default_rng(13)
    n = 2200
    # Reservoir replacement bounds a row's sum by ~2 * users * user_cut,
    # so the user count (not the event count) is what pushes the hub row
    # past int8's 128 promote threshold.
    users = rng.integers(0, 40, n)
    # Zipf-ish: item 0 dominates so it sits in most users' reservoirs.
    items = np.where(rng.random(n) < 0.4, 0,
                     rng.integers(1, 60, n)).astype(np.int64)
    ts = np.sort(rng.integers(0, 300, n)).astype(np.int64)
    kw = dict(user_cut=6, item_cut=500, cell_dtype=cell_dtype)
    chained = _run_sparse(users, items, ts, fused_window="off", **kw)
    fused = _run_sparse(users, items, ts, fused_window="on", **kw)
    assert _table(chained) == _table(fused)
    scorer = fused.scorer
    if cell_dtype == "int8":
        assert scorer.wide_rows.any(), "stream never promoted a row"


def test_fused_sparse_spill_repromotion_bit_identical():
    """Tiered store on: windows that re-promote spilled rows carry promo
    sections and route chained; spill-on fused output equals spill-on
    chained output bit-for-bit, and rows really spilled."""
    users, items, ts = [], [], []
    rng = np.random.default_rng(3)
    # Cohort churn: each window its own users/items, so earlier rows go
    # cold; late windows re-touch window-0 items (re-promotion).
    for w in range(8):
        base = 0 if w >= 6 else w * 40
        for _ in range(160):
            users.append(int(rng.integers(0, 5)) + w * 10)
            items.append(base + int(rng.integers(0, 30)))
            ts.append(w * 10 + 5)
    users, items, ts = (np.asarray(users), np.asarray(items),
                        np.asarray(ts, dtype=np.int64))
    kw = dict(user_cut=6, item_cut=500, spill_threshold_windows=2,
              spill_target_hbm_frac=0.0)
    REGISTRY.reset()
    chained = _run_sparse(users, items, ts, fused_window="off", **kw)
    fused = _run_sparse(users, items, ts, fused_window="on", **kw)
    assert _table(chained) == _table(fused)
    assert REGISTRY.gauge("cooc_spill_evictions_total").get() > 0
    assert REGISTRY.gauge("cooc_spill_promotions_total").get() > 0


def test_fused_sparse_checkpoint_restore_resumes_identically():
    """Kill-and-resume across the fused path: the device registry
    mirror is rebuilt from the restored index (all-dirty resync), and
    the resumed run's output is bit-identical to the uninterrupted one.
    """
    import tpu_cooccurrence.state.sparse_scorer as ss
    from tpu_cooccurrence.sampling.reservoir import PairDeltaBatch

    def window(seed):
        r = np.random.default_rng(seed)
        src = r.integers(0, 120, 500)
        dst = r.integers(0, 120, 500)
        m = dst == src
        dst[m] = (dst[m] + 1) % 120
        return PairDeltaBatch(src.astype(np.int64), dst.astype(np.int64),
                              np.ones(500, dtype=np.int32))

    def resume_run(fused):
        first = ss.SparseDeviceScorer(
            top_k=5, defer_results=True, fused_window=fused,
            wire_format="packed", cell_dtype="int16",
            capacity=1 << 16, items_capacity=1 << 10)
        for w in range(5):
            first.process_window(w * 10, window(w))
        first.flush()  # results before the snapshot are drained
        blob = first.checkpoint_state()
        resumed = ss.SparseDeviceScorer(
            top_k=5, defer_results=True, fused_window=fused,
            wire_format="packed", cell_dtype="int16",
            capacity=1 << 16, items_capacity=1 << 10)
        resumed.restore_state(blob)
        for w in range(5, 10):
            resumed.process_window(w * 10, window(w))
        return resumed.flush()

    def rows_of(b):
        return {int(r): (list(map(int, i)), list(map(float, v)))
                for r, i, v in zip(b.rows, b.idx, b.vals)}

    # Restore re-lays each row's cells in key order (canonical blob), so
    # equal-score ties may sit differently than in an uninterrupted run
    # — checkpoint semantics that predate this path. The fused resume
    # must be bit-identical to the CHAINED resume over the identical
    # restore schedule: the rebuilt device registry mirror (all-dirty
    # resync) reproduces the chained path's layout exactly.
    assert rows_of(resume_run("on")) == rows_of(resume_run("off"))


# -- journal + metrics --------------------------------------------------


def test_fused_sparse_registry_counters_and_journal(tmp_path):
    REGISTRY.reset()
    users, items, ts = _wide_row_stream()
    jpath = tmp_path / "journal.jsonl"
    _run_sparse(users, items, ts, user_cut=6, fused_window="on",
                journal=str(jpath))
    fused_total = REGISTRY.gauge("cooc_fused_dispatches_total").get()
    chained_total = REGISTRY.gauge("cooc_chained_dispatches_total").get()
    assert fused_total > 0, "no window ever took the fused sparse path"
    # Per-bucket shape specialization is visible and bounded.
    compiles = REGISTRY.gauge("cooc_fused_bucket_compilations_total").get()
    assert 0 < compiles <= fused_total + 4
    from tpu_cooccurrence.observability.journal import (read_records,
                                                        validate_record)

    recs = [r for r in read_records(str(jpath)) if "seq" in r]
    for r in recs:
        validate_record(r)
    flags = [r["fused"] for r in recs]
    assert set(flags) <= {0, 1}
    assert flags.count(1) == fused_total
    # The journal's per-window counts split the same windows: every
    # dispatching window launched programs (the fused ones exactly one
    # window program, besides capacity growth), dispatch-free empty
    # windows none.
    launched = [r for r in recs if r.get("counts", {}).get("launches")]
    assert sum(r["fused"] for r in launched) == fused_total
    assert sum(1 - r["fused"] for r in launched) == chained_total
    assert all(r["counts"]["launches"] >= 1 for r in launched)


def test_fused_sparse_uplink_is_ledger_booked(tmp_path):
    """The fused dispatch's uplink (packed words + registry delta +
    score rows) books on the TransferLedger like every other upload —
    the journal's per-window wire delta stays exact."""
    users, items, ts = _wide_row_stream()
    jpath = tmp_path / "journal.jsonl"
    _run_sparse(users, items, ts, user_cut=6, fused_window="on",
                wire_format="packed", journal=str(jpath))
    recs = [json.loads(line) for line in open(jpath)]
    fused_recs = [r for r in recs if r.get("fused") == 1 and r.get("pairs")]
    assert fused_recs
    for r in fused_recs:
        assert r["wire"]["h2d_bytes"] > 0
        # Packed wire: the encoded-uplink pair is accounted per window.
        assert r["wire"]["uplink_enc_bytes"] > 0
        assert (r["wire"]["uplink_raw_bytes"]
                >= r["wire"]["uplink_enc_bytes"])


# -- config validation --------------------------------------------------


def test_fused_sparse_config_validation():
    # Single-process sparse accepts a forced 'on'.
    Config(window_size=10, backend=Backend.SPARSE, fused_window="on")
    # Sharded sparse now accepts it too (PR 16: one launch per worker).
    Config(window_size=10, backend=Backend.SPARSE, num_shards=2,
           fused_window="on")
    # ... but per-window result streaming still cannot fuse, on any
    # topology (the fused program scatters results on device).
    with pytest.raises(ValueError, match="deferred results"):
        Config(window_size=10, backend=Backend.SPARSE, emit_updates=True,
               fused_window="on")
    with pytest.raises(ValueError, match="deferred results"):
        Config(window_size=10, backend=Backend.SPARSE, num_shards=2,
               emit_updates=True, fused_window="on")
    # Hybrid's sparse half stays single-process fused only.
    with pytest.raises(ValueError, match="single-process"):
        Config(window_size=10, backend=Backend.HYBRID, num_shards=2,
               item_cut=100, fused_window="on")
    # Oracle stays chained-only.
    with pytest.raises(ValueError, match="device or sparse"):
        Config(window_size=10, backend=Backend.ORACLE, fused_window="on")


def test_fused_sparse_emit_updates_auto_degrades_chained():
    """'auto'/'on'+streaming cannot fuse; with auto the scorer quietly
    stays chained (defer-only contract) and results are unchanged."""
    users, items, ts = _ladder_edge_stream()
    kw = dict(user_cut=4, item_cut=500, emit_updates=True)
    REGISTRY.reset()
    chained = _run_sparse(users, items, ts, fused_window="off", **kw)
    auto = _run_sparse(users, items, ts, fused_window="auto", **kw)
    assert _table(chained) == _table(auto)
    assert REGISTRY.gauge("cooc_fused_dispatches_total").get() == 0
