"""The window's stage spans on the profiler clock and its per-window
counts: every StageClock stage is a ``cooc/<stage>`` host event in a
profiler trace, counts reset with the seconds, the journal's core spans
(``index`` included) still partition the window's wall time, and the
scorers count what each window launched and scored."""

import glob
import os

import numpy as np
import pytest

from tpu_cooccurrence.config import Backend, Config
from tpu_cooccurrence.job import CooccurrenceJob
from tpu_cooccurrence.observability import StageClock
from tpu_cooccurrence.observability import journal as jn
from tpu_cooccurrence.observability import trace

BACKENDS = ("device", "sparse")
HOST_STAGES = ("cooc/ingest-admission", "cooc/sample", "cooc/index",
               "cooc/uplink-encode", "cooc/rescore")


def _stream(n=3000, seed=7):
    rng = np.random.default_rng(seed)
    users = rng.integers(0, 50, n).astype(np.int64)
    items = rng.integers(0, 120, n).astype(np.int64)
    ts = np.cumsum(rng.integers(0, 2, n)).astype(np.int64)
    return users, items, ts


def _run(backend, journal=None):
    kw = dict(window_size=100, seed=5, item_cut=30, user_cut=12,
              backend=Backend(backend), journal=journal)
    if backend == "device":
        kw["num_items"] = 128
    job = CooccurrenceJob(Config(**kw))
    users, items, ts = _stream()
    for lo in range(0, len(users), 500):
        job.add_batch(users[lo:lo + 500], items[lo:lo + 500],
                      ts[lo:lo + 500])
    job.finish()
    return job


def test_stage_clock_resets_counts_with_seconds():
    clk = StageClock()
    with clk.stage("index"):
        clk.add("launches")
    clk.add("launches", 2)
    clk.add("score_cells", 64)
    assert clk.counts == {"launches": 3, "score_cells": 64}
    assert clk.seconds["index"] >= 0.0
    with clk.stage("index"):  # re-entry accumulates
        pass
    assert set(clk.seconds) == {"index"}
    clk.reset()
    assert clk.seconds == {} and clk.counts == {}


@pytest.mark.parametrize("backend", BACKENDS)
def test_profiler_trace_holds_the_window_stages(tmp_path, backend):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        _run(backend)
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    names = {e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert set(HOST_STAGES) <= names, sorted(
        n for n in names if n.startswith("cooc/"))


@pytest.mark.parametrize("backend", BACKENDS)
def test_journal_spans_with_index_reconcile(tmp_path, backend):
    path = str(tmp_path / "j.jsonl")
    job = _run(backend, journal=path)
    recs = [r for r in jn.read_records(path) if "seq" in r]
    assert len(recs) == job.windows_fired > 5
    for r in recs:
        jn.validate_record(r)
        assert [s[0] for s in r["spans"]][:len(jn.CORE_STAGES)] == list(
            jn.CORE_STAGES)
    assert trace.reconcile(recs)["ok"]
    # The scorer's own index stage read something on a window with
    # pairs, and the ring carries the same carve as the journal.
    assert any(dict((s[0], s[2]) for s in r["spans"])["index"] > 0
               for r in recs if r["pairs"])
    last = job.step_timer.windows[-1]
    assert set(last.stages) == set(jn.CORE_STAGES)
    assert sum(last.stages.values()) == pytest.approx(last.seconds)


@pytest.mark.parametrize("backend", BACKENDS)
def test_window_counts_cover_the_scored_rows(tmp_path, backend):
    path = str(tmp_path / "j.jsonl")
    job = _run(backend, journal=path)
    scored = [w for w in job.step_timer.windows if w.rows_scored]
    assert scored
    for w in scored:
        c = w.counts
        assert c["launches"] >= 1
        assert c["score_cells"] >= c["live_cells"] > 0
    recs = [r for r in jn.read_records(path) if "seq" in r and r["pairs"]]
    assert all(r["counts"]["launches"] >= 1 for r in recs)


class _LaunchCounter:
    """Counts top-level calls of every jitted callable of the given
    modules (a call made while tracing another program is part of that
    program, not a launch)."""

    def __init__(self, monkeypatch, *modules):
        self.n = 0
        self._depth = 0
        for mod in modules:
            for name, fn in list(vars(mod).items()):
                if callable(fn) and hasattr(fn, "lower"):
                    monkeypatch.setattr(mod, name, self._wrap(fn))

    def _wrap(self, fn):
        def counted(*args, **kwargs):
            self.n += self._depth == 0
            self._depth += 1
            try:
                return fn(*args, **kwargs)
            finally:
                self._depth -= 1
        return counted


def _growing_windows(seed=3, n_win=10):
    """Windows whose vocab and pair counts grow, so the item capacity,
    the heap and the results table grow and rows relocate; large deltas
    push int8 rows over the promotion bound."""
    rng = np.random.default_rng(seed)
    out = []
    for w in range(n_win):
        vocab = 200 + 400 * w
        n = 300 + 200 * w
        src = rng.integers(0, vocab, n).astype(np.int64)
        dst = rng.integers(0, vocab, n).astype(np.int64)
        dst[dst == src] = (dst[dst == src] + 1) % vocab
        out.append((src, dst, rng.integers(1, 40, n).astype(np.int64)))
    return out


@pytest.mark.parametrize("backend,fused", [
    ("device", "off"), ("sparse", "off"), ("sparse", "on")])
def test_launches_count_every_program_of_the_window(monkeypatch, backend,
                                                    fused):
    from tpu_cooccurrence.ops import device_scorer as ds
    from tpu_cooccurrence.sampling.reservoir import PairDeltaBatch
    from tpu_cooccurrence.state import sparse_scorer as ss

    if backend == "device":
        scorer = ds.DeviceScorer(0, 5, defer_results=True)
    else:
        scorer = ss.SparseDeviceScorer(
            5, defer_results=True, capacity=1 << 10, compact_min_heap=256,
            cell_dtype="int8", fused_window=fused)
    counter = _LaunchCounter(monkeypatch, ds, ss)
    grew = False
    for i, (src, dst, delta) in enumerate(_growing_windows()):
        before = counter.n
        scorer.process_window(i, PairDeltaBatch(src=src, dst=dst,
                                                delta=delta))
        launched = counter.n - before
        assert scorer.stage_clock.counts["launches"] == launched, i
        grew |= launched > 2
    assert grew  # the stream exercised the growth programs


needs4 = pytest.mark.skipif(
    len(__import__("jax").devices()) < 4,
    reason="sharded tests need >= 4 (virtual) devices")


@needs4
@pytest.mark.parametrize("fused", ["off", "on"])
def test_sharded_window_counts_and_stages(fused):
    from tpu_cooccurrence.parallel.sharded_sparse import ShardedSparseScorer
    from tpu_cooccurrence.sampling.reservoir import PairDeltaBatch

    scorer = ShardedSparseScorer(5, num_shards=4, defer_results=True,
                                 fused_window=fused)
    saw_fused = False
    for i, (src, dst, delta) in enumerate(_growing_windows(n_win=4)
                                          + _growing_windows(n_win=4)):
        scorer.process_window(i, PairDeltaBatch(src=src, dst=dst,
                                                delta=delta))
        c, secs = scorer.stage_clock.counts, scorer.stage_clock.seconds
        assert c["launches"] >= 1
        assert c["score_cells"] >= c["live_cells"] > 0
        assert {"index", "uplink-encode", "rescore"} <= set(secs)
        if scorer.last_dispatch_fused:
            saw_fused = True
            assert c["launches"] == 1  # one program for the window
    assert saw_fused == (fused == "on")
