"""Graceful-degradation plane (ISSUE 5): controller, shedding, breaker.

Contracts under test:

* **Hysteresis** — escalation only after `trip_windows` consecutive
  overloaded windows, de-escalation only after `clear_windows` healthy
  ones, exactly one level per decision (no flapping, no jumps).
* **NORMAL parity** — with the controller installed but never leaving
  NORMAL (and quarantine off), per-window outputs are bit-identical to
  the seed path at pipeline depths 0 and 2.
* **Shedding monotonicity** — tighter cuts never *add* pairs: the
  tighter mask/pair set is a subset of the looser one.
* **Overload soak** — a stream forced into sustained overload completes
  (no deadlock, no watchdog needed), and the journal shows monotone
  one-step level transitions.
* **Quarantine / provenance / breaker / healthz** — the satellite
  fixes, end-to-end through the CLI where the wiring lives.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tpu_cooccurrence.config import Backend, Config
from tpu_cooccurrence.io.synthetic import zipfian_interactions
from tpu_cooccurrence.job import CooccurrenceJob
from tpu_cooccurrence.observability.registry import REGISTRY
from tpu_cooccurrence.robustness import degrade
from tpu_cooccurrence.robustness.degrade import (
    DegradationController,
    DegradationLevel,
    LEVEL_EVENTS,
    TRANSITION_RULES,
    ScorerCircuitBreaker,
)

from test_cli import write_stream

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


@pytest.fixture(autouse=True)
def _clean_plane():
    """No controller or metrics may leak between tests."""
    REGISTRY.reset()
    degrade.uninstall()
    yield
    degrade.uninstall()
    REGISTRY.reset()


# ---------------------------------------------------------------------------
# controller state machine


def _controller(**kw):
    kw.setdefault("window_wall_s", 1.0)
    kw.setdefault("trip_windows", 3)
    kw.setdefault("clear_windows", 4)
    kw.setdefault("pause_ms", 0)
    return DegradationController(**kw)


def test_escalation_needs_consecutive_overload():
    c = _controller(trip_windows=3)
    # Two bad, one good, two bad, ... never three in a row -> NORMAL.
    for _ in range(5):
        c.observe_window(2.0)
        c.observe_window(2.0)
        c.observe_window(0.01)
    assert c.level == DegradationLevel.NORMAL


def test_escalates_one_level_per_trip_and_caps_at_pause():
    c = _controller(trip_windows=2)
    seen = []
    for _ in range(20):
        level, events = c.observe_window(2.0)
        seen.append(level)
    assert c.level == DegradationLevel.PAUSE_INGEST
    # Monotone, one step at a time.
    for a, b in zip(seen, seen[1:]):
        assert b - a in (0, 1)


def test_deescalation_needs_clear_windows_and_steps_down():
    c = _controller(trip_windows=1, clear_windows=3)
    c.observe_window(2.0)
    assert c.level == DegradationLevel.SHED_SAMPLING
    c.observe_window(0.01)
    c.observe_window(0.01)
    assert c.level == DegradationLevel.SHED_SAMPLING  # not yet
    _, events = c.observe_window(0.01)
    assert c.level == DegradationLevel.NORMAL
    assert events == [LEVEL_EVENTS["NORMAL"]]


def test_ring_saturation_and_stall_count_as_overload():
    c = _controller(trip_windows=1)
    c.observe_window(0.01, ring_depth=2, ring_capacity=2)
    assert c.level == DegradationLevel.SHED_SAMPLING
    c2 = _controller(trip_windows=1)
    c2.observe_window(0.01, stall_seconds=0.9)
    assert c2.level == DegradationLevel.SHED_SAMPLING


def test_queue_wait_marks_next_window_overloaded():
    c = _controller(trip_windows=1)
    c.note_queue_wait(0.9)
    c.observe_window(0.01)
    assert c.level == DegradationLevel.SHED_SAMPLING


def test_effective_knobs_identity_at_normal_and_monotone_by_level():
    c = _controller()
    assert c.effective_item_cut(500) == 500
    assert c.effective_user_cut(500) == 500
    assert c.effective_top_k(10) == 10
    prev_cut, prev_k = 500, 10
    for _ in range(3):  # walk up the ladder
        for _ in range(c.trip_windows):
            c.observe_window(2.0)
        assert c.effective_item_cut(500) <= prev_cut
        assert c.effective_top_k(10) <= prev_k
        prev_cut, prev_k = c.effective_item_cut(500), c.effective_top_k(10)
    assert c.level == DegradationLevel.PAUSE_INGEST
    assert c.effective_item_cut(500) == 500 // 4
    assert c.effective_top_k(10) == 5
    assert c.effective_item_cut(1) == 1  # never below 1


def test_pause_ingest_admission_is_bounded_not_a_stall():
    c = _controller(trip_windows=1, pause_ms=10)
    c.observe_window(2.0)
    c.observe_window(2.0)
    c.observe_window(2.0)
    assert c.level == DegradationLevel.PAUSE_INGEST
    # admit() returns (bounded delay), it does not block until recovery.
    assert c.admit() == pytest.approx(0.01)
    assert c.admit() == pytest.approx(0.01)


def test_stale_ingest_escalates_once_per_period(monkeypatch):
    c = _controller(stale_after_s=10.0)
    t = [1000.0]
    monkeypatch.setattr(degrade.time, "monotonic", lambda: t[0])
    c.observe_window(0.01)  # a window completed at t=1000
    t[0] += 11.0
    c.admit()
    assert c.level == DegradationLevel.SHED_SAMPLING
    c.admit()  # same stale period: no second step
    assert c.level == DegradationLevel.SHED_SAMPLING
    t[0] += 11.0
    c.admit()
    assert c.level == DegradationLevel.SHED_K


def test_stale_escalation_event_journaled_on_next_window(monkeypatch):
    """An admission-side (stale-ingest) transition must not vanish from
    the journal: its event token is drained into the NEXT observed
    window's record."""
    c = _controller(stale_after_s=10.0)
    t = [1000.0]
    monkeypatch.setattr(degrade.time, "monotonic", lambda: t[0])
    c.observe_window(0.01)
    t[0] += 11.0
    c.admit()  # escalates on the ingest thread, no window record yet
    assert c.level == DegradationLevel.SHED_SAMPLING
    level, events = c.observe_window(0.01)
    assert level == int(DegradationLevel.SHED_SAMPLING)
    assert events == [LEVEL_EVENTS["SHED_SAMPLING"]]
    _, events = c.observe_window(0.01)
    assert events == []  # drained exactly once


def test_stale_gate_covers_first_dispatch_wedge(monkeypatch):
    """A scorer that wedges before the FIRST window completes must
    still trip the stale gate — staleness is measured from controller
    construction until a window lands."""
    t = [1000.0]
    monkeypatch.setattr(degrade.time, "monotonic", lambda: t[0])
    c = _controller(stale_after_s=10.0)
    c.admit()
    assert c.level == DegradationLevel.NORMAL  # within warm-up
    t[0] += 11.0
    c.admit()  # no window EVER completed; ingest still arriving
    assert c.level == DegradationLevel.SHED_SAMPLING


def test_every_level_has_rule_and_event():
    for member in DegradationLevel:
        assert member.name in TRANSITION_RULES
        assert member.name in LEVEL_EVENTS
    assert len(set(LEVEL_EVENTS.values())) == len(LEVEL_EVENTS)


# ---------------------------------------------------------------------------
# shedding monotonicity: tighter cuts never ADD pairs


def test_item_cut_mask_monotone_under_tighter_cut():
    from tpu_cooccurrence.sampling.item_cut import ItemInteractionCut

    rng = np.random.default_rng(0)
    items = rng.integers(0, 30, 500)
    loose = ItemInteractionCut(8, capacity=64)
    tight = ItemInteractionCut(8, capacity=64)
    tight.set_effective_cut(3)
    m_loose = loose.fire(items)
    m_tight = tight.fire(items)
    # Pointwise: sampled under the tighter cut => sampled under the looser.
    assert not np.any(m_tight & ~m_loose)
    assert m_tight.sum() < m_loose.sum()


def test_sliding_sampler_pairs_subset_under_tighter_cuts():
    from tpu_cooccurrence.sampling.sliding import SlidingBasketSampler

    rng = np.random.default_rng(1)
    users = rng.integers(0, 12, 400).astype(np.int64)
    items = rng.integers(0, 40, 400).astype(np.int64)

    def pair_multiset(item_cut, user_cut):
        s = SlidingBasketSampler(8, 6, skip_cuts=False)
        s.set_effective_cuts(item_cut, user_cut)
        out = s.fire(users, items)
        from collections import Counter

        return Counter(zip(out.src.tolist(), out.dst.tolist()))

    loose = pair_multiset(8, 6)
    for cuts in [(4, 6), (8, 3), (4, 3), (2, 2)]:
        tight = pair_multiset(*cuts)
        assert all(tight[p] <= loose[p] for p in tight), cuts


def test_effective_cut_clamps_to_configured_and_floor():
    from tpu_cooccurrence.sampling.item_cut import ItemInteractionCut

    cut = ItemInteractionCut(10, capacity=16)
    cut.set_effective_cut(999)
    assert cut.effective_cut == 10  # tighten-only
    cut.set_effective_cut(0)
    assert cut.effective_cut == 1  # never zero


def test_topk_batch_truncated_and_rescorer_knob():
    from tpu_cooccurrence.state.rescorer import HostRescorer
    from tpu_cooccurrence.state.results import TopKBatch

    b = TopKBatch(np.arange(3, dtype=np.int32),
                  np.arange(12, dtype=np.int32).reshape(3, 4),
                  np.linspace(4, 1, 12, dtype=np.float32).reshape(3, 4))
    t = b.truncated(2)
    assert t.idx.shape == (3, 2) and t.vals.shape == (3, 2)
    assert b.truncated(4) is b  # identity when wide enough
    r = HostRescorer(10)
    r.set_effective_top_k(3)
    assert r.effective_top_k == 3
    r.set_effective_top_k(99)
    assert r.effective_top_k == 10  # tighten-only


# ---------------------------------------------------------------------------
# NORMAL parity: controller installed, never leaves NORMAL -> bit-identical


def _run_job(users, items, ts, depth, backend="oracle", **cfg_kw):
    REGISTRY.reset()
    degrade.uninstall()
    cfg = Config(window_size=100, seed=7, item_cut=50, user_cut=50,
                 backend=Backend(backend), pipeline_depth=depth, **cfg_kw)
    job = CooccurrenceJob(cfg)
    emitted = []
    job.on_update = lambda out: emitted.append(
        [(int(r), None) for r in out.rows] if hasattr(out, "rows")
        else [(i, tuple(top)) for i, top in out])
    for lo in range(0, len(users), 997):
        job.add_batch(users[lo:lo + 997], items[lo:lo + 997],
                      ts[lo:lo + 997])
    job.finish()
    return job, emitted


@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("backend", ["oracle", "sparse"])
def test_normal_parity_bit_identical(depth, backend):
    users, items, ts = zipfian_interactions(
        8000, n_items=300, n_users=120, alpha=1.1, seed=3, events_per_ms=40)
    seed_job, seed_em = _run_job(users, items, ts, depth, backend)
    norm_job, norm_em = _run_job(users, items, ts, depth, backend,
                                 degrade=True,
                                 degrade_window_wall_s=1e9,
                                 degrade_stale_after_s=1e9)
    assert seed_job.counters.as_dict() == norm_job.counters.as_dict()
    assert seed_job.windows_fired == norm_job.windows_fired
    assert set(seed_job.latest) == set(norm_job.latest)
    for item in seed_job.latest:
        assert seed_job.latest[item] == norm_job.latest[item], item
    assert seed_em == norm_em


# ---------------------------------------------------------------------------
# overload soak: sheds, survives, journals monotone transitions


def test_overload_soak_completes_and_journal_levels_monotone(tmp_path):
    """A stream forced into sustained overload (wall threshold below any
    real window) must escalate with hysteresis, keep completing windows
    (bounded admission — no deadlock), and journal every level step."""
    users, items, ts = zipfian_interactions(
        12000, n_items=300, n_users=120, alpha=1.1, seed=5,
        events_per_ms=5)
    jpath = tmp_path / "journal.jsonl"
    job, _ = _run_job(users, items, ts, 2, "oracle",
                      degrade=True,
                      degrade_window_wall_s=1e-9,  # every window overloaded
                      degrade_trip_windows=2,
                      degrade_pause_ms=1,
                      journal=str(jpath))
    assert job.windows_fired > 10
    from tpu_cooccurrence.observability.journal import read_records

    recs = list(read_records(str(jpath)))
    levels = [r["degradation_level"] for r in recs]
    assert levels[-1] == int(DegradationLevel.PAUSE_INGEST)
    # Monotone one-step escalation, never a jump, never a dip (the
    # overload is sustained, so nothing should de-escalate).
    for a, b in zip(levels, levels[1:]):
        assert b - a in (0, 1), levels
    # Hysteresis: at least trip_windows records between distinct levels.
    changes = [i for i, (a, b) in enumerate(zip(levels, levels[1:]))
               if b != a]
    for c1, c2 in zip(changes, changes[1:]):
        assert c2 - c1 >= 2
    # Transition events journaled exactly where the level steps.
    for i in changes:
        assert recs[i + 1].get("degrade_events"), recs[i + 1]
    assert int(REGISTRY.gauge("cooc_shed_events_total").get()) > 0
    # Shedding really tightened the applied cut.
    assert job.item_cut.effective_cut < job.config.item_cut


# ---------------------------------------------------------------------------
# scorer circuit breaker (unit; the CLI chaos case lives in test_chaos.py)


class _FlakyScorer:
    accepts_aggregated = True

    def __init__(self, fail_windows):
        self.fail_windows = set(fail_windows)
        self.calls = 0
        self.last_dispatched_rows = 0

    def process_window(self, ts, pairs):
        self.calls += 1
        if self.calls in self.fail_windows:
            raise RuntimeError(f"injected dispatch failure {self.calls}")
        return [(1, [(2, 1.0)])]

    def flush(self):
        return []


def _pairs():
    from tpu_cooccurrence.sampling.reservoir import PairDeltaBatch

    return PairDeltaBatch(np.array([1]), np.array([2]),
                          np.array([1], dtype=np.int32))


def test_breaker_trips_after_threshold_and_probes_back():
    b = ScorerCircuitBreaker(_FlakyScorer({2, 3}), top_k=5,
                             threshold=2, probe_after_windows=2)
    assert b.process_window(0, _pairs()) and b.breaker_state == "closed"
    b.process_window(1, _pairs())          # failure 1: still closed
    assert b.breaker_state == "closed"
    b.process_window(2, _pairs())          # failure 2: trip
    assert b.breaker_state == "open" and b.trips == 1
    b.process_window(3, _pairs())          # open: fallback, primary idle
    assert b.primary.calls == 3
    b.process_window(4, _pairs())          # half-open probe succeeds
    assert b.breaker_state == "closed"
    assert int(REGISTRY.gauge("cooc_scorer_breaker_trips_total").get()) == 1


def test_breaker_failed_probe_reopens():
    b = ScorerCircuitBreaker(_FlakyScorer({1, 2}), top_k=5,
                             threshold=1, probe_after_windows=2)
    b.process_window(0, _pairs())   # primary call 1 fails -> trip
    assert b.breaker_state == "open"
    b.process_window(1, _pairs())   # open: fallback (primary idle)
    b.process_window(2, _pairs())   # half-open probe (primary call 2)
    assert b.breaker_state == "open" and b.trips == 2


def test_breaker_every_window_scored_on_fallback():
    """No window's pairs are dropped: failures route to the fallback,
    which accumulates its own exact state."""
    b = ScorerCircuitBreaker(_FlakyScorer(range(1, 100)), top_k=5,
                             threshold=1, probe_after_windows=1000)
    outs = [b.process_window(i, _pairs()) for i in range(6)]
    assert all(len(o) == 1 for o in outs)
    # Fallback is the exact host rescorer and saw every delta.
    assert b._fallback.observed == 6


def test_breaker_flush_keeps_fallback_rows_authoritative():
    """Once tripped, the primary's (stale) flush must not overwrite
    items the fallback has since scored — its rows are filtered out of
    the final absorption; items only the primary saw still flow."""
    from tpu_cooccurrence.state.results import TopKBatch

    class DeferredPrimary(_FlakyScorer):
        def flush(self):
            # Stale device table covering items 1 and 9.
            return TopKBatch(np.array([1, 9], np.int32),
                             np.zeros((2, 3), np.int32),
                             np.ones((2, 3), np.float32))

    b = ScorerCircuitBreaker(DeferredPrimary(range(1, 100)), top_k=3,
                             threshold=1, probe_after_windows=1000)
    b.process_window(0, _pairs())  # trip; fallback scores item 1
    assert b.breaker_state == "open" and 1 in b._fallback_owned
    flushed = b.flush()
    assert flushed.rows.tolist() == [9]  # item 1 belongs to the fallback

    # Recovery reclaims ownership: the primary re-scoring item 1 makes
    # its table authoritative again, so the flush emits both rows.
    b3 = ScorerCircuitBreaker(DeferredPrimary({1}), top_k=3,
                              threshold=1, probe_after_windows=1)
    b3.process_window(0, _pairs())  # call 1 fails -> trip, fallback owns 1
    b3.process_window(1, _pairs())  # half-open probe: call 2 re-scores 1
    assert b3.breaker_state == "closed" and not b3._fallback_owned
    assert b3.flush().rows.tolist() == [1, 9]

    class FailingFlushPrimary(DeferredPrimary):
        def flush(self):
            raise RuntimeError("device gone")

    b2 = ScorerCircuitBreaker(FailingFlushPrimary(range(1, 100)), top_k=3,
                              threshold=1, probe_after_windows=1000)
    b2.process_window(0, _pairs())
    assert b2.flush() == []  # dropped, not raised — run completes


def test_admission_side_transition_written_as_journal_event(
        tmp_path, monkeypatch):
    """With a journal attached, a stale-ingest escalation reaches disk
    immediately as an out-of-band event record — even though no window
    ever completes again (the exact scenario the path exists for)."""
    from tpu_cooccurrence.observability.journal import (
        RunJournal, read_records, validate_record)

    jpath = tmp_path / "j.jsonl"
    journal = RunJournal(str(jpath))
    c = _controller(stale_after_s=10.0)
    import time as _time

    c.journal_event = lambda event: journal.record(
        {"v": 1, "event": event, "wall_unix": round(_time.time(), 3)})
    t = [1000.0]
    monkeypatch.setattr(degrade.time, "monotonic", lambda: t[0])
    c.observe_window(0.01)
    t[0] += 11.0
    c.admit()  # escalates; no further window will ever be observed
    journal.close()
    recs = list(read_records(str(jpath)))
    assert len(recs) == 1
    validate_record(recs[0])
    assert recs[0]["event"] == LEVEL_EVENTS["SHED_SAMPLING"]
    # And it is NOT double-journaled by a later window drain.
    _, events = c.observe_window(0.01)
    assert events == []


def test_breaker_delegates_to_primary_attributes():
    class P(_FlakyScorer):
        defer_results = True
        custom_knob = 42

    b = ScorerCircuitBreaker(P(()), top_k=5)
    assert b.defer_results is True and b.custom_knob == 42
    assert b.accepts_aggregated is True


# (test_degrade_rejected_on_multihost was retired by ISSUE 10: the
# blanket multi-host rejection became the per-window worst-signal
# allgather — see test_multihost_degrade_config_now_accepted below and
# the gang chaos lockstep test in test_gang_chaos.py.)


def test_breaker_config_validation():
    with pytest.raises(ValueError, match="oracle backend IS"):
        Config(window_size=10, backend=Backend.ORACLE,
               scorer_breaker_threshold=1)
    with pytest.raises(ValueError, match="single-process"):
        Config(window_size=10, backend=Backend.SPARSE, num_shards=2,
               scorer_breaker_threshold=1)
    job = CooccurrenceJob(Config(window_size=10, backend=Backend.SPARSE,
                                 scorer_breaker_threshold=2, seed=1))
    assert isinstance(job.scorer, ScorerCircuitBreaker)


# ---------------------------------------------------------------------------
# parse provenance + quarantine through the CLI (the wiring under test)


def test_cli_parse_error_names_path_and_line(tmp_path):
    f = tmp_path / "in.csv"
    f.write_text("1,100,5\n2,101,6\nPOISONED-LINE\n3,102,7\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.cli", "-i", str(f),
         "-ws", "10", "--backend", "oracle"],
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=300)
    assert proc.returncode != 0
    assert f"{f}:3" in proc.stderr
    assert "POISONED-LINE" in proc.stderr


def test_cli_quarantine_diverts_and_run_completes(tmp_path):
    f = tmp_path / "in.csv"
    write_stream(f, n=400)
    lines = f.read_text().splitlines()
    lines.insert(100, "garbage,line")
    lines.insert(200, "1,2,3,4,5")
    f.write_text("\n".join(lines) + "\n")
    dead = tmp_path / "dead.jsonl"
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.cli", "-i", str(f),
         "-ws", "40", "-ic", "8", "-uc", "5", "-s", "0xC0FFEE",
         "--backend", "oracle", "--quarantine-file", str(dead),
         "--max-quarantine-rate", "0.5"],
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=300)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert proc.stdout  # results still emitted
    recs = [json.loads(l) for l in dead.read_text().splitlines()]
    assert len(recs) == 2
    assert recs[0]["path"] == str(f) and recs[0]["lineno"] == 101
    assert recs[0]["raw"] == "garbage,line"
    assert recs[1]["lineno"] == 201


def test_cli_quarantine_rate_breaker_exits_2_even_for_short_input(tmp_path):
    """The min_lines warm-up only defers the MID-stream trip; the
    end-of-stream check applies the pure rate, so a short fully-garbage
    input exits 2 instead of 'succeeding' with zero output."""
    f = tmp_path / "in.csv"
    f.write_text("\n".join("junk-%d" % i for i in range(300)) + "\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.cli", "-i", str(f),
         "-ws", "10", "--backend", "oracle",
         "--quarantine-file", str(tmp_path / "dead.jsonl"),
         "--max-quarantine-rate", "0.01"],
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=300)
    assert proc.returncode == 2
    assert "quarantine rate breaker tripped" in proc.stderr


def test_quarantine_check_final_waives_warmup_but_respects_rate():
    import tempfile

    from tpu_cooccurrence.robustness.quarantine import (
        Quarantine, QuarantineRateExceeded)

    d = tempfile.mkdtemp()
    q = Quarantine(os.path.join(d, "dead.jsonl"), max_rate=0.5)
    q.note_lines(10)
    for i in range(3):  # 30% < 50%: under the rate, final check passes
        q.quarantine("f", i, "junk", "bad")
    q.check_final()
    q2 = Quarantine(os.path.join(d, "dead2.jsonl"), max_rate=0.1)
    q2.note_lines(10)
    for i in range(3):  # 30% > 10%, but seen < min_lines: no mid-trip
        q2.quarantine("f", i, "junk", "bad")
    with pytest.raises(QuarantineRateExceeded):
        q2.check_final()


def test_cli_quarantine_rate_breaker_exits_2(tmp_path):
    f = tmp_path / "in.csv"
    f.write_text("\n".join("junk-%d" % i for i in range(2000)) + "\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.cli", "-i", str(f),
         "-ws", "10", "--backend", "oracle",
         "--quarantine-file", str(tmp_path / "dead.jsonl"),
         "--max-quarantine-rate", "0.01"],
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=300)
    assert proc.returncode == 2
    assert "quarantine rate breaker tripped" in proc.stderr


# ---------------------------------------------------------------------------
# /healthz degradation fields (satellite: paused must not read healthy)


def test_healthz_reports_level_and_refuses_healthy_while_paused():
    from tpu_cooccurrence.observability.http import MetricsServer
    from tpu_cooccurrence.observability.registry import MetricsRegistry

    reg = MetricsRegistry()
    srv = MetricsServer(reg, stale_after_s=300.0)
    payload, healthy = srv.health()
    assert healthy and payload["degradation_level"] == 0
    assert payload["quarantined_total"] == 0
    reg.gauge("cooc_last_window_unix_seconds").set(__import__("time").time())
    reg.gauge("cooc_quarantined_lines_total").set(7)
    reg.gauge("cooc_degradation_level").set(
        int(DegradationLevel.PAUSE_INGEST))
    payload, healthy = srv.health()
    assert not healthy and payload["status"] == "paused"
    assert payload["degradation_level"] == 3
    assert payload["quarantined_total"] == 7
    # De-escalated: healthy again (window is recent).
    reg.gauge("cooc_degradation_level").set(int(DegradationLevel.SHED_K))
    payload, healthy = srv.health()
    assert healthy and payload["status"] == "ok"
    srv.stop()


def test_config_degrade_validation():
    with pytest.raises(ValueError, match="shed-factor"):
        Config(window_size=10, degrade_shed_factor=1)
    with pytest.raises(ValueError, match="quarantine-rate"):
        Config(window_size=10, max_quarantine_rate=0.0)
    with pytest.raises(ValueError, match="trip-windows"):
        Config(window_size=10, degrade_trip_windows=0)


# -- dead-letter rotation (--max-quarantine-bytes, ISSUE-10 satellite) --


def test_quarantine_rotation_caps_active_file(tmp_path):
    import json as _json

    from tpu_cooccurrence.robustness.quarantine import (
        QUARANTINE_BACKUPS, Quarantine)

    path = str(tmp_path / "dead.jsonl")
    q = Quarantine(path, max_rate=1.0, max_bytes=400)
    q.note_lines(10_000)
    for i in range(40):
        q.quarantine("in.csv", i + 1, "x" * 40, "bad line")
    q.close()
    assert q.rotations > 0
    # Active file stays under the cap; numbered backups exist and are
    # bounded by the keep window.
    assert os.path.getsize(path) <= 400
    backups = sorted(p.name for p in tmp_path.iterdir()
                     if p.name.startswith("dead.jsonl."))
    assert backups and len(backups) <= QUARANTINE_BACKUPS
    # Every surviving line is still intact JSONL (rotation never tears
    # a record), and the run-total counter survived the rotations.
    kept = 0
    for p in [path] + [str(tmp_path / b) for b in backups]:
        with open(p) as f:
            for line in f:
                _json.loads(line)
                kept += 1
    assert q.quarantined == 40 and kept <= 40


def test_quarantine_rotation_shifts_backups_and_drops_oldest(tmp_path):
    from tpu_cooccurrence.robustness.quarantine import (
        QUARANTINE_BACKUPS, Quarantine)

    path = str(tmp_path / "dead.jsonl")
    q = Quarantine(path, max_rate=1.0, max_bytes=150)
    q.note_lines(100_000)
    for i in range(60):
        q.quarantine("in.csv", i + 1, "y" * 30, "bad")
    q.close()
    assert q.rotations > QUARANTINE_BACKUPS  # oldest really dropped
    assert not os.path.exists(f"{path}.{QUARANTINE_BACKUPS + 1}")


def test_quarantine_unbounded_without_cap(tmp_path):
    from tpu_cooccurrence.robustness.quarantine import Quarantine

    path = str(tmp_path / "dead.jsonl")
    q = Quarantine(path, max_rate=1.0)
    q.note_lines(10_000)
    for i in range(50):
        q.quarantine("in.csv", i + 1, "z" * 40, "bad")
    q.close()
    assert q.rotations == 0
    assert not os.path.exists(path + ".1")


def test_max_quarantine_bytes_validation():
    from tpu_cooccurrence.config import Config
    from tpu_cooccurrence.robustness.quarantine import Quarantine

    with pytest.raises(ValueError, match="max-quarantine-bytes"):
        Config(window_size=10, max_quarantine_bytes=-1)
    with pytest.raises(ValueError, match="max_bytes"):
        Quarantine("/tmp/x.jsonl", max_bytes=-5)


# -- multi-host worst-signal exchange (ISSUE-10 degrade plane) ---------


def test_exchange_vote_drives_ladder_from_peer_signal():
    """A host whose OWN windows are healthy must still escalate when a
    peer votes overloaded — the exchange returns the gang max."""
    c = DegradationController(window_wall_s=1.0, trip_windows=2,
                              clear_windows=2)
    votes = []

    def exchange(local):
        votes.append(local)
        return 1  # a peer is drowning

    c.exchange = exchange
    for _ in range(2):
        level, _ = c.observe_window(wall_seconds=0.001)
    assert level == int(DegradationLevel.SHED_SAMPLING)
    assert votes == [0, 0]  # this host's local signal stayed healthy


def test_exchange_vote_clears_when_gang_healthy():
    c = DegradationController(window_wall_s=1.0, trip_windows=1,
                              clear_windows=2)
    c.exchange = lambda local: local  # single-host gang: identity
    c.observe_window(wall_seconds=9.0)  # trip
    assert c.level == DegradationLevel.SHED_SAMPLING
    c.observe_window(wall_seconds=0.001)
    level, _ = c.observe_window(wall_seconds=0.001)
    assert level == int(DegradationLevel.NORMAL)


def test_exchange_disables_admission_side_stale_escalation():
    """Wall-clock staleness is per-host-nondeterministic: with an
    exchange attached the admit() gate must never move the ladder."""
    c = DegradationController(window_wall_s=1.0, trip_windows=3,
                              stale_after_s=0.001)
    c.exchange = lambda local: local
    c._started_monotonic -= 100.0  # way past stale
    c.admit()
    assert c.level == DegradationLevel.NORMAL
    # Control: without the exchange the same state escalates.
    c2 = DegradationController(window_wall_s=1.0, trip_windows=3,
                               stale_after_s=0.001)
    c2._started_monotonic -= 100.0
    c2.admit()
    assert c2.level == DegradationLevel.SHED_SAMPLING


def test_multihost_degrade_config_now_accepted():
    """The PR-5 blanket rejection is gone: --degrade rides multi-host
    at depth 0; pipelined multi-host degrade is still rejected (the
    vote would race the sampling thread)."""
    from tpu_cooccurrence.config import Config

    Config(window_size=10, degrade=True, coordinator="h:1",
           num_processes=2, process_id=0)
    with pytest.raises(ValueError, match="pipeline-depth 0"):
        Config(window_size=10, degrade=True, coordinator="h:1",
               num_processes=2, process_id=0, pipeline_depth=1)
