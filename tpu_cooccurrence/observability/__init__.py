"""Tracing / profiling / per-window instrumentation.

The reference's observability is wall-clock duration + accumulators
(SURVEY §5: ``FlinkCooccurrences.java:173-181``); Flink's own metrics UI
provides the rest. The TPU build's upgrade: per-window step timing with
stage breakdown (sampling vs scoring), retained as a ring buffer and
summarizable, plus optional XLA profiler traces (``jax.profiler``) for
TensorBoard.

This package is the observability plane (the standalone replacement for
the Flink UI the reference leans on):

* this module — step timing, stage occupancy, the transfer ledger;
* :mod:`.journal` — append-only JSONL flight recorder, one record per
  fired window, crash-survivable;
* :mod:`.registry` — typed gauges and fixed-log-bucket histograms with
  p50/p95/p99 summaries and Prometheus text exposition;
* :mod:`.http` — the live scrape endpoint (``/metrics``, ``/healthz``).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Deque, Dict, Iterator, Optional


@dataclasses.dataclass
class WindowStats:
    timestamp: int
    events: int
    pairs: int
    rows_scored: int
    sample_seconds: float
    score_seconds: float
    #: The window's core span seconds (journal.CORE_STAGES), the same
    #: carve the journal's ``spans`` and ``/healthz`` carry.
    stages: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: The scorer's per-window counts (:attr:`StageClock.counts`).
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.sample_seconds + self.score_seconds

    def as_dict(self) -> Dict[str, object]:
        """JSON-serializable form (journal records, summary logs)."""
        return {
            "timestamp": self.timestamp,
            "events": self.events,
            "pairs": self.pairs,
            "rows_scored": self.rows_scored,
            "sample_seconds": round(self.sample_seconds, 6),
            "score_seconds": round(self.score_seconds, 6),
            "seconds": round(self.seconds, 6),
        }


class StepTimer:
    """Ring buffer of per-window stats with aggregate summary."""

    def __init__(self, keep: int = 1024) -> None:
        self.windows: Deque[WindowStats] = collections.deque(maxlen=keep)
        self.total_windows = 0
        self.total_events = 0
        self.total_pairs = 0
        self.total_sample_seconds = 0.0
        self.total_score_seconds = 0.0

    def record(self, stats: WindowStats) -> None:
        self.windows.append(stats)
        self.total_windows += 1
        self.total_events += stats.events
        self.total_pairs += stats.pairs
        self.total_sample_seconds += stats.sample_seconds
        self.total_score_seconds += stats.score_seconds

    def summary(self) -> Dict[str, float]:
        return {
            "windows": self.total_windows,
            "events": self.total_events,
            "pairs": self.total_pairs,
            "sample_seconds": round(self.total_sample_seconds, 4),
            "score_seconds": round(self.total_score_seconds, 4),
        }

    def slowest(self, n: int = 3) -> list:
        """The n slowest recent windows (ring-buffer scope) — the first place
        to look when a run's step timing regresses."""
        return sorted(self.windows, key=lambda w: -w.seconds)[:n]

    def slowest_as_dicts(self, n: int = 3) -> list:
        """JSON-serializable slowest-``n`` (end-of-run summary log)."""
        return [w.as_dict() for w in self.slowest(n)]

    def occupancy(self, wall_seconds: float) -> Dict[str, float]:
        """Per-stage busy fractions of a run's wall clock.

        The pipeline-overlap diagnostic (pipeline.py): a serial run's
        ``host_busy_pct + score_busy_pct`` sums to at most ~100 (plus
        ingest overhead outside both stages); a pipelined run exceeds
        100 exactly by the overlap won. ``score_busy_pct`` counts the
        scorer stage's thread time (host index/pack work + dispatch +
        result materialization), not raw device occupancy — on an async
        backend the device can be busy past it.
        """
        w = max(wall_seconds, 1e-9)
        return {
            "host_busy_pct": round(100.0 * self.total_sample_seconds / w, 1),
            "score_busy_pct": round(100.0 * self.total_score_seconds / w, 1),
            "wall_seconds": round(wall_seconds, 4),
        }


@dataclasses.dataclass
class TransferEvent:
    direction: str  # "h2d" | "d2h"
    label: str      # call-site tag ("update", "window-meta", ...)
    nbytes: int


class TransferLedger:
    """Host<->device wire-byte accounting (VERDICT r3, Next #3).

    The scorers record every host-constructed buffer they ship up and
    every device buffer they fetch down, at the call site, with a label.
    On a latency-bound link (DCN-attached hosts in general) transfer
    volume IS wall time, so the steady-state contract — a
    deferred sparse window is aggregated-delta uplink only, ZERO
    downlink; a flush fetches dirty rows only — is pinned by CI
    (``tests/test_wire_bytes.py``) against this ledger, and a stray
    blocking fetch or an uplink-size regression fails the build instead
    of silently doubling link wall time.

    Replaces-by-accounting the serialization boundaries the reference
    crosses at every keyBy/broadcast (FlinkCooccurrences.java:89-167).
    One module-level instance (:data:`LEDGER`); events are a bounded
    ring so unbounded streams can't grow host memory.

    Totals are locked (same discipline as ``metrics.Counters``): in
    pipelined execution the sampling thread (checkpoint uplinks) and the
    scorer worker (window dispatches) both record, and the ``+=`` on the
    byte totals is a read-modify-write the GIL does not make atomic.
    ``snapshot()`` returns a consistent (bytes, calls) view taken under
    the same lock — the journal's per-window deltas are exact, never a
    torn read between a bytes and a calls update.
    """

    def __init__(self, keep_events: int = 4096) -> None:
        self.events: Deque[TransferEvent] = collections.deque(
            maxlen=keep_events)
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.h2d_bytes = 0
            self.d2h_bytes = 0
            self.h2d_calls = 0
            self.d2h_calls = 0
            # Compressed-uplink accounting (state/wire.py): for every
            # encoded upload, the bytes the raw layout would have shipped
            # vs the bytes that actually crossed — the compression cut is
            # a first-class bench/journal metric, not a derived guess.
            self.uplink_raw_bytes = 0
            self.uplink_enc_bytes = 0
            # PR-6 BasketBatch packed uplink under its own counter: the
            # fused-vs-chained wire comparison needs basket bytes split
            # out of the generic h2d total they used to fold into.
            self.basket_h2d_bytes = 0
            self.basket_h2d_calls = 0
            self.events.clear()

    def up(self, label: str, *arrays) -> None:
        """Record one host->device upload (all buffers of one dispatch)."""
        n = sum(int(a.nbytes) for a in arrays)
        with self._lock:
            self.h2d_bytes += n
            self.h2d_calls += 1
            self.events.append(TransferEvent("h2d", label, n))

    def up_encoded(self, label: str, raw_nbytes: int, *arrays) -> None:
        """Record one ENCODED host->device upload: ``arrays`` are the
        buffers that actually ship (counted on the h2d totals like any
        upload); ``raw_nbytes`` is what the raw wire format would have
        shipped for the same window, tracked on the raw/encoded pair."""
        n = sum(int(a.nbytes) for a in arrays)
        with self._lock:
            self.h2d_bytes += n
            self.h2d_calls += 1
            self.uplink_raw_bytes += int(raw_nbytes)
            self.uplink_enc_bytes += n
            self.events.append(TransferEvent("h2d", label, n))

    def up_basket(self, label: str, *arrays) -> None:
        """Record one packed BasketBatch upload (--fused-window): rides
        the h2d totals AND its own byte/call pair."""
        n = sum(int(a.nbytes) for a in arrays)
        with self._lock:
            self.h2d_bytes += n
            self.h2d_calls += 1
            self.basket_h2d_bytes += n
            self.basket_h2d_calls += 1
            self.events.append(TransferEvent("h2d", label, n))

    def down(self, label: str, *arrays) -> None:
        """Record one device->host fetch."""
        n = sum(int(a.nbytes) for a in arrays)
        with self._lock:
            self.d2h_bytes += n
            self.d2h_calls += 1
            self.events.append(TransferEvent("d2h", label, n))

    def labels(self, direction: str) -> list:
        with self._lock:
            return [e.label for e in self.events if e.direction == direction]

    def snapshot(self) -> Dict[str, int]:
        """Consistent totals: every (bytes, calls) pair reflects the same
        set of recorded transfers (no torn mid-``up()`` reads)."""
        with self._lock:
            return {"h2d_bytes": self.h2d_bytes, "h2d_calls": self.h2d_calls,
                    "d2h_bytes": self.d2h_bytes, "d2h_calls": self.d2h_calls,
                    "uplink_raw_bytes": self.uplink_raw_bytes,
                    "uplink_enc_bytes": self.uplink_enc_bytes,
                    "basket_h2d_bytes": self.basket_h2d_bytes,
                    "basket_h2d_calls": self.basket_h2d_calls}

    def summary(self) -> Dict[str, int]:
        return self.snapshot()


#: Process-wide ledger the scorers record into.
LEDGER = TransferLedger()


@contextlib.contextmanager
def xla_trace(profile_dir: Optional[str]) -> Iterator[None]:
    """Wrap a run in a ``jax.profiler`` trace when a directory is given."""
    if not profile_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(profile_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class clock:  # noqa: N801 - tiny helper
    """``with clock() as c: ...; c.seconds``"""

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        return False


class StageClock:
    """Per-window stage seconds and counts: the tracing plane's one span
    primitive.

    The scorers :meth:`reset` it at ``process_window`` entry and wrap
    their index, encode/upload and rescore sections with :meth:`stage`;
    the job reads :attr:`seconds` afterwards to carve the window's
    ``score_seconds`` into journal span tuples (the job's own clock
    times ``sample`` and ``ingest-admission`` the same way). Each stage
    is also a ``jax.profiler.TraceAnnotation`` named ``cooc/<stage>``,
    so a profiler trace shows the program's stages on the device
    trace's clock. Re-entering the same stage accumulates (the chained
    path uploads three operand groups under one ``uplink-encode``
    stage); stages do not nest. :meth:`add` keeps per-window integer
    counts (``launches``, ``score_cells``, ``live_cells``) beside the
    seconds. Not thread-safe by design: one thread owns one clock.
    """

    def __init__(self) -> None:
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    def reset(self) -> None:
        self.seconds = {}
        self.counts = {}

    def add(self, name: str, n: int = 1) -> None:
        self.counts[name] = self.counts.get(name, 0) + int(n)

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        # Imported here, not at module top: journal.py and trace.py
        # import this package and must stay jax-free.
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        try:
            with TraceAnnotation("cooc/" + name):
                yield
        finally:
            self.seconds[name] = (self.seconds.get(name, 0.0)
                                  + time.perf_counter() - t0)
