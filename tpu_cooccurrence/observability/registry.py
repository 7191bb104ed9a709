"""Typed metrics registry: gauges + fixed-log-bucket latency histograms.

The reference's metric system is Flink accumulators dumped once at job
end (``FlinkCooccurrences.java:181``); distributions (per-operator
latency, backpressure) live in the Flink UI this standalone build does
not have. This registry is the replacement plane: counters stay in
``metrics.Counters`` (byte-identical reference names), while everything
that needs a *distribution* — per-window sample/score/total seconds,
uplink bytes, pipeline queue wait — lands in histograms here, with
p50/p95/p99 summaries for bench JSON and Prometheus text exposition for
the live scrape endpoint (:mod:`.http`).

Histogram buckets are fixed log-spaced bounds chosen at construction
(never resized), so ``observe`` is O(log B) with zero allocation and two
concurrent recorders (the sampling thread and the scorer worker in
pipelined mode) only contend on a per-instrument lock. Percentiles are
bucket-resolved: the reported pXX is the upper bound of the bucket the
rank falls in — exact enough to see a tail regress by a bucket step
(base 2 by default), which is the decision granularity perf PRs need.

One process-global :data:`REGISTRY` (same pattern as
``observability.LEDGER``); tests and bench reset it between runs.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, List, Optional, Sequence


def log_buckets(lo: float, hi: float, base: float = 2.0) -> List[float]:
    """Log-spaced bucket upper bounds covering ``[lo, hi]``.

    Bounds are exact powers ``base**k`` (no accumulation drift), first
    bound >= ``lo``, last bound >= ``hi``.
    """
    if not (lo > 0 and hi > lo and base > 1):
        raise ValueError(f"bad bucket spec lo={lo} hi={hi} base={base}")
    k = math.floor(math.log(lo, base))
    if base ** k < lo:
        k += 1
    bounds = []
    while True:
        b = base ** k
        bounds.append(b)
        if b >= hi:
            return bounds
        k += 1


#: Default bucket ladders. Seconds: ~61 us .. 64 s (21 buckets) covers a
#: fast CPU window through a stalled dispatch. Bytes: 64 B .. 4 GiB.
SECONDS_BUCKETS = log_buckets(2.0 ** -14, 2.0 ** 6)
BYTES_BUCKETS = log_buckets(2.0 ** 6, 2.0 ** 32)

#: Every ``cooc_*`` gauge/histogram name the process may register or
#: expose, in one place. This is the metric-name registry the static
#: analyzer (``tpu_cooccurrence.analysis``, rule ``metric-name``)
#: enforces: a ``REGISTRY.gauge("cooc_...")`` call site — or a doc
#: quoting a metric — whose name is not listed here fails tier-1, so a
#: typo cannot silently create a parallel series dashboards never see.
#: Add the name here in the same PR that introduces the metric.
CANONICAL_METRICS = frozenset({
    # per-window stage timing / liveness (job.py)
    "cooc_window_sample_seconds",
    "cooc_window_score_seconds",
    "cooc_window_total_seconds",
    "cooc_window_uplink_bytes",
    "cooc_windows_fired",
    "cooc_last_window_unix_seconds",
    # pipelined execution (pipeline.py)
    "cooc_pipeline_queue_wait_seconds",
    "cooc_pipeline_ring_depth",
    # fused one-dispatch window path (--fused-window; the scorers count
    # the dispatches of each path)
    "cooc_fused_dispatches_total",
    "cooc_chained_dispatches_total",
    # fused-sparse shape specialization (state/sparse_scorer.py): how
    # many distinct fused-program shapes (= XLA compiles) the pow2
    # (ops, touched-rows, registry-delta) bucketing produced
    "cooc_fused_bucket_compilations_total",
    # checkpoint plane (state/checkpoint.py)
    "cooc_checkpoint_quarantined_total",
    "cooc_checkpoint_generation",
    # incremental checkpoints + delta log (--checkpoint-incremental,
    # state/checkpoint.py + state/delta.py): per-commit cost and the
    # chain depth behind the newest generation
    "cooc_checkpoint_commit_bytes",
    "cooc_checkpoint_commit_seconds",
    "cooc_checkpoint_delta_chain_len",
    "cooc_checkpoint_compactions_total",
    # gang / epoch-commit plane (state/checkpoint.py epoch markers,
    # robustness/gang.py peer table)
    "cooc_epoch_committed",
    "cooc_checkpoint_partial_total",
    "cooc_gang_stale_peers",
    # load-driven gang autoscaler (robustness/autoscale.py): the
    # topology in force, voluntary rescales performed, and the last
    # gang-wide load signal (-1 idle / 0 neutral / 1 pressure)
    "cooc_gang_target_workers",
    "cooc_gang_rescales_total",
    "cooc_autoscale_level",
    # sharded scorers (parallel/sharded.py)
    "cooc_scorer_dispatch_rows",
    "cooc_shard_row_imbalance",
    # supervisor state relayed into the child (cli.py)
    "cooc_supervisor_restarts",
    "cooc_supervisor_backoff_ms",
    # graceful-degradation plane (robustness/degrade.py, quarantine.py)
    "cooc_degradation_level",
    "cooc_shed_events_total",
    "cooc_quarantined_lines_total",
    "cooc_scorer_breaker_state",
    "cooc_scorer_breaker_trips_total",
    # TransferLedger totals rendered by render_prometheus below
    "cooc_transfer_h2d_bytes_total",
    "cooc_transfer_h2d_calls_total",
    "cooc_transfer_d2h_bytes_total",
    "cooc_transfer_d2h_calls_total",
    # compressed wire format (state/wire.py): encoded-uplink accounting
    # and the BasketBatch packed uplink split out of the generic totals
    "cooc_transfer_uplink_raw_bytes_total",
    "cooc_transfer_uplink_encoded_bytes_total",
    "cooc_transfer_basket_h2d_bytes_total",
    "cooc_transfer_basket_h2d_calls_total",
    # compressed sparse state (state/sparse_scorer.py): host index RSS
    # and device slab footprint, refreshed per window
    "cooc_host_index_rss_bytes",
    "cooc_slab_device_bytes",
    "cooc_slab_live_cells",
    # per-shard breakdown of the two series above (sharded-sparse,
    # parallel/sharded_sparse.py): emitted as <name><shard-id> — the
    # entries here are the f-string prefixes the emission sites use
    "cooc_host_index_rss_bytes_shard",
    "cooc_slab_live_cells_shard",
    # per-shard fused/chained dispatch split (sharded-sparse fused
    # window, parallel/sharded_sparse.py): same <name><shard-id>
    # prefix convention as the RSS gauges above
    "cooc_fused_dispatches_total_shard",
    "cooc_chained_dispatches_total_shard",
    # tiered elastic state (state/store.TieredSlabStore): spill/promote
    # counters and the host arena footprint, refreshed per window
    "cooc_spill_evictions_total",
    "cooc_spill_promotions_total",
    "cooc_spill_resident_rows",
    "cooc_spill_arena_bytes",
    "cooc_spill_row_touches_total",
    # serving plane (serving/, observability/http.py): per-route request
    # latency histograms plus snapshot double-buffer state
    "cooc_query_seconds",
    "cooc_scrape_seconds",
    "cooc_healthz_seconds",
    "cooc_snapshot_generation",
    "cooc_snapshot_swaps_total",
    "cooc_snapshot_built_unix_seconds",
    "cooc_snapshot_rows",
    # degradation plane QUERY_PRESSURE signal (robustness/degrade.py)
    "cooc_query_pressure_events_total",
    # serving fleet read replicas (serving/replica.py): delta-log
    # catch-up position, the lag behind the ingest writer, and the
    # robustness counters behind the lag block on the replica /healthz
    "cooc_replica_generation",
    "cooc_replica_generation_lag",
    "cooc_replica_deltas_applied_total",
    "cooc_replica_resyncs_total",
    # ingest plane (io/partitioned.py offsets committed by
    # state/checkpoint.py): worst per-partition unread bytes at the last
    # fired window, and offset sections committed with the state
    "cooc_ingest_partition_lag",
    "cooc_ingest_offset_commits_total",
})

#: TransferLedger snapshot key -> exposition series name. Explicit
#: literals (not an f-string template) so the analyzer's reverse check
#: can see every canonical transfer name at a real emission site.
TRANSFER_METRICS = {
    "h2d_bytes": "cooc_transfer_h2d_bytes_total",
    "h2d_calls": "cooc_transfer_h2d_calls_total",
    "d2h_bytes": "cooc_transfer_d2h_bytes_total",
    "d2h_calls": "cooc_transfer_d2h_calls_total",
    "uplink_raw_bytes": "cooc_transfer_uplink_raw_bytes_total",
    "uplink_enc_bytes": "cooc_transfer_uplink_encoded_bytes_total",
    "basket_h2d_bytes": "cooc_transfer_basket_h2d_bytes_total",
    "basket_h2d_calls": "cooc_transfer_basket_h2d_calls_total",
}


class Gauge:
    """A single instantaneous value (last write wins)."""

    def __init__(self, name: str, help: str = "") -> None:
        self.name = name
        self.help = help
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def add(self, delta: float) -> None:
        with self._lock:
            self._value += float(delta)

    def get(self) -> float:
        with self._lock:
            return self._value


class Histogram:
    """Fixed-log-bucket histogram with bucket-resolved percentiles.

    ``bounds`` are the finite bucket upper bounds (ascending); an
    implicit +Inf bucket catches overflow. Tracks count/sum/min/max
    exactly; percentiles resolve to a bucket upper bound.
    """

    def __init__(self, name: str, bounds: Sequence[float],
                 help: str = "") -> None:
        if not bounds or any(b2 <= b1 for b1, b2 in zip(bounds, bounds[1:])):
            raise ValueError(f"bucket bounds must ascend, got {bounds!r}")
        self.name = name
        self.help = help
        self.bounds = list(bounds)
        self._counts = [0] * (len(self.bounds) + 1)  # +Inf tail
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self._lock = threading.Lock()

    def _bucket_index(self, value: float) -> int:
        lo, hi = 0, len(self.bounds)
        while lo < hi:
            mid = (lo + hi) // 2
            if value <= self.bounds[mid]:
                hi = mid
            else:
                lo = mid + 1
        return lo  # == len(bounds) -> +Inf bucket

    def observe(self, value: float) -> None:
        value = float(value)
        i = self._bucket_index(value)
        with self._lock:
            self._counts[i] += 1
            self.count += 1
            self.sum += value
            if value < self.min:
                self.min = value
            if value > self.max:
                self.max = value

    def percentile(self, p: float) -> float:
        """Upper bound of the bucket holding the ``p``-quantile rank
        (0 < p <= 100). The max observed caps the +Inf bucket so a pXX
        is never reported as infinity."""
        with self._lock:
            if self.count == 0:
                return 0.0
            rank = math.ceil(self.count * p / 100.0)
            seen = 0
            for i, c in enumerate(self._counts):
                seen += c
                if seen >= rank:
                    if i < len(self.bounds):
                        return min(self.bounds[i], self.max)
                    return self.max
            return self.max  # unreachable; guards float edge cases

    def summary(self) -> Dict[str, float]:
        """JSON-serializable tail summary (bench output, history)."""
        with self._lock:
            if self.count == 0:
                return {"count": 0}
            base = {"count": self.count, "sum": round(self.sum, 6),
                    "min": round(self.min, 6), "max": round(self.max, 6)}
        for p, key in ((50, "p50"), (95, "p95"), (99, "p99")):
            base[key] = round(self.percentile(p), 6)
        return base

    def exposition_snapshot(self) -> "tuple[List[int], float, int]":
        """One locked view of (cumulative bucket counts incl. +Inf, sum,
        count) — the text format requires the +Inf bucket to equal
        ``_count``, so the three must come from the same instant (an
        observe landing between two reads would tear them apart)."""
        with self._lock:
            out, acc = [], 0
            for c in self._counts:
                acc += c
                out.append(acc)
            return out, self.sum, self.count

    def cumulative_counts(self) -> List[int]:
        """Prometheus-style cumulative per-bucket counts (incl. +Inf)."""
        return self.exposition_snapshot()[0]


class MetricsRegistry:
    """Named gauges + histograms, with Prometheus text exposition.

    ``histogram``/``gauge`` are get-or-create (idempotent at a call
    site, so recorders don't need construction-order coordination);
    re-registering a histogram with different bounds is an error.
    """

    def __init__(self) -> None:
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._lock = threading.Lock()

    def reset(self) -> None:
        with self._lock:
            self._gauges.clear()
            self._histograms.clear()

    def gauge(self, name: str, help: str = "") -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name, help)
            return g

    def histogram(self, name: str,
                  bounds: Optional[Sequence[float]] = None,
                  help: str = "") -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram(
                    name, list(bounds) if bounds else SECONDS_BUCKETS, help)
            elif bounds is not None and list(bounds) != h.bounds:
                raise ValueError(
                    f"histogram {name!r} re-registered with different "
                    f"bounds")
            return h

    def summaries(self) -> Dict[str, Dict[str, float]]:
        """All histogram tail summaries (folded into bench JSON)."""
        with self._lock:
            hists = list(self._histograms.values())
        return {h.name: h.summary() for h in hists if h.count}

    # -- Prometheus text exposition (format 0.0.4) ----------------------

    def render_prometheus(self, counters=None, ledger=None) -> str:
        """The ``/metrics`` payload.

        ``counters`` (a ``metrics.Counters``) renders each reference-named
        accumulator as its own counter metric — names are kept
        byte-identical to the reference's (CamelCase is valid Prometheus);
        ``ledger`` (the ``TransferLedger``) renders the wire-byte totals.
        """
        lines: List[str] = []
        with self._lock:
            gauges = sorted(self._gauges.values(), key=lambda g: g.name)
            hists = sorted(self._histograms.values(), key=lambda h: h.name)
        if counters is not None:
            from ..metrics import CANONICAL_COUNTERS

            values = {name: 0 for name in CANONICAL_COUNTERS}
            values.update(counters.as_dict())
            for name, value in sorted(values.items()):
                lines.append(f"# TYPE {name} counter")
                lines.append(f"{name} {value}")
        if ledger is not None:
            snap = ledger.snapshot()
            for key, name in TRANSFER_METRICS.items():
                lines.append(f"# TYPE {name} counter")
                lines.append(f"{name} {snap[key]}")
        for g in gauges:
            if g.help:
                lines.append(f"# HELP {g.name} {g.help}")
            lines.append(f"# TYPE {g.name} gauge")
            lines.append(f"{g.name} {_fmt(g.get())}")
        for h in hists:
            if h.help:
                lines.append(f"# HELP {h.name} {h.help}")
            lines.append(f"# TYPE {h.name} histogram")
            cum, total, count = h.exposition_snapshot()
            for bound, c in zip(h.bounds, cum):
                lines.append(
                    f'{h.name}_bucket{{le="{_fmt(bound)}"}} {c}')
            lines.append(f'{h.name}_bucket{{le="+Inf"}} {cum[-1]}')
            lines.append(f"{h.name}_sum {_fmt(total)}")
            lines.append(f"{h.name}_count {count}")
            # Pre-resolved tail quantiles (bucket upper bounds) as their
            # own gauge families — scrape-side percentile math optional.
            for p, suffix in ((50, "p50"), (95, "p95"), (99, "p99")):
                lines.append(f"# TYPE {h.name}_{suffix} gauge")
                lines.append(f"{h.name}_{suffix} {_fmt(h.percentile(p))}")
        return "\n".join(lines) + "\n"


def _fmt(v: float) -> str:
    """Float rendering without exponent surprises for integral values."""
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


#: Process-wide registry (the scorers and the job record into it);
#: tests / bench reset it between runs.
REGISTRY = MetricsRegistry()
