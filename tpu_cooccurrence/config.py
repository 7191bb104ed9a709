"""Run configuration.

Mirrors every flag of the reference CLI (reference:
``Configuration.java:56-199``) plus TPU-framework extensions (backend
selection, device-matrix sizing, sharding, sliding windows, checkpointing).

Defaults match the reference exactly: item cut 500, user cut 500, top-k 10,
window unit milliseconds, buffer timeout 100 ms, seed from the clock
(``Configuration.java:151-182``).
"""

from __future__ import annotations

import argparse
import dataclasses
import enum
import sys
import time
from typing import List, Optional, Sequence

from . import tuning


class WindowUnit(enum.Enum):
    """Time unit for window sizes (reference: ``Configuration.java:157-179``)."""

    MILLISECONDS = 1
    SECONDS = 1_000
    MINUTES = 60_000
    HOURS = 3_600_000
    DAYS = 86_400_000

    @property
    def millis(self) -> int:
        return self.value

    @classmethod
    def parse(cls, s: str) -> "WindowUnit":
        try:
            return cls[s.upper()]
        except KeyError:
            raise ValueError(f"Unrecognized window unit {s}") from None


class Backend(enum.Enum):
    """Execution backend for the scoring/aggregation path.

    ``ORACLE`` is the pure-Python/NumPy reference implementation (float64,
    dict-based state) used as the correctness oracle; ``DEVICE`` is the
    JAX/XLA path (CPU or TPU depending on available devices); ``SHARDED``
    is the multi-chip ``shard_map`` path over a device mesh.
    """

    ORACLE = "oracle"
    DEVICE = "device"
    SHARDED = "sharded"
    HYBRID = "hybrid"  # RETIRED (round 3): alias for SPARSE, which beat it
    # 2.2x on its flagship config and covers the same vocab range;
    # checkpoints are interchangeable so old flags/state keep working
    SPARSE = "sparse"  # device-resident sparse slab, host index (big vocab,
    # minimal host<->device transfer — see state/sparse_scorer.py)


def _parse_seed(value: str) -> int:
    """Parse a decimal or ``0x``-prefixed hex seed (``Configuration.java:211-220``)."""
    if value.startswith("0x") or value.startswith("0X"):
        return int(value[2:], 16)
    return int(value)


@dataclasses.dataclass
class Config:
    """Configuration of a co-occurrence run.

    Reference parity (``Configuration.java``):
      input, skip_cuts, item_cut (fMax), user_cut (kMax), top_k,
      window_size/window_unit, seed (hex-capable), buffer_timeout.
    """

    input: Optional[str] = None
    skip_cuts: bool = False
    item_cut: int = 500
    user_cut: int = 500
    top_k: int = 10
    window_size: int = 0
    window_unit: WindowUnit = WindowUnit.MILLISECONDS
    seed: Optional[int] = None
    buffer_timeout: int = 100  # ms a parsed line may wait in a partial
    # batch when tailing continuously (reference: record flush bound,
    # FlinkCooccurrences.java:46); no-op in process-once runs
    source_format: str = "files"  # ingest source shape: "files" = the
    # reference's file-monitor tail (io/source.py); "partitioned" = the
    # append-only partitioned log (io/partitioned.py: part-* files,
    # Kafka shape without the dependency) whose per-partition offsets
    # commit atomically with the checkpoint under the epoch protocol —
    # exactly-once from the wire up
    ingest_partitions: int = 0  # expected part-* file count with
    # --source-format partitioned: pins the partition/offset contract up
    # front (a drifted directory fails fast, like a Kafka topic changing
    # partition count under a consumer group); 0 = derive from the
    # directory at first listing

    # --- TPU-framework extensions (no reference analogue) ---
    backend: Backend = Backend.DEVICE
    num_items: int = 0  # dense device vocab capacity; 0 = derive from the
    # data (the device backend doubles its C on vocab growth; the sharded
    # backend doubles-with-reshard the same way, except multi-host runs,
    # which still need an explicit capacity agreed across processes)
    num_shards: int = 1  # item-axis shards over the device mesh
    window_slide: Optional[int] = None  # sliding windows; None = tumbling
    max_pairs_per_step: int = 1 << 20  # COO padding bucket (recompile guard)
    # (--sample-workers was RETIRED in round 3 and fully removed in PR 8:
    # passing it now raises a clear "retired" error in from_args —
    # --partition-sampling is the ingest scale-out axis.)
    checkpoint_dir: Optional[str] = None
    checkpoint_every_windows: int = 0  # 0 = disabled
    checkpoint_retain: int = 3  # generation-numbered checkpoints kept
    # (state.<gen>.npz; restore falls back to the newest generation that
    # verifies its digest, quarantining corrupt ones as *.corrupt).
    # Chain-aware under --checkpoint-incremental: a base or intermediate
    # delta a retained generation still chains through is never deleted.
    checkpoint_incremental: bool = False  # dirty-row incremental
    # generations (state/delta.py): a full base plus per-generation
    # delta.<gen>.bin files holding only rows touched since the previous
    # committed generation, coded with the PR-7 delta+zigzag+varint
    # primitives — commit bytes scale with per-generation churn, not
    # vocab. Restore replays base + deltas into byte-identical state.
    # Sparse backends only (the canonical rows_key/rows_cnt blob is the
    # delta's domain); the same files are the consumable delta log
    # (state/delta.read_delta_stream) future read replicas tail.
    checkpoint_compact_ratio: float = tuning.default("checkpoint_compact_ratio")  # ratio trigger: once the
    # delta chain's bytes exceed this fraction of the base's, the next
    # checkpoint rewrites a fresh full base (bounds restore replay) and
    # the old chain ages out under --checkpoint-retain
    restart_on_failure: int = 0  # supervisor: respawn the job up to N
    # times on abnormal exit, resuming from --checkpoint-dir when set
    # (the reference delegates this to Flink's restart strategies,
    # SURVEY §5); 0 = no supervision
    restart_delay_ms: int = 1000  # fixed delay between restart attempts
    # (the analogue of Flink's fixed-delay restart strategy)
    restart_backoff_base_ms: int = 0  # >0 switches restart delays to
    # exponential backoff with decorrelated jitter, starting here
    restart_backoff_max_ms: int = 30000  # backoff delay cap
    crash_loop_threshold: int = 3  # failures within the sliding window
    # that open the crash-loop breaker (step back one checkpoint
    # generation, then give up on a re-trip); 0 = breaker off
    crash_loop_window_s: float = 60.0  # breaker sliding-window seconds
    watchdog_stale_after_s: float = 0.0  # supervisor hang watchdog: kill
    # a child whose --journal has not grown for this many seconds (the
    # /healthz "no window fired" liveness signal); 0 = off
    degrade: bool = False  # graceful-degradation controller
    # (robustness/degrade.py): watch per-window health signals and step
    # NORMAL -> SHED_SAMPLING -> SHED_K -> PAUSE_INGEST, tightening the
    # paper's frequency cuts / emitted top-K and finally applying
    # bounded admission delay at the source; off = today's behavior
    degrade_window_wall_s: float = 1.0  # a window slower than this
    # wall-clock (sample+score) counts as overloaded
    degrade_trip_windows: int = 3  # consecutive overloaded windows that
    # escalate one level (hysteresis: escalation is never single-sample)
    degrade_clear_windows: int = 8  # consecutive healthy windows that
    # de-escalate one level (asymmetric on purpose: recover slower than
    # you shed, so the level cannot flap)
    degrade_shed_factor: int = 2  # cut/top-K divisor per shedding level
    degrade_pause_ms: int = 200  # bounded per-admit delay at PAUSE_INGEST
    # (a throttle, never an unbounded stall — no self-deadlock)
    degrade_stale_after_s: float = 30.0  # ingest-side staleness signal:
    # no window completed for this long while lines keep arriving
    # escalates one level (rate-limited to one step per stale period)
    quarantine_file: Optional[str] = None  # poison-input dead-letter
    # JSONL (robustness/quarantine.py): malformed lines divert here with
    # path:lineno provenance instead of crashing the job; None = off
    # (a malformed line raises, with the same provenance in the error)
    max_quarantine_rate: float = 0.01  # quarantine breaker: abort (exit
    # 2, permanent) once more than this fraction of input lines has
    # been quarantined — a systematically wrong input must not
    # "succeed" on its crumbs
    max_quarantine_bytes: int = 0  # dead-letter size cap: the active
    # file rolls over to .1/.2/... at this size, oldest backup beyond
    # the keep window deleted — a week-long stream cannot grow the
    # dead-letter JSONL unboundedly. 0 = unbounded (today's behavior)
    scorer_breaker_threshold: int = 0  # scorer circuit breaker
    # (robustness/degrade.py): N consecutive process_window failures
    # open the breaker onto the exact host-oracle fallback scorer, so a
    # failing device dispatch degrades the run instead of killing it;
    # 0 = off (single-process device/sparse backends only)
    scorer_breaker_probe_windows: int = 8  # windows the breaker stays
    # open before a half-open probe retries the primary scorer
    inject_fault: Optional[List[str]] = None  # fault-injection specs
    # (robustness/faults.py): site[:window_seq][:kind[:arg]], each fires
    # exactly once; None/[] = injection off (zero hot-path cost)
    fault_state_dir: Optional[str] = None  # markers making injected
    # faults fire once per RUN (across supervised restarts), not once
    # per attempt
    profile_dir: Optional[str] = None  # XLA profiler trace output (TensorBoard)
    journal: Optional[str] = None  # run-journal JSONL path: one flushed
    # record per fired window (observability/journal.py flight recorder);
    # a supervised crash leaves its tail intact and the supervisor quotes
    # it in the restart log. None = off
    metrics_port: Optional[int] = None  # live scrape endpoint
    # (observability/http.py): /metrics Prometheus text + /healthz
    # staleness probe on 127.0.0.1; 0 = ephemeral port (logged at
    # startup); None = off
    healthz_stale_after_s: float = 300.0  # /healthz turns 503 once no
    # window has fired for this many wall seconds
    serve_port: Optional[int] = None  # online serving plane
    # (serving/): /recommend beside /metrics + /healthz on
    # 127.0.0.1:PORT, backed by double-buffered zero-lock snapshots of
    # the per-item top-K table swapped at window boundaries; 0 =
    # ephemeral port (logged at startup); None = off
    serve_history: int = 50  # per-user recent-history ring length the
    # blend multiplies against the co-occurrence rows (bounded memory:
    # 4 B x users x length)
    serve_stale_after_s: float = 0.0  # /healthz turns 503 once the
    # published snapshot is older than this many seconds (load-balancer
    # drain signal for a wedged job); 0 = off
    serve_query_slo_s: float = 0.25  # query-latency SLO: a /recommend
    # slower than this raises the degradation plane's QUERY_PRESSURE
    # signal, shedding INGEST (tighter cuts, pause) before query latency
    # degrades — never the reverse; 0 = signal off
    score_ladder: int = tuning.default("score_ladder")  # sparse score-bucket
    # ladder base (power of two >= 2). Coarser = fewer dispatches, more
    # padding — the high-latency-link lever.
    fixed_score: str = tuning.default("fixed_score")  # sparse fixed-shape scoring: auto|on|off
    # (auto = on for real TPUs when results are deferred; constant
    # per-bucket rectangles -> one compile + one dispatch per bucket)
    pallas: str = "auto"  # fused score/top-K kernel: auto|on|off (auto = on
    # for int16 counts on a real TPU where it wins 247x, off otherwise —
    # measured, see ops/device_scorer.pallas_auto)
    fused_window: str = "off"  # one-dispatch fused window path.
    # device backend (tumbling mode): the sampler uplinks baskets (star
    # ops) and expansion + count scatter + row sums + LLR + top-K run
    # as ONE program per shape bucket
    # (ops/device_scorer._fused_window_*). sparse backend
    # (single-process, deferred results): packed-wire decode + slab
    # update scatter + device registry sync + rescore + results-table
    # scatter run as ONE program per shape bucket
    # (state/sparse_scorer._fused_sparse_window_*); relocation /
    # promotion / spill-re-promotion windows route chained per window.
    # auto = on-chip only — the CPU fallback stays on the chained
    # scatter+score path

    count_dtype: str = tuning.default("count_dtype")  # dense C cell dtype; int16 halves HBM
    # (reference-style short counts incl. its wraparound, doubles the
    # dense/sharded vocab ceiling)
    cell_dtype: str = tuning.default("cell_dtype")  # sparse slab cnt cell dtype: auto|int32|
    # int16|int8 (state/wire.py). Narrow cells stay EXACT — a row is
    # promoted to the wide int32 side-table before any cell could
    # saturate — unlike the dense --count-dtype, which wraps like the
    # reference's Java shorts. auto = int16 on the single-process sparse
    # backend, int32 elsewhere.
    spill_threshold_windows: int = tuning.default("spill_threshold_windows")  # tiered elastic state
    # (state/store.TieredSlabStore): rows untouched for this many fired
    # windows spill from the HBM slab to a host-side packed arena
    # (index keys really freed, capacity reused by hot rows) and
    # re-promote exactly on next touch, batched into the window's
    # existing uplink. 0 = tiering off (every row device-resident for
    # the whole run). Bit-identical output and checkpoints either way.
    spill_target_hbm_frac: float = tuning.default("spill_target_hbm_frac")  # spilling engages only while
    # live slab cells exceed this fraction of the allocated device slab
    # capacity (0.0 = spill every eligible cold row unconditionally;
    # 1.0 = only under a full slab)
    wire_format: str = tuning.default("wire_format")  # sparse per-window uplink encoding:
    # auto|raw|packed. packed = per-section sorted delta + zigzag +
    # bit-pack of the update buffer, decoded on device by a jit prologue
    # (state/wire.py) — fewer uplink bytes at bit-identical results.
    # Also selects the checkpoint blob codec
    # (raw = pre-codec layout, else delta+varint). auto = packed on the
    # single-process sparse backend, raw elsewhere.
    pipeline_depth: int = tuning.default("pipeline_depth")  # pipelined execution: the caller thread
    # samples window N+1 while a worker thread runs the scorer for
    # window N (pipeline.py). 0 = serial (today's behavior); 1 =
    # single-window overlap; 2 = double-buffered (absorbs stage jitter).
    # Bit-identical output to serial at every depth (parity-tested).
    development_mode: bool = False  # invariant checks (FlinkCooccurrences.java:34)
    emit_updates: bool = False  # stream every window's updated top-K rows
    # to stdout as they materialize (the consumable form of the
    # reference's continuous sink emission); off = final state only
    process_continuously: bool = False  # PROCESS_ONCE vs PROCESS_CONTINUOUSLY
    # Multi-host (multi-controller JAX): run one process per host, each
    # consuming the same input stream; state shards over all hosts' chips
    # and each process emits the rows its chips own (parallel/distributed.py).
    coordinator: Optional[str] = None  # host:port of process 0
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    run_id: Optional[str] = None  # tracing correlation id stamped on
    # every journal record (observability/journal.py); None = inherit
    # TPU_COOC_RUN_ID from a supervising parent, else mint fresh. Set
    # explicitly to join separately launched processes (e.g. a writer
    # and a standalone replica) into one cooc-trace timeline
    gang_workers: int = 0  # gang supervision (robustness/gang.py): this
    # process becomes the gang supervisor — it launches N workers with
    # the multi-controller identity flags filled in (fresh local
    # coordinator port per attempt), monitors exits + heartbeat files,
    # and gang-kills + gang-restarts the WHOLE set on any failure (JAX
    # collectives cannot survive peer loss); --restart-on-failure is
    # the gang's restart budget. 0 = off
    gang_heartbeat_s: float = 5.0  # worker heartbeat write interval
    autoscale: str = "off"  # load-driven gang autoscaler
    # (robustness/autoscale.py, gang runs only): sustained SHED_*
    # pressure grows the gang, sustained idle shrinks it — workers
    # drain a checkpoint at a gang-voted window boundary and exit
    # voluntarily, the supervisor relaunches at the new size, and the
    # topology-aware restore vote re-buckets N-shard state onto M
    # (scale before you shed; the degradation ladder only sheds once
    # the gang is at --autoscale-max-workers). off = today's behavior
    autoscale_min_workers: int = 2  # scale-down floor (a gang needs 2)
    autoscale_max_workers: int = 0  # scale-up ceiling; REQUIRED (> 0)
    # with --autoscale on — the operator owns the capacity budget
    autoscale_trip_windows: int = tuning.default("autoscale_trip_windows")  # consecutive gang-overloaded
    # windows that trigger a scale-up (hysteresis mirrors the ladder)
    autoscale_clear_windows: int = tuning.default("autoscale_clear_windows")  # consecutive gang-idle windows
    # that trigger a scale-down (asymmetric: grow fast, shrink slow)
    autoscale_cooldown_windows: int = tuning.default("autoscale_cooldown_windows")  # observed windows ignored
    # after every rescale decision (restore + recompile warm-up must
    # not read as a fresh signal)
    gang_stale_after_s: float = 60.0  # heartbeat age past which a peer
    # counts as dead: the gang supervisor restarts the gang, /healthz
    # 503s ("peer_stale") so a load balancer drains first; 0 = off
    collective_timeout_s: float = tuning.default("collective_timeout_s")  # collective-entry watchdog
    # (parallel/distributed.py): a guarded collective blocked this long
    # means a peer is gone — exit 75 for the gang supervisor to restart
    # the whole gang, instead of hanging forever; 0 = off
    partition_sampling: bool = False  # split host-side sampling across
    # processes by user (u % P) — the reservoir in tumbling mode, basket
    # expansion in sliding mode (cuts stay replicated) — and allgather
    # pair deltas per window: the reference's keyed-parallel ingest
    # scaling (sampling/multihost.py); off = every process samples the
    # full stream (replicated host state)

    def __post_init__(self):
        if self.seed is None:
            self.seed = time.time_ns()  # reference: System.nanoTime()
        if self.top_k <= 0:
            raise ValueError(f"{self.top_k} is <= 0")
        if self.source_format not in ("files", "partitioned"):
            raise ValueError(
                f"--source-format must be 'files' or 'partitioned', got "
                f"{self.source_format!r}")
        if self.ingest_partitions < 0:
            raise ValueError(
                f"--ingest-partitions must be >= 0, got "
                f"{self.ingest_partitions}")
        if self.ingest_partitions and self.source_format != "partitioned":
            raise ValueError(
                "--ingest-partitions only applies to --source-format "
                "partitioned (the files source has no partition "
                "contract to pin)")
        if self.restart_on_failure > 0 and self.process_continuously:
            raise ValueError(
                "--restart-on-failure buffers each attempt's stdout until "
                "it exits cleanly; a --process-continuously job never "
                "exits, so the combination would stream nothing and grow "
                "without bound — supervise continuous jobs externally "
                "(systemd/k8s) instead")
        if self.restart_on_failure > 0 and self.coordinator is not None:
            raise ValueError(
                "--restart-on-failure supervises one process; in a "
                "multi-host run a respawned child would re-join the "
                "coordinator while surviving peers are blocked "
                "mid-collective — use --gang-workers (the gang "
                "supervisor restarts all processes together) or "
                "supervise externally")
        multihost = (self.coordinator, self.num_processes, self.process_id)
        if any(v is not None for v in multihost):
            if any(v is None for v in multihost):
                raise ValueError(
                    "multi-host needs all of --coordinator, --num-processes "
                    "and --process-id (or none of them)")
            if not (0 <= self.process_id < self.num_processes):
                raise ValueError(
                    f"--process-id {self.process_id} out of range for "
                    f"--num-processes {self.num_processes}")
        if self.partition_sampling:
            if self.coordinator is None and not self.gang_workers:
                raise ValueError(
                    "--partition-sampling is a multi-host mode — it needs "
                    "--coordinator/--num-processes/--process-id (or "
                    "--gang-workers, which assigns them)")
        if self.gang_workers:
            if self.gang_workers < 2:
                raise ValueError(
                    f"--gang-workers needs >= 2 workers (a gang of one "
                    f"is --restart-on-failure), got {self.gang_workers}")
            if self.coordinator is not None or self.process_id is not None \
                    or self.num_processes is not None:
                raise ValueError(
                    "--gang-workers assigns --coordinator/--num-processes"
                    "/--process-id to its workers itself — do not pass "
                    "them to the supervisor")
            if self.process_continuously:
                raise ValueError(
                    "--gang-workers buffers each worker's stdout until "
                    "the gang exits cleanly; a --process-continuously "
                    "job never exits — supervise continuous gangs "
                    "externally (restart all processes together)")
            if self.serve_port is not None:
                raise ValueError(
                    "--serve-port is single-process only; gang workers "
                    "hold partial top-K tables — serve reads from a "
                    "replica fleet instead (cooc-replica --state-dir "
                    "<checkpoint dir>, with --checkpoint-incremental "
                    "on the ingest job)")
            backend_multihost = (
                self.backend == Backend.SHARDED
                or (self.backend in (Backend.SPARSE, Backend.HYBRID)
                    and self.num_shards > 1))
            if not backend_multihost:
                raise ValueError(
                    "--gang-workers runs a multi-controller job: use "
                    "--backend sharded, or sparse with --num-shards > 1 "
                    "(other backends would run one full independent job "
                    "per worker and clobber the shared checkpoint dir)")
        if self.gang_heartbeat_s <= 0:
            raise ValueError(
                f"--gang-heartbeat-s must be positive, got "
                f"{self.gang_heartbeat_s}")
        if self.autoscale not in ("off", "on"):
            raise ValueError(
                f"--autoscale must be off|on, got {self.autoscale!r}")
        if (self.autoscale_trip_windows < 1
                or self.autoscale_clear_windows < 1):
            raise ValueError(
                "--autoscale-trip-windows and --autoscale-clear-windows "
                "must be >= 1")
        if self.autoscale_cooldown_windows < 0:
            raise ValueError(
                f"--autoscale-cooldown-windows must be >= 0, got "
                f"{self.autoscale_cooldown_windows}")
        if self.autoscale == "on":
            if not self.gang_workers and self.coordinator is None:
                raise ValueError(
                    "--autoscale on is gang machinery — it needs "
                    "--gang-workers (the supervisor owns relaunching at "
                    "a new topology)")
            if not self.degrade:
                raise ValueError(
                    "--autoscale on reads the degradation plane's "
                    "per-window pressure signal — it needs --degrade")
            if not self.checkpoint_dir:
                raise ValueError(
                    "--autoscale on drains a checkpoint at every "
                    "rescale boundary — it needs --checkpoint-dir")
            if self.backend not in (Backend.SPARSE, Backend.HYBRID):
                raise ValueError(
                    "--autoscale on needs --backend sparse (the N->M "
                    "rescale restore re-buckets the sparse slab's "
                    "global key space; the dense sharded matrix has no "
                    "rescale-on-restore path)")
            if self.partition_sampling:
                raise ValueError(
                    "--autoscale on cannot run with "
                    "--partition-sampling: the per-process reservoir "
                    "partition (u %% P) changes shape at a rescale and "
                    "has no redistribution path")
            if self.autoscale_min_workers < 2:
                raise ValueError(
                    f"--autoscale-min-workers must be >= 2 (a gang of "
                    f"one is --restart-on-failure), got "
                    f"{self.autoscale_min_workers}")
            if self.autoscale_max_workers < self.autoscale_min_workers:
                raise ValueError(
                    "--autoscale on needs --autoscale-max-workers >= "
                    f"--autoscale-min-workers (got "
                    f"{self.autoscale_max_workers} < "
                    f"{self.autoscale_min_workers}) — the operator "
                    "owns the capacity ceiling")
            launch = (self.gang_workers
                      if self.gang_workers else (self.num_processes or 0))
            if launch and not (self.autoscale_min_workers <= launch
                               <= self.autoscale_max_workers):
                raise ValueError(
                    f"the launch topology ({launch} workers) must sit "
                    f"inside [--autoscale-min-workers, "
                    f"--autoscale-max-workers] = "
                    f"[{self.autoscale_min_workers}, "
                    f"{self.autoscale_max_workers}]")
        if self.gang_stale_after_s < 0:
            raise ValueError(
                f"--gang-stale-after-s must be >= 0, got "
                f"{self.gang_stale_after_s}")
        if self.collective_timeout_s < 0:
            raise ValueError(
                f"--collective-timeout-s must be >= 0, got "
                f"{self.collective_timeout_s}")
        if self.inject_fault is None:
            self.inject_fault = []
        if self.inject_fault:
            # Fail fast on a bad spec (unknown site/kind, missing
            # delay arg) — at config time, not mid-run at first fire.
            from .robustness.faults import FaultPlan

            FaultPlan.parse(self.inject_fault)
            if self.restart_on_failure > 0 and not self.fault_state_dir:
                raise ValueError(
                    "--inject-fault under --restart-on-failure needs "
                    "--fault-state-dir: without persisted fired-markers "
                    "every respawned attempt re-injects the same faults "
                    "and the run can only exhaust its restarts")
        if self.checkpoint_retain < 1:
            raise ValueError(
                f"--checkpoint-retain must be >= 1, got "
                f"{self.checkpoint_retain}")
        if self.checkpoint_compact_ratio <= 0:
            raise ValueError(
                f"--checkpoint-compact-ratio must be > 0, got "
                f"{self.checkpoint_compact_ratio}")
        if self.checkpoint_incremental:
            if self.backend not in (Backend.SPARSE, Backend.HYBRID):
                # The delta records' domain is the canonical sparse
                # rows_key/rows_cnt blob; dense C matrices have no
                # dirty-row representation to replay.
                raise ValueError(
                    "--checkpoint-incremental needs a sparse-family "
                    "backend (--backend sparse, any shard count); got "
                    f"--backend {self.backend.value}")
            if self.scorer_breaker_threshold > 0:
                # A tripped breaker scores on the host fallback: rows it
                # rescored never reach the store's dirty log, so a delta
                # written mid-trip would silently miss them.
                raise ValueError(
                    "--checkpoint-incremental cannot run with "
                    "--scorer-breaker-threshold: fallback-scored rows "
                    "bypass the dirty-row log — disable one of the two")
        if self.restart_backoff_base_ms < 0 or self.restart_backoff_max_ms < 0:
            raise ValueError("restart backoff values must be >= 0")
        if (self.restart_backoff_base_ms
                and self.restart_backoff_max_ms < self.restart_backoff_base_ms):
            raise ValueError(
                "--restart-backoff-max-ms must be >= "
                "--restart-backoff-base-ms")
        if self.watchdog_stale_after_s < 0:
            raise ValueError(
                f"--watchdog-stale-after-s must be >= 0, got "
                f"{self.watchdog_stale_after_s}")
        if self.watchdog_stale_after_s > 0:
            if self.restart_on_failure <= 0 and not self.gang_workers:
                raise ValueError(
                    "--watchdog-stale-after-s is supervisor machinery — "
                    "it needs --restart-on-failure (or --gang-workers)")
            if not self.journal:
                raise ValueError(
                    "--watchdog-stale-after-s watches the run journal "
                    "for liveness — it needs --journal")
        if self.metrics_port is not None and not (
                0 <= self.metrics_port <= 65535):
            raise ValueError(
                f"--metrics-port must be 0..65535, got {self.metrics_port}")
        if self.serve_port is not None:
            if not (0 <= self.serve_port <= 65535):
                raise ValueError(
                    f"--serve-port must be 0..65535, got {self.serve_port}")
            if (self.metrics_port is not None
                    and self.metrics_port == self.serve_port):
                raise ValueError(
                    "--serve-port already serves /metrics and /healthz; "
                    "binding --metrics-port to the same port would fail "
                    "at startup — drop one (or use distinct ports)")
            if self.coordinator is not None or self.partition_sampling:
                # Each multi-host process materializes only the rows its
                # chips own; a per-process snapshot would silently serve
                # a partial catalog as if it were the whole table.
                raise ValueError(
                    "--serve-port is single-process only (a multi-host "
                    "process holds a partial top-K table) — serve reads "
                    "from a replica fleet instead (cooc-replica "
                    "--state-dir <checkpoint dir>, with "
                    "--checkpoint-incremental on the ingest job)")
        if self.serve_history < 1:
            raise ValueError(
                f"--serve-history must be >= 1, got {self.serve_history}")
        if self.serve_stale_after_s < 0:
            raise ValueError(
                f"--serve-stale-after-s must be >= 0, got "
                f"{self.serve_stale_after_s}")
        if self.serve_query_slo_s < 0:
            raise ValueError(
                f"--serve-query-slo-s must be >= 0, got "
                f"{self.serve_query_slo_s}")
        if self.healthz_stale_after_s <= 0:
            raise ValueError(
                f"--healthz-stale-after-s must be positive, got "
                f"{self.healthz_stale_after_s}")
        if self.degrade_window_wall_s <= 0:
            raise ValueError(
                f"--degrade-window-wall-s must be positive, got "
                f"{self.degrade_window_wall_s}")
        if self.degrade_trip_windows < 1 or self.degrade_clear_windows < 1:
            raise ValueError(
                "--degrade-trip-windows and --degrade-clear-windows "
                "must be >= 1")
        if self.degrade_shed_factor < 2:
            raise ValueError(
                f"--degrade-shed-factor must be >= 2, got "
                f"{self.degrade_shed_factor}")
        if self.degrade_pause_ms < 0:
            raise ValueError(
                f"--degrade-pause-ms must be >= 0, got "
                f"{self.degrade_pause_ms}")
        if self.degrade_stale_after_s <= 0:
            raise ValueError(
                f"--degrade-stale-after-s must be positive, got "
                f"{self.degrade_stale_after_s}")
        if (self.degrade and self.pipeline_depth > 0
                and (self.coordinator is not None or self.gang_workers)):
            # Multi-host --degrade stays in lockstep through a
            # per-window worst-signal allgather on the window-record
            # thread (robustness/degrade.py exchange); at depth 0 that
            # thread IS the sampling thread, so the level every host
            # samples under is deterministic. Pipelined, the sampling
            # thread would read the level mid-flight while the scorer
            # worker votes — hosts could sample the same window under
            # different cuts and diverge the pair streams.
            raise ValueError(
                "--degrade on multi-host runs needs --pipeline-depth 0 "
                "(the per-window shed vote is only in lockstep with "
                "sampling on the serial path)")
        if not (0.0 < self.max_quarantine_rate <= 1.0):
            raise ValueError(
                f"--max-quarantine-rate must be in (0, 1], got "
                f"{self.max_quarantine_rate}")
        if self.max_quarantine_bytes < 0:
            raise ValueError(
                f"--max-quarantine-bytes must be >= 0, got "
                f"{self.max_quarantine_bytes}")
        if self.scorer_breaker_threshold < 0:
            raise ValueError(
                f"--scorer-breaker-threshold must be >= 0, got "
                f"{self.scorer_breaker_threshold}")
        if self.scorer_breaker_probe_windows < 1:
            raise ValueError(
                f"--scorer-breaker-probe-windows must be >= 1, got "
                f"{self.scorer_breaker_probe_windows}")
        if self.scorer_breaker_threshold > 0:
            if self.backend == Backend.ORACLE:
                raise ValueError(
                    "--scorer-breaker-threshold: the oracle backend IS "
                    "the breaker's fallback — there is nothing to break "
                    "over")
            if (self.backend == Backend.SHARDED or self.num_shards > 1
                    or self.coordinator is not None):
                raise ValueError(
                    "--scorer-breaker-threshold is single-process "
                    "device/sparse only (a per-process host fallback "
                    "cannot substitute for a mesh collective)")
        if self.cell_dtype not in ("auto", "int32", "int16", "int8"):
            raise ValueError(
                f"--cell-dtype must be auto|int32|int16|int8, got "
                f"{self.cell_dtype!r}")
        if self.wire_format not in ("auto", "raw", "packed"):
            raise ValueError(
                f"--wire-format must be auto|raw|packed, got "
                f"{self.wire_format!r}")
        sparse_single = (self.backend in (Backend.SPARSE, Backend.HYBRID)
                         and self.num_shards == 1
                         and self.coordinator is None)
        # The sharded-sparse mesh (single controller) carries the wide
        # side-table and the packed uplink too; only multi-controller
        # runs are excluded (per-process snapshots have no wide blocks,
        # and every worker would re-encode the same replicated window).
        sparse_local = (sparse_single
                        or (self.backend == Backend.SPARSE
                            and self.coordinator is None))
        if self.cell_dtype in ("int16", "int8") and not sparse_local:
            # 'auto' degrades gracefully; an explicit narrow request the
            # backend cannot honor must fail loudly (same rule as
            # --fused-window on).
            raise ValueError(
                f"--cell-dtype {self.cell_dtype} is --backend sparse "
                f"without --coordinator only (multi-controller "
                f"per-process snapshots carry no wide side-table "
                f"blocks)")
        if self.wire_format == "packed" and not (
                sparse_local or self.backend == Backend.SPARSE):
            raise ValueError(
                "--wire-format packed applies to the sparse backend's "
                "update uplink (other backends ship raw COO or basket "
                "formats)")
        if self.spill_threshold_windows < 0:
            raise ValueError(
                f"--spill-threshold-windows must be >= 0, got "
                f"{self.spill_threshold_windows}")
        if not (0.0 <= self.spill_target_hbm_frac <= 1.0):
            raise ValueError(
                f"--spill-target-hbm-frac must be in [0, 1], got "
                f"{self.spill_target_hbm_frac}")
        if self.spill_threshold_windows > 0 and not sparse_single:
            # Same single-process-sparse scoping rule as --cell-dtype:
            # the spill arena and promotion extras are per-process slab
            # state (the sharded backend's elastic axis is
            # rescale-on-restore instead).
            raise ValueError(
                "--spill-threshold-windows is single-process --backend "
                "sparse only (the spill arena is per-process slab "
                "state; sharded runs rescale via --num-shards at "
                "restore instead)")
        if self.fused_window not in ("auto", "on", "off"):
            raise ValueError(
                f"--fused-window must be auto|on|off, got "
                f"{self.fused_window!r}")
        if self.fused_window == "on":
            # 'auto' may ride along anywhere (it only engages where a
            # fused-capable backend resolves it); a forced 'on' that
            # cannot engage must fail loudly, not silently run chained.
            if self.backend == Backend.DEVICE:
                if self.window_slide is not None:
                    raise ValueError(
                        "--fused-window on with --backend device applies "
                        "to tumbling reservoir sampling; sliding windows "
                        "stay on the chained path")
                if self.partition_sampling or self.coordinator is not None:
                    raise ValueError(
                        "--fused-window on is single-process only (the "
                        "partitioned sampler allgathers expanded COO)")
            elif self.backend in (Backend.SPARSE, Backend.HYBRID):
                if self.backend == Backend.HYBRID and not sparse_single:
                    raise ValueError(
                        "--fused-window on with --backend hybrid is "
                        "single-process only")
                if self.emit_updates:
                    raise ValueError(
                        "--fused-window on with --backend sparse needs "
                        "deferred results (drop --emit-updates): the "
                        "fused program scatters top-K into the "
                        "device-resident table, never downlinks per "
                        "window")
            else:
                raise ValueError(
                    f"--fused-window on is --backend device or sparse "
                    f"only (got {self.backend.value}); other backends "
                    f"stay on the chained path")
        if self.pipeline_depth not in (0, 1, 2):
            raise ValueError(
                f"--pipeline-depth must be 0, 1 or 2, got "
                f"{self.pipeline_depth}")
        if self.pipeline_depth > 0 and self.partition_sampling:
            # Multi-controller collectives must be issued in the same
            # order on every process; the partitioned sampler's
            # per-window allgather runs on the sampling thread, which
            # would race the scorer worker's dispatches. Plain
            # multi-host pipelining is fine: every collective (scorer
            # dispatch, degrade-off, epoch barrier behind
            # pipeline.barrier()) issues from one thread in window
            # order.
            raise ValueError(
                "--pipeline-depth > 0 is incompatible with "
                "--partition-sampling (the partitioned sampler's "
                "allgather on the sampling thread would race the "
                "scorer worker's collectives)")

    @property
    def window_millis(self) -> int:
        return self.window_size * self.window_unit.millis

    @property
    def slide_millis(self) -> Optional[int]:
        if self.window_slide is None:
            return None
        return self.window_slide * self.window_unit.millis

    def log_configuration(self, logger) -> None:
        """Echo the config at startup (reference: ``Configuration.java:272-282``)."""
        logger.info("input\t%s", self.input)
        logger.info("skip cuts\t%s", self.skip_cuts)
        logger.info("item cut (fMax)\t%s", self.item_cut)
        logger.info("user cut (kMax)\t%s", self.user_cut)
        logger.info("topK\t%s", self.top_k)
        logger.info("windowSize\t%s", self.window_size)
        logger.info("windowUnit\t%s", self.window_unit.name)
        logger.info("seed\t%s", self.seed)
        logger.info("buffer timeout\t%s", self.buffer_timeout)
        logger.info("backend\t%s", self.backend.value)
        logger.info("numItems\t%s", self.num_items)
        logger.info("numShards\t%s", self.num_shards)

    @classmethod
    def from_args(cls, argv: Optional[Sequence[str]] = None) -> "Config":
        """CLI parsing mirroring the reference flags (``Configuration.java:56-199``)."""
        p = argparse.ArgumentParser(
            prog="tpu-cooccurrence",
            description="TPU-native streaming item-item co-occurrence (LLR) recommender",
            # No prefix abbreviations: the supervisor strips its own flags
            # from the child argv by exact name, and an abbreviated
            # `--restart-on` would survive the strip and recurse into a
            # nested supervisor (also matches commons-cli, which has no
            # abbreviation).
            allow_abbrev=False,
        )
        p.add_argument("-i", "--input", required=True,
                       help="Input file/directory to consume (expected format 'user,item,timestamp')")
        p.add_argument("--source-format", choices=("files", "partitioned"),
                       default="files", dest="source_format",
                       help="Ingest source shape: 'files' tails the "
                            "input in modification-time order; "
                            "'partitioned' consumes an append-only "
                            "partitioned log (part-* files) whose "
                            "per-partition offsets commit atomically "
                            "with the checkpoint (default: files)")
        p.add_argument("--ingest-partitions", type=int, default=0,
                       dest="ingest_partitions",
                       help="Expected part-* partition count with "
                            "--source-format partitioned; a directory "
                            "with a different count fails fast "
                            "(0 = derive from the directory)")
        p.add_argument("-sc", "--skip-cuts", action="store_true", dest="skip_cuts",
                       help="Skip the interaction cuts")
        p.add_argument("-ic", "--item-cut", type=int, default=500, dest="item_cut",
                       help="Item interaction cut (default: 500)")
        p.add_argument("-uc", "--user-cut", type=int, default=500, dest="user_cut",
                       help="User interaction cut (default: 500)")
        p.add_argument("-k", "--top-k", type=int, default=10, dest="top_k",
                       help="Top K (default: 10)")
        p.add_argument("-ws", "--window-size", type=int, required=True, dest="window_size",
                       help="Window size")
        p.add_argument("-wu", "--window-unit", type=WindowUnit.parse,
                       default=WindowUnit.MILLISECONDS, dest="window_unit",
                       help="TimeUnit for the window (default: milliseconds)")
        p.add_argument("-s", "--seed", type=_parse_seed, default=None,
                       help="Seed for random number generator (decimal or 0x-hex)")
        p.add_argument("-bt", "--buffer-timeout", type=int, default=100, dest="buffer_timeout",
                       help="Buffer timeout (default: 100ms)")
        # Extensions
        p.add_argument("--backend", type=Backend, choices=list(Backend),
                       default=Backend.DEVICE)
        p.add_argument("--num-items", type=int, default=0, dest="num_items",
                       help="Dense item-vocabulary capacity on device "
                            "(0 = derive from data; device backend only — "
                            "sharded requires an explicit capacity)")
        p.add_argument("--num-shards", type=int, default=1, dest="num_shards",
                       help="Item-axis shards over the device mesh")
        p.add_argument("--window-slide", type=int, default=None, dest="window_slide",
                       help="Slide (same unit as window) for sliding windows")
        p.add_argument("--profile-dir", default=None, dest="profile_dir",
                       help="Write a jax.profiler trace for TensorBoard")
        p.add_argument("--journal", default=None, dest="journal",
                       help="Append one JSONL record per fired window to "
                            "this path (flight recorder; survives crashes "
                            "and is quoted by the supervisor's restart log)")
        p.add_argument("--metrics-port", type=int, default=None,
                       dest="metrics_port",
                       help="Serve Prometheus /metrics and /healthz on "
                            "127.0.0.1:PORT (0 = ephemeral, logged at "
                            "startup; omit to disable)")
        p.add_argument("--healthz-stale-after-s", type=float, default=300.0,
                       dest="healthz_stale_after_s",
                       help="/healthz reports 503 once no window has fired "
                            "for this many seconds (default: 300)")
        p.add_argument("--serve-port", type=int, default=None,
                       dest="serve_port",
                       help="Serve /recommend (plus /metrics and /healthz) "
                            "on 127.0.0.1:PORT from zero-lock double-"
                            "buffered top-K snapshots swapped at window "
                            "boundaries (0 = ephemeral, logged at "
                            "startup; omit to disable)")
        p.add_argument("--serve-history", type=int, default=50,
                       dest="serve_history",
                       help="Per-user recent-history ring length the "
                            "/recommend blend uses (default: 50)")
        p.add_argument("--serve-stale-after-s", type=float, default=0.0,
                       dest="serve_stale_after_s",
                       help="/healthz reports 503 once the serving "
                            "snapshot is older than this many seconds, so "
                            "load balancers can drain a wedged job "
                            "(default: 0 = off)")
        p.add_argument("--serve-query-slo-s", type=float, default=0.25,
                       dest="serve_query_slo_s",
                       help="Query-latency SLO: a /recommend slower than "
                            "this raises QUERY_PRESSURE so the "
                            "degradation plane sheds ingest before query "
                            "latency degrades (default: 0.25; 0 = off)")
        p.add_argument("--pallas", choices=["auto", "on", "off"],
                       default="auto",
                       help="Fused Pallas score/top-K kernel (auto: on for "
                            "int16 counts on TPU, off otherwise — measured)")
        p.add_argument("--fused-window", choices=["auto", "on", "off"],
                       default="off", dest="fused_window",
                       help="One-dispatch fused window path. device: ship "
                            "baskets, run expansion + count update + LLR "
                            "+ top-K as one program per shape bucket. "
                            "sparse (single-process, deferred results): "
                            "packed-wire decode + slab update + registry "
                            "sync + rescore as one program; relocation/"
                            "promotion/spill windows route chained. "
                            "(auto: on-chip only — the CPU fallback "
                            "stays on the chained path)")
        p.add_argument("--count-dtype",
                       choices=list(tuning.get("count_dtype").choices),
                       default=tuning.default("count_dtype"),
                       dest="count_dtype",
                       help="Dense count-matrix cell dtype (int16 halves "
                            "device memory; counts then wrap like the "
                            "reference's Java shorts)")
        p.add_argument("--cell-dtype",
                       choices=list(tuning.get("cell_dtype").choices),
                       default=tuning.default("cell_dtype"),
                       dest="cell_dtype",
                       help="Sparse slab cell dtype — EXACT narrow "
                            "counts: rows promote to a wide int32 "
                            "side-table before saturation (auto: int16 "
                            "on the single-process sparse backend)")
        p.add_argument("--spill-threshold-windows", type=int,
                       default=tuning.default("spill_threshold_windows"),
                       dest="spill_threshold_windows",
                       help="Tiered elastic state (sparse backend): "
                            "spill rows untouched for this many windows "
                            "from the HBM slab to a host-side arena, "
                            "re-promoting exactly on touch (0 = off; "
                            "output and checkpoints stay bit-identical)")
        p.add_argument("--spill-target-hbm-frac", type=float,
                       default=tuning.default("spill_target_hbm_frac"),
                       dest="spill_target_hbm_frac",
                       help="Spill cold rows only while live slab cells "
                            "exceed this fraction of the allocated "
                            "device slab capacity (0.0 = spill every "
                            "eligible row; default: 0.5)")
        p.add_argument("--wire-format",
                       choices=list(tuning.get("wire_format").choices),
                       default=tuning.default("wire_format"),
                       dest="wire_format",
                       help="Sparse per-window uplink + checkpoint blob "
                            "encoding: packed = sorted delta + zigzag + "
                            "bit-pack, decoded on device, bit-identical "
                            "results (auto: packed on the single-process "
                            "sparse backend)")
        p.add_argument("--score-ladder", type=int,
                       default=tuning.default("score_ladder"),
                       dest="score_ladder",
                       help="Sparse-backend score-bucket ladder base "
                            "(power of two >= 2; default 4). Coarser = "
                            "fewer dispatches, more padding")
        p.add_argument("--fixed-score",
                       choices=list(tuning.get("fixed_score").choices),
                       default=tuning.default("fixed_score"),
                       dest="fixed_score",
                       help="Sparse-backend fixed-shape scoring (constant "
                            "per-bucket rectangles; auto = on for real "
                            "TPUs when results are deferred)")
        p.add_argument("--pipeline-depth", type=int, choices=[0, 1, 2],
                       default=tuning.default("pipeline_depth"),
                       dest="pipeline_depth",
                       help="Overlap host sampling with device scoring: "
                            "sample window N+1 while the scorer runs "
                            "window N on a worker thread (0 = serial, "
                            "2 = double-buffered; output is bit-identical "
                            "at every depth)")
        p.add_argument("--checkpoint-dir", default=None, dest="checkpoint_dir")
        p.add_argument("--checkpoint-every-windows", type=int, default=0,
                       dest="checkpoint_every_windows")
        p.add_argument("--checkpoint-retain", type=int, default=3,
                       dest="checkpoint_retain",
                       help="Generation-numbered checkpoints to keep "
                            "(restore falls back to the newest one that "
                            "verifies; chain-aware: a base or delta some "
                            "retained generation chains through is never "
                            "deleted; default: 3)")
        p.add_argument("--checkpoint-incremental", action="store_true",
                       dest="checkpoint_incremental",
                       help="Dirty-row incremental checkpoint generations "
                            "(sparse backends): a full base plus per-"
                            "generation delta.<gen>.bin files holding only "
                            "rows touched since the previous generation — "
                            "commit bytes scale with churn, not vocab; "
                            "restore replays base + deltas bit-identically")
        p.add_argument("--checkpoint-compact-ratio", type=float,
                       default=tuning.default("checkpoint_compact_ratio"),
                       dest="checkpoint_compact_ratio",
                       help="Rewrite a fresh full base once the delta "
                            "chain's bytes exceed this fraction of the "
                            "base's (bounds restore replay; default: 0.5)")
        p.add_argument("--restart-on-failure", type=int, default=0,
                       dest="restart_on_failure",
                       help="Supervise the run: respawn the job up to N "
                            "times on abnormal exit, resuming from "
                            "--checkpoint-dir when set (Flink restart-"
                            "strategy analogue)")
        p.add_argument("--restart-delay-ms", type=int, default=1000,
                       dest="restart_delay_ms",
                       help="Fixed delay between restart attempts")
        p.add_argument("--restart-backoff-base-ms", type=int, default=0,
                       dest="restart_backoff_base_ms",
                       help="Enable exponential restart backoff with "
                            "decorrelated jitter, starting at this delay "
                            "(0 = fixed --restart-delay-ms)")
        p.add_argument("--restart-backoff-max-ms", type=int, default=30000,
                       dest="restart_backoff_max_ms",
                       help="Backoff delay cap (default: 30000)")
        p.add_argument("--crash-loop-threshold", type=int, default=3,
                       dest="crash_loop_threshold",
                       help="Failures within --crash-loop-window-s that "
                            "open the crash-loop breaker: step back one "
                            "checkpoint generation, then give up on a "
                            "re-trip (0 = breaker off; default: 3)")
        p.add_argument("--crash-loop-window-s", type=float, default=60.0,
                       dest="crash_loop_window_s",
                       help="Crash-loop breaker sliding window seconds "
                            "(default: 60)")
        p.add_argument("--watchdog-stale-after-s", type=float, default=0.0,
                       dest="watchdog_stale_after_s",
                       help="Supervisor hang watchdog: SIGTERM/SIGKILL a "
                            "child whose --journal has not grown for this "
                            "many seconds and count a failed attempt "
                            "(0 = off; needs --restart-on-failure and "
                            "--journal)")
        p.add_argument("--degrade", action="store_true", dest="degrade",
                       help="Enable the graceful-degradation controller: "
                            "shed load (tighter cuts, narrower top-K, "
                            "bounded admission delay) under sustained "
                            "overload instead of stalling or dying")
        p.add_argument("--degrade-window-wall-s", type=float, default=1.0,
                       dest="degrade_window_wall_s",
                       help="Per-window wall-time threshold above which a "
                            "window counts as overloaded (default: 1.0)")
        p.add_argument("--degrade-trip-windows", type=int, default=3,
                       dest="degrade_trip_windows",
                       help="Consecutive overloaded windows that escalate "
                            "one degradation level (default: 3)")
        p.add_argument("--degrade-clear-windows", type=int, default=8,
                       dest="degrade_clear_windows",
                       help="Consecutive healthy windows that de-escalate "
                            "one level (default: 8)")
        p.add_argument("--degrade-shed-factor", type=int, default=2,
                       dest="degrade_shed_factor",
                       help="Cut/top-K divisor applied per shedding level "
                            "(default: 2)")
        p.add_argument("--degrade-pause-ms", type=int, default=200,
                       dest="degrade_pause_ms",
                       help="Bounded per-admit source delay at "
                            "PAUSE_INGEST (default: 200)")
        p.add_argument("--degrade-stale-after-s", type=float, default=30.0,
                       dest="degrade_stale_after_s",
                       help="Escalate one level when no window has "
                            "completed for this long while ingest "
                            "continues (default: 30)")
        p.add_argument("--gang-workers", type=int, default=0,
                       dest="gang_workers",
                       help="Gang supervision: launch N multi-controller "
                            "workers (coordinator flags assigned per "
                            "attempt), monitor heartbeats, and gang-kill "
                            "+ gang-restart the whole set from the last "
                            "committed epoch on any failure "
                            "(--restart-on-failure = restart budget)")
        p.add_argument("--gang-heartbeat-s", type=float, default=5.0,
                       dest="gang_heartbeat_s",
                       help="Worker heartbeat-file write interval "
                            "(default: 5)")
        p.add_argument("--autoscale", choices=["off", "on"],
                       default="off",
                       help="Load-driven gang autoscaler: sustained "
                            "pressure grows the gang, sustained idle "
                            "shrinks it — workers drain a checkpoint "
                            "at a gang-voted window boundary and the "
                            "supervisor relaunches at the new size, "
                            "re-bucketing N-shard state onto M; the "
                            "degradation ladder only sheds once the "
                            "gang is at --autoscale-max-workers "
                            "(needs --gang-workers, --degrade and "
                            "--checkpoint-dir; default: off)")
        p.add_argument("--autoscale-min-workers", type=int, default=2,
                       dest="autoscale_min_workers",
                       help="Scale-down floor (default: 2 — the gang "
                            "minimum)")
        p.add_argument("--autoscale-max-workers", type=int, default=0,
                       dest="autoscale_max_workers",
                       help="Scale-up ceiling; required with "
                            "--autoscale on (the operator owns the "
                            "capacity budget)")
        p.add_argument("--autoscale-trip-windows", type=int,
                       default=tuning.default("autoscale_trip_windows"),
                       dest="autoscale_trip_windows",
                       help="Consecutive gang-overloaded windows that "
                            "trigger a scale-up (default: 3)")
        p.add_argument("--autoscale-clear-windows", type=int,
                       default=tuning.default("autoscale_clear_windows"),
                       dest="autoscale_clear_windows",
                       help="Consecutive gang-idle windows that "
                            "trigger a scale-down (asymmetric on "
                            "purpose; default: 8)")
        p.add_argument("--autoscale-cooldown-windows", type=int,
                       default=tuning.default("autoscale_cooldown_windows"),
                       dest="autoscale_cooldown_windows",
                       help="Windows ignored by the scale policy after "
                            "every rescale decision (default: 8)")
        p.add_argument("--gang-stale-after-s", type=float, default=60.0,
                       dest="gang_stale_after_s",
                       help="Heartbeat age past which a gang peer counts "
                            "as dead: the supervisor restarts the gang, "
                            "/healthz 503s 'peer_stale' (default: 60; "
                            "0 = off)")
        p.add_argument("--collective-timeout-s", type=float,
                       default=tuning.default("collective_timeout_s"),
                       dest="collective_timeout_s",
                       help="Collective-entry watchdog: a guarded "
                            "collective blocked this long exits 75 (a "
                            "gang peer is gone; the gang supervisor "
                            "restarts the whole set) instead of hanging "
                            "forever (default: 0 = off)")
        p.add_argument("--quarantine-file", default=None,
                       dest="quarantine_file",
                       help="Divert malformed input lines to this "
                            "dead-letter JSONL (path:lineno provenance + "
                            "raw line) instead of crashing the job")
        p.add_argument("--max-quarantine-rate", type=float, default=0.01,
                       dest="max_quarantine_rate",
                       help="Abort (exit 2, permanent) once more than "
                            "this fraction of input lines has been "
                            "quarantined (default: 0.01)")
        p.add_argument("--max-quarantine-bytes", type=int, default=0,
                       dest="max_quarantine_bytes",
                       help="Roll the dead-letter file over to .1/.2/... "
                            "at this size (oldest backup beyond the keep "
                            "window deleted) so a long stream cannot "
                            "grow it unboundedly (default: 0 = "
                            "unbounded)")
        p.add_argument("--scorer-breaker-threshold", type=int, default=0,
                       dest="scorer_breaker_threshold",
                       help="Scorer circuit breaker: consecutive dispatch "
                            "failures that open onto the host-oracle "
                            "fallback scorer (0 = off; single-process "
                            "device/sparse backends)")
        p.add_argument("--scorer-breaker-probe-windows", type=int,
                       default=8, dest="scorer_breaker_probe_windows",
                       help="Windows the scorer breaker stays open before "
                            "a half-open probe retries the primary "
                            "(default: 8)")
        p.add_argument("--inject-fault", action="append", default=None,
                       dest="inject_fault",
                       metavar="SITE[@PROC][:SEQ][:KIND[:ARG]]",
                       help="Fault injection (repeatable): fire KIND "
                            "(crash|exception|delay_ms|torn_write; default "
                            "crash) once at the named site, optionally at "
                            "window ordinal SEQ and only in process PROC "
                            "(multi-host chaos) — e.g. "
                            "--inject-fault checkpoint_post_write:3:"
                            "torn_write, or ckpt_commit@1:5:crash to kill "
                            "exactly worker 1 at the generation-5 commit "
                            "(sites: robustness/faults.py)")
        p.add_argument("--fault-state-dir", default=None,
                       dest="fault_state_dir",
                       help="Directory persisting fired-fault markers so "
                            "each --inject-fault spec fires once per run, "
                            "across supervised restarts")
        p.add_argument("--emit-updates", action="store_true",
                       dest="emit_updates",
                       help="Stream each window's updated top-K rows to "
                            "stdout as they materialize (instead of one "
                            "final dump)")
        p.add_argument("--development-mode", action="store_true", dest="development_mode")
        p.add_argument("--process-continuously", action="store_true",
                       dest="process_continuously")
        p.add_argument("--partition-sampling", action="store_true",
                       dest="partition_sampling",
                       help="Multi-host: partition host-side sampling "
                            "across processes by user (u %% P; reservoir "
                            "in tumbling mode, basket expansion in sliding "
                            "mode) and allgather pair deltas per window "
                            "instead of replicating all host sampling on "
                            "every process")
        p.add_argument("--coordinator", default=None,
                       help="Multi-host: host:port of process 0")
        p.add_argument("--num-processes", type=int, default=None,
                       dest="num_processes", help="Multi-host: process count")
        p.add_argument("--process-id", type=int, default=None,
                       dest="process_id", help="Multi-host: this process's id")
        p.add_argument("--run-id", default=None, dest="run_id",
                       help="Tracing: correlation id stamped on every "
                            "journal record (default: inherit "
                            "TPU_COOC_RUN_ID from a supervising parent, "
                            "else mint fresh); set explicitly to join "
                            "separately launched processes into one "
                            "cooc-trace timeline")
        raw = list(argv) if argv is not None else sys.argv[1:]
        if any(
                a == "--sample-workers" or a.startswith("--sample-workers=")
                for a in raw):
            # Fully retired (PR 8; ignored since round 3): fail with the
            # reason and the replacement, not argparse's bare
            # "unrecognized arguments".
            raise ValueError(
                "--sample-workers is retired: thread-partitioned host "
                "sampling measured ~0.9x serial (GIL-bound) and was "
                "removed; the serial native sampler always runs — use "
                "--partition-sampling for multi-process ingest scale-out")
        ns = p.parse_args(argv)
        return cls(**vars(ns))

    def __str__(self) -> str:
        return (
            f"Config{{input={self.input}, skipCuts={self.skip_cuts}, "
            f"fMax={self.item_cut}, kMax={self.user_cut}, topK={self.top_k}, "
            f"windowSize={self.window_size}, windowUnit={self.window_unit.name}, "
            f"seed=0x{self.seed:x}, bufferTimeout={self.buffer_timeout}, "
            f"backend={self.backend.value}}}"
        )


def main(argv: Optional[Sequence[str]] = None) -> None:
    """Standalone config smoke test (reference: ``Configuration.java:299-302``)."""
    print(Config.from_args(argv))


if __name__ == "__main__":
    main()
