"""The production job: wires ingest -> windowing -> sampling -> scoring.

TPU-native equivalent of the reference's topology builder + driver
(``FlinkCooccurrences.java:36-182``): instead of a DataStream graph with
keyed shuffles, the host streams micro-batches through the window engine and
the vectorized cut operators, and each fired window becomes one device step
(scatter-update + LLR + top-K). The feedback edge (reject -> item-counter
decrement, reference's in-JVM ``BlockingQueueBroker`` hack) is a plain
same-host update applied between window fires.

Duration and the accumulator dump mirror the reference's end-of-run logging
(``FlinkCooccurrences.java:173-181``).
"""

from __future__ import annotations

import json
import logging
import time
from typing import Dict, Iterable, Optional

import numpy as np

from .config import Backend, Config
from .metrics import (
    Counters,
    FEEDBACK_QUEUES,
    ITEM_LATE_ELEMENTS,
    USER_LATE_ELEMENTS,
    USER_RECEIVED_ELEMENTS,
)
from .io.parse import InteractionBatch
from .sampling.item_cut import ItemInteractionCut
from .sampling.reservoir import UserReservoirSampler
from .sampling.sliding import SlidingBasketSampler
from .observability import (LEDGER, StageClock, StepTimer, WindowStats,
                            clock)
from .observability.journal import CORE_STAGES
from .observability.registry import BYTES_BUCKETS, REGISTRY
from .robustness import faults
from .state.rescorer import HostRescorer, WindowTopK
from .state.results import LatestResults, TopKBatch
from .state.vocab import IdMap
from .windowing.engine import WindowEngine

LOG = logging.getLogger("tpu_cooccurrence")


class CooccurrenceJob:
    """Streaming co-occurrence job over a pluggable scoring backend."""

    def __init__(self, config: Config, scorer=None) -> None:
        if config.window_millis <= 0:
            raise ValueError("window size must be positive")
        # Before the scorer's first compile (xla_cache.py says where).
        from .xla_cache import enable_compilation_cache

        enable_compilation_cache()
        self.config = config
        self.counters = Counters()
        # Graceful-degradation plane (--degrade, robustness/degrade.py):
        # installed process-globally so the source's admission gate can
        # reach it without plumbing; identity at NORMAL (parity-tested),
        # uninstalled in finish().
        self.degrade = None
        if config.degrade:
            from .robustness import degrade as degrade_mod

            self.degrade = degrade_mod.install(
                degrade_mod.DegradationController(
                    window_wall_s=config.degrade_window_wall_s,
                    trip_windows=config.degrade_trip_windows,
                    clear_windows=config.degrade_clear_windows,
                    shed_factor=config.degrade_shed_factor,
                    pause_ms=config.degrade_pause_ms,
                    stale_after_s=config.degrade_stale_after_s))
        # Sliding mode (framework extension; the reference is tumbling-only,
        # FlinkCooccurrences.java:139,153) switches the sampler to stateless
        # windowed-basket co-occurrence — see sampling/sliding.py for the
        # documented semantics.
        self.sliding = config.window_slide is not None
        self.engine = WindowEngine(config.window_millis, config.slide_millis)
        self.item_vocab = IdMap()
        self.user_vocab = IdMap()
        self.item_cut = ItemInteractionCut(config.item_cut, capacity=1024)
        if self.sliding:
            if config.partition_sampling:
                from .parallel.distributed import init_multihost
                from .sampling.multihost import (
                    ProcessPartitionedSlidingSampler)

                init_multihost(config.coordinator, config.num_processes,
                               config.process_id)
                self.sampler = ProcessPartitionedSlidingSampler(
                    config.item_cut, config.user_cut, config.skip_cuts,
                    counters=self.counters)
            else:
                self.sampler = SlidingBasketSampler(
                    config.item_cut, config.user_cut, config.skip_cuts,
                    counters=self.counters)
        elif config.partition_sampling:
            # Needs the multi-controller runtime up before process_index()
            # is meaningful; idempotent with the scorer's own init.
            from .parallel.distributed import init_multihost
            from .sampling.multihost import ProcessPartitionedSampler

            init_multihost(config.coordinator, config.num_processes,
                           config.process_id)
            self.sampler = ProcessPartitionedSampler(
                config.user_cut, config.seed, config.skip_cuts,
                counters=self.counters)
        else:
            self.sampler = UserReservoirSampler(
                config.user_cut, config.seed, config.skip_cuts,
                counters=self.counters)
        self.scorer = scorer if scorer is not None else self._make_scorer()
        # Incremental-checkpoint job-side dirty tracker (state/delta.py):
        # users touched per fired window + vocab-length cursors. None =
        # incremental off (zero hot-path cost).
        self._ckpt_dirty = None
        if config.checkpoint_incremental:
            # Incremental checkpoints (state/delta.py): arm the store's
            # dirty-row log — the scorer feeds it the same per-window
            # touched-rows set the tiered store's recency clock stamps,
            # and checkpoint.save drains it per generation. Config
            # validation restricted the flag to sparse-family backends,
            # all of which expose a StateStore.
            store = getattr(self.scorer, "store", None)
            if store is None:
                raise ValueError(
                    "--checkpoint-incremental needs a StateStore-backed "
                    "scorer (sparse backends)")
            store.enable_ckpt_dirty()
            from .state.delta import JobDirtyTracker

            self._ckpt_dirty = JobDirtyTracker()
        if self.degrade is not None and config.coordinator is not None:
            # Multi-host degradation (robustness/gang.py plane): every
            # observed window exchanges each host's worst signal
            # (gang-wide max over the overloaded bit, one tiny guarded
            # allgather per window) so all hosts step the ladder
            # identically and sampling stays in lockstep. Wired after
            # scorer construction — its init joined the
            # multi-controller runtime the exchange rides on.
            from .parallel.distributed import allgather_max

            self.degrade.exchange = allgather_max
        # Load-driven autoscaling (--autoscale on, robustness/
        # autoscale.py): the tap votes one packed idle/drain int per
        # window, writes the gang-dir pressure beacon the supervisor's
        # scale policy reads, and flips the drain flag once the whole
        # gang has seen a RESCALE request. Armed only inside a gang
        # worker (gang dir env + multi-controller identity).
        self.autoscale = None
        if config.autoscale == "on" and config.coordinator is not None:
            from . import tuning
            from .robustness.autoscale import AutoscaleTap
            from .robustness.gang import GANG_DIR_ENV

            gang_dir = tuning.env_read(GANG_DIR_ENV)
            if gang_dir:
                self.autoscale = AutoscaleTap(
                    gang_dir, config.process_id, config.num_processes,
                    idle_wall_s=config.degrade_window_wall_s / 4.0)
                if (self.degrade is not None
                        and config.num_processes
                        < config.autoscale_max_workers):
                    # Scale-before-shed precedence: with capacity
                    # headroom the ladder may not leave NORMAL —
                    # sustained pressure is a rescale trigger first.
                    # At max capacity the flag stays False and the
                    # ladder sheds exactly as before. Static per
                    # attempt and identical on every host, so the
                    # multi-host transition lockstep is preserved.
                    # Guarded by the tap arming: a worker launched
                    # outside gang supervision (no gang dir) has no
                    # autoscaler to relieve the pressure, so holding
                    # its ladder would strip ALL shed protection.
                    self.degrade.hold_escalation = True
        if (getattr(self.scorer, "wants_baskets", False)
                and isinstance(self.sampler, UserReservoirSampler)):
            # Fused-window uplink (--fused-window, ops/device_scorer):
            # the sampler hands the scorer un-expanded baskets — host
            # expansion and the 3x-wider COO uplink disappear for
            # fused-routable windows; non-routable ones expand host-side
            # inside the scorer (bit-identical either way). Gated on the
            # tumbling reservoir sampler: sliding/partitioned samplers
            # stay on the expanded-COO contract. Dense backend only
            # (wants_baskets): the sparse fused path keeps the host
            # fold — slot allocation needs the aggregated cells anyway.
            self.sampler.emit_baskets = True
        if config.partition_sampling and not self.sliding:
            # Sliding mode is exempt: its partitioned sampler is stateless
            # (nothing partition-distinct ever reaches a checkpoint).
            import jax

            if (jax.process_count() > 1
                    and not getattr(self.scorer, "process_suffix", "")):
                # Partitioned reservoir snapshots are per-process-distinct;
                # a backend without per-process checkpoint files would have
                # every process clobber the same state.npz (last writer
                # wins, other partitions' reservoirs unrecoverable).
                raise ValueError(
                    "--partition-sampling needs a backend with per-process "
                    "checkpoints: --backend sharded, or sparse with "
                    "--num-shards > 1")
        # results: external item id -> [(external other, score) desc];
        # array-backed, lazily materialized (state/results.py)
        self.latest = LatestResults(self.item_vocab)
        # Online serving plane (--serve-port, serving/): double-buffered
        # zero-lock top-K snapshots swapped at window boundaries plus the
        # per-user history blend behind /recommend. Pure observer of the
        # ingest path: it reads mapped ids and emitted rows, never
        # touches sampling/scorer state — serving on vs off is
        # bit-identical on ingest output (parity-tested at depths 0, 2).
        self.serving = None
        if config.serve_port is not None:
            from .serving import ServingPlane

            self.serving = ServingPlane(
                self.item_vocab, self.user_vocab,
                history_len=config.serve_history,
                query_slo_s=config.serve_query_slo_s)
        # Optional streaming-result hook: called with every materialized
        # window output (dense-id rows, post-absorption) — the consumable
        # form of the reference's continuous emission into its sink
        # (FlinkCooccurrences.java:169-171). None = final-state-only.
        self.on_update = None
        self.emissions = 0
        self.windows_fired = 0
        self.step_timer = StepTimer()
        # The sampling thread's stage clock (sample, ingest-admission):
        # reset per fired window, read into the window's sample_seconds.
        self._sample_clock = StageClock()
        # Tracing plane (observability/journal.py): fleet correlation
        # identity, stamped on every journal record this job writes. A
        # supervising parent mints run_id once and threads it (plus the
        # restart-attempt ordinal) through the env; an unsupervised run
        # mints its own. --run-id overrides for deliberate joins.
        from .observability.journal import run_context
        env_run_id, self.attempt = run_context()
        self.run_id = config.run_id or env_run_id
        self.process_id = int(config.process_id or 0)
        # Boundary-stage seconds (snapshot-publish measured in _absorb,
        # checkpoint-commit in checkpoint()) land AFTER the window's
        # record flushed — they ride the NEXT record as trailing spans
        # (see journal.SPAN_STAGES) and stay out of the core-span
        # wall-seconds reconciliation.
        self._pending_publish_s = 0.0
        self._pending_ckpt_s = 0.0
        # /healthz last_window block: reassigned atomically per window
        # (readers on the HTTP thread only ever see a whole dict).
        self.last_window_health: Optional[dict] = None
        # Flight recorder (observability/journal.py): one flushed JSONL
        # record per fired window. Per-window counter / wire deltas diff
        # against these snapshots; both are read only by whichever thread
        # records windows (the caller thread serially, the scorer worker
        # pipelined), so no extra locking beyond the registries' own.
        self.journal = None
        if config.journal:
            from .observability.journal import RunJournal

            self.journal = RunJournal(config.journal)
            if self.degrade is not None:
                # Durable sink for admission-side (stale-ingest)
                # transitions: they must reach the journal even when no
                # window ever completes again (the stalled-scorer
                # scenario the escalation exists for). RunJournal.record
                # is locked, so the ingest thread may write concurrently
                # with the window-record thread.
                self.degrade.journal_event = self._journal_degrade_event
        self._prev_counters: Dict[str, int] = {}
        self._prev_wire: Dict[str, int] = LEDGER.snapshot()
        # Metrics plane (observability/registry.py): latency/byte
        # distributions behind BENCH tail summaries and /metrics.
        self._hist_sample = REGISTRY.histogram(
            "cooc_window_sample_seconds",
            help="host sampling stage seconds per fired window")
        self._hist_score = REGISTRY.histogram(
            "cooc_window_score_seconds",
            help="scorer stage seconds per fired window")
        self._hist_total = REGISTRY.histogram(
            "cooc_window_total_seconds",
            help="sample+score seconds per fired window")
        self._hist_uplink = REGISTRY.histogram(
            "cooc_window_uplink_bytes", BYTES_BUCKETS,
            help="host->device bytes shipped per fired window")
        self._gauge_windows = REGISTRY.gauge(
            "cooc_windows_fired", help="fired-window ordinal")
        self._gauge_last_window = REGISTRY.gauge(
            "cooc_last_window_unix_seconds",
            help="wall clock of the last fired window "
                 "(healthz staleness input)")
        # Optional file source attached by the CLI so periodic checkpoints
        # snapshot the input offset too (crash recovery resumes mid-stream).
        self.source = None
        # Per-window ingest snapshots (partitioned source only): captured
        # on the sampling thread at window fire — the only thread driving
        # the line generator — then read by _record_window on whichever
        # thread scores that seq (distinct keys; no lock needed).
        self._ingest_by_seq: Dict[int, dict] = {}
        # One in-process feedback channel (the reference counts one queue
        # handshake per subtask open,
        # UserInteractionCounterOneInputStreamOperator.java:109). Sliding
        # mode has no feedback edge (per-window caps, no rejections).
        if not config.skip_cuts and not self.sliding:
            self.counters.add(FEEDBACK_QUEUES, 1)
        # Pipelined execution (--pipeline-depth > 0): the caller thread
        # keeps sampling window N+1 while a worker thread runs the scorer
        # for window N (pipeline.py — the Flink-operator-overlap
        # analogue). Depth 0 is the serial path, bit-identical by the
        # parity tests. The feedback edge stays on the sampling thread,
        # so its between-fires ordering is untouched.
        self.pipeline = None
        if config.pipeline_depth > 0:
            from .pipeline import PipelineDriver

            self.pipeline = PipelineDriver(self, config.pipeline_depth)

    def _maybe_breaker(self, scorer):
        """Wrap a single-process device scorer in the circuit breaker
        (--scorer-breaker-threshold > 0): consecutive dispatch failures
        fail over to the exact host-oracle scorer instead of killing
        the run (config validation restricts the flag to the backends
        where a host fallback is sound)."""
        if self.config.scorer_breaker_threshold <= 0:
            return scorer
        from .robustness.degrade import ScorerCircuitBreaker

        return ScorerCircuitBreaker(
            scorer, self.config.top_k, self.counters,
            threshold=self.config.scorer_breaker_threshold,
            probe_after_windows=self.config.scorer_breaker_probe_windows)

    def _make_scorer(self):
        backend = self.config.backend
        if backend == Backend.HYBRID:
            # Retired round 3: on its flagship config (1M-item Zipfian) the
            # sparse backend measured 2.2x the hybrid's on-chip throughput
            # (71.9k vs 32.1k pairs/s, 2026-07-30, before this round) and
            # covers the same beyond-dense-ceiling vocabularies. The flag
            # stays accepted: checkpoints were interchangeable by design
            # (state/sparse_scorer.py snapshot docstring), so a hybrid
            # checkpoint restores under sparse unchanged. Aliased before
            # any validation so every sparse flag (e.g. --fixed-score)
            # works identically under the alias.
            LOG.warning("--backend hybrid is retired; running the sparse "
                        "backend (checkpoints are interchangeable)")
            backend = Backend.SPARSE
        if backend != Backend.SPARSE and self.config.fixed_score != "auto":
            # An explicit setting the backend cannot honor must not be
            # silently ignored (same rule as the sparse branch's
            # emit-updates conflict). A bogus value is refused first,
            # by the same parse the sparse scorers use.
            from .state.sparse_scorer import resolve_fixed_shapes

            resolve_fixed_shapes(self.config.fixed_score, True)
            raise ValueError(
                f"--fixed-score {self.config.fixed_score} only applies to "
                f"--backend sparse (got {backend.value})")
        if backend == Backend.ORACLE:
            return HostRescorer(self.config.top_k, self.counters,
                                self.config.development_mode)
        if backend == Backend.DEVICE:
            from .ops.device_scorer import DeviceScorer

            # num_items == 0 derives the vocab from the data (the scorer
            # doubles its dense C on growth); an explicit value is a hard
            # capacity check, enforced in add_batch.
            num_items = self.config.num_items
            # defer_results: see the sparse branch below.
            return self._maybe_breaker(DeviceScorer(
                num_items, self.config.top_k, self.counters,
                max_pairs_per_step=self.config.max_pairs_per_step,
                use_pallas=self.config.pallas,
                count_dtype=self.config.count_dtype,
                defer_results=not self.config.emit_updates,
                fused_window=self.config.fused_window))
        if backend == Backend.SPARSE:
            if self.config.num_shards > 1:
                from .parallel.distributed import maybe_multihost_mesh

                # Join the multi-controller runtime BEFORE importing the
                # scorer module: its jits probe the backend at import
                # (ops/donation.py), and jax.distributed.initialize must
                # precede any backend initialization.
                mesh = maybe_multihost_mesh(self.config)
                from .parallel.sharded_sparse import ShardedSparseScorer
                from .state.wire import (resolve_cell_dtype,
                                         resolve_wire_format)

                return ShardedSparseScorer(
                    self.config.top_k, num_shards=self.config.num_shards,
                    counters=self.counters,
                    mesh=mesh,
                    development_mode=self.config.development_mode,
                    score_ladder=self.config.score_ladder,
                    defer_results=not self.config.emit_updates,
                    fixed_score=self.config.fixed_score,
                    use_pallas=self.config.pallas,
                    cell_dtype=resolve_cell_dtype(
                        self.config.cell_dtype, sparse_single_device=False),
                    wire_format=resolve_wire_format(
                        self.config.wire_format,
                        sparse_single_device=False),
                    fused_window=self.config.fused_window)
            if self.config.coordinator is not None:
                # A coordinator with the default single shard would run one
                # full independent job per process (and clobber a shared
                # checkpoint dir) — misconfiguration, not a mode.
                raise ValueError(
                    "--coordinator with --backend sparse needs "
                    "--num-shards > 1 (the sharded-sparse mesh)")
            from .state.sparse_scorer import SparseDeviceScorer
            from .state.wire import resolve_cell_dtype, resolve_wire_format

            # Final-state consumption (no --emit-updates): keep results in
            # a device-resident table and fetch once at flush — per-window
            # result transfer drops to zero (the dominant wall cost of
            # large windows on a high-latency link). Streaming consumers
            # keep the per-window pipeline.
            return self._maybe_breaker(SparseDeviceScorer(
                self.config.top_k, self.counters,
                self.config.development_mode,
                score_ladder=self.config.score_ladder,
                defer_results=not self.config.emit_updates,
                fixed_score=self.config.fixed_score,
                use_pallas=self.config.pallas,
                cell_dtype=resolve_cell_dtype(
                    self.config.cell_dtype, sparse_single_device=True),
                wire_format=resolve_wire_format(
                    self.config.wire_format, sparse_single_device=True),
                spill_threshold_windows=self.config.spill_threshold_windows,
                spill_target_hbm_frac=self.config.spill_target_hbm_frac,
                fused_window=self.config.fused_window))
        if backend == Backend.SHARDED:
            from .parallel.distributed import maybe_multihost_mesh

            # Multi-controller init before the scorer import — see the
            # sharded-sparse branch above.
            mesh = maybe_multihost_mesh(self.config)
            from .parallel.sharded import ShardedScorer

            num_items = self.config.num_items
            # num_items == 0 derives the vocab from the data: the scorer
            # starts small and doubles (resharding) on growth, like the
            # dense backend. Multi-host still needs an explicit capacity
            # (ShardedScorer raises: capacity must agree across processes).
            return ShardedScorer(num_items, self.config.top_k,
                                 num_shards=self.config.num_shards,
                                 counters=self.counters,
                                 mesh=mesh,
                                 count_dtype=self.config.count_dtype,
                                 use_pallas=self.config.pallas)
        raise ValueError(f"unknown backend {backend}")

    # ------------------------------------------------------------------

    def add_batch(self, users: np.ndarray, items: np.ndarray, ts: np.ndarray) -> None:
        """Ingest one parsed interaction batch (stream order)."""
        dense_items = self.item_vocab.map_batch(items)
        if self.config.num_items and len(self.item_vocab) > self.config.num_items:
            raise ValueError(
                f"item vocabulary exceeded --num-items capacity "
                f"({len(self.item_vocab)} > {self.config.num_items})")
        dense_users = self.user_vocab.map_batch(users)
        if self.serving is not None:
            # Feed the per-user history rings on the ingest thread (the
            # blend's "recent history" side; bounded memory per user).
            self.serving.feed(dense_users, dense_items)
        n_late = self.engine.add_batch(dense_users, dense_items, ts)
        if n_late:
            # The reference counts late drops at both cut operators
            # (ItemInteractionCounter...:75-77, UserInteractionCounter...:121-123).
            self.counters.add(ITEM_LATE_ELEMENTS, n_late)
            self.counters.add(USER_LATE_ELEMENTS, n_late)
        if self.config.development_mode:
            self.counters.add(USER_RECEIVED_ELEMENTS, len(users) - n_late)
        self._drain(final=False)

    def finish(self) -> None:
        """End of stream — Watermark(MAX_VALUE) fires everything."""
        try:
            self._finish()
        finally:
            if self.degrade is not None:
                # Drop the process-global controller whatever happened —
                # a failed job must not keep gating a successor's source
                # (instance-checked, so it never evicts a newer job's).
                from .robustness import degrade as degrade_mod

                degrade_mod.uninstall(self.degrade)

    def abort(self) -> None:
        """Best-effort teardown after an externally-raised abort mid-run
        (e.g. the quarantine rate breaker firing inside the ingest
        generator, before ``finish`` was ever reachable): join the
        scorer worker so no daemon thread keeps dispatching, close the
        journal so its tail is durable, and drop the process-global
        degradation controller. Idempotent; never raises over the
        original failure."""
        try:
            if self.pipeline is not None:
                self.pipeline._shutdown_worker()
        finally:
            if self.journal is not None:
                self.journal.close()
            if self.degrade is not None:
                from .robustness import degrade as degrade_mod

                degrade_mod.uninstall(self.degrade)

    def _finish(self) -> None:
        try:
            self._drain(final=True)
        except BaseException:
            if self.pipeline is not None:
                # Join the worker so no daemon thread outlives the job,
                # but keep the in-flight exception as THE failure — a
                # close() here could replace it with the worker's own
                # latched error and point the operator at the wrong one.
                self.pipeline._shutdown_worker()
            raise
        if self.pipeline is not None:
            # Ordered shutdown: the final drain already barriered, so the
            # close is immediate; it also surfaces any latched worker
            # error before the balance check below can mask it.
            self.pipeline.close()
        if (self.config.development_mode
                and not getattr(self.scorer, "process_suffix", "")
                and not getattr(self.scorer, "defer_results", False)
                and not getattr(self.scorer, "trips", 0)):
            # A tripped scorer breaker is exempt too: rows the primary
            # dispatched (and counted) before failing may have been
            # re-scored by the fallback and filtered from the final
            # flush — the imbalance is the documented fidelity trade,
            # not a lost window.
            # Pipeline-drain invariant (the moral equivalent of the
            # reference's buffered-element balance counters,
            # UserInteractionCounterOneInputStreamOperator.java:134-137):
            # every row dispatched into a scorer's result pipeline must be
            # materialized exactly once — a flush that drops or double-
            # emits an in-flight window shows up as a mismatch here.
            # Multi-host processes are exempt: each materializes only the
            # rows its chips own while the dispatch counter sees all rows.
            # Deferred-results backends are exempt too: the scatter into
            # the device table rides the same dispatch as the scoring (no
            # separate pipeline to lose), and a row rescored in N windows
            # materializes once from the table, not N times.
            from .metrics import RESCORED_ITEMS

            rescored = self.counters.get(RESCORED_ITEMS)
            if self.emissions != rescored:
                raise AssertionError(
                    f"result pipeline out of balance: {rescored} rows "
                    f"dispatched but {self.emissions} materialized")
        if self.journal is not None:
            # Every window is recorded by now (the final drain barriered);
            # close so the last line is durably on disk at process exit.
            self.journal.close()

    def run(self, batches: Iterable[InteractionBatch]) -> "LatestResults":
        start = time.monotonic_ns()
        for users, items, ts in batches:
            self.add_batch(users, items, ts)
        self.finish()
        duration_ms = (time.monotonic_ns() - start) // 1_000_000
        # Reference end-of-run logging shape (FlinkCooccurrences.java:179-181).
        LOG.info("Duration\t%d", duration_ms)
        LOG.info("Accumulator results: %s", self.counters)
        LOG.info("Step timing: %s", self.step_timer.summary())
        # Per-stage busy fractions over the wall clock: a serial run sums
        # to <= ~100%, an overlapped pipelined run exceeds it — the
        # one-line visibility of the pipeline win (ROADMAP: host bubble).
        LOG.info("Stage occupancy: %s",
                 self.step_timer.occupancy(duration_ms / 1000.0))
        # Tail visibility in the summary itself (not just dev-mode lines):
        # the slowest windows, JSON-shaped so log scrapers can parse them.
        LOG.info("Slowest windows: %s",
                 json.dumps(self.step_timer.slowest_as_dicts()))
        self.duration_ms = duration_ms
        return self.latest

    # ------------------------------------------------------------------

    def _drain(self, final: bool) -> None:
        for ts, users, items in self.engine.fire_ready(final=final):
            self.windows_fired += 1
            if self.source is not None:
                # Wire position at the fire boundary (sampling thread —
                # the generator is suspended, so the snapshot is exact):
                # the journal's per-window ingest fields, matched by the
                # checkpoint this same boundary commits.
                health = self.source.ingest_health()
                if health is not None:
                    self._ingest_by_seq[self.windows_fired] = health
            if self._ckpt_dirty is not None:
                # Incremental-checkpoint user feed: the reservoir only
                # mutates for this window's users, so they are exactly
                # the sampler-state dirty set (state/delta.py).
                self._ckpt_dirty.users.note(np.unique(users))
            if self.degrade is not None:
                # Apply the level in force to this window's cuts BEFORE
                # sampling (sampling-thread-only writes; identity at
                # NORMAL). Tumbling mode sheds via the item cut only —
                # the user reservoir's kMax is structural state whose
                # mid-run shrink would corrupt eviction deltas.
                if self.sliding:
                    self.sampler.set_effective_cuts(
                        self.degrade.effective_item_cut(self.config.item_cut),
                        self.degrade.effective_user_cut(self.config.user_cut))
                elif not self.config.skip_cuts:
                    self.item_cut.set_effective_cut(
                        self.degrade.effective_item_cut(self.config.item_cut))
                if self.pipeline is None:
                    # Host backends can shed at the heap itself (fewer
                    # offers kept per row). Serial mode only: in
                    # pipelined mode the scorer worker owns the heap and
                    # a producer-side swap would race it — the _absorb
                    # truncation below sheds for that mode instead.
                    setk = getattr(self.scorer, "set_effective_top_k", None)
                    if setk is not None:
                        setk(self.degrade.effective_top_k(
                            self.config.top_k))
            # The window's sampling side: ingest-admission (the item
            # cut) and sample (everything else) on the job's own
            # StageClock; sample_seconds is their sum.
            clk = self._sample_clock
            clk.reset()
            with clk.stage("sample"):
                # Inside the sample stage on purpose: a delay_ms
                # injected here bills the window's wall time, so chaos
                # tests can manufacture exactly the overloaded windows
                # the degradation/autoscale planes key on. (Crash kinds
                # are indifferent to the clock.)
                if faults.PLAN is not None:
                    faults.PLAN.fire("window_fire", seq=self.windows_fired)
                if self.sliding:
                    # The sliding sampler folds admission into its own
                    # fire; no separate admission cut to time.
                    pairs = self.sampler.fire(users, items)
            if not self.sliding:
                # Item cut (or pass-through when --skip-cuts): the
                # journal's ingest-admission span.
                with clk.stage("ingest-admission"):
                    if self.config.skip_cuts:
                        sampled = np.ones(len(items), dtype=bool)
                    else:
                        sampled = self.item_cut.fire(items)
                with clk.stage("sample"):
                    # User reservoir.
                    pairs, feedback_items = self.sampler.fire(users, items, sampled)
                    # Feedback decrements before the next window fire
                    # (ItemInteractionCounterTwoInputStreamOperator.java:94-116).
                    if not self.config.skip_cuts and len(feedback_items):
                        self.item_cut.apply_feedback(
                            feedback_items, self.config.development_mode, self.counters)
            if self.pipeline is not None:
                with clk.stage("sample"):
                    # Pre-fold on the sampling thread for backends that
                    # accept aggregated deltas — the scorer worker's turn
                    # then starts at slot allocation / COO packing.
                    payload, slot, stall = self._stage(pairs)
            sample_seconds = sum(clk.seconds.values())
            admit_seconds = clk.seconds.get("ingest-admission", 0.0)
            if self.pipeline is not None:
                from .pipeline import StagedWindow

                self.pipeline.submit(StagedWindow(
                    ts=ts, payload=payload, events=len(items),
                    raw_pairs=len(pairs),
                    sample_seconds=sample_seconds, slot=slot,
                    seq=self.windows_fired, stall_seconds=stall,
                    admit_seconds=admit_seconds))
            else:
                # Score on the backend.
                if faults.PLAN is not None:
                    faults.PLAN.fire("scorer_dispatch",
                                     seq=self.windows_fired)
                with clock() as score_clock:
                    window_out: WindowTopK = self.scorer.process_window(ts, pairs)
                # Pipelined backends return the previous window's results;
                # they expose the count actually dispatched for this window.
                self._record_window(WindowStats(
                    timestamp=ts, events=len(items), pairs=len(pairs),
                    rows_scored=getattr(self.scorer, "last_dispatched_rows",
                                        len(window_out)),
                    sample_seconds=sample_seconds,
                    score_seconds=score_clock.seconds),
                    seq=self.windows_fired,
                    admit_seconds=admit_seconds)
                self._absorb(window_out)
            checkpointed = (
                self.config.checkpoint_dir
                and self.config.checkpoint_every_windows > 0
                and self.windows_fired
                % self.config.checkpoint_every_windows == 0)
            if checkpointed:
                # checkpoint() barriers the pipeline first, so the
                # snapshot point is identical to the serial path's.
                self.checkpoint(source=self.source)
            if self.autoscale is not None and self.autoscale.drain:
                # Rescale drain boundary (gang-voted this window, so
                # every worker drains HERE): commit a checkpoint under
                # the epoch protocol — unless the periodic save above
                # already committed this exact boundary — journal the
                # AUTOSCALE record, and take the voluntary exit. The
                # rescale_drain site sits between commit and exit: a
                # crash there dies inside the seam, after the state is
                # durable and before the supervisor relaunches.
                from .robustness.autoscale import RescaleDrain

                if not checkpointed:
                    self.checkpoint(source=self.source)
                req = self.autoscale.drain
                self._journal_autoscale(req, self.windows_fired)
                if faults.PLAN is not None:
                    faults.PLAN.fire("rescale_drain",
                                     seq=self.windows_fired)
                raise RescaleDrain(req, self.windows_fired)
        if final:
            if self.pipeline is not None:
                self.pipeline.barrier()
            # Backends with a result pipeline (device) hold the last window's
            # top-K in flight; drain it.
            self._absorb(self._flush_scorer())

    def _stage(self, pairs):
        """Producer-side staging: fold into a ring slot when the backend
        accepts pre-aggregated deltas; raw pass-through otherwise.
        Returns ``(payload, slot, stall_seconds)`` — the stall is the
        producer's wait for a free ring slot (memory-bound backpressure),
        surfaced per window in the journal."""
        if len(pairs) and getattr(self.scorer, "accepts_aggregated", False):
            payload, slot = self.pipeline.ring.stage(pairs)
            return payload, slot, self.pipeline.ring.last_stall_seconds
        return pairs, None, 0.0

    def _build_spans(self, stats: WindowStats,
                     admit_seconds: float) -> list:
        """Carve one window's wall time into ordered journal span tuples
        ``[stage, start_offset_s, seconds]`` (journal.SPAN_STAGES).

        The core stages (journal.CORE_STAGES) partition
        ``sample_seconds + score_seconds`` exactly by construction:
        admission is the timed cut share of sampling (clamped), index /
        uplink-encode / rescore come from the scorer's StageClock
        (clamped into score_seconds, in that order), and dispatch is
        the residual. Boundary stages stashed by the PREVIOUS window's
        post-record work (_absorb publish, checkpoint commit) ride this
        record as trailing spans.
        """
        admit = max(0.0, min(admit_seconds, stats.sample_seconds))
        sc = getattr(self.scorer, "stage_clock", None)
        stage_s = sc.seconds if sc is not None else {}
        carved = {}
        left = stats.score_seconds
        for name in ("index", "uplink-encode", "rescore"):
            carved[name] = max(0.0, min(stage_s.get(name, 0.0), left))
            left -= carved[name]
        carved["dispatch"] = max(0.0, left)
        carved["ingest-admission"] = admit
        carved["sample"] = stats.sample_seconds - admit
        off = 0.0
        spans = []
        for name in CORE_STAGES:
            secs = carved[name]
            spans.append([name, round(off, 9), round(secs, 9)])
            off += secs
        pub, self._pending_publish_s = self._pending_publish_s, 0.0
        ck, self._pending_ckpt_s = self._pending_ckpt_s, 0.0
        if pub > 0.0:
            spans.append(["snapshot-publish", round(off, 9),
                          round(pub, 9)])
            off += pub
        if ck > 0.0:
            spans.append(["checkpoint-commit", round(off, 9),
                          round(ck, 9)])
        return spans

    def _stamp(self, rec: dict) -> dict:
        """Stamp the uniform correlation trio (run_id / process_id /
        attempt) every record type carries — cooc-trace's join keys."""
        rec["run_id"] = self.run_id
        rec["process_id"] = self.process_id
        rec["attempt"] = self.attempt
        return rec

    def _record_window(self, stats: WindowStats, seq: int,
                       ring_depth: int = 0,
                       stall_seconds: float = 0.0,
                       admit_seconds: float = 0.0) -> None:
        """One fired window's observability fan-out: step timer ring,
        latency/byte histograms, liveness gauges, and (when attached)
        one flushed journal record.

        Runs on whichever thread scores windows — the caller thread
        serially, the scorer worker pipelined — so the delta snapshots it
        keeps are single-threaded per mode. Checkpoint uplinks happen on
        the sampling thread between fires; their bytes attribute to the
        next window's wire delta (totals stay exact).
        """
        spans = self._build_spans(stats, admit_seconds)
        stats.stages = {name: secs for name, _off, secs in spans
                        if name in CORE_STAGES}
        sc = getattr(self.scorer, "stage_clock", None)
        stats.counts = dict(sc.counts) if sc is not None else {}
        self.step_timer.record(stats)
        wire = LEDGER.snapshot()
        wire_delta = {k: wire[k] - self._prev_wire.get(k, 0) for k in wire}
        self._prev_wire = wire
        self._prev_counters, counter_delta = self.counters.snapshot_and_diff(
            self._prev_counters)
        self._hist_sample.observe(stats.sample_seconds)
        self._hist_score.observe(stats.score_seconds)
        self._hist_total.observe(stats.seconds)
        self._hist_uplink.observe(wire_delta["h2d_bytes"])
        # Dispatch path: only backends that expose the flag (incl.
        # behind the breaker wrapper) report it.
        fused = getattr(self.scorer, "last_dispatch_fused", None)
        self._gauge_windows.set(seq)
        self._gauge_last_window.set(time.time())
        level = degrade_events = None
        if self.degrade is not None:
            # Feed the controller this window's health signals; any
            # transition it applies is journaled on this very record.
            level, degrade_events = self.degrade.observe_window(
                wall_seconds=stats.seconds, ring_depth=ring_depth,
                ring_capacity=(self.pipeline.depth
                               if self.pipeline is not None else 0),
                stall_seconds=stall_seconds)
        if self.autoscale is not None:
            # Autoscale vote + pressure beacon (one guarded allgather;
            # every process, every window, in the same order — right
            # after the controller's own vote). The pressure input is
            # the controller's post-exchange gang-max bit.
            self.autoscale.observe(
                seq, stats.seconds,
                self.degrade.overloaded_bit()
                if self.degrade is not None else False)
        # Ingest plane (partitioned source only): the wire position the
        # sampling thread snapshotted when this seq fired — per-partition
        # offsets + lag into the journal, the worst lag onto the gauge.
        ingest = self._ingest_by_seq.pop(seq, None)
        if ingest is not None:
            REGISTRY.gauge(
                "cooc_ingest_partition_lag",
                help="worst per-partition unread bytes on disk at the "
                     "last fired window").set(max(
                         (p["lag"] for p in ingest["partitions"].values()),
                         default=0))
        # /healthz last_window block (observability/http.py): the same
        # stage carve, visible without pulling the journal. One dict
        # reassignment — HTTP-thread readers see whole snapshots only.
        self.last_window_health = {
            "window_seq": seq,
            "seconds": round(stats.seconds, 6),
            "fused": bool(fused) if fused is not None else None,
            "stages": {name: round(secs, 6)
                       for name, _off, secs in spans},
        }
        if self.journal is not None:
            from .observability.journal import VERSION

            rec = {
                "v": VERSION, "seq": seq, "ts": stats.timestamp,
                "events": stats.events, "pairs": stats.pairs,
                "rows_scored": stats.rows_scored,
                "sample_seconds": round(stats.sample_seconds, 6),
                "score_seconds": round(stats.score_seconds, 6),
                "ring_depth": ring_depth,
                "stall_seconds": round(stall_seconds, 6),
                "wall_unix": round(time.time(), 3),
                "counters": counter_delta,
                "wire": wire_delta,
            }
            self._stamp(rec)
            rec["spans"] = spans
            if stats.counts:
                rec["counts"] = stats.counts
            if ingest is not None:
                # The exactly-once ledger: the restored checkpoint's
                # ingest_offsets section must match the last committed
                # window's fields here (the chaos capstone asserts it).
                rec["ingest_offsets"] = {
                    name: {"byte_offset": p["byte_offset"],
                           "records": p["records"]}
                    for name, p in sorted(ingest["partitions"].items())}
                rec["ingest_lag"] = {
                    name: p["lag"]
                    for name, p in sorted(ingest["partitions"].items())}
            if level is not None:
                rec["degradation_level"] = level
                if degrade_events:
                    rec["degrade_events"] = degrade_events
            if fused is not None:
                rec["fused"] = int(fused)
                reason = getattr(self.scorer, "last_fallback_reason",
                                 None)
                if not fused and reason:
                    rec["fallback_reason"] = reason
            fc = getattr(self.scorer, "fused_compilations", None)
            if fc is not None:
                # Cumulative distinct fused-program shapes: a seam or a
                # fresh bucket shows up as a step in this series.
                rec["fused_compiles"] = int(fc)
            if self.serving is not None:
                # Swap bookkeeping: the snapshot generation and row count
                # in force when this record was written (this window's
                # own swap lands just after, in _absorb — the fields
                # therefore read "serving state the queries saw while
                # this window computed", identically at every pipeline
                # depth).
                rec["snapshot_generation"] = self.serving.generation
                rec["snapshot_rows"] = self.serving.rows
            breaker_state = getattr(self.scorer, "breaker_state", None)
            if breaker_state is not None:
                rec["breaker_state"] = breaker_state
            if self.config.coordinator is not None:
                # Gang forensics: the newest epoch this process has
                # committed when the record was written — a restart's
                # journal shows exactly which epoch the gang resumed
                # from.
                from .state.checkpoint import EPOCH_GAUGE

                rec["epoch"] = int(REGISTRY.gauge(EPOCH_GAUGE).get())
            self.journal.record(rec)

    def _journal_degrade_event(self, event: str) -> None:
        """Append one out-of-band degradation event record (the
        admission-side transition path — see journal.EVENT_SCHEMA)."""
        from .observability.journal import VERSION

        self.journal.record(self._stamp(
            {"v": VERSION, "event": event,
             "wall_unix": round(time.time(), 3),
             "window_seq": self.windows_fired}))

    def _journal_ingest_event(self, event: str) -> None:
        """Append one out-of-band ingest event record (a rewritten
        in-flight file dead-lettered, a partition quarantined, a
        partition reassignment on the rescale seam — journal
        EVENT_SCHEMA; cooc-trace annotates the reassign seams)."""
        if self.journal is None:
            return
        from .observability.journal import VERSION

        self.journal.record(self._stamp(
            {"v": VERSION, "event": event,
             "wall_unix": round(time.time(), 3),
             "window_seq": self.windows_fired}))

    def _journal_autoscale(self, request: dict, window: int) -> None:
        """Append the AUTOSCALE drain record (journal.AUTOSCALE_SCHEMA)
        before the voluntary rescale exit: decision, from/to workers,
        trigger signal and the policy cooldown armed by the decision —
        the flight-recorder proof of every scale-before-shed event."""
        if self.journal is None:
            return
        from .observability.journal import VERSION

        self.journal.record(self._stamp({
            "v": VERSION,
            "autoscale": str(request.get("decision", "grow")),
            "from": int(request.get("from", 0)),
            "to": int(request.get("to", 0)),
            "trigger": str(request.get("trigger", "pressure")),
            "window": int(window),
            "cooldown": int(request.get("cooldown", 0)),
            "wall_unix": round(time.time(), 3),
        }))

    def _flush_scorer(self) -> WindowTopK:
        flush = getattr(self.scorer, "flush", None)
        return flush() if flush is not None else []

    def _absorb(self, window_out: WindowTopK) -> None:
        if self.degrade is not None and len(window_out):
            # Result-side shedding (level SHED_K): narrow the emitted
            # top-K at absorption — a host-side slice, so device
            # backends keep their compiled K and nothing recompiles.
            # Row count is untouched (the emissions balance holds).
            k = self.degrade.effective_top_k(self.config.top_k)
            if k < self.config.top_k:
                if isinstance(window_out, TopKBatch):
                    window_out = window_out.truncated(k)
                else:
                    window_out = [(item, top[:k])
                                  for item, top in window_out]
        if isinstance(window_out, TopKBatch):
            self.latest.absorb_batch(window_out)
            self.emissions += len(window_out)
        else:
            for dense_item, top in window_out:
                self.latest.set_row(dense_item, top)
                self.emissions += 1
        if self.serving is not None:
            # Window boundary: fold this window's rows into the build
            # buffer and swap the next read-optimized snapshot in (one
            # atomic reference assignment — readers never lock, never
            # tear). Runs on the absorbing thread (caller serially, the
            # scorer worker pipelined), same single-writer contract as
            # `latest` absorption.
            with clock() as publish_clock:
                if len(window_out):
                    self.serving.absorb(window_out)
                self.serving.publish()
            # Rides the NEXT window record as a trailing
            # snapshot-publish span (journal.SPAN_STAGES): this swap
            # lands after the current record already flushed.
            self._pending_publish_s += publish_clock.seconds
        if self.on_update is not None and len(window_out):
            self.on_update(window_out)

    def checkpoint(self, source=None) -> None:
        from .state import checkpoint as ckpt

        if self.pipeline is not None:
            # Feedback-edge/result ordering forces a sync here: every
            # submitted window must be scored and absorbed before the
            # snapshot, or the scorer state would lag the sampler's.
            self.pipeline.barrier()
        # Results still in the scorer's fetch pipeline belong to already-
        # processed windows; land them in `latest` before snapshotting.
        self._absorb(self._flush_scorer())
        ckpt.save(self, self.config.checkpoint_dir, source=source)
        if self.journal is not None and ckpt.LAST_COMMIT is not None:
            # One out-of-band checkpoint record per commit (journal
            # CKPT_SCHEMA): the commit-cost trajectory — bytes, wall
            # seconds, full-vs-delta and the chain depth — is flight-
            # recorder data, not just a gauge snapshot.
            from .observability.journal import VERSION

            c = ckpt.LAST_COMMIT
            self.journal.record(self._stamp({
                "v": VERSION, "checkpoint": c["gen"], "kind": c["kind"],
                "bytes": int(c["bytes"]),
                "seconds": round(c["seconds"], 6),
                "chain_len": int(c["chain_len"]),
                "wall_unix": round(time.time(), 3),
                # cooc-trace's window -> generation join for freshness:
                # the fired-window ordinal this commit snapshotted, and
                # the uniform generation alias replica records share.
                "window_seq": self.windows_fired,
                "generation": int(c["gen"]),
            }))
            # The commit's wall seconds ride the next window record as
            # a trailing checkpoint-commit span (journal.SPAN_STAGES).
            self._pending_ckpt_s += float(c["seconds"])

    def restore_rescaled(self, gen: int, writers: int,
                         source=None) -> None:
        """Cross-topology gang restore (the autoscale rescale seam):
        land the generation the topology-aware restore vote agreed on,
        written by a ``writers``-process gang, in THIS differently-
        sized gang (state/checkpoint.restore_rescaled merges the old
        per-process blobs and re-buckets onto this run's shards)."""
        from .state import checkpoint as ckpt

        ckpt.restore_rescaled(self, self.config.checkpoint_dir, gen,
                              writers, source=source)
        # Same post-restore bookkeeping as restore() below.
        if self.serving is not None:
            self.serving.seed(self.latest.snapshot())
        self._prev_counters = self.counters.as_dict()
        self._prev_wire = LEDGER.snapshot()

    def restore(self, source=None) -> None:
        from .state import checkpoint as ckpt

        ckpt.restore(self, self.config.checkpoint_dir, source=source)
        if self.serving is not None:
            # Serve the checkpointed rows immediately: a resumed job must
            # not answer /recommend from an empty table until its first
            # post-restore window fires.
            self.serving.seed(self.latest.snapshot())
        # Re-baseline the journal's deltas: the restored counter totals
        # predate this attempt, and the restore itself ships state up
        # (e.g. the sparse slab's restore upload) — neither may be
        # reported as the first post-restore window's own delta.
        self._prev_counters = self.counters.as_dict()
        self._prev_wire = LEDGER.snapshot()
