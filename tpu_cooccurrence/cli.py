"""Command-line entry point.

Mirrors the reference driver (``FlinkCooccurrences.java:36-182``): parse
config, echo it, build and run the job over the file input, then log
duration and the accumulator dump in the reference's format.
"""

from __future__ import annotations

import json
import logging
import os
import sys
from typing import Optional, Sequence

from . import tuning
from .config import Config
from .io.parse import batched_lines
from .io.source import FileMonitorSource
from .job import CooccurrenceJob
from .supervisor import EX_CONFIG, SUPERVISOR_STATE_ENV

LOG = logging.getLogger("tpu_cooccurrence")


def _render_row(item, top) -> str:
    """The output row format (stream and final dump share it)."""
    return f"{item}	" + " ".join(f"{other}:{score:.4f}"
                                  for other, score in top)


def main(argv: Optional[Sequence[str]] = None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        stream=sys.stderr,  # reference logs INFO to stderr (log4j.properties:1-6)
        format="%(asctime)s %(levelname)s %(name)s - %(message)s",
    )
    from .robustness.faults import UnknownFaultSiteError

    try:
        config = Config.from_args(argv)
    except UnknownFaultSiteError as exc:
        # Exit 2 (already in the supervisor's PERMANENT_EXIT_CODES): a
        # typo'd --inject-fault site must kill the run outright, not
        # spend the restart budget on a child that can never arm. The
        # message lists the registered sites (faults.SITES).
        LOG.error("configuration error: %s", exc)
        return 2
    except ValueError as exc:
        # EX_CONFIG (sysexits): a permanent failure the supervisor must
        # not retry — a bad flag does not get better with restarts.
        LOG.error("configuration error: %s", exc)
        return EX_CONFIG

    if config.gang_workers:
        # Gang-supervisor mode (robustness/gang.py — the JobManager
        # analogue): launch/monitor one multi-controller worker per
        # gang slot and gang-restart the WHOLE set from the last
        # committed epoch on any failure. Workers run the job path
        # below with the coordinator flags filled in; their stdouts are
        # spooled and forwarded in process order only on clean exit.
        from .robustness.gang import (GangSupervisor,
                                      check_one_process_per_chip)

        import tempfile

        try:
            check_one_process_per_chip(max(
                config.gang_workers,
                config.autoscale_max_workers
                if config.autoscale == "on" else 0))
        except ValueError as exc:
            LOG.error("configuration error: %s", exc)
            return EX_CONFIG

        raw = list(argv) if argv is not None else sys.argv[1:]
        gang_dir = (os.path.join(config.checkpoint_dir, "gang")
                    if config.checkpoint_dir
                    else tempfile.mkdtemp(prefix="cooc-gang-"))
        scale_policy = None
        if config.autoscale == "on":
            # The supervisor-side half of the autoscaler: the policy
            # reads the workers' pressure beacons from the gang dir and
            # decides target topologies (robustness/autoscale.py).
            from .robustness.autoscale import LadderScalePolicy

            scale_policy = LadderScalePolicy(
                max_workers=config.autoscale_max_workers,
                min_workers=config.autoscale_min_workers,
                trip_windows=config.autoscale_trip_windows,
                clear_windows=config.autoscale_clear_windows,
                cooldown_windows=config.autoscale_cooldown_windows)
            LOG.info("autoscale armed: %d..%d workers, trip=%d "
                     "clear=%d cooldown=%d windows",
                     config.autoscale_min_workers,
                     config.autoscale_max_workers,
                     config.autoscale_trip_windows,
                     config.autoscale_clear_windows,
                     config.autoscale_cooldown_windows)
        if config.inject_fault and any(
                s.startswith("rescale_relaunch")
                for s in config.inject_fault):
            # The rescale_relaunch site fires in THIS (supervisor)
            # process; every other site only ever fires in the job
            # children, which arm their own plans from the pass-through
            # argv — so the supervisor arms only when a spec actually
            # targets its side of the seam. Markers are unqualified
            # (no .p<i>), disjoint from the workers' namespaced ones.
            from .robustness import faults

            faults.arm(config.inject_fault, config.fault_state_dir)
            LOG.warning("fault injection armed in the gang supervisor: "
                        "%s", config.inject_fault)
        LOG.info("gang supervising %d workers (up to %d restart(s); "
                 "heartbeats in %s)", config.gang_workers,
                 config.restart_on_failure, gang_dir)
        return GangSupervisor(
            raw, config.gang_workers,
            attempts=config.restart_on_failure,
            gang_dir=gang_dir,
            stale_after_s=config.gang_stale_after_s,
            delay_s=config.restart_delay_ms / 1000.0,
            backoff_base_s=(config.restart_backoff_base_ms / 1000.0
                            if config.restart_backoff_base_ms > 0
                            else None),
            backoff_max_s=config.restart_backoff_max_ms / 1000.0,
            journal_path=config.journal,
            watchdog_stale_after_s=(config.watchdog_stale_after_s
                                    if config.watchdog_stale_after_s > 0
                                    else None),
            scale_policy=scale_policy).run()

    if config.restart_on_failure > 0:
        # Supervisor mode (Flink restart-strategy analogue, SURVEY §5):
        # respawn the job as a child process on abnormal exit; the child
        # resumes from --checkpoint-dir by itself via the restore path
        # below. The child runs WITHOUT the restart flags.
        from .supervisor import child_argv, supervise

        raw = list(argv) if argv is not None else sys.argv[1:]
        cmd = [sys.executable, "-m", "tpu_cooccurrence.cli"] + child_argv(raw)
        LOG.info("supervising job (up to %d restart(s), delay %d ms)",
                 config.restart_on_failure, config.restart_delay_ms)
        # --journal flows through to the child (it writes the records);
        # the supervisor only reads the tail for crash forensics and the
        # hang watchdog's liveness signal. --inject-fault flows through
        # too: faults fire in the job child, never in the supervisor.
        return supervise(
            cmd, config.restart_on_failure,
            delay_s=config.restart_delay_ms / 1000.0,
            journal_path=config.journal,
            backoff_base_s=(config.restart_backoff_base_ms / 1000.0
                            if config.restart_backoff_base_ms > 0 else None),
            backoff_max_s=config.restart_backoff_max_ms / 1000.0,
            crash_loop_threshold=config.crash_loop_threshold,
            crash_loop_window_s=config.crash_loop_window_s,
            watchdog_stale_after_s=(config.watchdog_stale_after_s
                                    if config.watchdog_stale_after_s > 0
                                    else None),
            checkpoint_dir=config.checkpoint_dir)

    if config.collective_timeout_s > 0:
        # The watchdog reads the env at every collective entry; setting
        # it here (before any backend init) arms the whole process —
        # including collectives issued during scorer construction.
        from .parallel.distributed import COLLECTIVE_TIMEOUT_ENV

        os.environ[COLLECTIVE_TIMEOUT_ENV] = str(
            config.collective_timeout_s)

    if config.inject_fault:
        # Armed only on the job path: a supervising parent passes the
        # specs through to its child instead of firing them itself.
        # process_id resolves site@proc qualifiers (gang chaos: kill
        # exactly worker 1) and namespaces the fired markers so gang
        # workers sharing one --fault-state-dir stay independent.
        from .robustness import faults

        faults.arm(config.inject_fault, config.fault_state_dir,
                   process_id=config.process_id)
        LOG.warning("fault injection armed: %s", config.inject_fault)

    # Gang worker: the supervising parent hands down the gang state dir;
    # start the heartbeat beacon BEFORE job construction so liveness
    # covers jax.distributed startup (a hang there must read as a stale
    # peer, not silence).
    heartbeat = None
    from .robustness.gang import GANG_DIR_ENV, HeartbeatWriter

    gang_dir = tuning.env_read(GANG_DIR_ENV)
    if gang_dir and config.process_id is not None:
        heartbeat = HeartbeatWriter(
            gang_dir, config.process_id,
            interval_s=config.gang_heartbeat_s).start()

    config.log_configuration(LOG)
    if config.degrade:
        LOG.info("graceful degradation armed: wall>%.3fs trips after %d "
                 "windows, clears after %d; shed factor %d; pause %d ms",
                 config.degrade_window_wall_s, config.degrade_trip_windows,
                 config.degrade_clear_windows, config.degrade_shed_factor,
                 config.degrade_pause_ms)
    if config.spill_threshold_windows > 0:
        # Make the tiering unmissable in the run log: cold rows leave
        # HBM, so slab-footprint numbers in the same log read
        # differently from an untiered run (results do not).
        LOG.info("tiered state armed: rows idle for %d windows spill to "
                 "the host arena (target HBM frac %.2f); output stays "
                 "bit-identical to spill-off",
                 config.spill_threshold_windows,
                 config.spill_target_hbm_frac)
    if config.pipeline_depth > 0:
        # Make the execution mode unmissable in the run log: with
        # --emit-updates the result stream is produced by the pipeline's
        # scorer worker (one step behind the device frontier), not the
        # ingest thread — relevant when correlating stdout with stderr
        # timing lines.
        LOG.info("pipelined execution: depth=%d (host sampling overlaps "
                 "device scoring; output is bit-identical to serial)",
                 config.pipeline_depth)

    job = CooccurrenceJob(config)
    # Ingest source selection (--source-format): the file-monitor tail,
    # or the partitioned log whose per-partition offsets commit with the
    # checkpoint (io/partitioned.py). Constructed before the HTTP plane
    # so /healthz can carry the ingest block.
    if config.source_format == "partitioned":
        from .io.partitioned import PartitionedLogSource

        source = PartitionedLogSource(
            config.input, job.counters,
            process_continuously=config.process_continuously,
            expected_partitions=config.ingest_partitions,
            process_id=config.process_id or 0,
            num_processes=config.num_processes or 1)
    else:
        source = FileMonitorSource(
            config.input, job.counters,
            process_continuously=config.process_continuously)
    # The job sees the source unconditionally: checkpoints snapshot its
    # cursor + offsets, and the journal's per-window ingest fields read
    # its health even on checkpoint-less runs.
    job.source = source
    # Supervisor state rides in on an env var (the scrape plane lives in
    # this child process, not the parent): restart/backoff gauges on
    # /metrics, last-restart info on /healthz.
    supervisor_info = None
    raw_state = tuning.env_read(SUPERVISOR_STATE_ENV)
    if raw_state:
        try:
            supervisor_info = json.loads(raw_state)
        except ValueError:
            LOG.warning("unparseable %s=%r; ignoring",
                        SUPERVISOR_STATE_ENV, raw_state)
    metrics_server = None
    serve_server = None
    if config.metrics_port is not None or config.serve_port is not None:
        # Live HTTP plane (observability/http.py): a long-running job is
        # monitorable (--metrics-port) and queryable (--serve-port)
        # without attaching to stdout/stderr. Port 0 binds an ephemeral
        # port; the bound port is in the startup log line.
        from .observability import LEDGER
        from .observability.http import MetricsServer
        from .observability.registry import REGISTRY

        if supervisor_info is not None:
            REGISTRY.gauge(
                "cooc_supervisor_restarts",
                help="restarts the supervising parent has performed "
                     "this run").set(supervisor_info.get("restarts", 0))
            REGISTRY.gauge(
                "cooc_supervisor_backoff_ms",
                help="restart backoff delay the supervisor applied "
                     "before this attempt").set(
                         supervisor_info.get("backoff_ms", 0))
            if "rescales" in supervisor_info:
                # Gang autoscale accounting relayed by the supervisor:
                # voluntary rescales performed so far (the /healthz
                # autoscale block reads this beside the tap's gauges).
                from .robustness.autoscale import RESCALES_GAUGE

                REGISTRY.gauge(
                    RESCALES_GAUGE,
                    help="voluntary gang rescales the supervisor has "
                         "performed this run").set(
                             supervisor_info.get("rescales", 0))
        peers = None
        if gang_dir and config.num_processes:
            # /healthz peers table: heartbeat ages + committed epochs
            # for every gang slot, 503 ("peer_stale") when any peer is
            # stale — the load-balancer drain signal ahead of the gang
            # restart.
            from .robustness.gang import PeerTable

            peers = PeerTable(gang_dir, config.num_processes,
                              stale_after_s=config.gang_stale_after_s,
                              checkpoint_dir=config.checkpoint_dir)
        # /healthz last_window block: the job reassigns the dict whole
        # per window, so the HTTP thread's read is a snapshot.
        last_window = lambda: job.last_window_health  # noqa: E731
        if config.metrics_port is not None:
            metrics_server = MetricsServer(
                REGISTRY, counters=job.counters, ledger=LEDGER,
                port=config.metrics_port,
                stale_after_s=config.healthz_stale_after_s,
                supervisor_info=supervisor_info, peers=peers,
                last_window=last_window,
                ingest=source.ingest_health).start()
        if config.serve_port is not None:
            # The serving endpoint carries the scrape routes too (one
            # port to probe behind a load balancer); --metrics-port may
            # still run its scrape-only twin on a second port.
            serve_server = MetricsServer(
                REGISTRY, counters=job.counters, ledger=LEDGER,
                port=config.serve_port,
                stale_after_s=config.healthz_stale_after_s,
                supervisor_info=supervisor_info,
                serving=job.serving,
                serve_stale_after_s=config.serve_stale_after_s,
                last_window=last_window,
                ingest=source.ingest_health).start()
    # Crash recovery (the reference delegates this to Flink restarts): when
    # a checkpoint exists in --checkpoint-dir, restore it — including the
    # source's exact position, mid-file included — and continue from there.
    # Periodic checkpoints during the run snapshot the source too
    # (job.source).
    if config.checkpoint_dir:
        from .state import checkpoint as ckpt

        if config.coordinator is not None and config.autoscale == "on":
            # Topology-aware restore vote (the autoscale seam): the
            # newest generation may have been committed by a DIFFERENT
            # gang size — agree on the newest generation whose WHOLE
            # writing topology committed, quarantine anything newer
            # across every suffix, then restore either normally (same
            # topology) or through the N->M merge + re-bucket path.
            from .robustness.gang import agree_restore_topology

            try:
                agreed, writers = agree_restore_topology(
                    config.checkpoint_dir, config.process_id)
            except ValueError as exc:
                # Pre-autoscale markers (upgrade hazard): a permanent
                # config-shaped failure — restarting cannot help.
                LOG.error("autoscale restore vote refused: %s", exc)
                return EX_CONFIG
            LOG.info("gang restore vote: committed epoch %d (written "
                     "by %d workers)", agreed, writers)
            if agreed >= 0:
                try:
                    if writers == config.num_processes:
                        job.restore(source=source)
                    else:
                        job.restore_rescaled(agreed, writers,
                                             source=source)
                except ValueError as exc:
                    # A checkpoint the launch flags cannot consume
                    # (e.g. an ingest-offset section written by the
                    # other --source-format) is permanent: restarting
                    # replays the same mismatch.
                    LOG.error("restore refused: %s", exc)
                    return EX_CONFIG
                LOG.info("restored checkpoint from %s "
                         "(windows_fired=%d)", config.checkpoint_dir,
                         job.windows_fired)
        else:
            if config.coordinator is not None:
                # Gang restore vote (robustness/gang.py): agree on the
                # newest generation committed on EVERY host and
                # quarantine anything newer as *.partial — a crash
                # mid-epoch-commit falls back one generation
                # everywhere instead of restoring a torn global state.
                # Runs after job construction (the scorer's init
                # joined the multi-controller runtime the vote's
                # allgather needs).
                from .robustness.gang import agree_restore_generation

                agreed = agree_restore_generation(
                    config.checkpoint_dir,
                    getattr(job.scorer, "process_suffix", ""))
                LOG.info("gang restore vote: committed epoch %d", agreed)
            if ckpt.exists(job, config.checkpoint_dir):
                try:
                    job.restore(source=source)
                except ValueError as exc:
                    LOG.error("restore refused: %s", exc)
                    return EX_CONFIG
                LOG.info("restored checkpoint from %s "
                         "(windows_fired=%d)", config.checkpoint_dir,
                         job.windows_fired)
    if config.emit_updates:
        from .state.results import TopKBatch

        def _stream(window_out) -> None:
            # One line per updated row, as windows materialize — the
            # consumable form of the reference's continuous emission into
            # its sink. on_update fires post-absorption, so job.latest
            # already holds each row in final (external-id, finite-
            # filtered) form — one shared renderer with the final dump.
            if isinstance(window_out, TopKBatch):
                dense_rows = window_out.rows.tolist()
            else:
                dense_rows = [dense for dense, _ in window_out]
            to_ext = job.item_vocab.to_external
            for dense in dense_rows:
                item = to_ext(dense)
                print(_render_row(item, job.latest[item]),
                      flush=config.process_continuously)

        job.on_update = _stream
        if job.windows_fired:
            # Resumed run: replay the restored state so the stream is
            # complete (rows not re-updated after the checkpoint would
            # otherwise never appear). One consistent snapshot — the
            # replay must not interleave with concurrent absorption.
            snap = job.latest.snapshot()
            for item in sorted(snap):
                print(_render_row(item, snap[item]),
                      flush=config.process_continuously)

    # Poison-input quarantine (robustness/quarantine.py): malformed
    # lines divert to the dead-letter file under the rate breaker
    # instead of crashing the job.
    quarantine = None
    if config.quarantine_file:
        from .robustness.quarantine import Quarantine

        quarantine = Quarantine(config.quarantine_file,
                                max_rate=config.max_quarantine_rate,
                                max_bytes=config.max_quarantine_bytes)
        LOG.info("quarantine armed: dead-letter %s, max rate %.2f%%",
                 config.quarantine_file, config.max_quarantine_rate * 100)
    # Arm the source's own dead-letter path (rewritten in-flight files,
    # poisoned partitions) and its journal event hook — after quarantine
    # construction, before the stream starts.
    source.attach(quarantine=quarantine,
                  on_event=job._journal_ingest_event)

    from .observability import xla_trace
    from .robustness.autoscale import RESCALE_EXIT, RescaleDrain
    from .robustness.quarantine import QuarantineRateExceeded
    from .state.sparse_scorer import SlabCapacityError

    try:
        with xla_trace(config.profile_dir):
            # --buffer-timeout bounds how long a parsed line may wait in a
            # partial batch (reference: FlinkCooccurrences.java:46); it only
            # matters when tailing input continuously — process-once runs
            # always flush at end of stream.
            latency = (config.buffer_timeout / 1000.0
                       if config.process_continuously else None)
            job.run(batched_lines(source.lines(), max_latency_s=latency,
                                  origin=source.origin,
                                  quarantine=quarantine))
        if quarantine is not None:
            # End-of-stream verdict (warm-up waived): a short input that
            # was mostly garbage must exit 2, not succeed on its crumbs.
            quarantine.check_final()
    except RescaleDrain as exc:
        # Voluntary rescale exit (robustness/autoscale.py): the drain
        # checkpoint is committed gang-wide and the supervisor is
        # waiting to relaunch this gang at the new size. Tear down
        # cleanly (join workers, seal the journal — the AUTOSCALE
        # record is already on disk) and take the dedicated exit code
        # the supervisor never bills against the restart budget.
        job.abort()
        if heartbeat is not None:
            heartbeat.stop()
        LOG.info("rescale drain complete: %s; exiting %d for the gang "
                 "supervisor to relaunch", exc, RESCALE_EXIT)
        return RESCALE_EXIT
    except QuarantineRateExceeded as exc:
        # Exit 2 (permanent): a systematically malformed input does not
        # get better with supervised restarts — stop the run and point
        # the operator at the dead-letter file. The breaker fires inside
        # the ingest generator, before finish() is reachable: tear the
        # job down explicitly (join the scorer worker, seal the journal,
        # drop the degradation controller).
        job.abort()
        LOG.error("quarantine rate breaker tripped: %s", exc)
        return 2
    except SlabCapacityError as exc:
        # EX_CONFIG (permanent): the stream outgrew the int32 cell-slot
        # space of one slab — a capacity/topology decision (shard it),
        # not a transient failure; restarts would only replay the growth.
        job.abort()
        LOG.error("slab capacity exhausted: %s", exc)
        return EX_CONFIG
    finally:
        if quarantine is not None:
            quarantine.close()

    if config.development_mode:
        for w in job.step_timer.slowest():
            LOG.info("slow window ts=%d events=%d pairs=%d rows=%d "
                     "sample=%.4fs score=%.4fs", w.timestamp, w.events,
                     w.pairs, w.rows_scored, w.sample_seconds, w.score_seconds)

    # Print the latest top-K per item to stdout (the reference's result
    # stream ends in a no-op sink, FlinkCooccurrences.java:169-171; we make
    # the results consumable instead). With --emit-updates the stream
    # already carried every update; skip the duplicate final dump.
    if not config.emit_updates:
        # One consistent point-in-time copy (state/results.snapshot):
        # with --serve-port the query plane may still be reading while
        # this dump runs, and the dump itself must not lock-step every
        # row read against it.
        snap = job.latest.snapshot()
        for item in sorted(snap):
            print(_render_row(item, snap[item]))
    for server in (metrics_server, serve_server):
        if server is not None:
            # A clean shutdown, not a finally: on a crash the daemon
            # thread dies with the process and the supervisor's
            # journal-tail read covers the forensics.
            server.stop()
    if heartbeat is not None:
        # Same rationale: stop only on the clean path — on a crash the
        # daemon beacon dies with the process and the resulting stale
        # heartbeat is exactly the gang supervisor's death signal.
        heartbeat.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
