"""Gang supervision for multi-controller runs.

The reference gets globally-consistent failure recovery for free from
Flink's JobManager/TaskManager runtime: the JobManager detects a dead
TaskManager by heartbeat, cancels the whole job graph, and restarts it
from the last completed (barrier-aligned) checkpoint (SURVEY §0, §2.6).
A JAX multi-controller gang has the same failure shape with none of the
machinery: collectives cannot survive peer loss — a surviving process
does not fail, it *hangs* — so the only sound restart unit is the whole
gang, restored from a checkpoint *every* host committed. This module is
the JobManager analogue, three pieces:

* :class:`GangSupervisor` (CLI ``--gang-workers N``) launches one
  worker process per gang slot on this machine (coordinator on a fresh
  local port per attempt), spools each worker's stdout, and monitors
  all of them: any abnormal exit — or a heartbeat file stale past
  ``--gang-stale-after-s`` — gang-kills the survivors and relaunches
  the whole set after backoff. Workers resume from the last *committed*
  epoch on their own (the restore vote below). Output discipline is the
  single-process supervisor's, per worker: spools are forwarded in
  process order only when the whole gang exits cleanly, so a chaotic
  run's total stdout is bit-identical to an uninterrupted one.

* :class:`HeartbeatWriter` runs inside each worker (armed by the
  ``TPU_COOC_GANG_DIR`` env the supervisor sets): a daemon thread
  touching ``heartbeat.p<i>`` every ``--gang-heartbeat-s`` seconds —
  the liveness signal that catches a worker wedged *outside* a
  collective (the collective-entry watchdog in
  ``parallel/distributed.py`` catches the wedged-``psum`` case and
  exits :data:`~tpu_cooccurrence.parallel.distributed.PEER_LOST_EXIT`).
  Each beat fires the ``peer_heartbeat`` fault site, so chaos tests can
  freeze exactly one process's liveness signal.

* :func:`agree_restore_generation` — the restore vote. Each process
  computes its newest *committed* checkpoint generation (one with an
  ``EPOCH`` marker; see ``state/checkpoint.py`` — under
  ``--checkpoint-incremental`` a generation counts only when its FULL
  delta chain is present and committed, so a torn delta commit can
  never be voted restorable), the gang allgathers the minimum, and
  every process quarantines anything newer as ``*.partial`` (delta
  files included). A crash anywhere between the first per-host
  generation rename and the last epoch marker therefore drags every
  host back to the same previous epoch — never a torn global restore
  (``test_gang_incremental_ckpt_mid_delta_crash_bit_identical``).

The ``peers`` table on ``/healthz`` (:class:`PeerTable`) reads the same
heartbeat files plus each suffix's committed-epoch markers, and turns a
stale peer into a 503 so a load balancer drains the process before the
gang restart lands.

:class:`ReplicaFleetSupervisor` is the SERVING gang (ISSUE 13): the
same spawn/heartbeat/liveness machinery supervising a fleet of read
replicas (``serving/replica.py``) under the opposite restart policy —
replicas hold no collectives, so a dead replica relaunches alone and
re-syncs itself while the rest of the fleet keeps serving.
"""

from __future__ import annotations

import json
import logging
import os
import random
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import List, Optional, Sequence

from ..observability.registry import REGISTRY
from .. import tuning
from . import autoscale, faults

LOG = logging.getLogger("tpu_cooccurrence.gang")

#: Env var carrying the gang state directory (heartbeat files) into the
#: workers; its presence is what arms the worker-side heartbeat thread.
GANG_DIR_ENV = "TPU_COOC_GANG_DIR"

#: The robustness plane's process-qualified fault sites (registered in
#: ``faults.SITES``; the cooclint ``gang-fault-sites`` rule holds this
#: tuple to the registry and to live fire() call sites). The two
#: ``rescale_*`` sites bracket the autoscaler's rescale seam
#: (robustness/autoscale.py): drain-commit → voluntary exit → relaunch.
#: The two ingest sites cover the exactly-once wire plane:
#: ``offset_commit`` fires when a generation's ingest offset section is
#: durable, ``partition_reassign`` when a rescaled restore re-derives
#: partition ownership at the new topology.
GANG_SITES = ("barrier_enter", "ckpt_commit", "peer_heartbeat",
              "rescale_drain", "rescale_relaunch", "offset_commit",
              "partition_reassign")

#: Stale-peer gauge refreshed by :meth:`PeerTable.snapshot` (the
#: /healthz scrape): peers whose heartbeat age exceeded the threshold.
STALE_PEERS_GAUGE = "cooc_gang_stale_peers"

#: Grace before a worker's FIRST heartbeat counts toward staleness:
#: interpreter + jax.distributed startup must not read as peer death.
HEARTBEAT_START_GRACE_S = 30.0

#: Supervisor poll period while the gang runs.
_POLL_S = 0.2


def heartbeat_path(gang_dir: str, process_id: int) -> str:
    return os.path.join(gang_dir, f"heartbeat.p{process_id}")


class HeartbeatWriter:
    """Worker-side liveness beacon: touch ``heartbeat.p<i>`` every
    ``interval_s`` seconds from a daemon thread.

    The write is a whole-file rewrite (tiny payload: beat ordinal +
    wall clock), not an ``os.utime``, so a reader can also see *what*
    the worker last reported; the mtime is the liveness signal. Each
    beat fires the ``peer_heartbeat`` fault site (seq = beat ordinal) —
    ``peer_heartbeat@1:3:delay_ms:600000`` freezes worker 1's beacon at
    beat 3, the deterministic "silently wedged peer" injection.
    """

    def __init__(self, gang_dir: str, process_id: int,
                 interval_s: float = 5.0) -> None:
        if interval_s <= 0:
            raise ValueError(f"interval_s must be positive, got "
                             f"{interval_s}")
        self.gang_dir = gang_dir
        self.process_id = process_id
        self.interval_s = interval_s
        self.beats = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        os.makedirs(gang_dir, exist_ok=True)

    def beat(self) -> None:
        """One heartbeat write (also the unit-test entry point)."""
        self.beats += 1
        if faults.PLAN is not None:
            faults.PLAN.fire("peer_heartbeat", seq=self.beats)
        path = heartbeat_path(self.gang_dir, self.process_id)
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                f.write(json.dumps({"beat": self.beats,
                                    "wall_unix": round(time.time(), 3)}))
            os.replace(tmp, path)
        except OSError as exc:
            # Liveness reporting must never kill the worker it reports
            # on; a missed beat reads as staleness, which is the truth.
            LOG.warning("heartbeat write failed: %s", exc)

    def _run(self) -> None:
        while not self._stop.is_set():
            self.beat()
            self._stop.wait(self.interval_s)

    def start(self) -> "HeartbeatWriter":
        self._thread = threading.Thread(
            target=self._run, name="cooc-gang-heartbeat", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join()
            self._thread = None


class PeerTable:
    """Read-only view of the gang for ``/healthz``: per-process
    heartbeat age and committed epoch.

    Reads only the filesystem (heartbeat files + ``EPOCH.p<i>.<gen>``
    markers), so it is safe inside the HTTP handler thread and needs no
    cross-process plumbing. A peer with no heartbeat file yet reports
    ``age_seconds: null`` and counts as stale only after a startup
    grace from table construction.
    """

    def __init__(self, gang_dir: str, num_processes: int,
                 stale_after_s: float,
                 checkpoint_dir: Optional[str] = None) -> None:
        self.gang_dir = gang_dir
        self.num_processes = num_processes
        self.stale_after_s = stale_after_s
        self.checkpoint_dir = checkpoint_dir
        self._started_unix = time.time()

    def snapshot(self) -> "tuple[list, bool]":
        """``(rows, any_stale)`` — one row per gang slot."""
        import re

        now = time.time()
        in_grace = (now - self._started_unix
                    <= max(self.stale_after_s, HEARTBEAT_START_GRACE_S))
        # One checkpoint-dir listing serves every gang slot: a load
        # balancer probes /healthz every few seconds, and N listdir
        # scans of a generation-filled directory per probe adds up.
        epochs_by_pid: "dict[int, int]" = {}
        if self.checkpoint_dir:
            pat = re.compile(r"^EPOCH\.p(\d+)\.(\d+)$")
            try:
                names = os.listdir(self.checkpoint_dir)
            except OSError:
                names = []
            for m in filter(None, map(pat.match, names)):
                pid, gen = int(m.group(1)), int(m.group(2))
                epochs_by_pid[pid] = max(epochs_by_pid.get(pid, -1), gen)
        rows, any_stale = [], False
        for pid in range(self.num_processes):
            try:
                age = now - os.path.getmtime(
                    heartbeat_path(self.gang_dir, pid))
            except OSError:
                age = None
            epoch = epochs_by_pid.get(pid, -1)
            if self.stale_after_s <= 0:
                # 0 = staleness handling off (matches the gang
                # supervisor's _stale_worker): never drain on age.
                stale = False
            else:
                stale = (age > self.stale_after_s if age is not None
                         else not in_grace)
            any_stale = any_stale or stale
            rows.append({
                "process": pid,
                "heartbeat_age_seconds": (round(age, 3)
                                          if age is not None else None),
                "committed_epoch": epoch,
                "stale": stale,
            })
        REGISTRY.gauge(
            STALE_PEERS_GAUGE,
            help="gang peers whose heartbeat age exceeds "
                 "--gang-stale-after-s (healthz drain signal)").set(
                     sum(r["stale"] for r in rows))
        return rows, any_stale


def agree_restore_generation(directory: str, suffix: str,
                             exchange=None) -> int:
    """The gang's restore vote; returns the agreed generation (-1 =
    fresh start) after quarantining anything newer on this host.

    Each process contributes its newest committed generation
    (``checkpoint.newest_committed`` — the newest ``EPOCH``-marked one
    whose delta chain, if any, is fully present and committed; or, for
    a pre-epoch legacy directory with no markers at all, the newest
    generation file); the gang-wide MINIMUM wins, because a generation
    missing a marker on *any* host may be a torn global commit.
    Generations above the agreed one are moved aside as ``*.partial``
    (their delta files too) so no later walk can restore them.

    ``exchange`` is the min-vote collective (injectable for tests);
    default is the watchdog-guarded
    :func:`~tpu_cooccurrence.parallel.distributed.allgather_min`.
    """
    from ..state import checkpoint as ckpt

    local = ckpt.newest_committed(directory, suffix)
    if exchange is None:
        from ..parallel.distributed import allgather_min

        exchange = allgather_min
    agreed = int(exchange(local))
    if agreed < local:
        LOG.warning(
            "gang restore vote: this host committed generation %d but "
            "the gang agreed on %d (a peer's commit is missing) — "
            "quarantining the newer generation(s)", local, agreed)
    quarantined = ckpt.quarantine_uncommitted(directory, suffix, agreed)
    if quarantined:
        LOG.warning("gang restore vote: quarantined generation(s) %s "
                    "for suffix %r", quarantined, suffix)
    return agreed


def agree_restore_topology(directory: str, process_id: int,
                           exchange=None, barrier=None
                           ) -> "tuple[int, int]":
    """Topology-aware restore vote (autoscale gangs): returns
    ``(agreed_gen, writers)`` — the newest generation committed by its
    WHOLE writing topology, which may differ from the topology voting
    (the rescale seam's defining property). ``(-1, 0)`` = fresh start.

    The per-host candidate list comes from epoch markers + directory
    listings alone (``checkpoint.topology_committed_generations``);
    the gang still exchanges the minimum — on the shared directory all
    hosts compute the same value, and the collective doubles as the
    rendezvous that keeps peers from racing the quarantine below.
    Process 0 then quarantines every generation above the agreed one
    across ALL suffixes (current and retired topologies alike), and a
    barrier holds the peers until the renames are durable — no peer
    may walk the directory while files are moving aside.

    ``exchange``/``barrier`` are injectable for tests; defaults are the
    watchdog-guarded collectives.
    """
    from ..state import checkpoint as ckpt

    cands = ckpt.topology_committed_generations(directory)
    local, writers = cands[0] if cands else (-1, 0)
    if not cands:
        # Upgrade hazards: voting -1 over a directory that actually
        # holds COMMITTED state would quarantine all of it and
        # silently restart from zero. Two shapes must refuse loudly:
        # topology-less markers (pre-autoscale commits — guessing the
        # topology from marker counts would qualify a torn legacy
        # commit), and per-process generation files with NO markers at
        # all (pre-epoch-commit legacy, which the fixed-topology vote
        # restores with a warning). A dir with SOME new-format markers
        # but no complete topology is a genuinely torn commit history
        # and proceeds to the quarantine below.
        if ckpt.has_legacy_epoch_markers(directory):
            raise ValueError(
                f"--autoscale on found pre-autoscale epoch markers in "
                f"{directory}: run one checkpoint cycle at a fixed "
                f"topology with the current version (its markers "
                f"record the writing process count) before enabling "
                f"the autoscaler")
        if (not ckpt.has_epoch_markers(directory)
                and ckpt.process_suffixes(directory)):
            raise ValueError(
                f"--autoscale on found per-process checkpoint files "
                f"but no epoch markers in {directory} (pre-epoch-"
                f"commit legacy, or a gang that never finished its "
                f"first commit): restore once at a fixed topology — "
                f"or clear the directory — before enabling the "
                f"autoscaler")
    if exchange is None:
        from ..parallel.distributed import allgather_min

        exchange = allgather_min
    if barrier is None:
        from ..parallel.distributed import gang_barrier

        barrier = gang_barrier
    agreed = int(exchange(local))
    if agreed != local:
        LOG.warning(
            "topology restore vote: this host saw committed generation "
            "%d but the gang agreed on %d — taking the minimum", local,
            agreed)
        writers = next((w for g, w in cands if g == agreed), 0)
        if writers == 0 and agreed >= 0:
            # The agreed generation was not in this host's candidate
            # snapshot (stale directory view — e.g. NFS attribute-cache
            # lag). Re-list once; if it is still invisible, fail THIS
            # attempt loudly (a transient, restartable error) rather
            # than limping into a zero-writer restore.
            writers = next(
                (w for g, w in
                 ckpt.topology_committed_generations(directory)
                 if g == agreed), 0)
        if writers == 0 and agreed >= 0:
            raise RuntimeError(
                f"topology restore vote agreed on generation {agreed} "
                f"but this host cannot see its committed markers "
                f"(stale directory view?) — failing the attempt for "
                f"the supervisor to retry")
    if process_id == 0:
        # One host sweeps: peers would race each other's renames on the
        # shared directory, and the quarantine set is identical anyway.
        for sfx in ckpt.process_suffixes(directory):
            quarantined = ckpt.quarantine_uncommitted(directory, sfx,
                                                      agreed)
            if quarantined:
                LOG.warning(
                    "topology restore vote: quarantined generation(s) "
                    "%s for suffix %r (agreed epoch %d)", quarantined,
                    sfx, agreed)
    barrier(f"rescale-vote/{agreed}")
    return agreed, writers


# -- the gang supervisor (parent side) ---------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


#: Per-process output files a gang must split by worker: a shared
#: append-mode file would interleave two processes' records.
_PER_PROCESS_FLAGS = ("--journal", "--quarantine-file")


def gang_child_argv(argv: Sequence[str], process_id: int,
                    num_processes: int, coordinator: str) -> List[str]:
    """One worker's argv: the supervisor's flags stripped (including the
    gang's own — a worker must not recurse into supervision), the
    multi-controller identity appended, and per-process output paths
    (``--journal``, ``--quarantine-file``) suffixed ``.p<i>``."""
    from ..supervisor import child_argv

    out: List[str] = []
    suffix_next = False
    for a in child_argv(argv):
        if suffix_next:
            a = f"{a}.p{process_id}"
            suffix_next = False
        elif a in _PER_PROCESS_FLAGS:
            suffix_next = True
        else:
            for flag in _PER_PROCESS_FLAGS:
                if a.startswith(flag + "="):
                    a = f"{a}.p{process_id}"
                    break
        out.append(a)
    out += ["--coordinator", coordinator,
            "--num-processes", str(num_processes),
            "--process-id", str(process_id)]
    return out


class _Worker:
    """One gang slot's live state: process, spool, liveness baselines."""

    def __init__(self, proc: "subprocess.Popen", spool,
                 spawned_monotonic: float,
                 journal_path: Optional[str] = None) -> None:
        self.proc = proc
        self.spool = spool
        self.spawned = spawned_monotonic
        # Journal-staleness watchdog state (same liveness signal as the
        # single-process supervisor's): size at spawn, growth marks
        # activity.
        from ..supervisor import _journal_size

        self.journal_path = journal_path
        self.journal_size = _journal_size(journal_path)
        self.journal_activity = spawned_monotonic
        self.journal_grew = False


def check_one_process_per_chip(max_workers: int, env=None) -> None:
    """Refuse a gang whose workers would share this host's chips.

    The gang launches every worker on THIS host, and a JAX process that
    is not pinned to the CPU claims every local accelerator chip: the
    second worker would fail or hang on chips the first one holds. So a
    multi-worker gang must run pinned to the CPU (``JAX_PLATFORMS=cpu``,
    the test and rehearsal setting); on a chip host, one process drives
    all local chips (``--num-shards N`` without ``--gang-workers``).
    Raises ValueError (the CLI's exit 78) before any worker starts."""
    env = os.environ if env is None else env
    if max_workers > 1 and env.get("JAX_PLATFORMS", "").strip() != "cpu":
        raise ValueError(
            f"--gang-workers would start up to {max_workers} JAX "
            f"processes on this host, and each would claim every local "
            f"accelerator chip; one process drives all local chips "
            f"(--backend sparse --num-shards N without --gang-workers), "
            f"or set JAX_PLATFORMS=cpu for a CPU gang")


class GangSupervisor:
    """Launch, monitor, gang-kill and gang-restart a multi-controller
    worker set (see the module docstring for the contract).

    ``argv`` is the operator's full CLI argv; each attempt derives the
    per-worker argv via :func:`gang_child_argv` with a fresh local
    coordinator port (a dead gang's port may linger in TIME_WAIT).
    ``attempts`` is the restart budget (``--restart-on-failure``);
    permanent exit codes (usage/config) are never retried.
    """

    def __init__(self, argv: Sequence[str], num_workers: int,
                 attempts: int, gang_dir: str,
                 stale_after_s: float = 60.0,
                 delay_s: float = 1.0,
                 backoff_base_s: Optional[float] = None,
                 backoff_max_s: float = 30.0,
                 timeout_s: Optional[float] = None,
                 stdout=None,
                 journal_path: Optional[str] = None,
                 watchdog_stale_after_s: Optional[float] = None,
                 python: Optional[Sequence[str]] = None,
                 scale_policy=None) -> None:
        if num_workers < 2:
            raise ValueError(
                f"a gang needs >= 2 workers, got {num_workers}")
        self.argv = list(argv)
        self.num_workers = num_workers
        self.attempts = attempts
        self.gang_dir = gang_dir
        self.stale_after_s = stale_after_s
        self.delay_s = delay_s
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        self.timeout_s = timeout_s
        self.stdout = stdout
        # Per-worker journal staleness (the hang watchdog's liveness
        # signal, ``--watchdog-stale-after-s``): heartbeat files prove a
        # worker process is ALIVE; journal growth proves it is making
        # WINDOW PROGRESS. A worker wedged outside a guarded collective
        # (alive, beating, not firing windows) is only caught here.
        self.journal_path = journal_path
        self.watchdog_stale_after_s = watchdog_stale_after_s
        #: Command prefix for one worker (overridable in tests).
        self.python = list(python) if python is not None else [
            sys.executable, "-m", "tpu_cooccurrence.cli"]
        # Load-driven autoscaling (robustness/autoscale.py, --autoscale
        # on): the policy reads the workers' pressure beacons from the
        # gang dir and decides target topologies; the supervisor turns a
        # decision into a RESCALE request beacon, treats the workers'
        # voluntary drain exits as "relaunch at the new size, free of
        # charge", and keeps the pending target across a crash inside
        # the seam (the topology-aware restore vote restores whatever
        # topology last committed, at whatever size we relaunch).
        self.scale_policy = scale_policy
        self.rescales = 0
        self._pending: Optional[dict] = None
        # Tracing correlation: one run id for the whole gang's lifetime
        # — every worker, every restart attempt, every rescale topology
        # journals under it (inherited when an outer parent already
        # minted one).
        from ..observability.journal import RUN_ID_ENV, mint_run_id
        self.run_id = tuning.env_read(RUN_ID_ENV) or mint_run_id()
        os.makedirs(gang_dir, exist_ok=True)

    # -- one attempt ---------------------------------------------------

    def _spawn(self, restarts: int, last_rc: int,
               backoff_s: float) -> List[_Worker]:
        from ..supervisor import SUPERVISOR_STATE_ENV

        # Clear the previous attempt's heartbeat and pressure files: a
        # dead gang's recent mtimes must not vouch for the new gang's
        # liveness, and a dead gang's load signals must not feed the
        # scale policy. (Beacons beyond num_workers too: a decayed gang
        # leaves the retired slots' files behind.)
        for name in os.listdir(self.gang_dir):
            if name.startswith(("heartbeat.p", "pressure.p")):
                try:
                    os.remove(os.path.join(self.gang_dir, name))
                except OSError:
                    pass
        if self._pending is None:
            # A stale RESCALE request (the gang dir persists under the
            # checkpoint dir across supervisor runs) must not make a
            # fresh gang drain on sight.
            try:
                os.remove(autoscale.request_path(self.gang_dir))
            except OSError:
                pass
        from ..observability.journal import ATTEMPT_ENV, RUN_ID_ENV

        coordinator = f"127.0.0.1:{_free_port()}"
        env = dict(os.environ)
        env[GANG_DIR_ENV] = self.gang_dir
        env[RUN_ID_ENV] = self.run_id
        env[ATTEMPT_ENV] = str(restarts)
        env[SUPERVISOR_STATE_ENV] = json.dumps({
            "restarts": restarts,
            "last_rc": last_rc,
            "backoff_ms": int(backoff_s * 1000) if restarts else 0,
            "last_restart_unix": round(time.time(), 3) if restarts else 0,
            "stepped_back": False,
            "rescales": self.rescales,
            "target_workers": self.num_workers,
            "run_id": self.run_id,
            "attempt": restarts,
        })
        workers = []
        now = time.monotonic()
        for pid in range(self.num_workers):
            cmd = self.python + gang_child_argv(
                self.argv, pid, self.num_workers, coordinator)
            spool = tempfile.TemporaryFile()
            proc = subprocess.Popen(cmd, stdout=spool, env=env)
            workers.append(_Worker(
                proc, spool, now,
                journal_path=(f"{self.journal_path}.p{pid}"
                              if self.journal_path else None)))
        LOG.info("gang attempt spawned: %d workers, coordinator %s",
                 self.num_workers, coordinator)
        return workers

    def _kill_gang(self, workers: List[_Worker]) -> None:
        from ..supervisor import _kill_child

        for w in workers:
            if w.proc.poll() is None:
                _kill_child(w.proc)

    def _stale_worker(self, workers: List[_Worker]) -> Optional[int]:
        """Process id of a worker whose heartbeat went stale, or None.

        Before a worker's first beat, staleness is measured from its
        spawn against ``max(stale_after_s, startup grace)`` — jax
        startup is not peer death.
        """
        if self.stale_after_s <= 0:
            return None
        now_mono = time.monotonic()
        now_wall = time.time()
        for pid, w in enumerate(workers):
            if w.proc.poll() is not None:
                # Exited workers have no liveness to report: a clean
                # exit froze its heartbeat legitimately (peers may
                # still be finishing a skewed tail), and an abnormal
                # one is _watch's failed-check's business, not ours.
                continue
            try:
                age = now_wall - os.path.getmtime(
                    heartbeat_path(self.gang_dir, pid))
                threshold = self.stale_after_s
            except OSError:
                age = now_mono - w.spawned
                threshold = max(self.stale_after_s,
                                HEARTBEAT_START_GRACE_S)
            if age > threshold:
                return pid
        return None

    def _watch(self, workers: List[_Worker]) -> int:
        """Wait for a gang verdict: 0 = every worker exited cleanly;
        :data:`autoscale.RESCALE_EXIT` = the whole gang drained
        voluntarily for a rescale (never a failure); other nonzero =
        the first failure's exit code (the survivors are gang-killed —
        their collectives can never complete without the dead peer);
        124 = overall timeout or stale heartbeat."""
        start = time.monotonic()
        while True:
            codes = [w.proc.poll() for w in workers]
            # A voluntary rescale exit is not a death: its peers are
            # commits away from the same exit (the drain boundary was
            # gang-voted), so keep waiting for them instead of
            # gang-killing a checkpointing worker mid-commit.
            failed = next(
                (rc for rc in codes if rc is not None
                 and rc not in (0, autoscale.RESCALE_EXIT)), None)
            if failed is not None:
                LOG.error("gang worker died with rc=%d; gang-killing "
                          "the survivors (a lost peer invalidates every "
                          "surviving process's collectives)", failed)
                self._kill_gang(workers)
                return failed
            if all(rc is not None for rc in codes):
                if all(rc == 0 for rc in codes):
                    return 0
                if all(rc == autoscale.RESCALE_EXIT for rc in codes):
                    return autoscale.RESCALE_EXIT
                # Mixed 0 / RESCALE_EXIT: the lockstep drain vote makes
                # this unreachable short of a bug — treat it as one
                # failed attempt (the restore vote re-synchronizes).
                LOG.error("gang exited with mixed clean/rescale codes "
                          "%s; counting a failed attempt", codes)
                return autoscale.RESCALE_EXIT
            if (self.scale_policy is not None
                    and self._pending is None):
                try:
                    self._poll_autoscale()
                except Exception:
                    # A broken policy must abort the RUN, not linger:
                    # the workers hold the degradation ladder at
                    # NORMAL on the promise that rescaling exists —
                    # continuing without it would leave sustained
                    # overload with no relief of either kind.
                    LOG.exception(
                        "scale policy failed; aborting the gang (its "
                        "workers hold the shed ladder on the promise "
                        "of rescaling)")
                    self._kill_gang(workers)
                    raise
            if (self.timeout_s is not None
                    and time.monotonic() - start > self.timeout_s):
                LOG.error("gang exceeded timeout_s=%.1f; gang-killing",
                          self.timeout_s)
                self._kill_gang(workers)
                return 124
            stale = self._stale_worker(workers)
            if stale is not None:
                LOG.error("gang worker %d heartbeat stale past %.1fs; "
                          "gang-killing for a whole-gang restart",
                          stale, self.stale_after_s)
                self._kill_gang(workers)
                return 124
            wedged = self._stale_journal(workers)
            if wedged is not None:
                LOG.error("gang worker %d journal stale past %.1fs "
                          "(alive but not firing windows — a silently "
                          "wedged peer); gang-killing for a whole-gang "
                          "restart", wedged, self.watchdog_stale_after_s)
                self._kill_gang(workers)
                return 124
            time.sleep(_POLL_S)

    def _poll_autoscale(self) -> None:
        """Feed the freshest pressure beacon to the scale policy and
        turn a decision into the RESCALE request beacon.

        The beacons carry GANG-WIDE bits and consecutive-run counters
        (the workers vote them per window, robustness/autoscale.py), so
        one beacon — whichever reports the newest window — is a
        complete, lossless signal; reading all of them just tolerates a
        lagging writer."""
        freshest = None
        for pid in range(self.num_workers):
            b = autoscale.read_json(
                autoscale.beacon_path(self.gang_dir, pid))
            if b is None or "window" not in b:
                continue
            if freshest is None or b["window"] > freshest["window"]:
                freshest = b
        if freshest is None:
            return
        decision = self.scale_policy.decide(
            int(freshest["window"]),
            bool(freshest.get("overloaded")),
            bool(freshest.get("idle")),
            int(freshest.get("bad_run", 0)),
            int(freshest.get("idle_run", 0)),
            self.num_workers)
        if decision is None or decision.target == self.num_workers:
            return
        self._pending = {
            "to": int(decision.target),
            "from": self.num_workers,
            "decision": decision.decision,
            "trigger": decision.trigger,
            "window": int(decision.window),
            "cooldown": int(decision.cooldown),
            "seq": self.rescales + 1,
        }
        autoscale.write_json(autoscale.request_path(self.gang_dir),
                             self._pending)
        LOG.warning(
            "autoscale decision: %s %d -> %d workers (trigger=%s at "
            "window %d); RESCALE request beacon written — workers drain "
            "a checkpoint at the next gang-voted window boundary",
            decision.decision, self.num_workers, decision.target,
            decision.trigger, decision.window)

    def _apply_rescale(self, target: int) -> None:
        """Commit a pending topology change before the next spawn."""
        try:
            os.remove(autoscale.request_path(self.gang_dir))
        except OSError:
            pass
        if self.num_workers != target:
            LOG.info("gang topology: %d -> %d workers", self.num_workers,
                     target)
        self.num_workers = target
        self._pending = None
        if self.scale_policy is not None:
            self.scale_policy.rescaled(target)

    def _stale_journal(self, workers: List[_Worker]) -> Optional[int]:
        """Process id of a worker whose journal stopped growing past
        ``watchdog_stale_after_s``, or None. Same semantics as the
        single-process supervisor's hang watchdog: the first growth
        must exceed the 1-byte torn-tail seal, and a startup grace
        covers imports + jax.distributed rendezvous + restore."""
        if not self.watchdog_stale_after_s or not self.journal_path:
            return None
        now = time.monotonic()
        from ..supervisor import WATCHDOG_START_GRACE_S, _journal_size

        for pid, w in enumerate(workers):
            if w.proc.poll() is not None:
                continue  # exited: no window progress to demand
            size = _journal_size(w.journal_path)
            if size > w.journal_size + (0 if w.journal_grew else 1):
                w.journal_size = size
                w.journal_activity = now
                w.journal_grew = True
            threshold = (self.watchdog_stale_after_s if w.journal_grew
                         else max(self.watchdog_stale_after_s,
                                  WATCHDOG_START_GRACE_S))
            if now - w.journal_activity > threshold:
                return pid
        return None

    def _forward(self, workers: List[_Worker]) -> None:
        """Forward every worker's spooled stdout in process order — the
        deterministic concatenation the parity tests compare."""
        sink = self.stdout if self.stdout is not None else sys.stdout
        for w in workers:
            w.spool.seek(0)
            if hasattr(sink, "buffer"):
                shutil.copyfileobj(w.spool, sink.buffer)
                sink.flush()
            else:
                import io

                reader = io.TextIOWrapper(w.spool, encoding="utf-8",
                                          errors="replace", newline="")
                try:
                    shutil.copyfileobj(reader, sink)
                finally:
                    reader.detach()

    # -- the restart loop ----------------------------------------------

    def run(self) -> int:
        from ..supervisor import PERMANENT_EXIT_CODES

        restarts = 0
        last_rc = 0
        prev_delay = (self.backoff_base_s
                      if self.backoff_base_s is not None else self.delay_s)
        while True:
            workers = self._spawn(restarts, last_rc,
                                  prev_delay if restarts else 0.0)
            try:
                rc = self._watch(workers)
                if rc == 0:
                    self._forward(workers)
                    if restarts or self.rescales:
                        LOG.info("gang completed after %d restart(s) "
                                 "and %d rescale(s)", restarts,
                                 self.rescales)
                    return 0
                voluntary = (rc == autoscale.RESCALE_EXIT
                             and all(w.proc.returncode
                                     == autoscale.RESCALE_EXIT
                                     for w in workers))
            finally:
                for w in workers:
                    w.spool.close()
            if voluntary and self._pending is not None:
                # The whole gang drained a committed checkpoint and took
                # the voluntary exit: relaunch at the requested topology
                # immediately — no restart budget, no crash-loop
                # accounting, no backoff (nothing failed).
                self.rescales += 1
                target = int(self._pending["to"])
                LOG.warning(
                    "gang rescale %d: all %d workers drained "
                    "voluntarily; relaunching at %d workers from the "
                    "drain-committed epoch", self.rescales,
                    self.num_workers, target)
                self._apply_rescale(target)
                if faults.PLAN is not None:
                    faults.PLAN.fire("rescale_relaunch",
                                     seq=self.rescales)
                continue
            if rc == autoscale.RESCALE_EXIT:
                # Mixed clean/drain codes (or a drain with no pending
                # request): a failed attempt — but 86 is the VOLUNTARY
                # contract code and must never surface as a failure
                # status, least of all as the supervisor's own exit.
                rc = 1
            last_rc = rc
            if self._pending is not None:
                # A crash inside the rescale seam (between the drain
                # decision and a clean relaunch): still honor the
                # pending target — the topology-aware restore vote
                # restores whatever topology last committed onto
                # whatever size we relaunch, so the target is always
                # safe — but the crash itself stays a billed restart.
                self._apply_rescale(int(self._pending["to"]))
            if rc in PERMANENT_EXIT_CODES:
                LOG.error("gang worker failed with rc=%d (usage/config "
                          "error — permanent); not restarting", rc)
                return rc
            restarts += 1
            if restarts > self.attempts:
                LOG.error("gang failed with rc=%d; restart attempts "
                          "exhausted (%d)", rc, self.attempts)
                return rc
            if self.backoff_base_s is not None:
                prev_delay = min(self.backoff_max_s,
                                 random.uniform(self.backoff_base_s,
                                                max(self.backoff_base_s,
                                                    prev_delay * 3)))
            else:
                prev_delay = self.delay_s
            LOG.warning(
                "gang attempt %d failed with rc=%d; gang-restarting all "
                "%d workers from the last committed epoch in %.1fs "
                "(%d attempt(s) left)", restarts, rc, self.num_workers,
                prev_delay, self.attempts - restarts)
            if prev_delay > 0:
                time.sleep(prev_delay)


# -- the serving gang (replica fleet) -----------------------------------


class ReplicaFleetSupervisor:
    """Supervision for a SERVING gang of read replicas
    (``serving/replica.py``) — the same liveness machinery as
    :class:`GangSupervisor` (spawn, monitor exits, heartbeat files in a
    shared gang dir) with the OPPOSITE restart policy: replicas hold no
    collectives, so one replica's death never invalidates the
    survivors. A dead or heartbeat-stale replica is killed and
    relaunched ALONE (it re-syncs itself from checkpoint + delta tail,
    with no writer involvement); the rest of the fleet keeps serving
    throughout — the availability property the whole fleet exists for.

    ``child_argv_fn(process_id) -> argv`` builds one replica's full
    command (the fleet has no coordinator to assign — replicas are
    independent). ``attempts`` is the fleet-wide relaunch budget;
    permanent exit codes (usage/config) abort the fleet immediately —
    a bad flag does not get better per slot.

    Runs until every replica has exited cleanly (bounded
    ``--run-seconds`` fleets) or :meth:`stop` is called.
    """

    def __init__(self, child_argv_fn, num_replicas: int, gang_dir: str,
                 attempts: int = 3, stale_after_s: float = 60.0,
                 relaunch_delay_s: float = 0.5, stdout=None) -> None:
        if num_replicas < 1:
            raise ValueError(
                f"a fleet needs >= 1 replica, got {num_replicas}")
        self.child_argv_fn = child_argv_fn
        self.num_replicas = num_replicas
        self.gang_dir = gang_dir
        self.attempts = attempts
        self.stale_after_s = stale_after_s
        self.relaunch_delay_s = relaunch_delay_s
        self.stdout = stdout
        self.relaunches = 0
        self._stop = threading.Event()
        self._workers: List[Optional[_Worker]] = [None] * num_replicas
        # Tracing correlation: one run id for the fleet; each slot's
        # relaunch count is its attempt ordinal (replicas restart
        # independently, so the ordinal is per-slot, not fleet-wide).
        from ..observability.journal import RUN_ID_ENV, mint_run_id
        self.run_id = tuning.env_read(RUN_ID_ENV) or mint_run_id()
        self._slot_attempts = [0] * num_replicas
        os.makedirs(gang_dir, exist_ok=True)

    def _spawn_one(self, pid: int) -> _Worker:
        from ..observability.journal import ATTEMPT_ENV, RUN_ID_ENV

        try:
            os.remove(heartbeat_path(self.gang_dir, pid))
        except OSError:
            pass
        env = dict(os.environ)
        # Replicas are host-only (numpy over the delta log): pinned to
        # the CPU, no replica can ever claim the writer's chip.
        env["JAX_PLATFORMS"] = "cpu"
        env[GANG_DIR_ENV] = self.gang_dir
        env[RUN_ID_ENV] = self.run_id
        env[ATTEMPT_ENV] = str(self._slot_attempts[pid])
        self._slot_attempts[pid] += 1
        spool = tempfile.TemporaryFile()
        proc = subprocess.Popen(self.child_argv_fn(pid), stdout=spool,
                                env=env)
        return _Worker(proc, spool, time.monotonic())

    def pids(self) -> "List[Optional[int]]":
        """Live OS pids by fleet slot (None = exited) — chaos tests and
        the bench kill a specific replica through this."""
        return [w.proc.pid if w is not None and w.proc.poll() is None
                else None for w in self._workers]

    def stop(self) -> None:
        """Kill the whole fleet and end :meth:`run` (deliberate
        teardown — not counted against the relaunch budget)."""
        self._stop.set()

    def _heartbeat_stale(self, pid: int, w: _Worker) -> bool:
        if self.stale_after_s <= 0:
            return False
        try:
            age = time.time() - os.path.getmtime(
                heartbeat_path(self.gang_dir, pid))
            return age > self.stale_after_s
        except OSError:
            return (time.monotonic() - w.spawned
                    > max(self.stale_after_s, HEARTBEAT_START_GRACE_S))

    def run(self) -> int:
        from ..supervisor import PERMANENT_EXIT_CODES, _kill_child

        for pid in range(self.num_replicas):
            self._workers[pid] = self._spawn_one(pid)
        LOG.info("replica fleet spawned: %d replicas (heartbeats in %s)",
                 self.num_replicas, self.gang_dir)
        done = [False] * self.num_replicas
        try:
            while not self._stop.is_set():
                for pid, w in enumerate(self._workers):
                    if done[pid] or w is None:
                        continue
                    rc = w.proc.poll()
                    if rc == 0:
                        done[pid] = True
                        continue
                    stale = rc is None and self._heartbeat_stale(pid, w)
                    if rc is None and not stale:
                        continue
                    if stale:
                        LOG.error("replica %d heartbeat stale past "
                                  "%.1fs; killing and relaunching it "
                                  "(the rest of the fleet keeps "
                                  "serving)", pid, self.stale_after_s)
                        _kill_child(w.proc)
                        rc = w.proc.poll()
                    if rc in PERMANENT_EXIT_CODES:
                        LOG.error("replica %d exited rc=%d (usage/"
                                  "config — permanent); stopping the "
                                  "fleet", pid, rc)
                        return rc
                    if self.relaunches >= self.attempts:
                        LOG.error("replica %d died rc=%s; relaunch "
                                  "budget (%d) exhausted", pid, rc,
                                  self.attempts)
                        return rc if isinstance(rc, int) and rc else 1
                    self.relaunches += 1
                    LOG.warning("replica %d died rc=%s; relaunching "
                                "slot %d (relaunch %d/%d) — it will "
                                "re-sync from checkpoint + delta tail",
                                pid, rc, pid, self.relaunches,
                                self.attempts)
                    w.spool.close()
                    if self.relaunch_delay_s > 0:
                        time.sleep(self.relaunch_delay_s)
                    self._workers[pid] = self._spawn_one(pid)
                if all(done):
                    LOG.info("replica fleet completed (%d relaunch(es))",
                             self.relaunches)
                    return 0
                time.sleep(_POLL_S)
            return 0
        finally:
            for w in self._workers:
                if w is not None:
                    if w.proc.poll() is None:
                        _kill_child(w.proc)
                    w.spool.close()
