"""Run journal: append-only JSONL flight recorder, one record per window.

The reference's only run artifact is the end-of-job accumulator dump
(``FlinkCooccurrences.java:173-181``); a crashed Flink job leaves its
state to the JobManager. This standalone build's supervisor
(``supervisor.py``) discards a crashed attempt's spooled stdout by
design (exactly-once output), which previously meant a crash discarded
*every* in-flight signal. The journal is the flight recorder that
survives: each fired window appends one self-contained JSON line,
flushed immediately, so after a SIGKILL the file's tail is the last
fired window and the supervisor can quote it in the restart log.

Record schema (:data:`SCHEMA`): logical fields (``seq``, ``ts``,
``events``, ``pairs``, ``rows_scored``, counter deltas) are identical
between serial and pipelined execution (pinned by
``tests/test_observability.py``); timing/occupancy fields
(``*_seconds``, ``ring_depth``, ``wall_unix``) are run-specific.
Counter deltas in pipelined mode are attributed to the window the
scorer worker just finished — sampling-side counters for the window the
producer is concurrently sampling may land one record later, so the
parity contract covers logical fields only.

Readers (:func:`read_records`, :func:`tail`) tolerate a truncated final
line — the expected shape of a file whose writer was SIGKILLed mid-
``write`` — and skip it rather than failing the whole read.

Besides window records, the file may carry out-of-band **event
records** (:data:`EVENT_SCHEMA`, distinguished by an ``"event"`` key):
today the degradation plane's admission-side level transitions, which
must reach disk even when no window ever completes again. Checkpoint
commits append **checkpoint records** (:data:`CKPT_SCHEMA`,
distinguished by a ``"checkpoint"`` key): per-generation commit bytes /
seconds / full-vs-delta kind / chain depth — the incremental plane's
cost trajectory. Serving replicas (``serving/replica.py``) append
**replica records** (:data:`REPLICA_SCHEMA`, distinguished by a
``"replica"`` key): one per delta generation replayed — the replica's
own flight record of its catch-up trajectory (generation, rows
replayed, lag behind the writer, resync count).

**Correlation fields (the tracing plane).** Every record type carries
the same optional trio — ``run_id`` (minted once by the supervising
parent or the CLI and inherited by every child process and restart
attempt through :data:`RUN_ID_ENV`), ``process_id`` (gang/fleet slot)
and ``attempt`` (supervisor restart ordinal, :data:`ATTEMPT_ENV`) — so
``cooc-trace`` (:mod:`.trace`) can merge a fleet's journals into one
timeline and stitch pre-crash records to their post-restart successors.
Window and replica records additionally carry ``spans``: ordered
``[stage, start_offset_s, seconds]`` tuples (:data:`SPAN_STAGES` /
:data:`REPLICA_SPAN_STAGES`) formalizing the stage-seconds breakdown.
The core window stages (:data:`CORE_STAGES`: ``ingest-admission`` →
``sample`` → ``index`` → ``uplink-encode`` → ``dispatch`` →
``rescore``) partition
``sample_seconds + score_seconds`` exactly; the boundary stages
(``snapshot-publish``, ``checkpoint-commit``) run after the record is
flushed, so they are journaled on the first record *after* the boundary
work ran and excluded from the wall-seconds reconciliation.
"""

from __future__ import annotations

import io
import json
import os
import threading
import uuid
from typing import Dict, Iterator, List, Optional, Tuple

from ..robustness import faults
from .. import tuning

#: Journal format version (bump on breaking schema changes).
VERSION = 1

#: Env var carrying the fleet-wide run id: minted once by whichever
#: process is the root of the tree (gang supervisor, single-process
#: supervisor, replica-fleet supervisor, or an unsupervised CLI job)
#: and inherited by every child so one run's journals join on it.
RUN_ID_ENV = "TPU_COOC_RUN_ID"

#: Env var carrying the supervisor restart ordinal (0 = first attempt).
#: Threaded through both supervisors so a restart's journal records
#: link to the prior attempt's instead of starting an unrelated stream.
ATTEMPT_ENV = "TPU_COOC_ATTEMPT"

#: Core window-record span stages, in lifecycle order: they partition
#: ``sample_seconds + score_seconds`` exactly.
CORE_STAGES = ("ingest-admission", "sample", "index", "uplink-encode",
               "dispatch", "rescore")

#: Canonical window-record span stages: the core stages, then two
#: boundary stages measured after the record flushes (journaled on the
#: NEXT record, excluded from wall-seconds reconciliation).
SPAN_STAGES = CORE_STAGES + ("snapshot-publish", "checkpoint-commit")

#: Replica-record span stages: replay one delta generation, then swap
#: the snapshot — the window's lifetime across the process boundary.
REPLICA_SPAN_STAGES = ("delta-apply", "publish")

#: Correlation trio shared by every record type (all optional: journals
#: written before the tracing plane stay valid).
_CORRELATION_FIELDS = {
    "run_id": (False, str),      # fleet-wide run id (RUN_ID_ENV)
    "process_id": (False, int),  # gang/fleet slot (0 single-process)
    "attempt": (False, int),     # supervisor restart ordinal
}


def mint_run_id() -> str:
    """A fresh run id (12 hex chars — short enough to read in a log
    line, random enough that two fleets over one state dir never
    collide)."""
    return uuid.uuid4().hex[:12]


def run_context() -> Tuple[str, int]:
    """(run_id, attempt) for this process: inherited from the
    supervising parent's env when present, otherwise a fresh mint with
    attempt 0 (the unsupervised-run shape)."""
    run_id = tuning.env_read(RUN_ID_ENV) or mint_run_id()
    try:
        attempt = int(tuning.env_read(ATTEMPT_ENV, "0"))
    except ValueError:
        attempt = 0
    return run_id, attempt

#: Field name -> (required, type). ``counters`` / ``wire`` hold per-window
#: deltas (not totals); empty deltas are omitted from ``counters``.
SCHEMA = {
    "v": (True, int),            # format version
    "seq": (True, int),          # 1-based fired-window ordinal (resumes
                                 # from the restored count after a restart)
    "ts": (True, int),           # window timestamp (stream time, ms)
    "events": (True, int),       # events in the fired window
    "pairs": (True, int),        # raw (pre-fold) pair deltas sampled
    "rows_scored": (True, int),  # rows dispatched to the scorer
    "sample_seconds": (True, float),
    "score_seconds": (True, float),
    "ring_depth": (True, int),   # staged windows in flight at dequeue
                                 # (0 on the serial path)
    "stall_seconds": (True, float),  # producer wait for a staging slot
    "wall_unix": (True, float),  # host wall clock at record time
    "counters": (True, dict),    # counter name -> delta since last record
    "wire": (True, dict),        # TransferLedger delta: h2d/d2h bytes+calls
    # Degradation plane (robustness/degrade.py, --degrade): present only
    # while a controller / scorer breaker is attached.
    "degradation_level": (False, int),   # level in force after this
                                         # window's observation
    "degrade_events": (False, list),     # transition event tokens this
                                         # window's observation applied
    "breaker_state": (False, str),       # scorer circuit breaker state
                                         # (closed | half_open | open)
    "fused": (False, int),               # 1 = this window took the fused
                                         # one-dispatch path, 0 = chained
                                         # (present for backends that
                                         # expose the dispatch split)
    "fused_compiles": (False, int),      # cumulative distinct fused-
                                         # program shapes (= XLA
                                         # compiles) when this record
                                         # was written — a seam or new
                                         # bucket steps this series
    "fallback_reason": (False, str),     # why a chained (fused: 0)
                                         # window fell back, when the
                                         # backend names it — one of the
                                         # ARCHITECTURE fallback-table
                                         # reasons (sharded sparse)
    # Serving plane (serving/, --serve-port): snapshot double-buffer
    # bookkeeping — the generation and live row count queries saw while
    # this window computed (the window's own swap lands right after).
    "snapshot_generation": (False, int),
    "snapshot_rows": (False, int),
    # Gang plane (robustness/gang.py, multi-host runs only): the newest
    # checkpoint epoch this process had committed when the record was
    # written — restart forensics show which epoch the gang resumed
    # from.
    "epoch": (False, int),
    # Ingest plane (io/partitioned.py, --source-format partitioned):
    # per-partition wire position when this window fired — the journal
    # side of the exactly-once contract (the restored checkpoint's
    # ingest_offsets section must match the last committed window's).
    "ingest_offsets": (False, dict),  # partition -> {byte_offset,
                                      # records} at window fire
    "ingest_lag": (False, dict),      # partition -> unread bytes on
                                      # disk at window fire
    # Tracing plane (this module + trace.py): fleet-wide correlation
    # trio, uniform across every record type.
    "run_id": (False, str),      # fleet run id (RUN_ID_ENV)
    "process_id": (False, int),  # gang/fleet slot (0 single-process)
    "attempt": (False, int),     # supervisor restart ordinal
    "spans": (False, list),      # ordered [stage, start_offset_s,
                                 # seconds] tuples (SPAN_STAGES)
    "counts": (False, dict),     # the scorer's per-window counts
                                 # (StageClock.counts: launches,
                                 # score_cells, live_cells; dense
                                 # fused_windows, expand_lanes,
                                 # expand_live; dense chained
                                 # scatter_lanes, scatter_live)
}


#: Out-of-band event record (no window attached): ``{"v", "event",
#: "wall_unix"}``. Today's only producer is the degradation plane's
#: admission-side escalation (robustness/degrade.py), which must journal
#: a transition even when no window ever completes again.
EVENT_SCHEMA = {
    "v": (True, int),
    "event": (True, str),
    "wall_unix": (True, float),
    "window_seq": (False, int),  # fired-window ordinal at emit time
    "run_id": (False, str),
    "process_id": (False, int),
    "attempt": (False, int),
}


#: Out-of-band checkpoint record (distinguished by the ``"checkpoint"``
#: key = generation number): one per commit, written by
#: ``job.checkpoint`` from ``state/checkpoint.LAST_COMMIT``. The
#: commit-cost trajectory (``bytes``, ``seconds``, full-vs-delta
#: ``kind``, delta ``chain_len``) is the operator's view of what
#: ``--checkpoint-incremental`` is buying per generation.
CKPT_SCHEMA = {
    "v": (True, int),
    "checkpoint": (True, int),   # generation number committed
    "kind": (True, str),         # "full" | "delta"
    "bytes": (True, int),        # npz + delta file bytes committed
    "seconds": (True, float),    # commit wall seconds
    "chain_len": (True, int),    # delta generations behind this one
    "wall_unix": (True, float),
    "window_seq": (False, int),  # fired-window ordinal at commit — the
                                 # window→generation join cooc-trace
                                 # uses for freshness
    "generation": (False, int),  # uniform join-key alias of
                                 # "checkpoint" (same value)
    "run_id": (False, str),
    "process_id": (False, int),
    "attempt": (False, int),
}


#: Out-of-band autoscale record (distinguished by the ``"autoscale"``
#: key = the decision, ``"grow"`` or ``"shrink"``): one per rescale
#: drain, written by the job at the gang-voted drain boundary just
#: before its voluntary exit (robustness/autoscale.py). The from/to
#: topology, the trigger signal and the policy cooldown armed by the
#: decision make the journal the flight-recorder proof that the gang
#: scaled BEFORE the ladder shed.
AUTOSCALE_SCHEMA = {
    "v": (True, int),
    "autoscale": (True, str),    # decision: "grow" | "shrink"
    "from": (True, int),         # workers before the rescale
    "to": (True, int),           # target workers after it
    "trigger": (True, str),      # "pressure" | "idle"
    "window": (True, int),       # fired-window ordinal of the drain
    "cooldown": (True, int),     # policy cooldown windows armed
    "wall_unix": (True, float),
    "run_id": (False, str),
    "process_id": (False, int),
    "attempt": (False, int),
}


#: Out-of-band replica record (distinguished by the ``"replica"`` key =
#: the delta-log generation just replayed): one per applied delta
#: generation, written by ``serving/replica.ReadReplica``. ``rows`` is
#: the snapshot's live row count after the publish, ``topk_rows`` the
#: top-K rows this generation replayed, ``lag`` the writer generations
#: still unconsumed at record time, ``resyncs`` the checkpoint-resync
#: count so far (DeltaCorrupt fallbacks).
REPLICA_SCHEMA = {
    "v": (True, int),
    "replica": (True, int),      # delta-log generation replayed
    "rows": (True, int),         # snapshot live rows after publish
    "topk_rows": (True, int),    # top-K rows replayed this generation
    "lag": (True, int),          # newest on-disk generation - replayed
    "resyncs": (True, int),      # checkpoint resyncs so far
    "wall_unix": (True, float),
    "generation": (False, int),  # uniform join-key alias of "replica"
                                 # (same value)
    "run_id": (False, str),
    "process_id": (False, int),
    "attempt": (False, int),
    "spans": (False, list),      # [stage, start_offset_s, seconds]
                                 # tuples (REPLICA_SPAN_STAGES)
}


def _validate_spans(spans: list, stages: tuple, rec: dict) -> None:
    """Spans are ordered ``[stage, start_offset_s, seconds]`` triples
    whose stages come from the canonical table and appear in table
    order (a stage may be absent, never out of order)."""
    last_idx = -1
    for span in spans:
        if (not isinstance(span, (list, tuple)) or len(span) != 3
                or not isinstance(span[0], str)
                or any(isinstance(x, bool)
                       or not isinstance(x, (int, float))
                       for x in span[1:])):
            raise ValueError(
                f"journal span {span!r} is not [stage, start_offset_s, "
                f"seconds]: {rec}")
        if span[0] not in stages:
            raise ValueError(
                f"journal span stage {span[0]!r} not in {stages}: {rec}")
        idx = stages.index(span[0])
        if idx <= last_idx:
            raise ValueError(
                f"journal span stage {span[0]!r} out of order "
                f"(canonical order {stages}): {rec}")
        last_idx = idx


def validate_record(rec: dict) -> None:
    """Raise ``ValueError`` unless ``rec`` matches :data:`SCHEMA` (window
    records) or one of the out-of-band schemas (:data:`EVENT_SCHEMA`,
    :data:`CKPT_SCHEMA`, :data:`REPLICA_SCHEMA`)."""
    if not isinstance(rec, dict):
        raise ValueError(f"journal record is not an object: {rec!r}")
    if "autoscale" in rec:
        for field, (required, typ) in AUTOSCALE_SCHEMA.items():
            v = rec.get(field)
            ok = (isinstance(v, (int, float)) if typ is float
                  else isinstance(v, typ)) and not isinstance(v, bool)
            if required and not ok:
                raise ValueError(
                    f"journal autoscale record field {field!r} bad: {rec}")
        unknown = set(rec) - set(AUTOSCALE_SCHEMA)
        if unknown:
            raise ValueError(
                f"journal autoscale record has unknown fields "
                f"{unknown}: {rec}")
        if rec["v"] != VERSION:
            raise ValueError(f"journal version {rec['v']} != {VERSION}")
        if rec["autoscale"] not in ("grow", "shrink"):
            raise ValueError(
                f"journal autoscale decision {rec['autoscale']!r} "
                f"must be grow|shrink")
        if rec["trigger"] not in ("pressure", "idle"):
            raise ValueError(
                f"journal autoscale trigger {rec['trigger']!r} "
                f"must be pressure|idle")
        return
    if "replica" in rec:
        for field, (required, typ) in REPLICA_SCHEMA.items():
            v = rec.get(field)
            ok = (isinstance(v, (int, float)) if typ is float
                  else isinstance(v, typ)) and not isinstance(v, bool)
            if required and not ok:
                raise ValueError(
                    f"journal replica record field {field!r} bad: {rec}")
        unknown = set(rec) - set(REPLICA_SCHEMA)
        if unknown:
            raise ValueError(
                f"journal replica record has unknown fields "
                f"{unknown}: {rec}")
        if rec["v"] != VERSION:
            raise ValueError(f"journal version {rec['v']} != {VERSION}")
        if "spans" in rec:
            _validate_spans(rec["spans"], REPLICA_SPAN_STAGES, rec)
        return
    if "checkpoint" in rec:
        for field, (required, typ) in CKPT_SCHEMA.items():
            v = rec.get(field)
            ok = (isinstance(v, (int, float)) if typ is float
                  else isinstance(v, typ)) and not isinstance(v, bool)
            if required and not ok:
                raise ValueError(
                    f"journal checkpoint record field {field!r} bad: {rec}")
        unknown = set(rec) - set(CKPT_SCHEMA)
        if unknown:
            raise ValueError(
                f"journal checkpoint record has unknown fields "
                f"{unknown}: {rec}")
        if rec["v"] != VERSION:
            raise ValueError(f"journal version {rec['v']} != {VERSION}")
        if rec["kind"] not in ("full", "delta"):
            raise ValueError(
                f"journal checkpoint record kind {rec['kind']!r} "
                f"must be full|delta")
        return
    if "event" in rec:
        for field, (required, typ) in EVENT_SCHEMA.items():
            v = rec.get(field)
            ok = (isinstance(v, (int, float)) if typ is float
                  else isinstance(v, typ)) and not isinstance(v, bool)
            if required and not ok:
                raise ValueError(
                    f"journal event record field {field!r} bad: {rec}")
        unknown = set(rec) - set(EVENT_SCHEMA)
        if unknown:
            raise ValueError(
                f"journal event record has unknown fields {unknown}: {rec}")
        if rec["v"] != VERSION:
            raise ValueError(f"journal version {rec['v']} != {VERSION}")
        return
    for field, (required, typ) in SCHEMA.items():
        if field not in rec:
            if required:
                raise ValueError(f"journal record missing {field!r}: {rec}")
            continue
        v = rec[field]
        if typ is float:
            ok = isinstance(v, (int, float)) and not isinstance(v, bool)
        else:
            ok = isinstance(v, typ) and not isinstance(v, bool)
        if not ok:
            raise ValueError(
                f"journal field {field!r} has type {type(v).__name__}, "
                f"expected {typ.__name__}: {rec}")
    unknown = set(rec) - set(SCHEMA)
    if unknown:
        raise ValueError(f"journal record has unknown fields {unknown}: {rec}")
    if rec["v"] != VERSION:
        raise ValueError(f"journal version {rec['v']} != {VERSION}")
    if "spans" in rec:
        _validate_spans(rec["spans"], SPAN_STAGES, rec)


class RunJournal:
    """Append-only writer. One line per :meth:`record`, flushed to the OS
    immediately — the crash-survivability contract. Opened in append mode
    so a supervised restart continues the same file (``seq`` resumes from
    the restored window count, so the ordinal stream stays monotone)."""

    def __init__(self, path: str) -> None:
        self.path = path
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        # A crashed predecessor may have died mid-write, leaving an
        # unterminated partial line; seal it with a newline so this
        # attempt's first record starts a fresh line instead of gluing
        # itself onto the torn one (readers skip the torn line either way).
        torn = False
        try:
            with open(path, "rb") as f:
                f.seek(-1, os.SEEK_END)
                torn = f.read(1) != b"\n"
        except OSError:
            pass  # missing or empty file
        self._f: Optional[io.TextIOBase] = open(  # noqa: SIM115 - long-lived
            path, "a", encoding="utf-8")
        if torn:
            self._f.write("\n")
            self._f.flush()
        # Window records come from one thread per execution mode, but
        # out-of-band event records (degradation-plane admission-side
        # transitions) arrive from the ingest thread concurrently — two
        # buffered writes must not interleave mid-line.
        # lock-ordering: leaf lock, held only around the write+flush
        self._lock = threading.Lock()

    def record(self, rec: dict) -> None:
        if self._f is None:
            raise ValueError("journal is closed")
        if faults.PLAN is not None:
            # torn_write here appends half a record then dies — the
            # exact SIGKILL-mid-write shape readers must tolerate.
            faults.PLAN.fire("journal_append", seq=rec.get("seq", 0),
                             path=self.path)
        # One write syscall per record + explicit flush: a SIGKILL can
        # truncate at most the line being written, never reorder lines.
        with self._lock:
            self._f.write(json.dumps(rec, sort_keys=True) + "\n")
            self._f.flush()

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self) -> "RunJournal":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_records(path: str) -> Iterator[dict]:
    """Parse a journal, skipping unparseable lines (a crash-torn final
    line is the expected case; the writer never produces one mid-file)."""
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                yield json.loads(line)
            except ValueError:
                continue


def tail(path: str, n: int = 5, read_back_bytes: int = 1 << 16,
         start_offset: int = 0) -> List[dict]:
    """Last ``n`` parseable records after ``start_offset``, ``[]`` when
    the file is missing or holds none — the supervisor's crash-forensics
    read.

    Reads only the final ``read_back_bytes`` of the eligible range: a
    long-running journal grows without rotation, and the restart path
    must not parse weeks of records to quote five. ``start_offset``
    scopes the read to one attempt's records (the caller passes the file
    size captured at spawn; that is always a line boundary, or the start
    of a torn line the writer seals). The first line of the chunk is
    dropped when the seek landed mid-record.
    """
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            start = max(start_offset, size - read_back_bytes)
            f.seek(start)
            chunk = f.read().decode("utf-8", errors="replace")
    except OSError:
        return []
    lines = chunk.splitlines()
    if start > start_offset and lines:
        lines = lines[1:]  # partial first line from the mid-record seek
    out: List[dict] = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            out.append(json.loads(line))
        except ValueError:
            continue
        if len(out) > n:
            out.pop(0)
    return out
