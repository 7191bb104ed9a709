"""The tracing plane: span-structured journal records, fleet-wide
correlation (run_id / process_id / attempt), the cooc-trace offline
analyzer (waterfall, reconciliation, freshness, seams, Chrome export),
the /healthz last_window block, and supervisor run-id threading.

``JOURNAL_SCHEMA_KEYS`` below is the canonical tests/ registry the
``journal-schema-registry`` cooclint rule points at: every key any
journal writer emits must appear here (and in the schema tables and the
ARCHITECTURE journal table) or the analyzer fails tier-1.
"""

import json
import os
import sys

import numpy as np
import pytest

from tpu_cooccurrence.observability import journal as jn
from tpu_cooccurrence.observability import trace
from tpu_cooccurrence.observability.journal import (
    CORE_STAGES, REPLICA_SPAN_STAGES, SPAN_STAGES, VERSION, RunJournal,
    mint_run_id, run_context, validate_record)

# The journal key registry (see module docstring). Kept as literals on
# purpose — the lint rule scans tests/ for the emitted key *strings*.
JOURNAL_SCHEMA_KEYS = [
    # window records (SCHEMA)
    "v", "seq", "ts", "events", "pairs", "rows_scored",
    "sample_seconds", "score_seconds", "ring_depth", "stall_seconds",
    "wall_unix", "counters", "wire", "degradation_level",
    "degrade_events", "breaker_state", "fused", "fused_compiles",
    "fallback_reason", "snapshot_generation", "snapshot_rows", "epoch",
    "run_id", "process_id", "attempt", "spans", "counts",
    "ingest_offsets", "ingest_lag",
    # event records (EVENT_SCHEMA)
    "event", "window_seq",
    # checkpoint records (CKPT_SCHEMA)
    "checkpoint", "kind", "bytes", "seconds", "chain_len", "generation",
    # autoscale records (AUTOSCALE_SCHEMA)
    "autoscale", "from", "to", "trigger", "window", "cooldown",
    # replica records (REPLICA_SCHEMA)
    "replica", "rows", "topk_rows", "lag", "resyncs",
]


def test_schema_key_registry_is_exact():
    """The literal registry above matches the schema tables exactly —
    a new journal field must be added to both (plus the ARCHITECTURE
    table) in the same PR."""
    tables = (jn.SCHEMA, jn.EVENT_SCHEMA, jn.CKPT_SCHEMA,
              jn.AUTOSCALE_SCHEMA, jn.REPLICA_SCHEMA)
    union = set()
    for t in tables:
        union |= set(t)
    assert set(JOURNAL_SCHEMA_KEYS) == union
    assert len(JOURNAL_SCHEMA_KEYS) == len(set(JOURNAL_SCHEMA_KEYS))


# ---------------------------------------------------------------------------
# record builders (every fixture is validated — schema-true by
# construction, so these tests can never drift from the writers)


def _spans(sample_s, score_s):
    """Core spans partitioning sample+score exactly, the job contract."""
    admit = 0.25 * sample_s
    parts = [("ingest-admission", admit), ("sample", sample_s - admit),
             ("index", 0.1 * score_s), ("uplink-encode", 0.2 * score_s),
             ("dispatch", 0.5 * score_s), ("rescore", 0.2 * score_s)]
    off, out = 0.0, []
    for stage, secs in parts:
        out.append([stage, round(off, 9), round(secs, 9)])
        off += secs
    return out


def _win(seq, run_id="r1", pid=0, attempt=0, wall=100.0, sample_s=0.4,
         score_s=0.6, **over):
    rec = {"v": VERSION, "seq": seq, "ts": seq * 10, "events": 5,
           "pairs": 3, "rows_scored": 2, "sample_seconds": sample_s,
           "score_seconds": score_s, "ring_depth": 0,
           "stall_seconds": 0.0, "wall_unix": wall, "counters": {},
           "wire": {}, "run_id": run_id, "process_id": pid,
           "attempt": attempt, "spans": _spans(sample_s, score_s)}
    rec.update(over)
    validate_record(rec)
    return rec


def _ckpt(gen, window_seq, run_id="r1", pid=0, attempt=0, wall=100.0):
    rec = {"v": VERSION, "checkpoint": gen, "kind": "delta", "bytes": 10,
           "seconds": 0.01, "chain_len": 1, "wall_unix": wall,
           "window_seq": window_seq, "generation": gen, "run_id": run_id,
           "process_id": pid, "attempt": attempt}
    validate_record(rec)
    return rec


def _replica(gen, run_id="r1", pid=0, attempt=0, wall=100.0, lag=0,
             resyncs=0):
    rec = {"v": VERSION, "replica": gen, "rows": 4, "topk_rows": 2,
           "lag": lag, "resyncs": resyncs, "wall_unix": wall,
           "generation": gen, "run_id": run_id, "process_id": pid,
           "attempt": attempt,
           "spans": [["delta-apply", 0.0, 0.002],
                     ["publish", 0.002, 0.001]]}
    validate_record(rec)
    return rec


def _write(path, records):
    with RunJournal(str(path)) as j:
        for rec in records:
            j.record(rec)
    return str(path)


# ---------------------------------------------------------------------------
# span schema validation


def test_span_validation_rejects_malformed():
    validate_record(_win(1))  # canonical order passes
    with pytest.raises(ValueError, match="not in"):
        validate_record(_win(1, spans=[["warp-core", 0.0, 0.1]]))
    with pytest.raises(ValueError, match="out of order"):
        validate_record(_win(1, spans=[["sample", 0.0, 0.1],
                                       ["ingest-admission", 0.1, 0.1]]))
    with pytest.raises(ValueError, match="not \\[stage"):
        validate_record(_win(1, spans=[["sample", 0.0]]))
    with pytest.raises(ValueError, match="not in"):
        # Replica stages are a different table: a window stage on a
        # replica record is a writer bug, not a new stage.
        validate_record(_replica(1, run_id="r")
                        | {"spans": [["sample", 0.0, 0.1]]})


def test_span_stage_tables():
    assert CORE_STAGES == ("ingest-admission", "sample", "index",
                           "uplink-encode", "dispatch", "rescore")
    assert SPAN_STAGES == CORE_STAGES + ("snapshot-publish",
                                         "checkpoint-commit")
    assert trace.CORE_STAGES is CORE_STAGES
    assert REPLICA_SPAN_STAGES == ("delta-apply", "publish")


def test_run_context_inherits_env(monkeypatch):
    monkeypatch.setenv(jn.RUN_ID_ENV, "abc123")
    monkeypatch.setenv(jn.ATTEMPT_ENV, "4")
    assert run_context() == ("abc123", 4)
    monkeypatch.delenv(jn.RUN_ID_ENV)
    monkeypatch.delenv(jn.ATTEMPT_ENV)
    run_id, attempt = run_context()
    assert len(run_id) == 12 and attempt == 0
    assert mint_run_id() != mint_run_id()


# ---------------------------------------------------------------------------
# the real writers: a journaled job run carries correlation + spans
# that reconcile with its own wall-seconds fields


def _run_job(tmp_path, name, pipeline_depth=0, run_id="tracerun12ab"):
    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.job import CooccurrenceJob

    rng = np.random.default_rng(11)
    n = 4000
    users = rng.integers(0, 40, n).astype(np.int64)
    items = rng.integers(0, 60, n).astype(np.int64)
    ts = np.cumsum(rng.integers(0, 2, n)).astype(np.int64)
    path = str(tmp_path / f"{name}.jsonl")
    job = CooccurrenceJob(Config(window_size=50, seed=5, item_cut=20,
                                 user_cut=10, backend=Backend("oracle"),
                                 journal=path, run_id=run_id,
                                 pipeline_depth=pipeline_depth))
    job.add_batch(users, items, ts)
    job.finish()
    return job, path


@pytest.mark.parametrize("depth", [0, 2])
def test_job_records_spans_that_reconcile(tmp_path, depth):
    job, path = _run_job(tmp_path, f"d{depth}", pipeline_depth=depth)
    recs = [r for r in jn.read_records(path) if "seq" in r]
    assert len(recs) == job.windows_fired > 5
    for r in recs:
        validate_record(r)
        assert r["run_id"] == "tracerun12ab"
        assert r["process_id"] == 0 and r["attempt"] == 0
        stages = [s[0] for s in r["spans"]]
        assert stages[:len(CORE_STAGES)] == list(CORE_STAGES)
        # The core contract: the core spans partition
        # sample_seconds + score_seconds (to field rounding).
        core = sum(s[2] for s in r["spans"] if s[0] in CORE_STAGES)
        assert core == pytest.approx(
            r["sample_seconds"] + r["score_seconds"], abs=2e-6)
        # Offsets are contiguous: each span starts where the prior ended.
        off = 0.0
        for _stage, start, secs in r["spans"]:
            assert start == pytest.approx(off, abs=2e-6)
            off += secs
    rep = trace.reconcile(recs)
    assert rep["ok"], rep
    assert job.last_window_health is not None
    assert job.last_window_health["window_seq"] == job.windows_fired
    assert set(job.last_window_health["stages"]) <= set(SPAN_STAGES)


def test_healthz_carries_last_window_block():
    from tpu_cooccurrence.observability.http import MetricsServer
    from tpu_cooccurrence.observability.registry import MetricsRegistry

    block = {"window_seq": 7, "seconds": 0.25, "fused": True,
             "stages": {"sample": 0.1, "dispatch": 0.15}}
    srv = MetricsServer(MetricsRegistry(), stale_after_s=300.0,
                        last_window=lambda: block)
    try:
        payload, _healthy = srv.health()
        assert payload["last_window"] == block
    finally:
        srv.stop()
    # Absent callback (or a job with no window yet): no block, no crash.
    srv = MetricsServer(MetricsRegistry(), stale_after_s=300.0,
                        last_window=lambda: None)
    try:
        payload, _healthy = srv.health()
        assert "last_window" not in payload
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# cooc-trace: merge, dedup, waterfall, reconciliation, freshness


def test_classify_and_discover(tmp_path):
    assert trace.classify(_win(1)) == "window"
    assert trace.classify(_ckpt(1, 1)) == "checkpoint"
    assert trace.classify(_replica(1)) == "replica"
    assert trace.classify({"v": 1, "event": "x",
                           "wall_unix": 1.0}) == "event"
    assert trace.classify({"not": "a record"}) is None
    _write(tmp_path / "journal.jsonl.p0", [_win(1)])
    _write(tmp_path / "replica.jsonl", [_replica(1)])
    (tmp_path / "ckpt.bin").write_bytes(b"\x00")  # ignored: not jsonl
    files = trace.discover([str(tmp_path)])
    assert [os.path.basename(f) for f in files] == [
        "journal.jsonl.p0", "replica.jsonl"]


def test_dedup_keeps_highest_attempt():
    a0 = [_win(s, attempt=0, wall=100.0 + s) for s in (1, 2, 3)]
    a1 = [_win(s, attempt=1, wall=200.0 + s) for s in (2, 3, 4)]
    kept, dropped = trace.dedup_windows(a0 + a1)
    assert dropped == 2
    by_seq = {r["seq"]: r["attempt"] for r in kept}
    assert by_seq == {1: 0, 2: 1, 3: 1, 4: 1}


def test_waterfall_covers_both_planes():
    wf = trace.waterfall([_win(1), _win(2)], [_replica(1)])
    assert wf["sample"]["count"] == 2
    assert wf["delta-apply"]["count"] == 1
    assert wf["sample"]["max"] == pytest.approx(0.3)
    assert "checkpoint-commit" not in wf  # no boundary spans emitted


def test_reconcile_flags_torn_partition():
    good = _win(1)
    bad = _win(2, spans=[["sample", 0.0, 0.1]])  # 0.1 != 1.0 wall
    rep = trace.reconcile([good, bad])
    assert rep["windows_checked"] == 2
    assert rep["violations"] == 1 and not rep["ok"]
    # Sub-millisecond windows are skipped (field rounding dominates).
    tiny = _win(3, sample_s=1e-5, score_s=1e-5)
    assert trace.reconcile([tiny])["windows_checked"] == 0


def test_freshness_joins_window_to_replica_via_generation():
    windows = [_win(1, wall=100.0), _win(2, wall=110.0)]
    ckpts = [_ckpt(3, window_seq=2, wall=110.5)]
    replicas = [_replica(3, run_id="r1", pid=0, wall=112.3, lag=0)]
    fr = trace.freshness(windows, ckpts, replicas)
    # Anchored at the *window* wall (110.0), not the commit (110.5).
    assert fr["count"] == 1 and fr["joined"] == 1
    assert fr["max"] == pytest.approx(2.3)
    assert "cross_run_join" not in fr
    # A separately launched replica (own run id) still joins on the
    # generation over the shared state dir — flagged, not dropped.
    other = [_replica(3, run_id="other", wall=115.0)]
    fr = trace.freshness(windows, ckpts, other)
    assert fr["joined"] == 1 and fr["cross_run_join"] is True
    # Unknown generation: counted as unjoined, never guessed.
    fr = trace.freshness(windows, ckpts, [_replica(99, wall=120.0)])
    assert fr["joined"] == 0 and fr["unjoined_replica_records"] == 1


# ---------------------------------------------------------------------------
# chaos: gang crash + restart, replica resync mid-tail (ISSUE 17
# satellite — the merged timeline must stay coherent through both)


def _gang_dir(tmp_path):
    """Two workers; p0 crashes after seq 4 and its restart (attempt 1)
    replays seq 3-6 into the SAME journal file (append mode)."""
    run = "gangrun00001"
    p0 = [_win(s, run_id=run, pid=0, attempt=0, wall=100.0 + s)
          for s in (1, 2, 3, 4)]
    p0 += [_win(s, run_id=run, pid=0, attempt=1, wall=150.0 + s)
           for s in (3, 4, 5, 6)]
    p0 += [_ckpt(1, window_seq=6, run_id=run, pid=0, attempt=1,
                 wall=157.0)]
    p1 = [_win(s, run_id=run, pid=1, attempt=0, wall=100.0 + s)
          for s in (1, 2, 3, 4, 5, 6)]
    _write(tmp_path / "journal.jsonl.p0", p0)
    _write(tmp_path / "journal.jsonl.p1", p1)
    reps = [_replica(1, run_id=run, pid=0, wall=158.0)]
    _write(tmp_path / "replica.jsonl.p0", reps)
    return run, str(tmp_path)


def test_chaos_gang_crash_restart_merges_cleanly(tmp_path):
    run, root = _gang_dir(tmp_path)
    analysis = trace.analyze(trace.discover([root]))
    an = analysis["annotations"]
    assert an["restarts"] == 1
    assert an["dropped_duplicate_windows"] == 2  # seq 3, 4 replayed
    assert analysis["reconcile"]["ok"]
    assert analysis["freshness"]["joined"] == 1
    assert sorted(analysis["processes"]) == [f"{run}/p0", f"{run}/p1"]
    # The merged Chrome timeline carries each (pid, window_seq, stage)
    # span exactly once — the dedup dropped the pre-crash attempts.
    ct = trace.chrome_trace(trace.discover([root]))
    seen = set()
    for ev in ct["traceEvents"]:
        if ev["ph"] == "X" and ev.get("cat") == "window":
            key = (ev["pid"], ev["args"]["window_seq"], ev["name"])
            assert key not in seen, f"duplicate span {key}"
            seen.add(key)
    # p0 fired 1-6 (surviving attempts), p1 fired 1-6: 12 windows x
    # the core spans.
    assert len(seen) == 12 * len(CORE_STAGES)


def test_chaos_replica_resync_mid_tail():
    """A replica that hits DeltaCorrupt mid-tail resyncs FORWARD from
    the newest checkpoint: its generation stream may skip but must
    never step back."""
    reps = [_replica(g, wall=100.0 + g, resyncs=0) for g in (1, 2, 3)]
    # resync: bootstrap jumps over 4-6 straight to 7
    reps += [_replica(g, wall=110.0 + g, resyncs=1) for g in (7, 8)]
    an = trace.annotations([], [], [], reps, 0)
    assert an["replica_resyncs"] == 1
    assert an["replica_generation_monotone"] is True
    # A genuinely backwards stream (corrupt merge, clock skew) flags.
    bad = reps + [_replica(2, wall=130.0, resyncs=1)]
    an = trace.annotations([], [], [], bad, 0)
    assert an["replica_generation_monotone"] is False


def test_annotations_count_seams():
    windows = [_win(1, fused=1), _win(2, fused=0,
                                      fallback_reason="width_overflow"),
               _win(3, fused=1, degrade_events=["shed_k_on"])]
    events = [{"v": VERSION, "event": "pause_on", "wall_unix": 104.0,
               "window_seq": 3, "run_id": "r1", "process_id": 0,
               "attempt": 0}]
    autos = [{"v": VERSION, "autoscale": "grow", "from": 2, "to": 4,
              "trigger": "pressure", "window": 3, "cooldown": 6,
              "wall_unix": 105.0, "run_id": "r1", "process_id": 0,
              "attempt": 0}]
    for rec in events + autos:
        validate_record(rec)
    an = trace.annotations(windows, events, autos, [], 1)
    assert an["fused_windows"] == 2 and an["chained_windows"] == 1
    assert an["fallback_reasons"] == {"width_overflow": 1}
    assert an["degrade_transitions"] == 2  # 1 in-window + 1 o-o-b event
    assert an["autoscale_drains"] == [
        {"decision": "grow", "from": 2, "to": 4, "trigger": "pressure",
         "window": 3}]
    assert an["dropped_duplicate_windows"] == 1


# ---------------------------------------------------------------------------
# Chrome-trace export + CLI


def test_chrome_trace_structure(tmp_path):
    _, root = _gang_dir(tmp_path)
    ct = trace.chrome_trace(trace.discover([root]))
    assert ct["displayTimeUnit"] == "ms"
    evs = ct["traceEvents"]
    assert {e["ph"] for e in evs} <= {"M", "X", "i"}
    # Metadata names every process/thread track before its spans.
    names = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "process_name"
               and e["args"]["name"].startswith("worker p0")
               for e in names)
    assert any(e["name"] == "process_name"
               and e["args"]["name"].startswith("replica p0")
               for e in names)
    assert any(e["name"] == "thread_name"
               and e["args"]["name"] == "attempt 1" for e in names)
    # Replicas live on their own pid plane; worker pids stay raw.
    pids = {e["pid"] for e in evs if e.get("cat") == "replica"}
    assert pids == {1000}
    xs = [e for e in evs if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and e["ts"] > 0 for e in xs)
    # Spans within one record are laid back-to-back (contiguous).
    one = sorted((e for e in xs if e.get("cat") == "window"
                  and e["pid"] == 1 and e["args"]["window_seq"] == 1),
                 key=lambda e: e["ts"])
    for a, b in zip(one, one[1:]):
        assert b["ts"] == pytest.approx(a["ts"] + a["dur"], abs=1.0)
    # The instant events mark the out-of-band records.
    assert any(e["ph"] == "i" and e["name"].startswith("checkpoint gen")
               for e in evs)
    # Stream is time-sorted and JSON-serializable (Perfetto's loader).
    ts = [e.get("ts", 0.0) for e in evs]
    assert ts == sorted(ts)
    json.dumps(ct)


def test_trace_cli_formats(tmp_path, capsys):
    _, root = _gang_dir(tmp_path)
    assert trace.main([root, "--format", "text"]) == 0
    out = capsys.readouterr().out
    assert "stage waterfall" in out and "restarts=1" in out
    assert "dropped-dup-windows=2" in out
    jpath = str(tmp_path / "analysis.json")
    assert trace.main(["--gang-dir", root, "--format", "json",
                       "--out", jpath]) == 0
    with open(jpath) as f:
        analysis = json.load(f)
    assert analysis["reconcile"]["ok"]
    cpath = str(tmp_path / "trace.chrome.json")
    assert trace.main(["--state-dir", root, "--format", "chrome",
                       "--out", cpath]) == 0
    with open(cpath) as f:
        assert json.load(f)["traceEvents"]
    with pytest.raises(SystemExit):  # no inputs at all
        trace.main(["--format", "text"])


def test_trace_module_runs_jax_free(tmp_path):
    """cooc-trace is an offline tool: it must import and run with jax
    imports poisoned (journals are analyzed on laptops, not TPU VMs)."""
    _, root = _gang_dir(tmp_path)
    code = (
        "import sys\n"
        "sys.modules['jax'] = None  # import jax -> TypeError\n"
        "from tpu_cooccurrence.observability import trace\n"
        f"rc = trace.main([{root!r}, '--format', 'text'])\n"
        "sys.exit(rc)\n"
    )
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "stage waterfall" in proc.stdout


# ---------------------------------------------------------------------------
# supervisor run-id threading (restart children link to the prior
# attempt instead of starting an unrelated trace)


class _Sink:
    def __init__(self):
        self.text = ""

    def write(self, s):
        self.text += s


def test_supervisor_threads_run_id_and_attempt(tmp_path, monkeypatch):
    from tpu_cooccurrence.supervisor import supervise

    monkeypatch.delenv(jn.RUN_ID_ENV, raising=False)
    monkeypatch.delenv(jn.ATTEMPT_ENV, raising=False)
    log = tmp_path / "env.log"
    code = (
        "import os, sys\n"
        f"p = {str(log)!r}\n"
        "with open(p, 'a') as f:\n"
        f"    f.write(os.environ['{jn.RUN_ID_ENV}'] + ' '\n"
        f"            + os.environ['{jn.ATTEMPT_ENV}'] + chr(10))\n"
        "n = sum(1 for _ in open(p))\n"
        "sys.exit(0 if n > 1 else 5)\n"  # crash the first attempt
    )
    rc = supervise([sys.executable, "-c", code], attempts=2, delay_s=0,
                   stdout=_Sink())
    assert rc == 0
    lines = log.read_text().splitlines()
    assert len(lines) == 2
    (run0, att0), (run1, att1) = (ln.split() for ln in lines)
    assert run0 == run1 and len(run0) == 12
    assert (att0, att1) == ("0", "1")


def test_gang_supervisor_spawn_env_carries_identity(tmp_path, monkeypatch):
    """GangSupervisor stamps every worker's env with the shared run id
    and the gang-wide attempt ordinal (the chaos-merge tests above rely
    on the children inheriting both)."""
    from tpu_cooccurrence.robustness.gang import GangSupervisor

    monkeypatch.delenv(jn.RUN_ID_ENV, raising=False)
    captured = []

    class FakeProc:
        pid = 4242

        def poll(self):
            return 0

    def fake_popen(cmd, **kw):
        captured.append(kw.get("env") or {})
        return FakeProc()

    monkeypatch.setattr(
        "tpu_cooccurrence.robustness.gang.subprocess.Popen", fake_popen)
    sup = GangSupervisor(["-i", "x.csv", "-ws", "10"], num_workers=2,
                         attempts=0, gang_dir=str(tmp_path))
    sup._spawn(restarts=1, last_rc=0, backoff_s=0.0)
    assert len(captured) == 2
    assert {env[jn.RUN_ID_ENV] for env in captured} == {sup.run_id}
    assert all(env[jn.ATTEMPT_ENV] == "1" for env in captured)
    state = json.loads(captured[0]["TPU_COOC_SUPERVISOR_STATE"])
    assert state["run_id"] == sup.run_id and state["attempt"] == 1
