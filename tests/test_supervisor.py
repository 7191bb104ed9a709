"""Auto-resume supervisor: crash recovery with zero operator action.

The reference delegates failure recovery to Flink's restart strategies
(SURVEY §5); here a parent process respawns the job and the child
resumes from its checkpoint. The headline property (VERDICT r2, Next
#7): SIGKILL the job under the supervisor and the total stdout is
byte-identical to an uninterrupted run."""

import os
import subprocess
import sys
import time

import pytest

from tpu_cooccurrence.supervisor import child_argv, supervise

from test_cli import write_stream

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def test_child_argv_strips_supervisor_flags():
    argv = ["-i", "x.csv", "--restart-on-failure", "3", "-ws", "10",
            "--restart-delay-ms=0", "--restart-on-failure=2"]
    assert child_argv(argv) == ["-i", "x.csv", "-ws", "10"]


class _Sink:
    def __init__(self):
        self.text = ""

    def write(self, s):
        self.text += s


def test_supervise_retries_then_succeeds(tmp_path):
    """Two failing attempts (partial output discarded), then success:
    rc 0 and ONLY the successful attempt's stdout comes through."""
    marker = tmp_path / "attempts"
    code = (
        "import os, sys\n"
        f"p = {str(marker)!r}\n"
        "n = int(open(p).read()) if os.path.exists(p) else 0\n"
        "open(p, 'w').write(str(n + 1))\n"
        "if n < 2:\n"
        "    print('partial garbage', flush=True)\n"
        "    sys.exit(3)\n"
        "print('final output')\n"
    )
    sink = _Sink()
    rc = supervise([sys.executable, "-c", code], attempts=2, delay_s=0,
                   stdout=sink)
    assert rc == 0
    assert sink.text == "final output\n"
    assert marker.read_text() == "3"


def test_supervise_exhausts_attempts(tmp_path):
    sink = _Sink()
    rc = supervise([sys.executable, "-c", "import sys; sys.exit(7)"],
                   attempts=2, delay_s=0, stdout=sink)
    assert rc == 7
    assert sink.text == ""


def test_supervise_timeout_counts_as_failed_attempt(tmp_path):
    """A hung attempt (timeout_s) is a failed attempt, not a supervisor
    crash: the child is killed, the retry runs, output comes through."""
    marker = tmp_path / "ran-once"
    code = (
        "import os, sys, time\n"
        f"p = {str(marker)!r}\n"
        "if not os.path.exists(p):\n"
        "    open(p, 'w').close()\n"
        "    time.sleep(600)\n"
        "print('after hang')\n"
    )
    sink = _Sink()
    rc = supervise([sys.executable, "-c", code], attempts=1, delay_s=0,
                   stdout=sink, timeout_s=3)
    assert rc == 0
    assert sink.text == "after hang\n"
    sink2 = _Sink()
    rc = supervise([sys.executable, "-c", "import time; time.sleep(600)"],
                   attempts=0, delay_s=0, stdout=sink2, timeout_s=1)
    assert rc == 124  # exhausted: timeout's conventional exit code
    assert sink2.text == ""


def test_restart_flag_abbreviation_rejected():
    """allow_abbrev=False: `--restart-on` must NOT parse as
    --restart-on-failure (an abbreviation would survive child_argv's
    exact-name strip and nest supervisors indefinitely)."""
    import pytest

    from tpu_cooccurrence.config import Config

    with pytest.raises(SystemExit):
        Config.from_args(["-i", "x.csv", "-ws", "10", "--restart-on", "2"])


def test_restart_rejected_with_process_continuously():
    import pytest

    from tpu_cooccurrence.config import Config

    with pytest.raises(ValueError, match="process-continuously"):
        Config.from_args(["-i", "x.csv", "-ws", "10",
                          "--restart-on-failure", "2",
                          "--process-continuously"])


def test_restart_rejected_with_multihost():
    """A respawned child re-joining the coordinator while surviving peers
    are blocked mid-collective would hang the distributed run; supervise
    multi-host jobs externally instead."""
    import pytest

    from tpu_cooccurrence.config import Config

    with pytest.raises(ValueError, match="multi-host"):
        Config.from_args(["-i", "x.csv", "-ws", "10",
                          "--restart-on-failure", "2",
                          "--coordinator", "127.0.0.1:9999",
                          "--num-processes", "2", "--process-id", "0"])


@pytest.mark.slow
def test_supervise_large_output_spools_to_disk(tmp_path):
    """A multi-hundred-MB child stream must not live in supervisor RAM:
    stdout spools to disk per attempt (VERDICT r3, Weak #3). Output
    integrity is checked end-to-end; RSS growth is bounded well under
    the stream size."""
    import resource

    n_mb = 256
    line = "x" * 1023  # 1 KB with newline
    code = (f"import sys\n"
            f"for _ in range({n_mb * 1024}):\n"
            f"    sys.stdout.write({line!r} + '\\n')\n")
    out_path = tmp_path / "out.txt"
    rss_before = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    with open(out_path, "w") as sink:  # has .buffer → binary fast path
        rc = supervise([sys.executable, "-c", code], attempts=0, delay_s=0,
                       stdout=sink)
    rss_after = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    assert rc == 0
    assert out_path.stat().st_size == n_mb * 1024 * 1024
    with open(out_path) as f:
        first = f.readline()
    assert first == line + "\n"
    # ru_maxrss is KB on Linux; allow 64 MB of slack for the interpreter,
    # far under the 256 MB stream a PIPE buffer would have held.
    assert rss_after - rss_before < 64 * 1024, (
        f"supervisor RSS grew {(rss_after - rss_before) // 1024} MB "
        f"on a {n_mb} MB stream — stdout is being buffered in memory")


def test_supervise_text_sink_multibyte_across_chunks():
    """Text sinks decode incrementally; multi-byte UTF-8 sequences that
    straddle copy-chunk boundaries must survive."""
    # 3-byte chars at 1-byte offset guarantee straddles at any power-of-2
    # chunk size.
    code = ("import sys\n"
            "sys.stdout.write('a' + '\\u20ac' * 100000)\n"
            "sys.stdout.write('x\\r\\ny')\n")
    sink = _Sink()
    rc = supervise([sys.executable, "-c", code], attempts=0, delay_s=0,
                   stdout=sink)
    assert rc == 0
    # \r\n must come through untranslated (byte-identical contract).
    assert sink.text == "a" + "\u20ac" * 100000 + "x\r\ny"


def test_supervisor_quotes_dead_childs_journal_tail(tmp_path, caplog):
    """A SIGKILLed child's journal survives (including a torn final
    line) and the supervisor's restart log quotes its tail — the crashed
    attempt's last fired windows are not lost with its discarded stdout."""
    import logging

    jpath = tmp_path / "j.jsonl"
    marker = tmp_path / "crashed-once"
    code = (
        "import os, signal, sys\n"
        "sys.path.insert(0, sys.argv[3])\n"
        "from tpu_cooccurrence.observability.journal import RunJournal, VERSION\n"
        "rec = dict(v=VERSION, seq=1, ts=100, events=5, pairs=3,\n"
        "           rows_scored=2, sample_seconds=0.01, score_seconds=0.02,\n"
        "           ring_depth=0, stall_seconds=0.0, wall_unix=1.0,\n"
        "           counters={}, wire={})\n"
        "j = RunJournal(sys.argv[1])\n"
        "if not os.path.exists(sys.argv[2]):\n"
        "    open(sys.argv[2], 'w').close()\n"
        "    j.record(rec)\n"
        "    j.record(dict(rec, seq=2, ts=200))\n"
        "    j._f.write('{\"v\": 1, \"seq\": 3, \"ts\"')  # torn mid-write\n"
        "    j._f.flush()\n"
        "    os.kill(os.getpid(), signal.SIGKILL)\n"
        "j.record(dict(rec, seq=3, ts=300))\n"
        "print('done')\n"
    )
    sink = _Sink()
    with caplog.at_level(logging.WARNING, "tpu_cooccurrence.supervisor"):
        rc = supervise([sys.executable, "-c", code, str(jpath), str(marker),
                        REPO],
                       attempts=1, delay_s=0, stdout=sink,
                       journal_path=str(jpath))
    assert rc == 0 and sink.text == "done\n"
    quoted = [r.message for r in caplog.records if "journal" in r.message]
    assert any("journal tail (2 record(s)" in m for m in quoted), quoted
    # The dead attempt's LAST fired window (seq 2, not the torn seq-3
    # line) is quoted verbatim.
    assert any('"seq": 2' in m and '"ts": 200' in m for m in quoted), quoted
    # The file itself carries both attempts: crash tail + clean rerun.
    from tpu_cooccurrence.observability.journal import read_records

    assert [r["seq"] for r in read_records(str(jpath))] == [1, 2, 3]


def test_supervisor_journal_tail_missing_file_logs_and_continues(tmp_path,
                                                                 caplog):
    import logging

    sink = _Sink()
    with caplog.at_level(logging.WARNING, "tpu_cooccurrence.supervisor"):
        rc = supervise([sys.executable, "-c", "import sys; sys.exit(3)"],
                       attempts=0, delay_s=0, stdout=sink,
                       journal_path=str(tmp_path / "never-written.jsonl"))
    assert rc == 3
    assert any("wrote no journal records" in r.message
               for r in caplog.records)


def test_supervisor_does_not_quote_stale_journal_as_dead_childs(tmp_path,
                                                                caplog):
    """A child that dies before its first window (startup crash) must not
    have an earlier run's journal records quoted as its last act — even
    when opening the journal grew the file by sealing a predecessor's
    torn line (the 1-byte write that defeats a size-only guard)."""
    import logging

    jpath = tmp_path / "j.jsonl"
    # Earlier run's record plus a torn final line (no trailing newline):
    # the child's RunJournal open seals it with "\n" before crashing.
    jpath.write_text('{"v": 1, "seq": 9, "ts": 900}\n{"v": 1, "seq": 10')
    code = ("import sys\n"
            "sys.path.insert(0, sys.argv[2])\n"
            "from tpu_cooccurrence.observability.journal import RunJournal\n"
            "RunJournal(sys.argv[1])\n"
            "sys.exit(5)\n")
    sink = _Sink()
    with caplog.at_level(logging.WARNING, "tpu_cooccurrence.supervisor"):
        rc = supervise([sys.executable, "-c", code, str(jpath), REPO],
                       attempts=0, delay_s=0, stdout=sink,
                       journal_path=str(jpath))
    assert rc == 5
    msgs = [r.message for r in caplog.records]
    assert any("wrote no journal records" in m for m in msgs), msgs
    assert not any('"seq": 9' in m for m in msgs), msgs


@pytest.mark.slow
def test_sigkill_under_supervisor_output_identical(tmp_path):
    """SIGKILL mid-run (right after the first periodic checkpoint lands);
    the supervisor restarts, the child restores, and total stdout is
    byte-identical to an uninterrupted run — zero operator action. The
    run journal survives the kill: every record validates and the
    supervisor quotes the dead attempt's tail."""
    f = tmp_path / "in.csv"
    write_stream(f, n=60_000)
    jpath = tmp_path / "journal.jsonl"
    cli_args = ["-i", str(f), "-ws", "20", "-ic", "8", "-uc", "5",
                "-s", "0xC0FFEE", "--backend", "oracle",
                "--checkpoint-every-windows", "5"]

    clean = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.cli"] + cli_args
        + ["--checkpoint-dir", str(tmp_path / "ck-clean")],
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=300)
    assert clean.returncode == 0, clean.stderr[-800:]

    ck = tmp_path / "ck"
    worker = os.path.join(REPO, "tests", "supervised_crash_worker.py")
    cmd = [sys.executable, worker, str(ck), str(tmp_path / "crashed-once")]
    cmd += cli_args + ["--checkpoint-dir", str(ck), "--journal", str(jpath)]
    sink = _Sink()
    rc = supervise(cmd, attempts=2, delay_s=0, stdout=sink,
                   journal_path=str(jpath))
    assert rc == 0
    assert (tmp_path / "crashed-once").exists(), "crash never injected"
    assert sink.text == clean.stdout
    # Journal integrity across the kill + restore: every surviving line
    # validates, and the stream replay is deterministic — any window
    # ordinal journaled by both attempts carries identical logical fields.
    from tpu_cooccurrence.observability.journal import (read_records,
                                                        validate_record)

    recs = list(read_records(str(jpath)))
    assert recs, "journal never written"
    by_seq = {}
    for r in recs:
        validate_record(r)
        logical = (r["ts"], r["events"], r["pairs"])
        assert by_seq.setdefault(r["seq"], logical) == logical
    assert max(by_seq) == len(by_seq), "window ordinals must be gapless"


def test_cli_restart_flag_healthy_run(tmp_path, capsys):
    """--restart-on-failure on a healthy run: supervised child executes
    once and the output matches an unsupervised run."""
    f = tmp_path / "in.csv"
    write_stream(f)
    base = ["-i", str(f), "-ws", "50", "--backend", "oracle",
            "-s", "0xC0FFEE"]
    plain = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.cli"] + base,
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=300)
    assert plain.returncode == 0, plain.stderr[-800:]
    supervised = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.cli"] + base
        + ["--restart-on-failure", "2", "--restart-delay-ms", "0"],
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=300)
    assert supervised.returncode == 0, supervised.stderr[-800:]
    assert supervised.stdout == plain.stdout


# -- hardened recovery loop (robustness PR) ----------------------------


def _fail_n_times_cmd(marker, n, rc=3, final_line="recovered"):
    """A child that exits ``rc`` its first ``n`` runs, then succeeds."""
    return [sys.executable, "-c", (
        "import os, sys\n"
        f"p = {str(marker)!r}\n"
        "k = int(open(p).read()) if os.path.exists(p) else 0\n"
        "open(p, 'w').write(str(k + 1))\n"
        f"if k < {n}:\n"
        f"    sys.exit({rc})\n"
        f"print({final_line!r})\n")]


def test_permanent_exit_code_not_retried(tmp_path):
    """EX_CONFIG (and argparse's 2) mean a bad flag: restarting cannot
    help, so the supervisor returns immediately without burning
    attempts."""
    from tpu_cooccurrence.supervisor import EX_CONFIG

    marker = tmp_path / "runs"
    code = (
        "import os, sys\n"
        f"p = {str(marker)!r}\n"
        "k = int(open(p).read()) if os.path.exists(p) else 0\n"
        "open(p, 'w').write(str(k + 1))\n"
        f"sys.exit({EX_CONFIG})\n")
    sink = _Sink()
    rc = supervise([sys.executable, "-c", code], attempts=5, delay_s=0,
                   stdout=sink)
    assert rc == EX_CONFIG
    assert marker.read_text() == "1", "a permanent failure must not retry"


def test_cli_config_error_exits_ex_config(tmp_path):
    """cli.main turns a config ValueError into EX_CONFIG (a permanent
    code), instead of an uncaught traceback's generic rc=1."""
    from tpu_cooccurrence.supervisor import EX_CONFIG

    f = tmp_path / "in.csv"
    write_stream(f, n=20)
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.cli", "-i", str(f),
         "-ws", "10", "--checkpoint-retain", "0"],
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=300)
    assert proc.returncode == EX_CONFIG, proc.stderr[-500:]
    assert "checkpoint-retain" in proc.stderr


def test_crash_loop_breaker_steps_back_then_gives_up(tmp_path, caplog):
    """Threshold failures inside the window: the breaker retires the
    newest checkpoint generation once (the poisoned-snapshot
    hypothesis); a re-trip gives up instead of burning every attempt."""
    import logging

    ck = tmp_path / "ck"
    ck.mkdir()
    (ck / "state.1.npz").write_bytes(b"older")
    (ck / "state.2.npz").write_bytes(b"poisoned")
    marker = tmp_path / "runs"
    cmd = _fail_n_times_cmd(marker, n=99)  # never recovers
    sink = _Sink()
    with caplog.at_level(logging.WARNING):
        rc = supervise(cmd, attempts=10, delay_s=0, stdout=sink,
                       crash_loop_threshold=2, crash_loop_window_s=60.0,
                       checkpoint_dir=str(ck))
    assert rc == 3
    assert (ck / "state.2.npz.rolledback").exists()
    assert (ck / "state.1.npz").exists()
    # fail, fail -> step back; fail, fail -> breaker open, give up: the
    # 10 attempts were NOT exhausted.
    assert marker.read_text() == "4"
    assert any("crash-loop breaker open" in r.message
               for r in caplog.records)


def test_breaker_without_checkpoint_keeps_full_attempt_budget(tmp_path):
    """The breaker only trades attempts for a step-back it actually
    performed: with no --checkpoint-dir it must NOT override the
    operator's --restart-on-failure budget."""
    marker = tmp_path / "runs"
    sink = _Sink()
    rc = supervise(_fail_n_times_cmd(marker, n=99), attempts=4,
                   delay_s=0, stdout=sink, crash_loop_threshold=3,
                   crash_loop_window_s=60.0)
    assert rc == 3
    assert marker.read_text() == "5", "all attempts must burn"


def test_breaker_single_generation_warns_and_continues(tmp_path, caplog):
    """A checkpoint dir with only one generation has nothing to fall
    back to: the breaker logs once and the full budget still applies."""
    import logging

    ck = tmp_path / "ck"
    ck.mkdir()
    (ck / "state.1.npz").write_bytes(b"only one")
    marker = tmp_path / "runs"
    sink = _Sink()
    with caplog.at_level(logging.WARNING, "tpu_cooccurrence.supervisor"):
        rc = supervise(_fail_n_times_cmd(marker, n=99), attempts=4,
                       delay_s=0, stdout=sink, crash_loop_threshold=2,
                       crash_loop_window_s=60.0, checkpoint_dir=str(ck))
    assert rc == 3
    assert marker.read_text() == "5"
    assert (ck / "state.1.npz").exists()
    warns = [r for r in caplog.records
             if "no older checkpoint generation" in r.message]
    assert len(warns) == 1, "the no-step-back warning must fire once"


def test_breaker_off_preserves_attempt_exhaustion(tmp_path):
    """crash_loop_threshold=0 disables the breaker: all attempts burn
    (the legacy semantics)."""
    marker = tmp_path / "runs"
    sink = _Sink()
    rc = supervise(_fail_n_times_cmd(marker, n=99), attempts=4,
                   delay_s=0, stdout=sink, crash_loop_threshold=0)
    assert rc == 3
    assert marker.read_text() == "5"


def test_backoff_decorrelated_jitter_bounds(tmp_path, monkeypatch):
    """Backoff draws uniform on [base, prev*3] capped at max — record
    the draw bounds instead of sleeping through them."""
    import random as _random

    draws = []

    def fake_uniform(lo, hi):
        draws.append((round(lo, 6), round(hi, 6)))
        return hi

    monkeypatch.setattr(_random, "uniform", fake_uniform)
    naps = []
    import tpu_cooccurrence.supervisor as sup
    monkeypatch.setattr(sup, "_POLL_S", 0.01)
    real_sleep = time.sleep
    monkeypatch.setattr(
        time, "sleep",
        lambda s: naps.append(s) if s > 0.01 else real_sleep(s))

    marker = tmp_path / "runs"
    sink = _Sink()
    rc = supervise(_fail_n_times_cmd(marker, n=3), attempts=5,
                   delay_s=0, stdout=sink, crash_loop_threshold=0,
                   backoff_base_s=0.05, backoff_max_s=0.2)
    assert rc == 0 and sink.text == "recovered\n"
    assert draws[0] == (0.05, round(0.05 * 3, 6))
    assert draws[1] == (0.05, round(0.15 * 3, 6))
    # Third delay hit the 0.2 cap: min(0.2, uniform(...)=1.35).
    assert naps[:3] == pytest.approx([0.15, 0.2, 0.2])


def test_journal_forensics_failure_does_not_kill_supervisor(
        tmp_path, monkeypatch, caplog):
    """A garbled/unreadable journal must cost the restart log its quote,
    never the restart itself."""
    import logging

    from tpu_cooccurrence.observability import journal as journal_mod

    def boom(*a, **kw):
        raise RuntimeError("journal reader exploded")

    monkeypatch.setattr(journal_mod, "tail", boom)
    marker = tmp_path / "runs"
    jpath = tmp_path / "j.jsonl"
    jpath.write_text("not json at all\n")
    sink = _Sink()
    with caplog.at_level(logging.WARNING, "tpu_cooccurrence.supervisor"):
        rc = supervise(_fail_n_times_cmd(marker, n=1), attempts=2,
                       delay_s=0, stdout=sink, journal_path=str(jpath))
    assert rc == 0 and sink.text == "recovered\n"
    assert any("restarting without the quote" in r.message
               for r in caplog.records)


def test_watchdog_kills_stale_child(tmp_path):
    """A child whose journal stops growing past the staleness threshold
    is killed (SIGTERM->SIGKILL) and counted as a failed attempt."""
    jpath = tmp_path / "j.jsonl"
    code = (
        "import sys, time\n"
        f"f = open({str(jpath)!r}, 'a')\n"
        "f.write('{\"seq\": 1}\\n')\n"
        "f.flush()\n"
        "time.sleep(600)\n")
    sink = _Sink()
    t0 = time.monotonic()
    rc = supervise([sys.executable, "-c", code], attempts=0, delay_s=0,
                   stdout=sink, journal_path=str(jpath),
                   watchdog_stale_after_s=1.0)
    assert rc == 124
    assert sink.text == ""
    assert time.monotonic() - t0 < 30, "watchdog should not wait the hang out"


def test_watchdog_start_grace_survives_torn_tail_seal(tmp_path,
                                                      monkeypatch):
    """A restarted child seals a predecessor's torn journal line with a
    single newline the moment it opens the journal — before restore.
    That 1-byte growth must NOT count as progress, or the startup grace
    collapses to the steady-state threshold and a healthy recovering
    child is killed mid-restore."""
    import tpu_cooccurrence.supervisor as sup

    monkeypatch.setattr(sup, "WATCHDOG_START_GRACE_S", 4.0)
    jpath = tmp_path / "j.jsonl"
    jpath.write_text('{"seq": 1}\n{"torn": tru')  # predecessor's torn tail
    code = (
        "import time\n"
        f"f = open({str(jpath)!r}, 'a')\n"
        "f.write('\\n')\n"  # the seal, written at journal open
        "f.flush()\n"
        "time.sleep(600)\n")  # "restore/replay" that never progresses
    sink = _Sink()
    t0 = time.monotonic()
    rc = supervise([sys.executable, "-c", code], attempts=0, delay_s=0,
                   stdout=sink, journal_path=str(jpath),
                   watchdog_stale_after_s=1.0)
    elapsed = time.monotonic() - t0
    assert rc == 124
    # Killed on the 4s startup grace, not 1s after the seal byte.
    assert elapsed > 3.0, (
        f"seal byte collapsed the startup grace (killed after "
        f"{elapsed:.1f}s)")


def test_supervisor_state_env_reaches_child(tmp_path):
    """The child of a restarted attempt sees restart count/backoff in
    TPU_COOC_SUPERVISOR_STATE (the scrape plane's input)."""
    import json as _json

    from tpu_cooccurrence.supervisor import SUPERVISOR_STATE_ENV

    marker = tmp_path / "runs"
    code = (
        "import json, os, sys\n"
        f"p = {str(marker)!r}\n"
        "k = int(open(p).read()) if os.path.exists(p) else 0\n"
        "open(p, 'w').write(str(k + 1))\n"
        "if k < 1:\n"
        "    sys.exit(3)\n"
        f"print(os.environ[{SUPERVISOR_STATE_ENV!r}])\n")
    sink = _Sink()
    rc = supervise([sys.executable, "-c", code], attempts=2, delay_s=0.01,
                   stdout=sink)
    assert rc == 0
    state = _json.loads(sink.text)
    assert state["restarts"] == 1
    assert state["last_rc"] == 3
    assert state["backoff_ms"] == 10
    assert state["last_restart_unix"] > 0
