"""Chaos soak: the exactly-once-output contract under injected faults.

The capstone of the robustness plane (ISSUE 3): run the *real* CLI
under the *real* supervisor with crashes injected at distinct hot-path
sites — window fire, scorer dispatch, checkpoint post-write-pre-rename
(a torn commit), journal append — and assert the total stdout is
bit-identical to an uninterrupted run. Every recovery layer is in the
loop: supervisor respawn, checkpoint-generation fallback past the torn
snapshot, journal torn-tail sealing, and (separately) the hang
watchdog killing a stalled child.

The quick variant is tier-1; the multi-site soak across pipeline
depths 0 and 2 is ``slow`` (full-suite / round-gate lane).
"""

import os
import subprocess
import sys

import pytest

from tpu_cooccurrence.supervisor import supervise

from test_cli import write_stream

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


class _Sink:
    def __init__(self):
        self.text = ""

    def write(self, s):
        self.text += s


def _clean_run(tmp_path, base_args):
    """The uninterrupted reference run (its own checkpoint dir)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.cli"] + base_args
        + ["--checkpoint-dir", str(tmp_path / "ck-clean")],
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stderr[-800:]
    return proc.stdout


def _supervised_run(tmp_path, base_args, fault_specs, attempts,
                    watchdog_stale_after_s=None):
    """Drive supervise() in-process over real CLI children with the
    fault plan armed (exactly-once across restarts via the marker dir)."""
    ck = tmp_path / "ck"
    jpath = tmp_path / "journal.jsonl"
    cmd = [sys.executable, "-m", "tpu_cooccurrence.cli"] + base_args
    cmd += ["--checkpoint-dir", str(ck), "--journal", str(jpath),
            "--fault-state-dir", str(tmp_path / "fault-state")]
    for spec in fault_specs:
        cmd += ["--inject-fault", spec]
    sink = _Sink()
    rc = supervise(cmd, attempts=attempts, delay_s=0, stdout=sink,
                   journal_path=str(jpath), crash_loop_threshold=0,
                   watchdog_stale_after_s=watchdog_stale_after_s,
                   checkpoint_dir=str(ck))
    return rc, sink.text


def _assert_all_fired(tmp_path, n):
    fired = sorted(os.listdir(tmp_path / "fault-state"))
    assert len(fired) == n, (
        f"expected {n} injected faults to have fired, got {fired}")


def test_chaos_quick_crash_parity(tmp_path):
    """Tier-1 variant: three distinct crash sites — a window-loop crash,
    a torn checkpoint commit (post-write-pre-rename), and a crash at
    journal append — at pipeline depth 0; stdout must be bit-identical
    to the uninterrupted run, with zero operator action."""
    f = tmp_path / "in.csv"
    write_stream(f, n=600)
    base = ["-i", str(f), "-ws", "40", "-ic", "8", "-uc", "5",
            "-s", "0xC0FFEE", "--backend", "oracle",
            "--checkpoint-every-windows", "3",
            # Wide retain window: the PR-9 sweep ages out *.corrupt
            # files whose generation leaves the window, and this test's
            # final assertion wants the torn generation's forensics
            # still on disk (the sweep itself is pinned by
            # tests/test_state_store.py).
            "--checkpoint-retain", "10"]
    clean = _clean_run(tmp_path, base)
    assert clean, "reference run produced no output"

    rc, out = _supervised_run(
        tmp_path, base,
        ["window_fire:4:crash",
         "checkpoint_post_write:6:torn_write",
         "journal_append:9:crash"],
        attempts=4)
    assert rc == 0
    assert out == clean
    _assert_all_fired(tmp_path, 3)
    # The torn checkpoint commit really was quarantined on fallback.
    corrupt = [p for p in os.listdir(tmp_path / "ck")
               if p.endswith(".corrupt")]
    assert corrupt, "torn snapshot should have been quarantined"


def test_chaos_watchdog_hang_recovery_parity(tmp_path):
    """A child stalled by delay_ms injection past the watchdog
    threshold is killed, restarted, and the run completes with exact
    output parity — a hang costs one attempt, not the whole run."""
    f = tmp_path / "in.csv"
    write_stream(f, n=600)
    base = ["-i", str(f), "-ws", "40", "-ic", "8", "-uc", "5",
            "-s", "0xBEEF", "--backend", "oracle",
            "--checkpoint-every-windows", "3"]
    clean = _clean_run(tmp_path, base)

    rc, out = _supervised_run(
        tmp_path, base, ["window_fire:5:delay_ms:600000"],
        attempts=2, watchdog_stale_after_s=2.0)
    assert rc == 0
    assert out == clean
    _assert_all_fired(tmp_path, 1)


def test_chaos_exception_kind_recovers_too(tmp_path):
    """The exception kind (clean unwind, not SIGKILL) exits nonzero
    through normal error handling and the supervised run still
    converges to bit-identical output."""
    f = tmp_path / "in.csv"
    write_stream(f, n=400)
    base = ["-i", str(f), "-ws", "50", "-ic", "8", "-uc", "5",
            "-s", "0xFEED", "--backend", "oracle",
            "--checkpoint-every-windows", "2"]
    clean = _clean_run(tmp_path, base)
    rc, out = _supervised_run(
        tmp_path, base, ["scorer_dispatch:3:exception"], attempts=2)
    assert rc == 0
    assert out == clean
    _assert_all_fired(tmp_path, 1)


def test_chaos_scorer_breaker_trips_and_run_completes_on_fallback(tmp_path):
    """Graceful-degradation capstone (ISSUE 5): an injected dispatch
    failure inside the device scorer trips the circuit breaker mid-run;
    the run completes on the host-oracle fallback WITHOUT a supervisor
    or restart — degrade, don't die — and the trip is visible in the
    journal's ``breaker_state`` field."""
    f = tmp_path / "in.csv"
    write_stream(f, n=600)
    jpath = tmp_path / "journal.jsonl"
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.cli", "-i", str(f),
         "-ws", "40", "-ic", "8", "-uc", "5", "-s", "0xC0FFEE",
         "--backend", "device", "--journal", str(jpath),
         "--scorer-breaker-threshold", "1",
         "--scorer-breaker-probe-windows", "3",
         "--inject-fault", "scorer_breaker:3:exception"],
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert proc.stdout, "run completed but emitted no results"
    from tpu_cooccurrence.observability.journal import read_records

    states = [r["breaker_state"] for r in read_records(str(jpath))]
    assert "open" in states, states          # the trip is journaled
    assert states[0] == "closed"             # and it happened mid-run
    assert states[-1] == "closed", states    # half-open probe recovered


def _run_cli(args, timeout=600, expect_rc=0):
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.cli"] + args,
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=timeout)
    if expect_rc is not None:
        assert proc.returncode == expect_rc, proc.stderr[-800:]
    return proc


@pytest.mark.parametrize("n_from,n_to,depth", [(2, 4, 0), (4, 2, 2)])
def test_chaos_rescale_kill_and_resume_other_topology(tmp_path, n_from,
                                                      n_to, depth):
    """Elastic-state capstone (ISSUE 9): kill a sharded-sparse run at
    ``--num-shards N`` mid-stream, resume at M — stdout bit-identical
    to resuming at N (the same-topology resume is the canonical
    reference: any restore rebuilds rows in key order, so rescale must
    change NOTHING beyond topology), both directions, depths 0 and 2.
    """
    f = tmp_path / "in.csv"
    write_stream(f, n=500)
    ck = tmp_path / "ck"

    def args(shards, extra=()):
        return ["-i", str(f), "-ws", "40", "-ic", "8", "-uc", "5",
                "-s", "0xC0FFEE", "--backend", "sparse",
                "--num-shards", str(shards),
                "--pipeline-depth", str(depth),
                "--checkpoint-every-windows", "3",
                "--checkpoint-dir", str(ck)] + list(extra)

    # Kill at N: the injected crash leaves a committed checkpoint behind
    # (rc != 0 — the crash is a SIGKILL-style exit, not a clean run).
    proc = _run_cli(args(n_from, ["--inject-fault", "window_fire:7:crash",
                                  "--fault-state-dir",
                                  str(tmp_path / "fault-state")]),
                    expect_rc=None)
    assert proc.returncode != 0
    assert not proc.stdout, "final dump must not have run before the kill"
    assert any(p.startswith("state") for p in os.listdir(ck)), \
        "no checkpoint to rescale from"
    import shutil

    shutil.copytree(ck, tmp_path / "ck-same")
    same_args = args(n_from)
    same_args[same_args.index(str(ck))] = str(tmp_path / "ck-same")

    # Resume at N (reference) and at M (rescaled) from the same kill.
    same = _run_cli(same_args)
    rescaled = _run_cli(args(n_to))
    assert same.stdout, "resumed run emitted nothing"
    assert "restored checkpoint" in rescaled.stderr
    assert rescaled.stdout == same.stdout
    _assert_all_fired(tmp_path, 1)


@pytest.mark.parametrize("depth", [0, 2])
def test_chaos_spill_enabled_stdout_identical_to_off(tmp_path, depth):
    """Tiered-state transparency through the real CLI: a spill-enabled
    sparse run's total stdout is bit-identical to spill-off on the same
    stream (spill/promote is exact movement, tie order included), at
    pipeline depths 0 and 2."""
    f = tmp_path / "in.csv"
    write_stream(f, n=450)
    base = ["-i", str(f), "-ws", "40", "-ic", "8", "-uc", "5",
            "-s", "0xC0FFEE", "--backend", "sparse",
            "--pipeline-depth", str(depth)]
    off = _run_cli(base)
    on = _run_cli(base + ["--spill-threshold-windows", "2",
                          "--spill-target-hbm-frac", "0.0"])
    assert off.stdout, "spill-off run emitted nothing"
    assert on.stdout == off.stdout
    assert "tiered state armed" in on.stderr


@pytest.mark.slow
@pytest.mark.parametrize("depth", [0, 2])
def test_chaos_soak_multi_site_parity(tmp_path, depth):
    """The full soak: crashes at four distinct sites (source read,
    window fire, torn checkpoint commit, journal append) plus a worker-
    thread crash at scorer dispatch, across pipeline depths 0 and 2 —
    total stdout bit-identical to the uninterrupted run at the same
    depth."""
    f = tmp_path / "in.csv"
    write_stream(f, n=4000)
    base = ["-i", str(f), "-ws", "150", "-ic", "8", "-uc", "5",
            "-s", "0xC0FFEE", "--backend", "oracle",
            "--pipeline-depth", str(depth),
            "--checkpoint-every-windows", "3",
            # Wide enough that the torn generation's *.corrupt survives
            # the PR-9 aged-quarantine sweep until the final assertion.
            "--checkpoint-retain", "12"]
    clean = _clean_run(tmp_path, base)
    faults = [
        "source_read:crash",                    # before any progress
        "window_fire:5:crash",
        "scorer_dispatch:9:crash",              # worker thread at depth 2
        "checkpoint_post_write:12:torn_write",  # corrupt committed latest
        "journal_append:15:crash",
    ]
    rc, out = _supervised_run(tmp_path, base, faults, attempts=7)
    assert rc == 0
    assert out == clean
    _assert_all_fired(tmp_path, len(faults))
    corrupt = [p for p in os.listdir(tmp_path / "ck")
               if p.endswith(".corrupt")]
    assert corrupt, "torn snapshot should have been quarantined"

    # Journal integrity across five kills: every surviving record
    # validates, ordinals are gapless, and any window journaled by
    # multiple attempts carries identical logical fields (the replay-
    # determinism contract).
    from tpu_cooccurrence.observability.journal import (read_records,
                                                        validate_record)

    recs = list(read_records(str(tmp_path / "journal.jsonl")))
    assert recs, "journal never written"
    by_seq = {}
    for r in recs:
        validate_record(r)
        logical = (r["ts"], r["events"], r["pairs"])
        assert by_seq.setdefault(r["seq"], logical) == logical
    assert max(by_seq) == len(by_seq), "window ordinals must be gapless"


def test_chaos_ckpt_commit_crash_in_torn_pointer_window(tmp_path):
    """ISSUE-10 durability satellite: crash INSIDE the torn-pointer
    window — generation file renamed into place but the directory
    entry not yet fsynced (the ckpt_commit site sits exactly between
    the rename and the directory fsync). The supervised restart must
    restore and converge to bit-identical output; the site's seq is
    the GENERATION number, so the spec pins the generation-2 commit."""
    f = tmp_path / "in.csv"
    write_stream(f, n=600)
    base = ["-i", str(f), "-ws", "40", "-ic", "8", "-uc", "5",
            "-s", "0xD1CE", "--backend", "oracle",
            "--checkpoint-every-windows", "3"]
    clean = _clean_run(tmp_path, base)
    rc, out = _supervised_run(
        tmp_path, base, ["ckpt_commit:2:crash"], attempts=2)
    assert rc == 0
    assert out == clean
    _assert_all_fired(tmp_path, 1)
