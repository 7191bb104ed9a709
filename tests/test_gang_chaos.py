"""Whole-gang chaos capstone (ISSUE 10): the real CLI in gang mode.

A 2-process CPU multi-controller gang (local ``jax.distributed``
coordinator, gloo collectives, 1 virtual device per worker) driven by
the real gang supervisor, with process-qualified faults injected:

* ``ckpt_commit@1:<gen>:crash`` kills exactly worker 1 inside the
  epoch-commit window — its generation file is renamed into place but
  no ``EPOCH`` marker exists, and worker 0 is wedged in the commit
  barrier (the collective-entry watchdog or the gang-kill resolves it).
  The gang restarts, the restore vote drags BOTH hosts back to the
  previous epoch (the torn generation quarantined as ``*.partial`` on
  both), and total stdout is bit-identical to an uninterrupted gang
  run — at pipeline depths 0 and 2.

* multi-host ``--degrade``: both workers journal the IDENTICAL
  transition sequence (the per-window worst-signal allgather keeps the
  ladder in lockstep) with sampling parity intact.

The deeper soak (more sites, the journal-staleness wedge detection) is
``slow``-lane; this module's quick variants are tier-1.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           XLA_FLAGS="--xla_force_host_platform_device_count=1")


@pytest.fixture(scope="module")
def stream(tmp_path_factory):
    path = tmp_path_factory.mktemp("gang") / "in.csv"
    with open(path, "w") as fh:
        # 350 events = 7 windows at ws 500: enough for the highest
        # chaos ordinal in this module (window/generation 5) with
        # margin, at ~2/3 the wall of the original 500-event stream —
        # the fixture feeds four-plus real gang runs (tier-1 budget).
        for i in range(350):
            fh.write(f"{i % 13},{i % 17},{i * 10}\n")
    return str(path)


def _gang_args(stream, ck_dir, extra):
    return [sys.executable, "-m", "tpu_cooccurrence.cli",
            "-i", stream, "-ws", "500", "-ic", "8", "-uc", "5",
            "-s", "0xC0FFEE", "--backend", "sharded",
            "--num-shards", "2", "--num-items", "32",
            "--checkpoint-dir", ck_dir,
            "--checkpoint-every-windows", "2",
            "--checkpoint-retain", "10",
            "--gang-workers", "2", "--gang-heartbeat-s", "1",
            "--collective-timeout-s", "15",
            "--restart-delay-ms", "0"] + extra


def _run(stream, ck_dir, extra, timeout=420):
    proc = subprocess.run(_gang_args(stream, ck_dir, extra),
                          capture_output=True, text=True, env=ENV,
                          cwd=REPO, timeout=timeout)
    return proc


@pytest.fixture(scope="module")
def clean(stream, tmp_path_factory):
    """One uninterrupted gang run — the parity reference for every
    chaos variant (bit-identical across pipeline depths by the PR-1
    contract, so one reference serves depth 0 and 2)."""
    ck = str(tmp_path_factory.mktemp("gang-clean") / "ck")
    proc = _run(stream, ck, [])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout, "clean gang run produced no output"
    return proc.stdout


@pytest.mark.parametrize("depth", [0, 2])
def test_gang_ckpt_commit_crash_recovers_bit_identical(
        tmp_path, stream, clean, depth):
    """Kill worker 1 at the generation-2 epoch commit: the gang
    restarts, the restore vote falls back to generation 1 on BOTH
    hosts (torn generation quarantined as *.partial — no torn restore,
    no crash loop), and stdout is bit-identical to the uninterrupted
    run."""
    ck = str(tmp_path / "ck")
    extra = ["--restart-on-failure", "2",
             "--inject-fault", "ckpt_commit@1:2:crash",
             "--fault-state-dir", str(tmp_path / "faults")]
    if depth:
        extra += ["--pipeline-depth", str(depth)]
    proc = _run(stream, ck, extra)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout == clean
    # Exactly worker 1's marker fired (the @proc qualifier held).
    assert sorted(os.listdir(tmp_path / "faults")) == ["fault0.p1.fired"]
    # The torn generation was quarantined on BOTH hosts: worker 1
    # crashed post-rename-pre-marker, worker 0 died wedged in the
    # commit barrier — neither may ever restore generation 2's files.
    partials = sorted(p for p in os.listdir(ck)
                      if p.endswith(".partial"))
    assert partials == ["state.p0.2.npz.partial",
                        "state.p1.2.npz.partial"]
    assert "gang restore vote" in proc.stderr
    assert "gang-restarting" in proc.stderr


def _sparse_gang_args(stream, ck_dir, incremental, extra):
    """Sparse-backend gang (the sharded-sparse mh checkpoint format —
    the topology the incremental delta chain must survive)."""
    return [sys.executable, "-m", "tpu_cooccurrence.cli",
            "-i", stream, "-ws", "500", "-ic", "8", "-uc", "5",
            "-s", "0xC0FFEE", "--backend", "sparse",
            "--num-shards", "2",
            "--checkpoint-dir", ck_dir,
            "--checkpoint-every-windows", "2",
            "--checkpoint-retain", "10",
            "--checkpoint-compact-ratio", "10",
            "--gang-workers", "2", "--gang-heartbeat-s", "1",
            "--collective-timeout-s", "15",
            "--restart-delay-ms", "0"] \
        + (["--checkpoint-incremental"] if incremental else []) + extra


def _run_sparse(stream, ck_dir, incremental, extra, timeout=420):
    return subprocess.run(
        _sparse_gang_args(stream, ck_dir, incremental, extra),
        capture_output=True, text=True, env=ENV, cwd=REPO,
        timeout=timeout)


@pytest.mark.slow
def test_gang_incremental_ckpt_mid_delta_crash_bit_identical(
        tmp_path, stream):
    """ISSUE 12 acceptance: a 2-process sparse gang running INCREMENTAL
    checkpoints, killed inside a DELTA generation's epoch-commit window
    (worker 1 at the generation-2 commit — its npz and delta file are
    renamed into place but no EPOCH marker exists). The restore vote
    counts only fully-committed chains, drags both hosts back to
    generation 1, quarantines the torn generation's npz AND delta as
    *.partial on both, and total stdout is bit-identical to the SAME
    crash recovered from full checkpoints (restore canonicalizes
    within-row slab order, so the full-checkpoint recovery — not an
    uninterrupted run — is the bit-exact comparator, same as every
    sparse resume test): the delta-chain restore is byte-equivalent to
    the full-checkpoint restore in the gang topology."""
    chaos = ["--restart-on-failure", "2",
             "--inject-fault", "ckpt_commit@1:2:crash"]
    ref_ck = str(tmp_path / "ck-full")
    ref = _run_sparse(stream, ref_ck, False,
                      chaos + ["--fault-state-dir",
                               str(tmp_path / "faults-full")])
    assert ref.returncode == 0, ref.stderr[-2000:]
    assert ref.stdout, "full-checkpoint chaos run produced no output"
    assert "gang restore vote" in ref.stderr

    ck = str(tmp_path / "ck")
    proc = _run_sparse(stream, ck, True,
                       chaos + ["--fault-state-dir",
                                str(tmp_path / "faults")])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout == ref.stdout
    assert "gang restore vote" in proc.stderr
    assert sorted(os.listdir(tmp_path / "faults")) == ["fault0.p1.fired"]
    # The torn DELTA generation was quarantined with its npz on both
    # hosts; the recovered run then rebuilt generation 2 (files exist
    # again) and kept chaining deltas.
    partials = sorted(p for p in os.listdir(ck)
                      if p.endswith(".partial"))
    assert partials == ["delta.p0.2.bin.partial",
                        "delta.p1.2.bin.partial",
                        "state.p0.2.npz.partial",
                        "state.p1.2.npz.partial"]
    for pid in (0, 1):
        assert any(n.startswith(f"delta.p{pid}.")
                   and n.endswith(".bin") for n in os.listdir(ck)), \
            f"no live delta generation for p{pid} after recovery"


def test_gang_degrade_lockstep_journals(tmp_path, stream):
    """--degrade on a multi-host run: the per-window worst-signal
    allgather steps both hosts' ladders identically — the journals
    carry the same (seq, level, events) sequence — and the run
    completes with both partitions emitted (sampling parity)."""
    ck = str(tmp_path / "ck")
    jpath = str(tmp_path / "journal.jsonl")
    proc = _run(stream, ck,
                ["--degrade", "--degrade-window-wall-s", "0.0001",
                 "--degrade-trip-windows", "2", "--journal", jpath])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout
    seqs = []
    for pid in (0, 1):
        with open(f"{jpath}.p{pid}") as f:
            recs = [json.loads(line) for line in f if line.strip()]
        seqs.append([(r["seq"], r.get("degradation_level"),
                      tuple(r.get("degrade_events", [])))
                     for r in recs if "seq" in r])
    assert seqs[0] == seqs[1], "hosts diverged on the shed ladder"
    levels = {lv for s in seqs for _, lv, _ in s}
    assert max(levels) >= 1, "the tiny wall threshold never tripped"
    # Window records in a multi-host run carry the committed epoch.
    with open(f"{jpath}.p0") as f:
        first = json.loads(next(iter(f)))
    assert "epoch" in first


@pytest.mark.slow
def test_gang_soak_more_sites_and_wedge_detection(tmp_path, stream,
                                                  clean):
    """Slow-lane soak: (a) a worker SIGKILLed mid-window recovers via
    gang restart; (b) a worker wedged alive (600s delay injected in
    the window loop, heartbeats still beating) is detected by the
    JOURNAL-staleness watchdog and the gang restarts — both with
    bit-identical stdout."""
    # (a) plain mid-window crash of worker 0 at window 5.
    ck = str(tmp_path / "ck-a")
    proc = _run(stream, ck,
                ["--restart-on-failure", "2",
                 "--inject-fault", "window_fire@0:5:crash",
                 "--fault-state-dir", str(tmp_path / "faults-a")])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout == clean
    assert sorted(os.listdir(tmp_path / "faults-a")) == [
        "fault0.p0.fired"]
    # (b) silently wedged peer: worker 1 stalls 600s inside the window
    # loop while its heartbeat thread keeps beating — only the journal
    # watchdog can see it.
    ck = str(tmp_path / "ck-b")
    jpath = str(tmp_path / "journal-b.jsonl")
    proc = _run(stream, ck,
                ["--restart-on-failure", "2",
                 "--journal", jpath,
                 "--watchdog-stale-after-s", "4",
                 "--inject-fault", "window_fire@1:5:delay_ms:600000",
                 "--fault-state-dir", str(tmp_path / "faults-b")])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout == clean
    assert "journal stale" in proc.stderr
