"""CLI driver: end-to-end runs and crash recovery (in-process main)."""

import numpy as np

from tpu_cooccurrence import cli


def write_stream(path, seed=0, n=600, ts_offset=0):
    rng = np.random.default_rng(seed)
    ts = ts_offset + np.cumsum(rng.integers(0, 3, n))
    with open(path, "w") as f:
        for u, i, t in zip(rng.integers(0, 20, n),
                           rng.integers(100, 140, n), ts):
            f.write(f"{u},{i},{t}\n")


def run_cli(capsys, *argv):
    rc = cli.main(list(argv))
    assert rc == 0
    return capsys.readouterr().out


def test_cli_unregistered_fault_site_exits_2_with_site_list(
        caplog, tmp_path):
    """A typo'd --inject-fault site is exit code 2 (in the supervisor's
    PERMANENT_EXIT_CODES — never retried) and the error names the
    registered sites so the operator can fix the spec blind."""
    from tpu_cooccurrence.robustness.faults import SITES

    f = tmp_path / "in.csv"
    write_stream(f, n=50)
    rc = cli.main(["-i", str(f), "-ws", "50", "--backend", "oracle",
                   "--inject-fault", "not_a_site:3:crash"])  # cooclint: disable=fault-site
    assert rc == 2
    err = "\n".join(r.getMessage() for r in caplog.records)
    assert "not_a_site" in err
    for site in SITES:
        assert site in err  # the full registered list is quoted
    # Other config errors keep the EX_CONFIG (78) classification.
    rc = cli.main(["-i", str(f), "-ws", "50", "--backend", "oracle",
                   "--inject-fault", "window_fire:3:delay_ms"])
    assert rc == 78


def test_cli_oracle_end_to_end(capsys, tmp_path):
    f = tmp_path / "in.csv"
    write_stream(f)
    out = run_cli(capsys, "-i", str(f), "-ws", "50", "--backend", "oracle",
                  "-s", "0xC0FFEE")
    lines = [l for l in out.splitlines() if l]
    assert lines, "expected per-item result lines"
    item, rest = lines[0].split("\t")
    scores = [float(t.split(":")[1]) for t in rest.split()]
    assert scores == sorted(scores, reverse=True)


def test_cli_restores_checkpoint_and_skips_consumed_input(capsys, tmp_path):
    f = tmp_path / "in.csv"
    write_stream(f)
    ckpt = tmp_path / "ckpt"
    base = ["-i", str(f), "-ws", "50", "--backend", "oracle", "-s", "7",
            "--checkpoint-dir", str(ckpt), "--checkpoint-every-windows", "1"]
    out1 = run_cli(capsys, *base)
    assert list(ckpt.glob("state.*.npz")), "no checkpoint generation landed"

    # Second invocation: restores (including the source offset), finds no
    # new input, and reproduces the same results.
    out2 = run_cli(capsys, *base)
    assert out2 == out1


def test_cli_restore_continues_with_new_files(capsys, tmp_path):
    d = tmp_path / "stream"
    d.mkdir()
    write_stream(d / "a.csv", seed=1)
    ckpt = tmp_path / "ckpt"
    base = ["-i", str(d), "-ws", "50", "--backend", "oracle", "-s", "9",
            "--checkpoint-dir", str(ckpt), "--checkpoint-every-windows", "1"]
    run_cli(capsys, *base)
    n_splits_1 = 1

    # A new file arrives whose event time continues the stream; the
    # restored run must consume only it (and fire new windows, which
    # refreshes the periodic checkpoint).
    write_stream(d / "b.csv", seed=2, ts_offset=2_000)
    import json

    run_cli(capsys, *base)
    meta = json.loads((ckpt / "meta.json").read_text())
    assert meta["counters"]["SplitReaderNumSplits"] == n_splits_1 + 1
    assert meta["counters"].get("UserInteractionCounterLateElements", 0) == 0


def test_midfile_checkpoint_resumes_exactly(tmp_path):
    """A checkpoint taken while a file is partially ingested must resume at
    the exact line, not re-ingest or drop the tail (the reference's marker
    is whole-file only — this closes that gap)."""
    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.io.parse import batched_lines
    from tpu_cooccurrence.io.source import FileMonitorSource
    from tpu_cooccurrence.job import CooccurrenceJob

    f = tmp_path / "in.csv"
    write_stream(f, seed=5, n=900)
    cfg = lambda: Config(window_size=50, seed=11, backend=Backend.ORACLE,
                         checkpoint_dir=str(tmp_path / "ck"))

    # Uninterrupted reference run.
    ref = CooccurrenceJob(cfg())
    src = FileMonitorSource(str(f), ref.counters)
    ref.run(batched_lines(src.lines()))

    # Run A: consume a few small batches, checkpoint mid-file, "crash".
    a = CooccurrenceJob(cfg())
    src_a = FileMonitorSource(str(f), a.counters)
    batches = batched_lines(src_a.lines(), batch_size=200)
    for _ in range(2):
        a.add_batch(*next(batches))
    a.checkpoint(source=src_a)

    # Run B: restore and continue to the end.
    b = CooccurrenceJob(cfg())
    src_b = FileMonitorSource(str(f), b.counters)
    b.restore(source=src_b)
    for batch in batched_lines(src_b.lines(), batch_size=200):
        b.add_batch(*batch)
    b.finish()

    assert set(ref.latest) == set(b.latest)
    for item in ref.latest:
        assert ref.latest[item] == b.latest[item], item
    for name, val in ref.counters.as_dict().items():
        if name != "SplitReaderNumSplits":  # split re-listed once on resume
            assert b.counters.as_dict()[name] == val, name


def test_cli_emit_updates_streams_and_final_state_matches(capsys, tmp_path):
    """--emit-updates streams one line per updated row per window; the
    LAST update of each item must equal the default final dump."""
    f = tmp_path / "in.csv"
    write_stream(f)
    final = run_cli(capsys, "-i", str(f), "-ws", "50", "--backend",
                    "oracle", "-s", "0xC0FFEE")
    stream = run_cli(capsys, "-i", str(f), "-ws", "50", "--backend",
                     "oracle", "-s", "0xC0FFEE", "--emit-updates")
    stream_lines = [l for l in stream.splitlines() if l]
    final_lines = sorted(l for l in final.splitlines() if l)
    # More updates than items (items rescore across windows)...
    assert len(stream_lines) > len(final_lines)
    # ...and the last streamed row per item is exactly the final state.
    last = {}
    for line in stream_lines:
        last[line.split("\t")[0]] = line
    assert sorted(last.values()) == final_lines


def test_cli_emit_updates_replays_restored_state(capsys, tmp_path):
    """A resumed --emit-updates run replays the restored rows so the
    stream is complete even for items never re-updated after resume."""
    f = tmp_path / "in.csv"
    write_stream(f)
    ck = str(tmp_path / "ck")
    base = ["-i", str(f), "-ws", "50", "--backend", "oracle",
            "-s", "0xC0FFEE", "--checkpoint-dir", ck]
    final = run_cli(capsys, *base, "--checkpoint-every-windows", "2")
    # Second run: input fully consumed, nothing new fires — the stream
    # must still carry the full restored state.
    stream = run_cli(capsys, *base, "--emit-updates")
    last = {}
    for line in (l for l in stream.splitlines() if l):
        last[line.split("\t")[0]] = line
    assert sorted(last.values()) == sorted(l for l in final.splitlines() if l)


def test_cli_sigkill_resume_bit_identical(tmp_path):
    """A real crash: SIGKILL the CLI mid-run (after its first periodic
    checkpoint lands), rerun the same command, and require byte-identical
    stdout to an uninterrupted run — the fault-tolerance property the
    reference cannot offer (its rescorer state dies with the JVM)."""
    import os
    import signal
    import subprocess
    import sys
    import time

    f = tmp_path / "in.csv"
    # 30k events: the SIGKILL lands right after the FIRST periodic
    # checkpoint (the glob loop below), so the stream tail past that
    # point only buys wall time, not coverage — half the events still
    # leave ~3/4 of the run to replay-after-resume (tier-1 budget).
    write_stream(f, n=30_000)
    ck = tmp_path / "ck"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    args = [sys.executable, "-m", "tpu_cooccurrence.cli", "-i", str(f),
            "-ws", "20", "-ic", "8", "-uc", "5", "-s", "0xC0FFEE",
            "--backend", "oracle", "--checkpoint-dir", str(ck),
            "--checkpoint-every-windows", "5"]

    clean = subprocess.run(args[:-4] + ["--checkpoint-dir",
                                        str(tmp_path / "ck-clean"),
                                        "--checkpoint-every-windows", "5"],
                           capture_output=True, text=True, env=env,
                           cwd=repo, timeout=300)
    assert clean.returncode == 0, clean.stderr[-800:]

    victim = subprocess.Popen(args, stdout=subprocess.PIPE,
                              stderr=subprocess.DEVNULL, env=env, cwd=repo)
    deadline = time.monotonic() + 240
    while not list(ck.glob("state.*.npz")) and time.monotonic() < deadline:
        if victim.poll() is not None:
            break
        time.sleep(0.05)
    if victim.poll() is None:
        victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=60)
        assert victim.returncode == -signal.SIGKILL
    assert list(ck.glob("state.*.npz")), \
        "no checkpoint landed before the run ended"

    resumed = subprocess.run(args, capture_output=True, text=True, env=env,
                             cwd=repo, timeout=300)
    assert resumed.returncode == 0, resumed.stderr[-800:]
    assert resumed.stdout == clean.stdout

