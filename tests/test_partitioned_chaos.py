"""Partitioned-ingest chaos capstone (ISSUE 18): the real CLI in gang
mode on a partitioned log, killed mid-run, rescaled on recovery.

The claim under test is the tentpole's end-to-end exactly-once story:
per-partition offsets commit atomically with the state, so a gang that
is **kill -9'd mid-window at N workers and resumed at M workers**
(autoscale target pending across the crash, topology-aware restore
vote, ``merge_ingest_offsets`` on the wire) produces **bit-identical
stdout** to an unkilled fixed-topology run — zero events lost, zero
double-counted.

The stream is split CONTIGUOUSLY across three ``part-*`` files, each
smaller than one round-robin turn (TURN_RECORDS=256), so the
partitioned drain order equals the single-file order and the files/
partitioned equivalence test below holds the two sources to the same
output. The comparator follows test_autoscale_chaos: a fixed 2-worker
run crash-recovered at the elastic run's drain windows (restore
canonicalizes slab order, so the reference must restore at the same
boundaries — the seam-crash restore lands on the drain-committed
generation, i.e. exactly those boundaries).

The ledger: the journal's per-window ``events`` counts are raw windowed
line counts, so with window seqs exactly ``1..N`` each-once, their sum
equals the stream length — 520 — iff no event was lost or
double-counted across the kill and both rescale seams. The final
committed checkpoint's ``ingest_offsets`` must match the last journaled
window's — the wire and the state commit the same boundary.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           XLA_FLAGS="--xla_force_host_platform_device_count=1")

N_EVENTS = 520


def _event(i):
    return f"{i % 13},{i % 17},{i * 10}\n"


@pytest.fixture(scope="module")
def stream_csv(tmp_path_factory):
    path = tmp_path_factory.mktemp("pchaos") / "in.csv"
    with open(path, "w") as fh:
        for i in range(N_EVENTS):
            fh.write(_event(i))
    return str(path)


@pytest.fixture(scope="module")
def plog(tmp_path_factory):
    """The same 520 events split contiguously over 3 partitions, each
    under TURN_RECORDS so one turn drains it whole and the interleaved
    order equals the single-file order (timestamps stay ascending)."""
    root = tmp_path_factory.mktemp("pchaos-log") / "plog"
    root.mkdir()
    bounds = [(0, 174), (174, 348), (348, N_EVENTS)]
    for p, (lo, hi) in enumerate(bounds):
        with open(root / f"part-{p:03d}", "w") as fh:
            for i in range(lo, hi):
                fh.write(_event(i))
    return str(root)


_PARTITIONED = ["--source-format", "partitioned",
                "--ingest-partitions", "3"]


def _args(inp, ck_dir, extra):
    return [sys.executable, "-m", "tpu_cooccurrence.cli",
            "-i", inp, "-ws", "250", "-ic", "8", "-uc", "5",
            "-s", "0xC0FFEE", "--backend", "sparse",
            "--num-shards", "2",
            "--checkpoint-dir", ck_dir,
            "--checkpoint-every-windows", "1",
            "--checkpoint-retain", "100",
            "--gang-workers", "2", "--gang-heartbeat-s", "1",
            "--collective-timeout-s", "60",
            "--restart-delay-ms", "0"] + _PARTITIONED + extra

_LOAD = ["--inject-fault", "window_fire@0:3:delay_ms:2500",
         "--inject-fault", "window_fire@0:4:delay_ms:2500",
         "--inject-fault", "window_fire@0:5:delay_ms:2500"]

_AUTOSCALE = ["--degrade", "--degrade-window-wall-s", "2.0",
              "--degrade-trip-windows", "3",
              "--autoscale", "on",
              "--autoscale-min-workers", "2",
              "--autoscale-max-workers", "4",
              "--autoscale-trip-windows", "2",
              "--autoscale-clear-windows", "3",
              "--autoscale-cooldown-windows", "2"]


def _run(inp, ck_dir, extra, timeout=420):
    return subprocess.run(_args(inp, ck_dir, extra),
                          capture_output=True, text=True, env=ENV,
                          cwd=REPO, timeout=timeout)


def _journal_records(jpath, pid):
    with open(f"{jpath}.p{pid}") as f:
        return [json.loads(line) for line in f if line.strip()]


def test_partitioned_stream_matches_files_stream(stream_csv, plog):
    """Single process, no gang: the partitioned source's interleave of
    the contiguous split reproduces the files source's stream exactly
    (the precondition every comparator below rests on)."""
    base = [sys.executable, "-m", "tpu_cooccurrence.cli",
            "-ws", "250", "-ic", "8", "-uc", "5", "-s", "0xC0FFEE",
            "--backend", "sparse"]
    a = subprocess.run(base + ["-i", stream_csv], capture_output=True,
                       text=True, env=ENV, cwd=REPO, timeout=300)
    b = subprocess.run(base + ["-i", plog] + _PARTITIONED,
                       capture_output=True, text=True, env=ENV,
                       cwd=REPO, timeout=300)
    assert a.returncode == 0, a.stderr[-3000:]
    assert b.returncode == 0, b.stderr[-3000:]
    assert a.stdout, "files run produced no output"
    assert a.stdout == b.stdout


def _fixed_topology_reference(plog, tmp_path, drain_windows,
                              last_window):
    """Bit-exact comparator: fixed 2-worker gang on the same partition
    set, crash-recovered at exactly the elastic run's drain windows
    (test_autoscale_chaos's comparator, on the partitioned source)."""
    replay = [w for w in drain_windows if w < last_window]
    ck = str(tmp_path / "ck-ref")
    extra = ["--restart-on-failure", str(len(replay))]
    for w in replay:
        # Built by concatenation, not an f-string: the fault-site text
        # scan must see the site name at the spec's head.
        extra += ["--inject-fault",
                  "window_fire@0:" + str(w + 1) + ":crash"]
    extra += ["--fault-state-dir", str(tmp_path / "faults-ref")]
    proc = _run(plog, ck, extra)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout, "reference run produced no output"
    assert proc.stderr.count("gang-restarting") == len(replay)
    return proc.stdout


def test_kill_midrun_resume_rescaled_exactly_once(tmp_path, plog):
    """THE capstone: a 2-worker gang on the partitioned log is killed
    inside the grow seam (``rescale_drain@1:crash`` — the drain
    checkpoint committed, worker 1 dies before its voluntary exit),
    relaunches at 4 workers via the pending autoscale target + restore
    vote, later decays back to 2 — and the stdout is bit-identical to
    the fixed-topology comparator, with the event ledger and the
    committed offsets proving zero loss / zero double-count."""
    ck = str(tmp_path / "ck")
    jpath = str(tmp_path / "journal.jsonl")
    proc = _run(plog, ck,
                _AUTOSCALE + _LOAD
                + ["--restart-on-failure", "2",
                   "--journal", jpath,
                   "--inject-fault", "rescale_drain@1:crash",
                   "--fault-state-dir", str(tmp_path / "faults")])
    assert proc.returncode == 0, proc.stderr[-3000:]
    # The kill was real (billed restart) and the recovery crossed the
    # topology: 2-writer generation restored onto the 4-worker gang.
    assert "gang-restarting" in proc.stderr
    assert "rescale restore: generation" in proc.stderr
    fired = sorted(os.listdir(tmp_path / "faults"))
    assert "fault3.p1.fired" in fired  # the seam kill, worker 1 only

    recs = _journal_records(jpath, 0)
    scale = [r for r in recs if "autoscale" in r]
    assert [(r["from"], r["to"]) for r in scale] == [(2, 4), (4, 2)]

    # Zero lost, zero duplicated windows across the kill + both seams.
    windows = [r for r in recs if "seq" in r]
    seqs = [r["seq"] for r in windows]
    assert sorted(seqs) == list(range(1, max(seqs) + 1))
    assert len(seqs) == len(set(seqs))

    # The event-count ledger: every one of the 520 stream events landed
    # in exactly one window record.
    assert sum(r["events"] for r in windows) == N_EVENTS

    # Per-window wire telemetry rode the journal (partitioned source).
    assert all("ingest_offsets" in r and "ingest_lag" in r
               for r in windows)

    # The reassignment seams were journaled (cooc-trace annotates them).
    events = [r["event"] for r in recs if "event" in r]
    assert "ingest/partition-reassign:2->4" in events
    assert "ingest/partition-reassign:4->2" in events

    # The wire and the state committed the same boundary: the final
    # generation's offset section equals the last journaled window's,
    # and it accounts for the entire stream.
    from tpu_cooccurrence.state import checkpoint as ckpt

    gen, path = ckpt.generations(ck, ".p0")[0]
    meta = json.loads(bytes(
        ckpt._load_verified(path)["meta_json"]).decode())
    section = meta["ingest_offsets"]
    assert section["format"] == "partitioned"
    committed = {name: {"byte_offset": e["byte_offset"],
                        "records": e["records"]}
                 for name, e in section["partitions"].items()}
    last = max(windows, key=lambda r: r["seq"])
    assert committed == last["ingest_offsets"]
    assert sum(e["records"] for e in
               section["partitions"].values()) == N_EVENTS

    # Bit-identity vs the fixed topology recovered at the same
    # boundaries: the gang was killed, restarted, rescaled twice — and
    # still produced the reference stream.
    ref = _fixed_topology_reference(
        plog, tmp_path, [r["window"] for r in scale], max(seqs))
    assert proc.stdout == ref
