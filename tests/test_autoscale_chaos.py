"""Autoscale chaos capstone (ISSUE 15): the real CLI in gang mode.

A 2-process CPU multi-controller sparse gang with ``--autoscale on``:

* **scale-before-shed, bit-identical** — injected load (delay faults
  billed into the window wall) forces a 2→4 rescale; the idle tail
  decays 4→2; final stdout is bit-identical to the same stream run at
  a FIXED 2-worker topology. The journals prove the precedence claim:
  the degradation ladder (armed, trip within reach) never leaves
  NORMAL — the pressure became capacity, not shed work — and carry the
  AUTOSCALE grow/shrink records.

* **crash inside the rescale seam** — ``rescale_drain@1:crash`` kills
  worker 1 after the drain checkpoint committed but before its
  voluntary exit. The gang restarts (one billed attempt), relaunches
  at the pending target, and the topology-aware restore vote merges
  the 2-writer generation onto the 4-worker gang — stdout still
  bit-identical to the fixed-topology reference.

**The comparator.** A sparse restore canonicalizes within-row slab
order (``rebuild_from_keys`` is key-sorted), and equal-score top-K
tie-breaks are slot-ordered — so ANY restored run differs from a
never-restored one at exactly the tied scores, whatever the topology.
Same precedent as the PR-12 gang chaos: the bit-exact comparator is a
fixed-topology run *recovered at the same window boundaries*, not an
uninterrupted one. The supervisor's beacon-driven decisions make the
drain windows timing-dependent, so the test is two-phase: run the
elastic gang, read its drain windows from the journal's AUTOSCALE
records, then run the fixed 2-worker reference with a crash injected
at each drain-successor window (``--checkpoint-every-windows 1``
guarantees a committed generation at every boundary) — both runs then
restore-canonicalize at the identical windows, and everything else is
pure rescale topology, which is bit-free by the PR-9 contract.

Timing levers: ``--degrade-window-wall-s 2`` makes a 2500 ms injected
delay an overloaded window and anything under 500 ms an idle one —
margins wide enough for a contended CI box. Only worker 0 is delayed
(``@0``); the gang-max vote spreads the signal.
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
    path = tmp_path_factory.mktemp("autoscale") / "in.csv"
    with open(path, "w") as fh:
        # 520 events = 20 windows at ws 250: pressure at windows 3..5,
        # grow drain ~5; the policy's cooldown (2) plus FRESH idle
        # evidence (clear 3) put the shrink drain ~10-13, leaving a
        # several-window tail at 2 workers before the final dump.
        for i in range(520):
            fh.write(f"{i % 13},{i % 17},{i * 10}\n")
    return str(path)


#: Reference stdout cache keyed by the drain-window tuple: the two
#: tier-1 chaos runs usually drain at the same windows, and a
#: fixed-topology reference is a whole extra gang run — reuse it when
#: the boundaries match (correctness never depends on the reuse).
_REFERENCE_CACHE = {}


def _args(stream, ck_dir, extra):
    return [sys.executable, "-m", "tpu_cooccurrence.cli",
            "-i", stream, "-ws", "250", "-ic", "8", "-uc", "5",
            "-s", "0xC0FFEE", "--backend", "sparse",
            "--num-shards", "2",
            "--checkpoint-dir", ck_dir,
            "--checkpoint-every-windows", "1",
            "--checkpoint-retain", "100",
            "--gang-workers", "2", "--gang-heartbeat-s", "1",
            "--collective-timeout-s", "60",
            "--restart-delay-ms", "0"] + extra


#: The load script: worker 0's windows 3..5 each stall 2.5 s inside
#: the sample clock — consecutive overloaded windows under a 2 s wall
#: threshold (the gang-max vote makes them gang-wide), then nothing:
#: the tail is idle. Fired-once markers survive the rescale relaunches,
#: so the pressure never returns at 4 workers.
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


def _run(stream, ck_dir, extra, timeout=420):
    return subprocess.run(_args(stream, ck_dir, extra),
                          capture_output=True, text=True, env=ENV,
                          cwd=REPO, timeout=timeout)


def _journal_records(jpath, pid):
    with open(f"{jpath}.p{pid}") as f:
        return [json.loads(line) for line in f if line.strip()]


def _fixed_topology_reference(stream, tmp_path, drain_windows,
                              last_window):
    """The bit-exact comparator: the same stream on a FIXED 2-worker
    gang, crash-recovered at exactly the elastic run's drain windows
    (see the module docstring for why an uninterrupted run cannot be
    the comparator). A crash at window W+1 fires before sampling, so
    the restore lands on the generation committed at W — the same
    boundary the drain checkpoint committed. A drain at the FINAL
    window needs no reference crash at all: the relaunched gang
    processes zero windows before the dump, and the dump prints the
    restored ``latest`` — exactly the rows the reference's own
    final-window checkpoint held, with nothing written post-restore to
    canonicalize differently."""
    replay = [w for w in drain_windows if w < last_window]
    key = tuple(replay)
    if key in _REFERENCE_CACHE:
        return _REFERENCE_CACHE[key]
    ck = str(tmp_path / "ck-ref")
    extra = ["--restart-on-failure", str(len(replay))]
    for w in replay:
        # Built by concatenation, not an f-string: the fault-site text
        # scan must see the site name at the spec's head.
        extra += ["--inject-fault",
                  "window_fire@0:" + str(w + 1) + ":crash"]
    extra += ["--fault-state-dir", str(tmp_path / "faults-ref")]
    proc = _run(stream, ck, extra)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout, "reference run produced no output"
    assert proc.stderr.count("gang-restarting") == len(replay)
    _REFERENCE_CACHE[key] = proc.stdout
    return proc.stdout


@pytest.fixture(scope="module")
def elastic(stream, tmp_path_factory):
    """THE capstone run: load forces 2→4, idle decays 4→2, with a ZERO
    restart budget — every relaunch must be a voluntary drain."""
    tmp_path = tmp_path_factory.mktemp("autoscale-elastic")
    ck = str(tmp_path / "ck")
    jpath = str(tmp_path / "journal.jsonl")
    proc = _run(stream, ck,
                _AUTOSCALE + _LOAD
                + ["--journal", jpath,
                   "--fault-state-dir", str(tmp_path / "faults")])
    assert proc.returncode == 0, proc.stderr[-3000:]
    recs = _journal_records(jpath, 0)
    return proc, recs, ck


def test_autoscale_grow_shrink_bit_identical(tmp_path, stream, elastic):
    proc, recs, ck = elastic
    scale = [r for r in recs if "autoscale" in r]
    assert [(r["autoscale"], r["from"], r["to"]) for r in scale] == [
        ("grow", 2, 4), ("shrink", 4, 2)]
    assert scale[0]["trigger"] == "pressure"
    assert scale[1]["trigger"] == "idle"
    assert "autoscale decision: grow 2 -> 4" in proc.stderr
    assert "autoscale decision: shrink 4 -> 2" in proc.stderr
    assert "gang rescale 1" in proc.stderr
    assert "gang rescale 2" in proc.stderr
    # No billed restarts: the gang ran with a ZERO restart budget, so
    # completing at all proves both rescale exits were free.
    assert "gang-restarting" not in proc.stderr
    # The 2→4 seam restored across topologies (merge + re-bucket).
    assert "rescale restore: generation" in proc.stderr
    # Scale-before-shed in the transition sequence: --degrade was armed
    # with its trip within reach (3 consecutive overloaded windows
    # existed), yet the ladder never left NORMAL — the pressure became
    # capacity, not shed work.
    windows = [r for r in recs if "seq" in r]
    assert windows, "no window records journaled"
    assert all(r.get("degradation_level") == 0 for r in windows), \
        "the ladder left NORMAL during a successful scale-up"
    assert not any(r.get("degrade_events") for r in windows)
    # Drain generations committed at BOTH topologies (2- and 4-writer
    # marker sets) — the rescale-tagged commit trail.
    from tpu_cooccurrence.state import checkpoint as ckpt

    topos = {w for _g, w in ckpt.topology_committed_generations(ck)}
    assert topos == {2, 4}
    # Bit-identity vs the fixed topology, recovered at the same
    # boundaries (module docstring): the elastic run destroyed and
    # rebuilt the gang twice and still produced the reference stream.
    ref = _fixed_topology_reference(
        stream, tmp_path, [r["window"] for r in scale],
        max(r["seq"] for r in windows))
    assert proc.stdout == ref


@pytest.mark.slow
def test_crash_inside_rescale_seam_recovers_via_vote(tmp_path, stream):
    """rescale_drain@1:crash: worker 1 dies AFTER the drain commit and
    BEFORE its voluntary exit. The crash bills one restart, the gang
    relaunches at the pending target (4), the topology-aware vote
    restores the 2-writer generation onto 4 workers, and the idle tail
    still decays back to 2 — with NO lost or duplicated windows: the
    journal's window-record seqs across every attempt are exactly
    1..N, each once (the drain committed before the crash, so the
    resumed gang continues at the very next window)."""
    ck = str(tmp_path / "ck")
    jpath = str(tmp_path / "journal.jsonl")
    proc = _run(stream, ck,
                _AUTOSCALE + _LOAD
                + ["--restart-on-failure", "2",
                   "--journal", jpath,
                   "--inject-fault", "rescale_drain@1:crash",
                   "--fault-state-dir", str(tmp_path / "faults")])
    assert proc.returncode == 0, proc.stderr[-3000:]
    # The seam crash was a REAL failure (billed restart)...
    assert "gang-restarting" in proc.stderr
    # ...that still relaunched at the pending target and crossed the
    # topology on restore.
    assert "rescale restore: generation" in proc.stderr
    fired = sorted(os.listdir(tmp_path / "faults"))
    assert "fault3.p1.fired" in fired  # the seam crash, worker 1 only
    recs = _journal_records(jpath, 0)
    scale = [r for r in recs if "autoscale" in r]
    assert [(r["from"], r["to"]) for r in scale] == [(2, 4), (4, 2)]
    # No lost or duplicated windows, across the crash and both seams.
    seqs = [r["seq"] for r in recs if "seq" in r]
    assert sorted(seqs) == list(range(1, max(seqs) + 1))
    assert len(seqs) == len(set(seqs))
    assert proc.stdout, "recovered gang produced no output"


@pytest.mark.slow
def test_autoscale_incremental_chain_crosses_the_seam(tmp_path, stream):
    """Slow lane: the same grow/shrink capstone with
    --checkpoint-incremental — the drain commit is a delta generation,
    the cross-topology restore resolves each writer's chain, and the
    first post-rescale save is forced to a full base (a delta against
    the old shard layout would be mis-keyed). The comparator is the
    full-checkpoint fixed topology recovered at the same boundaries —
    delta-chain restore is byte-equivalent to full restore (PR 12)."""
    ck = str(tmp_path / "ck")
    jpath = str(tmp_path / "journal.jsonl")
    proc = _run(stream, ck,
                _AUTOSCALE + _LOAD
                + ["--checkpoint-incremental",
                   "--checkpoint-compact-ratio", "10",
                   "--journal", jpath,
                   "--fault-state-dir", str(tmp_path / "faults")])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "rescale restore: generation" in proc.stderr
    recs = _journal_records(jpath, 0)
    scale = [r for r in recs if "autoscale" in r]
    assert [(r["from"], r["to"]) for r in scale] == [(2, 4), (4, 2)]
    ref = _fixed_topology_reference(
        stream, tmp_path, [r["window"] for r in scale],
        max(r["seq"] for r in recs if "seq" in r))
    assert proc.stdout == ref
