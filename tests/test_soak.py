"""Cross-surface soak: the combinations no single-feature test crosses.

Each case drives the REAL CLI end to end on a moderately large stream
and holds the framework's strongest property — byte-identical stdout —
across feature products that interact through independent subsystems:
sparse slab state x sliding windows x per-window emission x periodic
checkpoints x a SIGKILL mid-run under the auto-resume supervisor
(reference analogues: sliding window math it never wires,
checkpointing it leaves off, Flink restart strategies — SURVEY §5,7).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _write_soak_stream(path, n=30_000, seed=0x50A):
    """Bursty stream with duplicates and mild ts jitter (late events)."""
    rng = np.random.default_rng(seed)
    users = rng.integers(0, 300, n)
    items = rng.zipf(1.3, n).clip(1, 5_000) + 99
    ts = np.cumsum(rng.integers(0, 4, n))
    jitter = rng.integers(0, 8, n)
    ts = ts - jitter * (rng.random(n) < 0.05)  # ~5% late arrivals
    with open(path, "w") as f:
        for u, i, t in zip(users, items, ts):
            f.write(f"{u},{i},{int(t)}\n")


def _run(args, timeout=600):
    r = subprocess.run([sys.executable, "-m", "tpu_cooccurrence.cli"]
                       + args, capture_output=True, text=True, env=ENV,
                       cwd=REPO, timeout=timeout)
    assert r.returncode == 0, r.stderr[-1500:]
    return r.stdout


def _fold_updates(out: str) -> dict:
    """Collapse an --emit-updates stream to its final state: each line
    replaces that item's row, so the last occurrence per item wins."""
    state = {}
    for line in out.splitlines():
        item, rest = line.split("\t")
        state[int(item)] = rest
    return state


@pytest.mark.slow
@pytest.mark.parametrize("backend,extra", [
    ("sparse", ["--emit-updates"]),
    ("sparse", []),              # deferred results + fixed-shape auto
    ("oracle", ["--emit-updates"]),
    ("oracle", []),
])
def test_sliding_sparse_sigkill_supervised_recovery(tmp_path, backend,
                                                    extra):
    """SIGKILL right after the first periodic checkpoint, under the
    supervisor, on a sliding-window cut stream. Final-dump mode must be
    BYTE-identical to an uninterrupted run; --emit-updates mode must be
    complete-and-equivalent (the resumed child replays restored rows
    once as current state rather than re-emitting each pre-crash
    window's historical updates — supervisor.py's documented contract),
    so the streams' folded final states must match exactly."""
    f = tmp_path / "in.csv"
    _write_soak_stream(f)
    base = ["-i", str(f), "-ws", "400", "--window-slide", "100",
            "-ic", "20", "-uc", "8", "-s", "0xC0FFEE",
            "--backend", backend,
            "--checkpoint-every-windows", "25"] + extra

    clean = _run(base + ["--checkpoint-dir", str(tmp_path / "ck-clean")])
    assert clean, "soak stream produced no output"

    from tpu_cooccurrence.supervisor import supervise

    class _Sink:
        text = ""

        def write(self, s):
            self.text += s

    ck = tmp_path / "ck"
    worker = os.path.join(REPO, "tests", "supervised_crash_worker.py")
    marker = tmp_path / "crashed-once"
    # supervise() respawns the worker; the worker arms its SIGKILL
    # watcher only on the first attempt (marker file). The child
    # inherits the conftest's forced-CPU env.
    sink = _Sink()
    rc = supervise([sys.executable, worker, str(ck), str(marker)] + base
                   + ["--checkpoint-dir", str(ck)],
                   attempts=2, delay_s=0, stdout=sink)
    assert rc == 0
    assert marker.exists(), "crash never injected"
    if "--emit-updates" in extra:
        assert _fold_updates(sink.text) == _fold_updates(clean), (
            "recovered stream's final state diverges from the clean run")
    else:
        assert sink.text == clean, "recovered stdout diverges from clean run"


@pytest.mark.slow
def test_backend_cross_agreement_on_soak_stream(tmp_path):
    """All four execution modes (oracle, device, sparse, sharded-sparse
    x8) agree item-for-item on the soak stream at display precision."""
    f = tmp_path / "in.csv"
    _write_soak_stream(f)
    base = ["-i", str(f), "-ws", "400", "-ic", "20", "-uc", "8",
            "-s", "0xC0FFEE"]
    outs = {
        "oracle": _run(base + ["--backend", "oracle"]),
        "device": _run(base + ["--backend", "device"]),
        "sparse": _run(base + ["--backend", "sparse"]),
        "sharded-sparse": _run(base + ["--backend", "sparse",
                                       "--num-shards", "8"]),
    }

    def parse(out):
        res = {}
        for line in out.splitlines():
            item, rest = line.split("\t")
            res[int(item)] = [(int(p.rsplit(":", 1)[0]),
                               float(p.rsplit(":", 1)[1]))
                              for p in rest.split()]
        return res

    from test_pipeline import assert_latest_close

    ref = parse(outs["oracle"])
    assert ref
    for name in ("device", "sparse", "sharded-sparse"):
        # The shared f32-vs-f64 protocol: scores to tolerance, ids exact
        # only where in-row score gaps beat it (near-ties legitimately
        # reorder across precisions/backends).
        assert_latest_close(ref, parse(outs[name]), atol=2e-3)
