"""PROCESS_CONTINUOUSLY end to end: the reference's tail-the-directory
mode (ContinuousFileMonitoringFunction.java:204-236) driven through the
real CLI — files appearing over time are picked up by modification
time, their events advance the watermark (firing earlier windows), and
updated rows stream out while the process keeps running.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _write(path, items, ts0, n=400, seed=1, mtime_ns=None):
    """Write under a hidden temp name, set mtime, then rename into the
    watched directory: the CLI's monitor polls concurrently, and a file
    observed mid-write (or before the utime backdate) would advance the
    monitor's mtime marker past the final mtime and lose the file.
    Hidden names (leading '.') are excluded from listing."""
    rng = np.random.default_rng(seed)
    ts = ts0 + np.cumsum(rng.integers(0, 3, n))
    path = str(path)
    tmp = os.path.join(os.path.dirname(path),
                       "." + os.path.basename(path) + ".tmp")
    with open(tmp, "w") as f:
        for u, i, t in zip(rng.integers(0, 30, n),
                           rng.choice(items, n), ts):
            f.write(f"{u},{i},{t}\n")
    if mtime_ns is not None:
        os.utime(tmp, ns=(mtime_ns, mtime_ns))
    os.rename(tmp, path)
    return int(ts[-1])


class _Reader:
    """Collects a process's stdout lines on a thread."""

    def __init__(self, proc):
        self.lines = []
        self._t = threading.Thread(target=self._pump, args=(proc,),
                                   daemon=True)
        self._t.start()

    def _pump(self, proc):
        for line in proc.stdout:
            self.lines.append(line.rstrip("\n"))

    def wait_for(self, pred, timeout_s=90.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if any(pred(ln) for ln in list(self.lines)):
                return True
            time.sleep(0.2)
        return False


@pytest.mark.slow
def test_process_continuously_picks_up_new_files(tmp_path):
    d = tmp_path / "stream"
    d.mkdir()
    end1 = _write(d / "a.csv", items=np.arange(100, 120), ts0=0,
                  mtime_ns=1_000_000_000)

    proc = subprocess.Popen(
        [sys.executable, "-m", "tpu_cooccurrence.cli",
         "-i", str(d), "-ws", "100", "-ic", "20", "-uc", "8",
         "-s", "0xC0FFEE", "--backend", "oracle",
         "--process-continuously", "--emit-updates", "-bt", "100"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=ENV, cwd=REPO)
    try:
        reader = _Reader(proc)
        # Phase 1: file a's early windows fire (its own later events
        # advance the watermark) and rows stream while the job runs.
        assert reader.wait_for(lambda ln: ln.startswith("1")), (
            "no rows emitted from the initial file")
        assert proc.poll() is None, "continuous job exited on its own"

        # Phase 2: a NEW file with a newer mtime and later timestamps —
        # the monitor must pick it up, and its items must appear.
        _write(d / "b.csv", items=np.arange(500, 520), ts0=end1 + 1,
               seed=2, mtime_ns=2_000_000_000)
        assert reader.wait_for(lambda ln: ln.split("\t")[0].startswith("5")), (
            "rows from the appended file never streamed")
        assert proc.poll() is None
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)


@pytest.mark.slow
def test_process_continuously_ignores_old_mtime(tmp_path):
    """A file whose mtime is NOT newer than the max seen is never
    re-forwarded (the reference's global_modification_time contract)."""
    from tpu_cooccurrence.io.source import FileMonitorSource

    d = tmp_path / "stream"
    d.mkdir()
    _write(d / "a.csv", items=np.arange(100, 110), ts0=0, n=50,
           mtime_ns=5_000_000_000)
    src = FileMonitorSource(str(d), process_continuously=True,
                            poll_interval_s=0.01)
    it = src.lines()
    got = []
    while True:
        ln = next(it)
        if ln is None:  # idle heartbeat: first listing exhausted
            break
        got.append(ln)
    assert len(got) == 50
    # An "older" file appearing later (mtime below the marker): ignored.
    _write(d / "b.csv", items=np.arange(200, 210), ts0=999, n=10,
           mtime_ns=4_000_000_000)
    for _ in range(3):
        assert next(it) is None  # nothing but heartbeats
    # A genuinely newer file: consumed.
    _write(d / "c.csv", items=np.arange(300, 310), ts0=2000, n=10,
           mtime_ns=6_000_000_000)
    new = []
    while len(new) < 10:
        ln = next(it)
        if ln is not None:
            new.append(ln)
    assert all(int(ln.split(",")[1]) >= 300 for ln in new)
