"""Real multi-host execution: 2 coordinated processes on the CPU backend.

Each subprocess joins the multi-controller runtime through
``jax.distributed.initialize`` (via ``--coordinator``/``--num-processes``/
``--process-id``), gets 4 virtual local devices, and runs the sharded
backend over the resulting 8-device global mesh. This exercises the real
multi-host code paths — ``init_multihost``, ``make_multihost_mesh`` (DCN-
aware hosts-major device order), ``put_global``'s per-shard callback
assembly, addressable-shard result extraction, and per-process
checkpoints — none of which single-process tests can reach.

The in-process reference is the same stream on a single-process 8-shard
virtual mesh (the conftest's), whose results the two processes' merged,
disjoint row partitions must reproduce exactly.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from tpu_cooccurrence.config import Backend, Config

from test_pipeline import random_stream, run_production

# Two-process coordinated runs: minutes of wall-clock. Slow lane
# (deselected by default; TPU_COOC_FULL_SUITE=1 selects it back in).
pytestmark = pytest.mark.slow

WORKER = os.path.join(os.path.dirname(__file__), "multihost_worker.py")

STREAM_KW = dict(window_size=10, seed=0x51AB, item_cut=6, user_cut=4,
                 num_items=32)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_procs(tmp_path, phase: str, half: int, stream_path: str,
                 checkpoint_dir: str, backend: str = "sharded",
                 partition_sampling: bool = False,
                 window_slide: int = None, nproc: int = 2,
                 expect_failure: bool = False, pipeline_depth: int = 0):
    """Launch all ``nproc`` processes of one phase; return parsed outputs
    (or, with ``expect_failure``, the list of (rc, stderr) per process).

    The global mesh is always 8 devices: each process gets ``8 // nproc``
    virtual local devices, so 2- and 4-process runs shard the same state
    over the same mesh size with different host boundaries."""
    assert 8 % nproc == 0
    coordinator = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={8 // nproc}")
    # `python path/to/worker.py` puts tests/ on sys.path, not the repo root.
    repo_root = os.path.dirname(os.path.dirname(WORKER))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    procs, outs = [], []
    for pid in range(nproc):
        spec = dict(STREAM_KW, stream=stream_path, coordinator=coordinator,
                    num_processes=nproc, process_id=pid, phase=phase,
                    half=half, checkpoint_dir=checkpoint_dir,
                    backend=backend, num_shards=8,
                    partition_sampling=partition_sampling,
                    window_slide=window_slide,
                    pipeline_depth=pipeline_depth)
        tag = (f"{backend}{'-ps' if partition_sampling else ''}"
               f"{'-sl' if window_slide else ''}"
               f"{f'-d{pipeline_depth}' if pipeline_depth else ''}"
               f"-n{nproc}")
        spec_path = tmp_path / f"spec-{tag}-{phase}-{pid}.json"
        out_path = tmp_path / f"out-{tag}-{phase}-{pid}.json"
        spec_path.write_text(json.dumps(spec))
        outs.append(out_path)
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, str(spec_path), str(out_path)],
            env=env, cwd=os.path.dirname(os.path.dirname(WORKER)),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    results, failures = [], []
    for p, out_path in zip(procs, outs):
        stdout, stderr = p.communicate(timeout=300)
        if expect_failure:
            failures.append((p.returncode, stderr))
            continue
        assert p.returncode == 0, f"worker failed:\n{stdout}\n{stderr}"
        results.append(json.loads(out_path.read_text()))
    return failures if expect_failure else results


def _spawn_pair(tmp_path, phase, half, stream_path, checkpoint_dir,
                backend="sharded", partition_sampling=False,
                window_slide=None):
    return _spawn_procs(tmp_path, phase, half, stream_path, checkpoint_dir,
                        backend=backend,
                        partition_sampling=partition_sampling,
                        window_slide=window_slide, nproc=2)


def _merge_latest(results):
    merged = {}
    for res in results:
        for item, top in res["latest"].items():
            assert item not in merged, \
                f"row {item} emitted by more than one process"
            merged[int(item)] = [(int(j), s) for j, s in top]
    return merged


def _reference_latest(users, items, ts, backend: str = "sharded",
                      window_slide: int = None):
    cfg = Config(**STREAM_KW, backend=Backend(backend), num_shards=8,
                 window_slide=window_slide)
    job = run_production(cfg, users, items, ts)
    return ({item: job.latest[item] for item in job.latest},
            job.counters.as_dict())


@pytest.fixture(scope="module")
def stream(tmp_path_factory):
    path = tmp_path_factory.mktemp("mh") / "stream.npz"
    users, items, ts = random_stream(61, n=500)
    np.savez(path, users=users, items=items, ts=ts)
    return str(path), users, items, ts


def _assert_matches_reference(results, users, items, ts,
                              backend: str = "sharded",
                              window_slide: int = None):
    ref_latest, ref_counters = _reference_latest(users, items, ts, backend,
                                                 window_slide)
    merged = _merge_latest(results)
    assert set(merged) == set(ref_latest)
    for item in ref_latest:
        r = ref_latest[item]
        m = merged[item]
        np.testing.assert_allclose([s for _, s in m], [s for _, s in r],
                                   rtol=1e-6, atol=1e-6)
        # Tie-aware id comparison: the sparse backend breaks equal scores
        # by slab slot order, which a checkpoint restore re-lays (sorted
        # key order) — ids must match as sets within each tie group.
        rv = np.asarray([s for _, s in r])
        lo = 0
        for hi in range(1, len(rv) + 1):
            if hi == len(rv) or not np.isclose(rv[hi], rv[lo], rtol=1e-6):
                assert ({j for j, _ in r[lo:hi]}
                        == {j for j, _ in m[lo:hi]}), f"row {item}"
                lo = hi
    # Host-side pipeline state is identical in every process (each consumes
    # the whole stream), so the counters must match the single-process run.
    for res in results:
        assert res["counters"] == ref_counters


def test_multihost_two_processes_match_single_process(tmp_path, stream):
    stream_path, users, items, ts = stream
    results = _spawn_pair(tmp_path, "full", len(users), stream_path,
                          checkpoint_dir=None)
    _assert_matches_reference(results, users, items, ts)


def test_multihost_per_process_checkpoint_resume(tmp_path, stream):
    stream_path, users, items, ts = stream
    ck_dir = str(tmp_path / "ck")
    half = 250
    _spawn_pair(tmp_path, "first-half", half, stream_path, ck_dir)
    # Both per-process snapshots must exist (hosts-major row blocks;
    # generation-numbered since the robustness PR).
    import glob as _glob

    assert _glob.glob(os.path.join(ck_dir, "state.p0.*.npz"))
    assert _glob.glob(os.path.join(ck_dir, "state.p1.*.npz"))
    results = _spawn_pair(tmp_path, "resume", half, stream_path, ck_dir)
    _assert_matches_reference(results, users, items, ts)


def test_multihost_sharded_sparse_matches_single_process(tmp_path, stream):
    """The row-sharded HBM-slab backend runs multi-controller too: same
    merged results and counters as a single-process 8-shard mesh."""
    stream_path, users, items, ts = stream
    results = _spawn_pair(tmp_path, "full", len(users), stream_path,
                          checkpoint_dir=None, backend="sparse")
    _assert_matches_reference(results, users, items, ts, backend="sparse")


def test_multihost_sharded_sparse_checkpoint_resume(tmp_path, stream):
    stream_path, users, items, ts = stream
    ck_dir = str(tmp_path / "ck-sparse")
    half = 250
    _spawn_pair(tmp_path, "first-half", half, stream_path, ck_dir,
                backend="sparse")
    assert os.path.exists(os.path.join(ck_dir, "state.p0.npz"))
    assert os.path.exists(os.path.join(ck_dir, "state.p1.npz"))
    results = _spawn_pair(tmp_path, "resume", half, stream_path, ck_dir,
                          backend="sparse")
    _assert_matches_reference(results, users, items, ts, backend="sparse")


def test_multihost_partitioned_sampling_matches_replicated(tmp_path, stream):
    """--partition-sampling: each process reservoirs 1/P of the users and
    the per-window allgather reproduces the serial pipeline exactly —
    results AND counters (the RNG is partition-independent by design)."""
    stream_path, users, items, ts = stream
    results = _spawn_pair(tmp_path, "full", len(users), stream_path,
                          checkpoint_dir=None, partition_sampling=True)
    _assert_matches_reference(results, users, items, ts)


def test_multihost_partitioned_sampling_checkpoint_resume(tmp_path, stream):
    stream_path, users, items, ts = stream
    ck_dir = str(tmp_path / "ck-ps")
    half = 250
    _spawn_pair(tmp_path, "first-half", half, stream_path, ck_dir,
                partition_sampling=True)
    results = _spawn_pair(tmp_path, "resume", half, stream_path, ck_dir,
                          partition_sampling=True)
    _assert_matches_reference(results, users, items, ts)


def test_multihost_sparse_with_partitioned_sampling(tmp_path, stream):
    """Both scale axes at once: row-sharded HBM slabs across hosts AND the
    user reservoir partitioned across the same processes."""
    stream_path, users, items, ts = stream
    results = _spawn_pair(tmp_path, "full", len(users), stream_path,
                          checkpoint_dir=None, backend="sparse",
                          partition_sampling=True)
    _assert_matches_reference(results, users, items, ts, backend="sparse")


def test_multihost_four_processes_sharded(tmp_path, stream):
    """4 coordinated processes x 2 local devices = the same 8-device mesh
    with host boundaries every 2 shards; merged results and counters must
    still match the single-process reference."""
    stream_path, users, items, ts = stream
    results = _spawn_procs(tmp_path, "full", len(users), stream_path,
                           checkpoint_dir=None, nproc=4)
    _assert_matches_reference(results, users, items, ts)


def test_multihost_four_processes_sharded_sparse_with_ps(tmp_path, stream):
    """Both scale axes at 4 processes: row-sharded HBM slabs AND the
    user reservoir partitioned 4 ways."""
    stream_path, users, items, ts = stream
    results = _spawn_procs(tmp_path, "full", len(users), stream_path,
                           checkpoint_dir=None, backend="sparse",
                           partition_sampling=True, nproc=4)
    _assert_matches_reference(results, users, items, ts, backend="sparse")


def test_multihost_four_process_checkpoint_resume(tmp_path, stream):
    stream_path, users, items, ts = stream
    ck_dir = str(tmp_path / "ck-n4")
    half = 250
    _spawn_procs(tmp_path, "first-half", half, stream_path, ck_dir, nproc=4)
    for pid in range(4):
        assert os.path.exists(os.path.join(ck_dir, f"state.p{pid}.npz"))
    results = _spawn_procs(tmp_path, "resume", half, stream_path, ck_dir,
                           nproc=4)
    _assert_matches_reference(results, users, items, ts)


def test_multihost_layout_mismatch_restore_fails(tmp_path, stream):
    """A checkpoint written by a 2-process run must REFUSE to restore
    under a 4-process layout (both backends validate; garbage slices
    would otherwise corrupt state silently)."""
    stream_path, users, items, ts = stream
    ck_dir = str(tmp_path / "ck-mismatch")
    half = 250
    _spawn_pair(tmp_path, "first-half", half, stream_path, ck_dir)
    failures = _spawn_procs(tmp_path, "resume", half, stream_path, ck_dir,
                            nproc=4, expect_failure=True)
    # p2/p3 find no state.p{2,3}.npz; p0/p1 find blocks for the wrong row
    # span. Every process must fail, none silently.
    assert all(rc != 0 for rc, _ in failures)
    assert any("layout" in err or "checkpoint" in err
               for _, err in failures)


def test_multihost_partitioned_sampling_layout_mismatch_fails(tmp_path,
                                                              stream):
    """--partition-sampling checkpoints record their (pid, nproc); a
    4-process resume of a 2-process snapshot fails with the layout
    error, not silent reservoir corruption."""
    stream_path, users, items, ts = stream
    ck_dir = str(tmp_path / "ck-ps-mismatch")
    half = 250
    _spawn_pair(tmp_path, "first-half", half, stream_path, ck_dir,
                partition_sampling=True)
    failures = _spawn_procs(tmp_path, "resume", half, stream_path, ck_dir,
                            nproc=4, partition_sampling=True,
                            expect_failure=True)
    assert all(rc != 0 for rc, _ in failures)


def test_multihost_partitioned_sliding_matches_replicated(tmp_path, stream):
    """Sliding mode under --partition-sampling: replicated cuts, user-
    partitioned basket expansion, packed allgather — same results and
    counters as the single-process sliding run."""
    stream_path, users, items, ts = stream
    results = _spawn_pair(tmp_path, "full", len(users), stream_path,
                          checkpoint_dir=None, partition_sampling=True,
                          window_slide=5)
    _assert_matches_reference(results, users, items, ts, window_slide=5)


def test_multihost_pipelined_depth2_matches_single_process(tmp_path,
                                                           stream):
    """ISSUE 10 relaxed the blanket multi-host pipeline rejection:
    without --partition-sampling every collective issues from the
    scorer worker in window order, so a depth-2 two-process run must
    reproduce the single-process serial reference exactly."""
    stream_path, users, items, ts = stream
    results = _spawn_procs(tmp_path, "full", len(users), stream_path,
                           checkpoint_dir=None, nproc=2,
                           pipeline_depth=2)
    _assert_matches_reference(results, users, items, ts)
