"""The sharded dense backend's interleaved row layout on a popularity-
ordered stream, on the 8-device virtual CPU mesh.

Item ids follow popularity here, as a stream's ranks and a first-seen
vocab do: global row ``r`` lives on shard ``r % D`` at local row
``r // D``, so each shard gets its share of the hot rows. The job must
still equal the float64 oracle and the single-device dense job, its
checkpoints must keep global row order, its state must be created on
the devices, and the benchmark's four-chip cell must run whole at its
rehearsal size."""

import json
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

import jax

from tpu_cooccurrence.config import Backend, Config
from tpu_cooccurrence.job import CooccurrenceJob
from tpu_cooccurrence.metrics import (
    OBSERVED_COOCCURRENCES,
    RESCORED_ITEMS,
    ROW_SUM_PROCESS_WINDOW,
)
from tpu_cooccurrence.parallel.mesh import ITEM_AXIS

from test_pipeline import run_production


requires_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")

#: Tumbling windows, cuts small enough that the item cut and the user
#: reservoir both engage.
JOB = dict(window_size=100, seed=0x7E1B, item_cut=40, user_cut=12,
           num_items=400)
COUNTERS = (OBSERVED_COOCCURRENCES, ROW_SUM_PROCESS_WINDOW, RESCORED_ITEMS)


def popular_stream(seed=29, n=5000, n_users=60, n_items=400):
    """Zipf items whose ids are their popularity ranks (0 the most
    reviewed), users of log-normal activity, 5 events a ms."""
    rng = np.random.default_rng(seed)
    w = np.arange(1, n_items + 1, dtype=np.float64) ** -1.1
    items = rng.choice(n_items, n, p=w / w.sum()).astype(np.int64)
    act = np.exp(rng.normal(0.0, 1.2, n_users))
    users = rng.choice(n_users, n, p=act / act.sum()).astype(np.int64)
    ts = np.arange(n, dtype=np.int64) // 5
    return users, items, ts


def sharded(shards=4, **kw):
    return Config(**{**JOB, **kw}, backend=Backend.SHARDED,
                  num_shards=shards)


@pytest.fixture(scope="module")
def stream():
    return popular_stream()


@pytest.fixture(scope="module")
def four_shards(stream):
    return run_production(sharded(), *stream, chunk=500)


def assert_same_topk(got, want, rtol):
    assert set(got.latest) == set(want.latest)
    for item in want.latest:
        g, w = got.latest[item], want.latest[item]
        assert [j for j, _ in g] == [j for j, _ in w], item
        np.testing.assert_allclose(np.array([s for _, s in g]),
                                   np.array([s for _, s in w]),
                                   rtol=rtol, atol=rtol)


@requires_mesh
def test_popular_stream_engages_the_cuts(four_shards, stream):
    """The stream is skewed enough for the test's claims: the reservoir
    and the item cut both drop events."""
    users, items, _ts = stream
    assert np.bincount(users).max() > JOB["user_cut"] * 5
    assert np.bincount(items).max() > JOB["item_cut"] * 5
    assert four_shards.counters.get(OBSERVED_COOCCURRENCES) > 0


@requires_mesh
@pytest.mark.parametrize("reference", ["oracle", "device"])
def test_four_shards_match_reference(four_shards, stream, reference):
    """Against the float64 oracle: counters exact, top-K within 1e-4.
    Against the single-device dense job: counters, top-K ids and the
    whole of C in global row order exact (the same float32 math)."""
    ref = run_production(Config(**JOB, backend=Backend(reference)),
                         *stream, chunk=500)
    for name in COUNTERS:
        assert four_shards.counters.get(name) == ref.counters.get(name), name
    if reference == "oracle":
        for item in ref.latest:
            o = np.array([s for _, s in ref.latest[item]])
            m = np.array([s for _, s in four_shards.latest[item]])
            assert len(o) == len(m), item
            np.testing.assert_allclose(m, o, rtol=1e-4, atol=1e-4)
        assert set(ref.latest) == set(four_shards.latest)
        return
    assert_same_topk(four_shards, ref, rtol=1e-6)
    np.testing.assert_array_equal(
        four_shards.scorer.checkpoint_state()["C"],
        ref.scorer.checkpoint_state()["C"])


@requires_mesh
def test_pallas_kernel_reads_local_rows(stream):
    """The sharded Pallas path (interpret mode here): each shard's kernel
    fetches its rows at their local index and skips the blocks past its
    live rows, and scores what the XLA path scores."""
    users, items, ts = (a[:2000] for a in stream)
    xla = run_production(sharded(), users, items, ts, chunk=500)
    kernel = run_production(sharded(pallas="on"), users, items, ts,
                            chunk=500)
    assert kernel.scorer.use_pallas and not xla.scorer.use_pallas
    assert_same_topk(kernel, xla, rtol=1e-6)


@requires_mesh
def test_rows_balance_over_shards():
    """The interleaved layout spreads a popularity-ordered catalog's
    rescoring: the fullest shard's rows stay within 10% of the mean,
    where contiguous row blocks of the same windows would not. Wider
    windows over a larger catalog than the other tests', so that a
    shard's share is not a handful of rows."""
    stream = popular_stream(n=20000, n_items=4000)
    job = CooccurrenceJob(sharded(window_size=1000, num_items=4000))
    scorer, seen = job.scorer, []
    rescore = scorer._rescore

    def spy(rows):
        seen.append(rows.copy())
        return rescore(rows)

    scorer._rescore = spy
    for lo in range(0, len(stream[0]), 500):
        job.add_batch(*(a[lo:lo + 500] for a in stream))
    job.finish()
    counts = [w.counts for w in job.step_timer.windows if w.counts]
    total = sum(c["shard_rows"] for c in counts)
    most = sum(c["shard_rows_max"] for c in counts)
    assert total == sum(len(r) for r in seen)
    assert most / (total / 4) <= 1.1
    block = scorer.rows_per_shard
    contiguous = sum(np.bincount(r // block, minlength=4).max() for r in seen)
    assert contiguous / (total / 4) > 1.5


@requires_mesh
def test_window_counts_and_uploads(four_shards, stream):
    """The sharded window path reports the dense scorer's counts, and its
    uploads and result fetches ride the transfer ledger."""
    from tpu_cooccurrence.observability import LEDGER

    before = LEDGER.snapshot()
    run_production(sharded(), *(a[:1000] for a in stream), chunk=500)
    after = LEDGER.snapshot()
    assert after["h2d_bytes"] > before["h2d_bytes"]
    assert after["d2h_bytes"] > before["d2h_bytes"]
    assert {"coo", "score-rows"} <= set(LEDGER.labels("h2d"))
    counts = [w.counts for w in four_shards.step_timer.windows if w.counts]
    assert counts
    for c in counts:
        assert c["launches"] >= 2
        assert 0 < c["live_cells"] <= c["score_cells"]
        assert 0 < c["scatter_live"] <= c["scatter_lanes"]
        assert c["shard_rows"] <= 4 * c["shard_rows_max"]
    stages = [w.stages for w in four_shards.step_timer.windows]
    assert any(s.get("rescore", 0) > 0 for s in stages)


@requires_mesh
@pytest.mark.parametrize("restore_shards", [4, 8])
def test_checkpoint_keeps_global_row_order(tmp_path, stream,
                                           restore_shards):
    """A 4-shard checkpoint holds C in global row order (the dense job's
    at the same point), and restores into a mesh of either size to
    finish as the uninterrupted run does."""
    half = 2500
    ck = str(tmp_path / "ck")
    whole = run_production(sharded(), *stream, chunk=500)
    a = CooccurrenceJob(sharded(checkpoint_dir=ck))
    dense = CooccurrenceJob(Config(**JOB, backend=Backend.DEVICE))
    for job in (a, dense):
        for lo in range(0, half, 500):
            job.add_batch(*(x[lo:lo + 500] for x in stream))
    np.testing.assert_array_equal(a.scorer.checkpoint_state()["C"],
                                  dense.scorer.checkpoint_state()["C"])
    a.checkpoint()
    b = CooccurrenceJob(sharded(restore_shards, checkpoint_dir=ck))
    b.restore()
    for lo in range(half, len(stream[0]), 500):
        b.add_batch(*(x[lo:lo + 500] for x in stream))
    b.finish()
    assert_same_topk(b, whole, rtol=1e-6)
    for name in COUNTERS:
        assert b.counters.get(name) == whole.counters.get(name), name


@requires_mesh
def test_state_is_created_on_the_devices(monkeypatch):
    """C comes into being under the mesh's row sharding, and no host
    array of num_items^2 cells is allocated or uploaded on the way."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_cooccurrence.parallel.sharded import ShardedScorer

    n, biggest = 4096, []

    def watch(fn):
        def wrapped(shape, *a, **kw):
            biggest.append(int(np.prod(shape)))
            return fn(shape, *a, **kw)
        return wrapped

    for name in ("zeros", "empty", "ones", "full"):
        monkeypatch.setattr(np, name, watch(getattr(np, name)))
    put = jax.device_put

    def device_put(x, *a, **kw):
        biggest.append(int(np.size(x)))
        return put(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", device_put)
    scorer = ShardedScorer(n, 10, num_shards=4, count_dtype="int16")
    assert max(biggest, default=0) < n * n
    assert scorer.C.shape == (n, n)
    assert scorer.C.sharding == NamedSharding(scorer.mesh, P(ITEM_AXIS, None))
    assert {s.data.shape for s in scorer.C.addressable_shards} == {(n // 4, n)}


@requires_mesh
@pytest.mark.parametrize("control", [0, 1])
def test_benchmark_cell_rehearsal(capsys, control):
    """The four-chip Yelp cell's whole run at its rehearsal size: sound
    reads correct, and the float32 reference in the program's place (the
    control) does not, by the score gap."""
    from benchmark import run as bench

    argv = ["--workload", "yelp-reviews-sharded.replay", "--seed",
            str(2**31 + 29 + control), "--seconds", "2", "--trace", "0",
            "--rehearse", "1", "--control", str(control)]
    assert bench.main(argv) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["attempted"] == 6  # round(2 s x 3 windows a second)
    checks = out["checks"]
    if control:
        assert not out["correct"], checks
        assert checks["score_gap"]["value"] > checks["score_gap"]["limit"]
    else:
        assert out["correct"], checks


def _reader(name):
    from benchmark import run as bench

    return bench.load_module(
        os.path.join(bench.BENCH, "metrics", f"{name}.py"),
        "metric_" + name.replace(".", "_"))


#: Trace texts as the device planes name ops: JAX names a collective
#: after its primitive, so only the opcode tells it apart.
PSUM = ("%psum.7 = s32[151552]{0} all-reduce(%fusion.8), channel_id=1, "
        "replica_groups={{0,1,2,3}}, to_apply=%region_1.0")
GATHER = ("%all-gather-start.2 = (s32[8]{0}, s32[32]{0}) "
          "all-gather-start(%p), dimensions={0}")
FUSION = ('%fusion.7 = s16[37888,151552]{1,0} fusion(%p0, %p1), kind=kLoop, '
          'metadata={op_name="jit(_update)/shard_map/psum"}')
KERNEL = "%pallas_score_topk.1 = (f32[32768,128]{1,0}) custom-call(%a)"


@pytest.mark.parametrize("ops, share", [
    ({PSUM: 0.08, FUSION: 3.6, KERNEL: 66.3}, 100 * 0.08 / (17.5 * 4)),
    ({PSUM: 0.08, GATHER: 0.06, KERNEL: 66.3}, 100 * 0.14 / (17.5 * 4)),
    ({FUSION: 3.6, KERNEL: 66.3}, 0.0),
])
def test_collective_share_reads_opcodes(ops, share):
    """collective_share.sharded counts the ops whose opcode is a
    collective, a psum among them, over every chip's busy time; a fusion
    whose metadata names a psum is not one."""
    run = NS(trace={"busy_s": 17.5, "op_seconds": ops},
             devices=[object()] * 4)
    assert _reader("collective_share.sharded").read(run) == (
        pytest.approx(share))


@pytest.mark.parametrize("counts, shards, balance", [
    ([{"shard_rows": 400, "shard_rows_max": 101},
      {"shard_rows": 200, "shard_rows_max": 52}], 4, 100 * 600 / (4 * 153)),
    ([{"shard_rows": 400, "shard_rows_max": 400}], 4, 25.0),
    ([{"launches": 3}], 4, None),          # a program that counts nothing
    ([{"shard_rows": 400, "shard_rows_max": 101}], None, None),
])
def test_shard_balance_reads_the_windows_counts(counts, shards, balance):
    """shard_balance.sharded: the rows scored over the shards times the
    fullest shard's, summed over the measured windows; silent without
    the counts or a shard count."""
    run = NS(window={"windows": len(counts)},
             job=NS(step_timer=NS(windows=[NS(counts=c) for c in counts])),
             spec=NS(config={"job": {"num_shards": shards}}))
    got = _reader("shard_balance.sharded").read(run)
    assert got == (None if balance is None else pytest.approx(balance))
