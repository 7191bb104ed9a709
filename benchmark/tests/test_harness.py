"""Whole runs of each cell at its rehearsal size on the CPU: the result
line's shape, the control, and the timed path broken underneath -- each
fault has to come out as not correct.

The harness's look for a chip is skipped (``--rehearse 1``); every other
step of a run is the one the chip runs.
"""

import json

import numpy as np
import pytest

from benchmark import run as bench

CELLS = ("ml25m-sliding.replay", "zipf1m-sparse.replay")
KEYS = ("correct", "attempted", "failed", "metrics", "device")


def one_run(capsys, cell, seed, *extra, hooks=None):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", "2",
            "--trace", "0", "--rehearse", "1", *extra]
    assert bench.main(argv, hooks=hooks) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[: len(KEYS)] == list(KEYS)
    assert list(out)[-1] == "checks"
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(capsys, cell):
    out = one_run(capsys, cell, 2**31 + 17)
    assert out["correct"], out["checks"]
    assert out["device"]["platform"] == "cpu"
    rate = "pairs_per_s.sparse" if "sparse" in cell else "pairs_per_s"
    assert {rate, "setup_s"} <= set(out["metrics"])
    for m in out["metrics"].values():
        # No CPU number under the name of a device metric.
        assert m["value"] is None


def test_fixed_work_is_the_same_on_every_seed(capsys):
    """The sparse cell's window feeds round(--seconds x batches_per_s)
    windows, whatever the seed and however long they take."""
    runs = [one_run(capsys, CELLS[1], s) for s in (5, 2**31 + 5)]
    assert [r["attempted"] for r in runs] == [10, 10]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(capsys, cell):
    out = one_run(capsys, cell, 23, "--control", "1")
    assert not out["correct"], out["checks"]
    assert out["checks"]["score_gap"]["value"] > \
        out["checks"]["score_gap"]["limit"]


def state_unchanged(run):
    """The scorer's step leaves its state as it was."""
    from tpu_cooccurrence.state.results import TopKBatch

    k = run.job.config.top_k
    run.job.scorer.process_window = lambda ts, pairs: TopKBatch.empty(k)


def half_the_batch(run):
    """Every other event of each batch never reaches the job."""
    add = run.job.add_batch
    run.job.add_batch = lambda u, i, t: add(u[::2], i[::2], t[::2])


def answer_altered(run):
    """Each row's best score is changed where the scorer produces it."""
    flush = run.job.scorer.flush

    def altered():
        out = flush()
        out.vals = np.array(out.vals, copy=True)
        out.vals[:, 0] *= 1.01
        return out

    run.job.scorer.flush = altered


@pytest.mark.parametrize("fault", [state_unchanged, half_the_batch,
                                   answer_altered])
def test_fault_is_not_correct(capsys, fault):
    out = one_run(capsys, CELLS[0], 31, hooks=fault)
    assert not out["correct"], out["checks"]
