"""scatter_fill.chained: the dense chained update's live COO lanes over
the lanes its scatter steps were shaped for, as the program counts them
per window; silent where the program counts nothing of the kind."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import run as bench

READER = bench.load_module(
    os.path.join(bench.BENCH, "metrics", "scatter_fill.chained.py"),
    "metric_scatter_fill_chained")


def fake_run(counts, windows=None):
    """A run whose job's StepTimer ring holds one record per ``counts``
    entry, the last ``windows`` of them the measured window's."""
    records = [NS(counts=c) for c in counts]
    n = len(records) if windows is None else windows
    return NS(window={"windows": n},
              job=NS(step_timer=NS(windows=records)))


@pytest.mark.parametrize("counts", [
    [{}, {}],                                  # a program without them
    [{"launches": 13}, {"launches": 13}],      # no such count in a window
    [{"fused_windows": 1, "expand_lanes": 32768, "expand_live": 30000}],
])
def test_reads_nothing_without_chained_counts(counts):
    assert READER.read(fake_run(counts)) is None


def test_reads_the_windows_live_share_of_the_lanes():
    counts = [{"scatter_lanes": 1 << 20, "scatter_live": 1 << 19},  # warm-up
              {"scatter_lanes": 753_664, "scatter_live": 748_000},
              {"scatter_lanes": 737_280, "scatter_live": 730_000},
              {"launches": 1}]                    # an empty window
    value = READER.read(fake_run(counts, windows=3))
    assert value == pytest.approx(100.0 * (748_000 + 730_000)
                                  / (753_664 + 737_280))


def test_traced_rehearsal_reports_it(capsys):
    argv = ["--workload", "ml25m-sliding.replay", "--seed",
            str(2**31 + 43), "--seconds", "2", "--trace", "1",
            "--rehearse", "1"]
    assert bench.main(argv) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert 0 < out["metrics"]["scatter_fill.chained"]["value"] <= 100
