"""The program's own stage spans and per-window counts as the
benchmark reads them: a traced CPU rehearsal of each cell reports the
cell's stage and count metrics, and the trace reducer puts an idle gap
inside a program stage down to that stage."""

import json
import time
from types import SimpleNamespace as NS

import pytest

from benchmark import run as bench
from benchmark.trace import reduce

NEW_METRICS = {
    "ml25m-sliding.replay": ("score_fill.replay",
                             "launches_per_window.replay"),
    "zipf1m-sparse.replay": ("index_share.sparse", "score_fill.sparse",
                             "launches_per_window.sparse"),
}


@pytest.mark.parametrize("cell", sorted(NEW_METRICS))
def test_traced_rehearsal_reports_stage_metrics(capsys, cell):
    argv = ["--workload", cell, "--seed", str(2**31 + 41), "--seconds",
            "2", "--trace", "1", "--rehearse", "1"]
    assert bench.main(argv) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    metrics = out["metrics"]
    for name in NEW_METRICS[cell]:
        value = metrics[name]["value"]
        if name.startswith("launches_per_window"):
            assert value >= 1, (name, value)
        else:
            assert 0 < value <= 100, (name, value)


def _host_event(planes, name):
    return next(e for p in planes for ln in p.lines for e in ln.events
                if e.name == name)


def test_idle_gap_inside_a_program_stage_names_it(tmp_path):
    """A program stage, traced on the CPU inside the harness's
    ``ingest`` span, labels the device's idle gap that falls in it: the
    reducer reads the program's own ``cooc/index`` annotation."""
    import jax

    from tpu_cooccurrence.observability import StageClock

    clk = StageClock()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host events are the annotations alone
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("window"), \
                jax.profiler.TraceAnnotation("ingest"):
            with clk.stage("index"):
                time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    host = [p for p in reduce.load(str(tmp_path)).planes
            if p.name.startswith("/host:")]
    win = _host_event(host, "window")
    idx = _host_event(host, "cooc/index")
    # A device busy for the whole window except the index stage.
    w0, w1 = win.start_ns, win.start_ns + win.duration_ns
    i0, i1 = idx.start_ns, idx.start_ns + idx.duration_ns
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        NS(name="fusion.1", start_ns=w0, duration_ns=i0 - w0),
        NS(name="fusion.2", start_ns=i1, duration_ns=w1 - i1)])])
    idle = dict(reduce.reduce(NS(planes=host + [device]))["idle_gaps"])
    assert idle == {"ingest>cooc/index": pytest.approx(idx.duration_ns / 1e9)}
