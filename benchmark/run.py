#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip this process finds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything about a cell is data, found by name:

* ``BENCHMARK.json`` (checkout root): the cell's configuration, traffic
  name and chips, and which metrics it reports;
* ``benchmark/workloads/<cell>.json``: the traffic's kind and parameters,
  and the limits of the correctness check;
* ``benchmark/configs/<config>.json`` (the ``file`` BENCHMARK.json names):
  the deployment -- the stream's generator and sizes, the job's settings;
* ``benchmark/traffic/<kind>.py``: the driver of a traffic kind;
* ``benchmark/metrics/<metric>.py``: each metric's reader.

The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, each number compared beside its
limit. The checks are also the last lines of stderr.

``--rehearse 1`` runs the cell at the tiny size its workload file gives,
on whatever JAX finds (the CPU here); it reports no time, rate or device
number, only counts. Without it a run that finds no TPU, or fewer chips
than the cell asks for, fails and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: JAX's persistent compilation cache: a fixed path in the checkout, so
#: that every run of a check after the first loads its programs.
CACHE_DIR = os.path.join(ROOT, ".xla_cache", "benchmark")
#: The sources whose numbers a CPU rehearsal must not report.
TIMED_SOURCES = ("host_clock", "device_trace")
if ROOT not in sys.path:  # the program under test and this package
    sys.path.insert(0, ROOT)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    """The cell's entries in BENCHMARK.json and its two data files."""

    def __init__(self, workload: str) -> None:
        bench = load_json(ROOT, "BENCHMARK.json")
        cells = {c["name"]: c for c in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; known: "
                             f"{sorted(cells)}")
        self.cell = cells[workload]
        conf = {c["name"]: c for c in bench["configs"]}[self.cell["config"]]
        self.config = load_json(ROOT, conf["file"])
        self.workload = load_json(BENCH, "workloads", f"{workload}.json")
        self.traffic = self.workload["traffic"]

        def mine(m):
            return workload in m.get("workloads", [workload])

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]

    def rehearse(self) -> None:
        """Shrink to the workload file's rehearsal sizes."""
        r = self.workload["rehearsal"]
        self.config = {**self.config,
                       "stream": {**self.config["stream"], **r["stream"]},
                       "job": {**self.config["job"], **r["job"]}}
        self.traffic = {**self.traffic, **r.get("traffic", {})}


class CompileCounter:
    """Programs compiled or loaded from the persistent cache: JAX
    reports one backend compile per program, whether XLA built it or the
    cache held it."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1


class Run:
    """One run: the job, its stream, the window and what was read in it."""

    def __init__(self, args, spec: Spec, on_chip: bool) -> None:
        import jax

        from benchmark.traffic import stream

        self.args = args
        self.spec = spec
        self.on_chip = on_chip
        self.traffic = spec.traffic
        self.compiles = CompileCounter()
        self.devices = jax.devices()[: spec.cell["chips"]]
        self.stream = stream
        self.window: dict = {}
        self.trace = None
        self.trace_dir = None
        self._span = None
        self.job = None
        self.hooks = None
        #: Seconds since process start at each step of set-up.
        self.marks = {"imports": time.monotonic() - T_START}

    def mark(self, name: str) -> None:
        self.marks[name] = time.monotonic() - T_START

    # -- the job ------------------------------------------------------------

    def make_job(self, scratch: bool = False):
        """The cell's job, as ``run.job``; a ``scratch`` job (a warm-up
        that is thrown away) is returned and never broken by hooks."""
        from tpu_cooccurrence.config import Backend, Config
        from tpu_cooccurrence.job import CooccurrenceJob

        job = dict(self.spec.config["job"])
        job["backend"] = Backend(job["backend"])
        job = CooccurrenceJob(Config(**job))
        if scratch:
            return job
        self.job = job
        self.mark("job")
        if self.hooks is not None:
            self.hooks(self)
        return job

    def ingest(self, lo: int, hi: int) -> None:
        import jax

        with jax.profiler.TraceAnnotation("ingest"):
            self.job.add_batch(self.users[lo:hi], self.items[lo:hi],
                               self.ts[lo:hi])

    def sync(self) -> None:
        """Wait until every dispatched window's results are on the
        device: block on every live array."""
        import jax

        with jax.profiler.TraceAnnotation("sync"):
            for a in jax.live_arrays():
                a.block_until_ready()

    # -- the measured window ----------------------------------------------

    def _readings(self) -> dict:
        from tpu_cooccurrence.observability import LEDGER

        c, t = self.job.counters, self.job.step_timer
        return {"t": time.monotonic(),
                "pairs": c.get("UserInteractionCounterObservedCooccurrences"),
                "rows": c.get("ItemRowRescorerRescoredItems"),
                "windows": self.job.windows_fired,
                "sample_s": t.total_sample_seconds,
                "h2d_bytes": LEDGER.snapshot()["h2d_bytes"],
                "compiles": self.compiles.count}

    def open_window(self) -> None:
        import jax

        self.setup_s = time.monotonic() - T_START
        self.setup_programs = self.compiles.count
        self.mark("warm-up")
        if self.args.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("window")
        self._span.__enter__()
        self._start = self._readings()

    def window_over(self) -> bool:
        return time.monotonic() - self._start["t"] >= self.args.seconds

    def close_window(self, **extra) -> None:
        import jax

        self.sync()
        end = self._readings()
        self._span.__exit__(None, None, None)
        self.window = {k: end[k] - self._start[k] for k in end}
        self.window["wall_s"] = self.window.pop("t")
        self.window.update(extra)
        if self.args.trace:
            from benchmark.trace import reduce

            jax.profiler.stop_trace()
            self.trace = reduce.reduce(reduce.load(self.trace_dir))
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    def memory_peak_bytes(self):
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None


def read_metrics(run: Run, metrics: list) -> dict:
    out = {}
    for m in metrics:
        if not run.on_chip and m["source"] in TIMED_SOURCES:
            out[m["name"]] = {"value": None, "unit": m["unit"],
                              "note": "not measured: no chip"}
            continue
        reader = load_module(os.path.join(BENCH, "metrics",
                                          f"{m['name']}.py"),
                             f"metric_{m['name']}")
        value = reader.read(run)
        if value is not None:  # a reader with nothing to read is silent
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the reference computed one precision "
                         "lower in the program's place (benchmark/tests)")
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0,
                    help="tiny sizes, any platform, no timed numbers")
    return ap.parse_args(argv)


def main(argv=None, hooks=None) -> int:
    """``hooks`` (tests only) may break the timed path: a callable given
    the Run once its job is built."""
    args = parse(argv)
    spec = Spec(args.workload)
    if args.rehearse:
        spec.rehearse()
    if not args.rehearse:  # a rehearsal's CPU programs stay out of it
        os.makedirs(CACHE_DIR, exist_ok=True)  # JAX does not create it
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    import tpu_cooccurrence  # noqa: F401  the system under test, or fail
    from benchmark.reference import check

    devices = jax.devices()
    platform = devices[0].platform
    chips = spec.cell["chips"]
    if not args.rehearse and platform != "tpu":
        sys.stderr.write(f"benchmark: JAX found no TPU (platform "
                         f"{platform!r}); this benchmark runs on the chip "
                         f"only\n")
        return 2
    if len(devices) < chips:
        sys.stderr.write(f"benchmark: {args.workload} needs {chips} chips, "
                         f"JAX found {len(devices)}\n")
        return 2
    on_chip = platform == "tpu"

    run = Run(args, spec, on_chip)
    driver = load_module(os.path.join(BENCH, "traffic",
                                      f"{spec.traffic['kind']}.py"),
                         f"traffic_{spec.traffic['kind']}")
    run.hooks = hooks
    driver.drive(run)
    peak = run.memory_peak_bytes()
    run.peak_bytes = peak
    metrics = read_metrics(
        run, spec.per_layer if args.trace else spec.end_to_end)
    # The job's own result path, then the program's state is freed
    # before the reference runs.
    run.job.finish()
    result = check.Result.of(run.job, run.consumed)
    run.job = None
    gc.collect()
    correct, checks = check.compare(run, result, control=args.control)

    dev = devices[0]
    out = {"correct": correct,
           "attempted": run.window["windows"],
           "failed": 0,
           "metrics": metrics,
           "device": {"platform": platform, "kind": dev.device_kind,
                      "count": len(run.devices),
                      "memory_peak_bytes": peak}}
    if args.trace and run.trace is not None:
        out["device"]["busy_s"] = run.trace["busy_s"]
        out["device"]["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    # What a reader of the run needs besides the result: where set-up
    # went, what the window did, what the reference cost. The checks
    # come last.
    sys.stderr.write("setup marks (s since start): " + json.dumps(
        {k: round(v, 3) for k, v in run.marks.items()}) + "\n")
    sys.stderr.write("window: " + json.dumps(run.window) + "\n")
    sys.stderr.write(f"reference: {run.reference_s:.3f} s, "
                     f"{run.rows_compared} rows compared\n")
    for name, c in checks.items():
        sys.stderr.write(f"check {name}: {c['value']} limit {c['limit']}\n")
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
