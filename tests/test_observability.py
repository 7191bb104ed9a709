"""The observability plane: step timing, the transfer ledger, metrics
registry (histograms/gauges/Prometheus text), the run journal, the
scrape endpoint, and the XLA trace wrapper."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tpu_cooccurrence.metrics import (CANONICAL_COUNTERS, Counters,
                                      OBSERVED_COOCCURRENCES)
from tpu_cooccurrence.observability import (StepTimer, TransferLedger,
                                            WindowStats, clock, xla_trace)
from tpu_cooccurrence.observability.journal import (VERSION, RunJournal,
                                                    read_records, tail,
                                                    validate_record)
from tpu_cooccurrence.observability.registry import (Histogram,
                                                     MetricsRegistry,
                                                     log_buckets)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def stats(ts, sample, score, events=10, pairs=20, rows=5):
    return WindowStats(timestamp=ts, events=events, pairs=pairs,
                       rows_scored=rows, sample_seconds=sample,
                       score_seconds=score)


def test_step_timer_summary_aggregates():
    t = StepTimer()
    t.record(stats(0, 0.25, 0.75, events=100, pairs=1000))
    t.record(stats(1, 0.5, 0.5, events=50, pairs=500))
    s = t.summary()
    assert s["windows"] == 2 and s["events"] == 150 and s["pairs"] == 1500
    assert s["sample_seconds"] == pytest.approx(0.75)
    assert s["score_seconds"] == pytest.approx(1.25)
    assert StepTimer().summary() == {"windows": 0, "events": 0, "pairs": 0,
                                     "sample_seconds": 0.0,
                                     "score_seconds": 0.0}


def test_step_timer_slowest_ranks_and_ring_bounds():
    t = StepTimer(keep=4)
    for i, dur in enumerate([0.1, 0.9, 0.2, 0.8, 0.3]):  # 0.1 evicted
        t.record(stats(i, dur, 0.0))
    slow = t.slowest(2)
    assert [w.timestamp for w in slow] == [1, 3]
    assert t.total_windows == 5 and len(t.windows) == 4


def test_xla_trace_writes_profile(tmp_path):
    """--profile-dir produces an on-disk trace consumable by TensorBoard."""
    import jax.numpy as jnp

    out = str(tmp_path / "trace")
    with xla_trace(out):
        jnp.arange(8).sum().block_until_ready()
    found = [os.path.join(r, f) for r, _, fs in os.walk(out) for f in fs]
    assert found, "no trace files written"


def test_xla_trace_none_is_noop():
    with xla_trace(None):
        pass


def test_clock_measures():
    import time

    with clock() as c:
        time.sleep(0.01)
    assert c.seconds >= 0.009


def test_job_records_step_timing():
    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.job import CooccurrenceJob

    rng = np.random.default_rng(3)
    users = rng.integers(0, 10, 500).astype(np.int64)
    items = rng.integers(0, 30, 500).astype(np.int64)
    ts = np.cumsum(rng.integers(0, 2, 500)).astype(np.int64)
    job = CooccurrenceJob(Config(window_size=20, seed=1,
                                 backend=Backend.ORACLE))
    job.add_batch(users, items, ts)
    job.finish()
    s = job.step_timer.summary()
    assert s["windows"] == job.windows_fired > 0
    assert s["pairs"] > 0
    assert job.step_timer.slowest(1)


def test_window_stats_as_dict_json_round_trips():
    w = stats(7, 0.25, 0.5)
    d = json.loads(json.dumps(w.as_dict()))
    assert d["timestamp"] == 7 and d["events"] == 10 and d["pairs"] == 20
    assert d["seconds"] == pytest.approx(0.75)
    t = StepTimer()
    t.record(w)
    assert json.loads(json.dumps(t.slowest_as_dicts()))[0] == d


# ---------------------------------------------------------------------------
# metrics registry: fixed-log-bucket histograms + Prometheus exposition


def test_log_buckets_cover_and_ascend():
    b = log_buckets(0.001, 10, base=2.0)
    assert b[0] >= 0.001 and b[0] / 2 < 0.001  # tightest first bound
    assert b[-1] >= 10
    assert all(y == 2 * x for x, y in zip(b, b[1:]))
    with pytest.raises(ValueError):
        log_buckets(0, 1)


def test_histogram_bucket_assignment_and_stats():
    h = Histogram("h", [1.0, 2.0, 4.0, 8.0])
    for v in (0.5, 1.0, 1.5, 3.0, 100.0):  # 1.0 lands in le=1 (inclusive)
        h.observe(v)
    assert h._counts == [2, 1, 1, 0, 1]  # last = +Inf overflow
    assert h.count == 5
    assert h.sum == pytest.approx(106.0)
    assert h.min == 0.5 and h.max == 100.0
    assert h.cumulative_counts() == [2, 3, 4, 4, 5]


def test_histogram_percentiles_bucket_resolved():
    h = Histogram("h", [1.0, 2.0, 4.0, 8.0, 16.0])
    # 100 observations: 50 in (1,2], 45 in (2,4], 5 in (8,16].
    for _ in range(50):
        h.observe(1.5)
    for _ in range(45):
        h.observe(3.0)
    for _ in range(5):
        h.observe(9.0)
    assert h.percentile(50) == 2.0   # rank 50 -> le=2 bucket
    assert h.percentile(95) == 4.0   # rank 95 -> le=4 bucket
    assert h.percentile(99) == 9.0   # rank 99 -> le=16, capped at max seen
    s = h.summary()
    assert (s["p50"], s["p95"], s["p99"]) == (2.0, 4.0, 9.0)
    assert Histogram("e", [1.0]).percentile(99) == 0.0  # empty: no crash


def test_histogram_percentile_exact_within_one_bucket():
    """The pXX error bound the registry promises: at most one bucket step
    (base 2 = a factor of two) above the true quantile."""
    h = Histogram("h", log_buckets(1e-4, 100.0))
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-2.0, sigma=1.0, size=2000)
    for v in vals:
        h.observe(v)
    for p in (50, 95, 99):
        true = float(np.quantile(vals, p / 100.0))
        got = h.percentile(p)
        assert true <= got <= 2.0 * true + 1e-12


def test_histogram_concurrent_observe_exact_totals():
    h = Histogram("h", log_buckets(1e-3, 10.0))

    def hammer():
        for _ in range(5000):
            h.observe(0.01)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == 20_000
    assert h.sum == pytest.approx(200.0)


def test_registry_get_or_create_and_bounds_conflict():
    r = MetricsRegistry()
    h1 = r.histogram("x", [1.0, 2.0])
    assert r.histogram("x") is h1  # no bounds -> existing instance
    with pytest.raises(ValueError, match="different"):
        r.histogram("x", [1.0, 3.0])
    g = r.gauge("g")
    g.set(2)
    g.add(0.5)
    assert r.gauge("g").get() == pytest.approx(2.5)
    r.reset()
    assert r.gauge("g").get() == 0.0


def test_render_prometheus_format_and_canonical_counters():
    r = MetricsRegistry()
    r.gauge("cooc_windows_fired", help="fired").set(3)
    h = r.histogram("cooc_window_score_seconds", [0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    c = Counters()
    c.add(OBSERVED_COOCCURRENCES, 41)
    led = TransferLedger()
    led.up("t", np.zeros(4, np.int32))
    text = r.render_prometheus(c, led)
    # Every reference-named counter appears, incremented or not.
    for name in CANONICAL_COUNTERS:
        assert f"\n{name} " in "\n" + text
    assert f"{OBSERVED_COOCCURRENCES} 41" in text
    assert "cooc_transfer_h2d_bytes_total 16" in text
    assert "cooc_windows_fired 3" in text
    assert 'cooc_window_score_seconds_bucket{le="0.1"} 1' in text
    assert 'cooc_window_score_seconds_bucket{le="+Inf"} 2' in text
    assert "cooc_window_score_seconds_count 2" in text
    assert "cooc_window_score_seconds_p50 0.1" in text
    assert "cooc_window_score_seconds_p99 0.5" in text
    # Text-format sanity: every sample line is "name[{labels}] value".
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        float(value)
        assert name and " " not in name.replace('{le="', "").replace('"}', "")


# ---------------------------------------------------------------------------
# transfer ledger / counters thread-safety (the PR-1 pipelined-mode race)


def test_ledger_concurrent_updates_exact():
    led = TransferLedger()
    buf = np.zeros(256, np.int8)  # 256 bytes

    def up():
        for _ in range(2000):
            led.up("u", buf)

    def down():
        for _ in range(2000):
            led.down("d", buf)

    threads = [threading.Thread(target=f) for f in (up, up, down, down)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = led.snapshot()
    assert snap["h2d_bytes"] == 4000 * 256 and snap["h2d_calls"] == 4000
    assert snap["d2h_bytes"] == 4000 * 256 and snap["d2h_calls"] == 4000
    assert led.summary() == snap


def test_counters_merge_and_snapshot_and_diff():
    a, b = Counters(), Counters()
    a.add("x", 1)
    b.add("x", 2)
    b.add("y", 5)
    a.merge(b)
    assert a.get("x") == 3 and a.get("y") == 5
    snap, diff = a.snapshot_and_diff({})
    assert snap == {"x": 3, "y": 5} and diff == snap
    a.add("y", 1)
    snap2, diff2 = a.snapshot_and_diff(snap)
    assert diff2 == {"y": 1}
    _, diff3 = a.snapshot_and_diff(snap2)
    assert diff3 == {}


def test_counters_concurrent_merge_consistent():
    dst = Counters()
    src = Counters()
    src.add("k", 1)
    stop = threading.Event()

    def mutate():
        while not stop.is_set():
            src.add("k", 1)

    t = threading.Thread(target=mutate)
    t.start()
    try:
        for _ in range(200):
            dst.merge(src)
    finally:
        stop.set()
        t.join()
    assert dst.get("k") > 0  # no deadlock, no exception, values sane


# ---------------------------------------------------------------------------
# run journal: schema round-trip, torn tails, serial/pipelined parity


def _journal_record(seq=1, ts=100, **over):
    rec = {"v": VERSION, "seq": seq, "ts": ts, "events": 5, "pairs": 3,
           "rows_scored": 2, "sample_seconds": 0.01, "score_seconds": 0.02,
           "ring_depth": 0, "stall_seconds": 0.0, "wall_unix": 1.5,
           "counters": {"X": 1}, "wire": {"h2d_bytes": 10}}
    rec.update(over)
    return rec


def test_journal_round_trip_and_validation(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with RunJournal(path) as j:
        j.record(_journal_record(seq=1))
        j.record(_journal_record(seq=2, ts=200))
    got = list(read_records(path))
    assert [r["seq"] for r in got] == [1, 2]
    for r in got:
        validate_record(r)
    for bad, match in [
            ({k: v for k, v in _journal_record().items() if k != "ts"},
             "missing"),
            (_journal_record(ts="100"), "type"),
            (_journal_record(extra=1), "unknown"),
            (_journal_record(v=99), "version"),
    ]:
        with pytest.raises(ValueError, match=match):
            validate_record(bad)


def test_journal_append_resumes_and_torn_tail_skipped(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with RunJournal(path) as j:
        j.record(_journal_record(seq=1))
    with open(path, "a") as f:
        f.write('{"v": 1, "seq": 2, "ts"')  # SIGKILL mid-write
    assert [r["seq"] for r in read_records(path)] == [1]
    assert tail(path, n=5)[-1]["seq"] == 1
    # A restarted attempt appends past the torn line.
    with RunJournal(path) as j:
        j.record(_journal_record(seq=2, ts=200))
    assert [r["seq"] for r in read_records(path)] == [1, 2]
    assert tail(str(tmp_path / "missing.jsonl")) == []


def _run_journaled_job(tmp_path, name, pipeline_depth, backend="oracle"):
    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.job import CooccurrenceJob

    rng = np.random.default_rng(11)
    n = 4000
    users = rng.integers(0, 40, n).astype(np.int64)
    items = rng.integers(0, 60, n).astype(np.int64)
    ts = np.cumsum(rng.integers(0, 2, n)).astype(np.int64)
    path = str(tmp_path / f"{name}.jsonl")
    job = CooccurrenceJob(Config(window_size=50, seed=5, item_cut=20,
                                 user_cut=10, backend=Backend(backend),
                                 journal=path,
                                 pipeline_depth=pipeline_depth))
    job.add_batch(users, items, ts)
    job.finish()
    return job, list(read_records(path))


def test_journal_matches_job_and_schema(tmp_path):
    job, recs = _run_journaled_job(tmp_path, "serial", 0)
    assert len(recs) == job.windows_fired > 5
    for r in recs:
        validate_record(r)
    assert [r["seq"] for r in recs] == list(range(1, len(recs) + 1))
    # Counter deltas tie out: summing every window's delta reproduces the
    # job's final totals for every counter that moved during windows.
    totals = {}
    for r in recs:
        for k, v in r["counters"].items():
            totals[k] = totals.get(k, 0) + v
    assert totals[OBSERVED_COOCCURRENCES] == \
        job.counters.get(OBSERVED_COOCCURRENCES)
    s = job.step_timer.summary()
    assert sum(r["events"] for r in recs) == s["events"]
    assert sum(r["pairs"] for r in recs) == s["pairs"]


def test_journal_parity_serial_vs_pipelined(tmp_path):
    """Depth 0 and depth 2 journals are identical on every logical field
    (the per-window timings and ring occupancy legitimately differ)."""
    _, serial = _run_journaled_job(tmp_path, "d0", 0)
    _, piped = _run_journaled_job(tmp_path, "d2", 2)
    assert len(serial) == len(piped) > 5
    logical = ("seq", "ts", "events", "pairs", "rows_scored")
    for a, b in zip(serial, piped):
        assert {k: a[k] for k in logical} == {k: b[k] for k in logical}


# ---------------------------------------------------------------------------
# scrape endpoint


def _get(url):
    from urllib.request import urlopen

    with urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def test_metrics_server_serves_metrics_and_healthz():
    from tpu_cooccurrence.observability.http import MetricsServer

    reg = MetricsRegistry()
    reg.histogram("cooc_window_score_seconds").observe(0.01)
    reg.gauge("cooc_windows_fired").set(4)
    reg.gauge("cooc_last_window_unix_seconds").set(time.time())
    c = Counters()
    c.add(OBSERVED_COOCCURRENCES, 9)
    srv = MetricsServer(reg, counters=c, ledger=TransferLedger(), port=0,
                        stale_after_s=120.0).start()
    try:
        assert srv.port > 0
        code, text = _get(f"http://127.0.0.1:{srv.port}/metrics")
        assert code == 200
        assert f"{OBSERVED_COOCCURRENCES} 9" in text
        assert 'cooc_window_score_seconds_bucket{le="+Inf"} 1' in text
        code, body = _get(f"http://127.0.0.1:{srv.port}/healthz")
        hz = json.loads(body)
        assert code == 200 and hz["status"] == "ok"
        assert hz["windows_fired"] == 4
        from urllib.error import HTTPError

        with pytest.raises(HTTPError) as e:
            _get(f"http://127.0.0.1:{srv.port}/nope")
        assert e.value.code == 404
        # Stale: last window an hour ago -> 503.
        reg.gauge("cooc_last_window_unix_seconds").set(time.time() - 3600)
        with pytest.raises(HTTPError) as e:
            _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert e.value.code == 503
        assert json.loads(e.value.read().decode())["status"] == "stale"
    finally:
        srv.stop()


def test_metrics_server_healthz_grace_before_first_window():
    from tpu_cooccurrence.observability.http import MetricsServer

    srv = MetricsServer(MetricsRegistry(), stale_after_s=300.0)
    try:
        payload, healthy = srv.health()
        assert healthy and payload["status"] == "starting"
        srv._started_unix -= 301  # grace expired, still no window
        payload, healthy = srv.health()
        assert not healthy and payload["status"] == "stale"
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# CLI end-to-end smoke: --journal + --metrics-port 0 on a live run


def test_cli_journal_and_metrics_endpoint_smoke(tmp_path):
    """The operator path: run the CLI with the flight recorder and an
    ephemeral scrape port, validate every journal line against the
    schema, and scrape /metrics + /healthz while the job is live."""
    import re

    from test_cli import write_stream

    f = tmp_path / "in.csv"
    write_stream(f, n=2000)
    jpath = tmp_path / "journal.jsonl"
    cmd = [sys.executable, "-m", "tpu_cooccurrence.cli",
           "-i", str(f), "-ws", "50", "-ic", "20", "-uc", "10",
           "-s", "0xC0FFEE", "--backend", "oracle",
           "--journal", str(jpath), "--metrics-port", "0",
           # Continuous mode keeps the process (and the endpoint) alive
           # after the file is consumed so the scrape below can't race
           # process exit.
           "--process-continuously", "--buffer-timeout", "10"]
    proc = subprocess.Popen(cmd, env=ENV, cwd=REPO,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.PIPE, text=True)
    stderr_lines = []

    def pump():
        for line in proc.stderr:
            stderr_lines.append(line)

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    try:
        port = None
        deadline = time.time() + 120
        while time.time() < deadline and port is None:
            for line in list(stderr_lines):
                m = re.search(r"serving /metrics and /healthz on "
                              r"http://127\.0\.0\.1:(\d+)", line)
                if m:
                    port = int(m.group(1))
            if proc.poll() is not None:
                raise AssertionError(
                    "CLI exited early:\n" + "".join(stderr_lines)[-2000:])
            time.sleep(0.05)
        assert port, "metrics port never logged:\n" + "".join(stderr_lines)
        while time.time() < deadline:  # at least one fired window
            if jpath.exists() and list(read_records(str(jpath))):
                break
            time.sleep(0.1)
        code, text = _get(f"http://127.0.0.1:{port}/metrics")
        assert code == 200
        for name in CANONICAL_COUNTERS:  # all reference-named counters
            assert f"\n{name} " in "\n" + text
        for hist in ("cooc_window_sample_seconds",
                     "cooc_window_score_seconds",
                     "cooc_window_total_seconds"):
            assert f"{hist}_count" in text
            for q in ("p50", "p95", "p99"):
                assert f"{hist}_{q} " in text
        code, body = _get(f"http://127.0.0.1:{port}/healthz")
        assert code == 200
        assert json.loads(body)["status"] in ("ok", "starting")
    finally:
        proc.terminate()
        proc.wait(timeout=30)
    recs = list(read_records(str(jpath)))
    assert recs, "no journal records written"
    for r in recs:
        validate_record(r)
    assert [r["seq"] for r in recs] == list(range(1, len(recs) + 1))


def test_metrics_server_healthz_carries_supervisor_info():
    """Restart forensics from the supervising parent surface on
    /healthz as last_restart (cli.py passes the env payload through)."""
    from tpu_cooccurrence.observability.http import MetricsServer

    info = {"restarts": 2, "last_rc": -9, "backoff_ms": 150,
            "last_restart_unix": 1234.5, "stepped_back": False}
    srv = MetricsServer(MetricsRegistry(), stale_after_s=300.0,
                        supervisor_info=info)
    try:
        payload, healthy = srv.health()
        assert healthy
        assert payload["last_restart"] == info
    finally:
        srv.stop()

    srv = MetricsServer(MetricsRegistry(), stale_after_s=300.0)
    try:
        payload, _ = srv.health()
        assert "last_restart" not in payload
    finally:
        srv.stop()
