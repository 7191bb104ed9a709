"""Benchmark entry: prints ONE JSON line with the north-star metric.

Metric (BASELINE.md): item-pairs/sec = ObservedCooccurrences / Duration on a
Zipfian basket stream, device backend. ``vs_baseline`` compares against the
first recorded CPU-oracle-backend run of this same framework (the reference
publishes no numbers — BASELINE.md "Published reference numbers: None").

Structure: the orchestrating parent never imports jax (it holds no
chip); a probe child, then the measurement child, run one after the
other, each under a hard deadline. ``bench.py --measure`` is the child
mode that measures. Unless ``JAX_PLATFORMS=cpu`` asks for a CPU run, an
accelerator is expected: when none is reached the run fails with a
non-zero exit and an error object, never a CPU number.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
_HISTORY = os.path.join(REPO, "bench_history.jsonl")

# Child deadlines. Accelerator: compiles legitimately take minutes. CPU:
# slower, but the run must terminate.
ACCEL_DEADLINE_S = float(os.environ.get("BENCH_ACCEL_DEADLINE_S", 2400))
CPU_DEADLINE_S = float(os.environ.get("BENCH_CPU_DEADLINE_S", 3600))

#: What the probe child runs: one real op, then the backend it ran on.
PROBE_CODE = ("import jax, jax.numpy as jnp; "
              "x = (jnp.ones(8) + 1).sum(); x.block_until_ready(); "
              "print('BACKEND-' + jax.default_backend())")


def probe_backend(timeout_s: float = 240.0):
    """Backend the probe child executed an op on ('tpu', 'cpu', ...), or
    None if it hung past the deadline or crashed. The child exits
    before the caller starts the next one: one process per chip."""
    try:
        r = subprocess.run([sys.executable, "-c", PROBE_CODE],
                           capture_output=True, text=True,
                           timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return None
    m = re.search(r"BACKEND-(\w+)", r.stdout)
    return m.group(1) if m else None


def run(backend: str, users, items, ts, num_items: int, window_ms: int,
        pipeline_depth: int = 0, journal: str = None,
        fused_window: str = "off", wire_format: str = "auto",
        cell_dtype: str = "auto", spill_threshold_windows: int = 0,
        spill_target_hbm_frac: float = 0.5):
    import hashlib

    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.job import CooccurrenceJob
    from tpu_cooccurrence.metrics import OBSERVED_COOCCURRENCES
    from tpu_cooccurrence.observability import LEDGER
    from tpu_cooccurrence.observability.registry import REGISTRY

    # Per-run metrics scope: the registry and ledger are process-global,
    # so clear them here and the summaries below describe exactly this
    # run's windows.
    REGISTRY.reset()
    LEDGER.reset()
    cfg = Config(window_size=window_ms, seed=0xC0FFEE, item_cut=500,
                 user_cut=500, backend=Backend(backend), num_items=num_items,
                 pipeline_depth=pipeline_depth, journal=journal,
                 fused_window=fused_window, wire_format=wire_format,
                 cell_dtype=cell_dtype,
                 spill_threshold_windows=spill_threshold_windows,
                 spill_target_hbm_frac=spill_target_hbm_frac)
    job = CooccurrenceJob(cfg)
    start = time.monotonic()
    job.add_batch(users, items, ts)
    job.finish()
    elapsed = time.monotonic() - start
    pairs = job.counters.get(OBSERVED_COOCCURRENCES)
    # Per-stage busy fractions (observability.StepTimer.occupancy): the
    # pipeline-overlap diagnostic — a serial run's host+score sums to
    # <= ~100%, an overlapped run exceeds it. Latency: per-window
    # p50/p95/p99 from the fixed-log-bucket histograms — BENCH_* carries
    # tails, not just means (a 2x p99 regression is invisible in a mean).
    # Degradation counters ride along (robustness/degrade.py): a bench
    # number earned by shedding load is not the same bench number — zero
    # here is the claim that nothing was shed or quarantined.
    degradation = {
        "level": int(REGISTRY.gauge("cooc_degradation_level").get()),
        "shed_events_total": int(
            REGISTRY.gauge("cooc_shed_events_total").get()),
        "quarantined_total": int(
            REGISTRY.gauge("cooc_quarantined_lines_total").get()),
    }
    # Dispatch-path counters (--fused-window): how many windows took the
    # fused one-dispatch program vs the chained scatter+score path.
    dispatches = {
        "fused_dispatches": int(
            REGISTRY.gauge("cooc_fused_dispatches_total").get()),
        "chained_dispatches": int(
            REGISTRY.gauge("cooc_chained_dispatches_total").get()),
        # Fused-sparse shape specialization: distinct fused-program
        # shapes compiled (per-bucket churn; 0 on the chained path).
        "fused_bucket_compilations": int(
            REGISTRY.gauge("cooc_fused_bucket_compilations_total").get()),
    }
    # Compressed-state accounting (sparse backend; zeros elsewhere): the
    # raw-vs-encoded uplink pair from the ledger, plus the host index /
    # device slab footprint gauges the scorer refreshes per window.
    snap = LEDGER.snapshot()
    windows = max(int(REGISTRY.gauge("cooc_windows_fired").get()), 1)
    wire = {
        "windows": windows,
        "uplink_bytes_raw": snap["uplink_raw_bytes"],
        "uplink_bytes_encoded": snap["uplink_enc_bytes"],
        "h2d_bytes": snap["h2d_bytes"],
        "host_index_rss_bytes": int(
            REGISTRY.gauge("cooc_host_index_rss_bytes").get()),
        "slab_device_bytes": int(
            REGISTRY.gauge("cooc_slab_device_bytes").get()),
        "slab_live_cells": int(
            REGISTRY.gauge("cooc_slab_live_cells").get()),
    }
    # Tiered-state accounting (PR 9): spill/promote counters, the rows
    # the run MANAGED (device-resident + spilled to the host arena —
    # identical across arms on the same stream), and a digest of the
    # final top-K so the spill A/B arm can assert bit-identity without
    # holding both result tables.
    scorer = job.scorer
    rows_managed = 0
    if hasattr(scorer, "index"):
        rows_managed = len(scorer.index.rows.occupied())
        if getattr(scorer, "index_w", None) is not None:
            rows_managed += len(scorer.index_w.rows.occupied())
        store = getattr(scorer, "store", None)
        if getattr(store, "tiered", False):
            rows_managed += len(store.arena)
    digest = hashlib.sha256()
    snap = job.latest.snapshot()
    for item in sorted(snap):
        digest.update(repr((item, snap[item])).encode())
    spill = {
        "evictions_total": int(
            REGISTRY.gauge("cooc_spill_evictions_total").get()),
        "promotions_total": int(
            REGISTRY.gauge("cooc_spill_promotions_total").get()),
        "touches_total": int(
            REGISTRY.gauge("cooc_spill_row_touches_total").get()),
        "resident_rows": int(
            REGISTRY.gauge("cooc_spill_resident_rows").get()),
        "arena_bytes": int(
            REGISTRY.gauge("cooc_spill_arena_bytes").get()),
        "rows_managed": rows_managed,
        "results_digest": digest.hexdigest(),
    }
    return pairs, elapsed, job.step_timer.occupancy(elapsed), \
        REGISTRY.summaries(), degradation, dispatches, wire, spill


def query_storm(seconds: float = None, threads: int = None,
                user_space: int = 1_000_000) -> dict:
    """Closed-loop query storm: a keep-alive HTTP client pool hammers
    ``/recommend`` on a live ingesting job (PR-8 serving plane).

    The job ingests a Zipfian stream on its own thread (oracle backend:
    steady host-side window cadence with no compile pauses, so the storm
    measures the *query plane*, not XLA warm-up) while ``threads``
    keep-alive clients draw uniform user ids from a million-user space —
    mostly cold users (the popularity-fallback path, the realistic storm
    shape) with the Zipf-head users exercising the blend. Client-side
    latencies give qps + p50/p95/p99; the server-side
    ``cooc_query_seconds`` histogram rides along for cross-checking, and
    the snapshot generation span proves the storm overlapped live window
    swaps.
    """
    import http.client

    import numpy as np

    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.io.synthetic import zipfian_interactions
    from tpu_cooccurrence.job import CooccurrenceJob
    from tpu_cooccurrence.observability import LEDGER
    from tpu_cooccurrence.observability.http import MetricsServer
    from tpu_cooccurrence.observability.registry import REGISTRY

    seconds = seconds if seconds is not None else float(
        os.environ.get("BENCH_STORM_SECONDS", 3.0))
    threads = threads if threads is not None else int(
        os.environ.get("BENCH_STORM_THREADS", 8))
    n_events = int(os.environ.get("BENCH_STORM_EVENTS", 200_000))
    REGISTRY.reset()
    LEDGER.reset()
    users, items, ts = zipfian_interactions(
        n_events, n_items=20_000, n_users=user_space, alpha=1.1, seed=9,
        events_per_ms=200)
    cfg = Config(window_size=100, seed=0xC0FFEE, item_cut=500,
                 user_cut=500, backend=Backend.ORACLE, serve_port=0)
    job = CooccurrenceJob(cfg)
    srv = MetricsServer(REGISTRY, counters=job.counters, ledger=LEDGER,
                        port=0, serving=job.serving).start()
    stop = threading.Event()
    latencies = [[] for _ in range(threads)]
    # Per-thread error tallies (summed at the end): a shared += would be
    # a read-modify-write raced across the pool and could undercount.
    errors = [0] * threads

    def client(tid: int) -> None:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=10)
        rng = np.random.default_rng(tid)
        lat = latencies[tid]
        while not stop.is_set():
            u = int(rng.integers(0, user_space))
            t0 = time.perf_counter()
            try:
                conn.request("GET", f"/recommend?user={u}&n=10")
                resp = conn.getresponse()
                resp.read()
                if resp.status != 200:
                    errors[tid] += 1
                    continue
            except Exception:
                errors[tid] += 1
                conn.close()
                conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                                  timeout=10)
                continue
            lat.append(time.perf_counter() - t0)
        conn.close()

    def ingest() -> None:
        chunk = 4000
        i = 0
        while not stop.is_set() and i < n_events:
            j = min(i + chunk, n_events)
            job.add_batch(users[i:j], items[i:j], ts[i:j])
            i = j

    gen0 = job.serving.generation
    feeder = threading.Thread(target=ingest, daemon=True)
    pool = [threading.Thread(target=client, args=(t,), daemon=True)
            for t in range(threads)]
    feeder.start()
    for t in pool:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in pool:
        t.join(timeout=30)
    feeder.join(timeout=120)
    job.finish()
    server_hist = REGISTRY.histogram("cooc_query_seconds").summary()
    srv.stop()
    flat = [x for lat in latencies for x in lat]
    total = len(flat)
    arr = np.asarray(flat) if flat else np.zeros(1)
    return {
        # Explicit status flag (ISSUE 13 satellite): a degraded arm
        # records {"ok": false, "error": ...} in bench_history.jsonl
        # instead of a silently absent block.
        "ok": True,
        "users": user_space,
        "threads": threads,
        "seconds": round(seconds, 3),
        "queries": total,
        "errors": sum(errors),
        "qps": round(total / max(seconds, 1e-9), 1),
        "query_p50_s": round(float(np.percentile(arr, 50)), 6),
        "query_p95_s": round(float(np.percentile(arr, 95)), 6),
        "query_p99_s": round(float(np.percentile(arr, 99)), 6),
        "generations": [gen0, job.serving.generation],
        "snapshot_swaps": job.serving.builder.swaps,
        "server_query_seconds": server_hist,
    }


def storm_client(url: str, seconds: float, threads: int,
                 fallback: str = None) -> dict:
    """Closed-loop keep-alive client pool against ONE replica (the
    ``--storm-client`` child mode of the fleet arm — client CPU must
    live outside the replicas' processes AND outside the orchestrating
    parent's GIL, or the fleet's aggregate qps would be client-bound).

    ``fallback``: a survivor's URL. On a connection failure (the chaos
    kill) the thread switches ALL remaining traffic there — the
    load-balancer drain. The failed attempt counts as a
    ``drain_failover``, not an error; errors AFTER the drain are the
    chaos case's acceptance metric (must be zero).
    """
    import http.client
    import urllib.parse

    import numpy as np

    def _conn(u):
        netloc = urllib.parse.urlparse(u).netloc
        host, _, port = netloc.partition(":")
        return http.client.HTTPConnection(host, int(port), timeout=10)

    latencies = [[] for _ in range(threads)]
    errors = [0] * threads
    failovers = [0] * threads
    stop = threading.Event()

    def client(tid: int) -> None:
        target = url
        conn = _conn(target)
        rng = np.random.default_rng(tid)
        lat = latencies[tid]
        while not stop.is_set():
            u = int(rng.integers(0, 1_000_000))
            t0 = time.perf_counter()
            try:
                conn.request("GET", f"/recommend?user={u}&n=10")
                resp = conn.getresponse()
                resp.read()
                if resp.status != 200:
                    errors[tid] += 1
                    continue
            except Exception:
                conn.close()
                if fallback is not None and target != fallback:
                    # The drain: all remaining traffic to the survivor.
                    target = fallback
                    failovers[tid] += 1
                else:
                    errors[tid] += 1
                conn = _conn(target)
                continue
            lat.append(time.perf_counter() - t0)
        conn.close()

    pool = [threading.Thread(target=client, args=(t,), daemon=True)
            for t in range(threads)]
    for t in pool:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in pool:
        t.join(timeout=30)
    flat = [x for lat in latencies for x in lat]
    arr = (np.asarray(flat) if flat else np.zeros(1))
    return {
        "url": url,
        "threads": threads,
        "seconds": round(seconds, 3),
        "queries": len(flat),
        "errors": sum(errors),
        "drain_failovers": sum(failovers),
        "qps": round(len(flat) / max(seconds, 1e-9), 1),
        "query_p50_s": round(float(np.percentile(arr, 50)), 6),
        "query_p95_s": round(float(np.percentile(arr, 95)), 6),
        "query_p99_s": round(float(np.percentile(arr, 99)), 6),
    }


def _wait_replica(port_file: str, timeout_s: float = 90.0) -> dict:
    """Wait for a replica's port file AND a 200 /healthz; returns the
    ``{"port", "pid", "url"}`` record."""
    import urllib.request

    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        try:
            with open(port_file) as f:
                info = json.load(f)
            urllib.request.urlopen(info["url"] + "/healthz", timeout=2)
            return info
        except Exception:
            time.sleep(0.25)
    raise TimeoutError(f"replica never came up ({port_file})")


def _replica_health(url: str) -> dict:
    import urllib.request

    with urllib.request.urlopen(url + "/healthz", timeout=5) as r:
        return json.load(r)


def _fleet_storm() -> dict:
    """The replicated-serving-fleet arm (ISSUE 13).

    One live ingest job (sparse backend, ``--checkpoint-incremental``)
    commits delta generations throughout; stateless ``cooc-replica``
    subprocesses bootstrap from its checkpoints and tail the delta log.
    Three phases against the same live writer:

    * **single** — 1 replica, 1 client subprocess: the per-replica
      baseline;
    * **fleet** — N (default 3) replicas under the serving-gang
      supervisor (``cooc-replica --fleet N``), one client subprocess
      per replica: per-replica and AGGREGATE qps + tails — reads scale
      with replicas, not with the TPU job;
    * **chaos** — mid-storm, replica 0 is SIGKILLed: its client drains
      to a survivor (zero failed queries after drain), and the fleet
      supervisor's relaunched replica re-syncs from checkpoint + delta
      tail to the live generation.
    """
    import shutil
    import signal
    import tempfile

    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.io.synthetic import zipfian_interactions
    from tpu_cooccurrence.job import CooccurrenceJob
    from tpu_cooccurrence.observability import LEDGER
    from tpu_cooccurrence.observability.registry import REGISTRY
    from tpu_cooccurrence.state import checkpoint as ckpt

    seconds = float(os.environ.get("BENCH_FLEET_SECONDS", 4.0))
    n_replicas = int(os.environ.get("BENCH_FLEET_REPLICAS", 3))
    threads = int(os.environ.get("BENCH_FLEET_CLIENT_THREADS", 4))
    n_events = int(os.environ.get("BENCH_FLEET_EVENTS", 120_000))
    REGISTRY.reset()
    LEDGER.reset()
    users, items, ts = zipfian_interactions(
        n_events, n_items=20_000, n_users=1_000_000, alpha=1.1, seed=9,
        events_per_ms=200)
    state_dir = tempfile.mkdtemp(prefix="bench-fleet-")
    job = CooccurrenceJob(Config(
        window_size=50, seed=0xC0FFEE, item_cut=500, user_cut=500,
        backend=Backend.SPARSE, checkpoint_dir=state_dir,
        checkpoint_every_windows=2, checkpoint_retain=10_000,
        checkpoint_incremental=True))
    # Enough ingest for a bootstrap checkpoint, then keep the writer
    # live across both storms (generations keep committing — the
    # replicas must tail a MOVING log, not a finished one).
    warm = n_events // 3
    chunk = 4000
    for lo in range(0, warm, chunk):
        job.add_batch(users[lo:lo + chunk], items[lo:lo + chunk],
                      ts[lo:lo + chunk])
    if not ckpt.generations(state_dir, ""):
        job.checkpoint()
    stop_feed = threading.Event()
    # Pace the remaining stream across both storms (~2 storm windows),
    # so the delta log the replicas tail keeps MOVING the whole time.
    n_chunks = max((n_events - warm + chunk - 1) // chunk, 1)
    feed_sleep = max(0.02, 2.0 * seconds / n_chunks)

    def feed() -> None:
        lo = warm
        while not stop_feed.is_set() and lo < n_events:
            hi = min(lo + chunk, n_events)
            job.add_batch(users[lo:hi], items[lo:hi], ts[lo:hi])
            lo = hi
            time.sleep(feed_sleep)

    feeder = threading.Thread(target=feed, daemon=True)
    feeder.start()

    # Replicas and clients are host-only: pinned to the CPU, none of
    # them can claim the chip this measurement child holds.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = []

    def spawn_replica(port_file: str, extra=()) -> "subprocess.Popen":
        p = subprocess.Popen(
            [sys.executable, "-m", "tpu_cooccurrence.serving.replica",
             "--state-dir", state_dir, "--port", "0",
             "--port-file", port_file, "--poll-interval-s", "0.2",
             "--stale-after-s", "0", *extra],
            env=env, cwd=REPO, stderr=subprocess.DEVNULL)
        procs.append(p)
        return p

    def spawn_client(url: str, fallback: str = None) -> "subprocess.Popen":
        cmd = [sys.executable, os.path.abspath(__file__),
               "--storm-client", url, str(seconds), str(threads)]
        if fallback:
            cmd.append(fallback)
        p = subprocess.Popen(cmd, env=env, cwd=REPO,
                             stdout=subprocess.PIPE, text=True)
        procs.append(p)
        return p

    def client_result(p: "subprocess.Popen") -> dict:
        out, _ = p.communicate(timeout=seconds + 120)
        for line in reversed(out.strip().splitlines()):
            if line.startswith("{"):
                return json.loads(line)
        raise RuntimeError("storm client printed no result")

    try:
        # -- single-replica baseline ---------------------------------
        pf = os.path.join(state_dir, "single.port")
        single_proc = spawn_replica(pf)
        single = _wait_replica(pf)
        single_res = client_result(spawn_client(single["url"]))
        single_proc.terminate()

        # -- fleet storm + chaos -------------------------------------
        fleet_dir = os.path.join(state_dir, "fleet")
        fleet_proc = subprocess.Popen(
            [sys.executable, "-m", "tpu_cooccurrence.serving.replica",
             "--state-dir", state_dir, "--fleet", str(n_replicas),
             "--fleet-dir", fleet_dir, "--poll-interval-s", "0.2",
             "--stale-after-s", "0", "--gang-stale-after-s", "0",
             "--restart-on-failure", "3"],
            env=env, cwd=REPO, stderr=subprocess.DEVNULL)
        procs.append(fleet_proc)
        infos = [_wait_replica(os.path.join(
            fleet_dir, f"replica.p{i}.port")) for i in range(n_replicas)]
        gen_start = _replica_health(infos[0]["url"])["replica"][
            "generation"]
        # Victim's client drains to replica 1; the rest have no chaos.
        clients = [spawn_client(
            infos[i]["url"],
            fallback=(infos[1]["url"] if i == 0 and n_replicas > 1
                      else None)) for i in range(n_replicas)]
        time.sleep(seconds * 0.4)
        os.kill(infos[0]["pid"], signal.SIGKILL)  # the chaos kill
        fleet_res = [client_result(c) for c in clients]

        # The supervisor relaunches slot 0; it must re-sync from
        # checkpoint + delta tail to the LIVE generation.
        stop_feed.set()
        feeder.join(timeout=120)
        job.finish()
        live_gen = ckpt.generations(state_dir, "")[0][0]
        relaunched_gen = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                info = _wait_replica(os.path.join(
                    fleet_dir, "replica.p0.port"), timeout_s=5)
                if info["pid"] != infos[0]["pid"]:
                    h = _replica_health(info["url"])
                    relaunched_gen = h["replica"]["generation"]
                    if relaunched_gen >= live_gen:
                        break
            except Exception:
                pass
            time.sleep(0.3)
        aggregate_qps = round(sum(r["qps"] for r in fleet_res), 1)
        survivors = fleet_res[1:] if n_replicas > 1 else fleet_res
        return {
            "ok": True,
            "seconds": round(seconds, 3),
            "events": n_events,
            "replicas": n_replicas,
            # Scaling context: aggregate qps scales with replicas only
            # while cores outnumber them (replica processes + client
            # processes + the live writer all need CPU) — a 2-core box
            # records ~1x honestly; the >= 2x claim needs the cores to
            # put the replicas on.
            "cpus": os.cpu_count(),
            "client_threads_per_replica": threads,
            "single": single_res,
            "fleet": {
                "per_replica_qps": [r["qps"] for r in fleet_res],
                "aggregate_qps": aggregate_qps,
                "queries": sum(r["queries"] for r in fleet_res),
                "query_p99_s_max": max(r["query_p99_s"]
                                       for r in fleet_res),
                "errors": sum(r["errors"] for r in fleet_res),
            },
            # The headline: reads scale with replicas (>= 2x at 3
            # replicas on uncontended cores; recorded honestly either
            # way — the arm runs wherever the bench runs).
            "qps_scaling": round(aggregate_qps
                                 / max(single_res["qps"], 1e-9), 3),
            "chaos": {
                "killed_pid": infos[0]["pid"],
                "drain_failovers": fleet_res[0]["drain_failovers"],
                # THE acceptance number: zero failed queries after the
                # drain (survivor errors are post-drain by definition).
                "errors_after_drain": sum(r["errors"]
                                          for r in survivors),
                "victim_errors_after_drain": fleet_res[0]["errors"],
                "relaunched": relaunched_gen is not None,
                "resynced_generation": relaunched_gen,
                "live_generation": live_gen,
            },
            "generations": [gen_start, live_gen],
        }
    finally:
        stop_feed.set()
        # SIGTERM first: the fleet supervisor's handler tears its
        # replica children down with it — a bare SIGKILL would orphan
        # them (no --run-seconds, polling a deleted dir forever).
        for p in procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + 15
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(deadline - time.monotonic(),
                                       0.1))
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
        # Belt and braces: any replica grandchild that survived its
        # supervisor is findable through the port-file pids.
        for dirpath, _dirs, files in os.walk(state_dir):
            for name in files:
                if not name.endswith(".port"):
                    continue
                try:
                    with open(os.path.join(dirpath, name)) as f:
                        os.kill(json.load(f)["pid"], signal.SIGKILL)
                except (OSError, ValueError, KeyError):
                    pass
        shutil.rmtree(state_dir, ignore_errors=True)


def _longtail_churn_stream(windows: int, users_per: int, events_per: int,
                           n_items: int, alpha: float, drift: int,
                           seed: int, window_ms: int):
    """Long-tail stream with genuinely COLD rows, for the spill arm.

    Two production shapes the plain Zipf generator cannot produce
    (reservoir expansion re-touches every history item's row on every
    event, so a persistent user base keeps nearly all rows hot):

    * **user cohorts** — each window has its own fresh user cohort;
      when a cohort leaves, its items stop being re-expanded, and
    * **catalog drift** — the Zipf head rotates ``drift`` item ids per
      window (new content replaces old), so even head rows go cold a
      few windows after the head moves past them.

    Rows touched once and never again are exactly the long-tail items
    the tiered store exists for.
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    ranks = np.arange(1, n_items + 1, dtype=np.float64)
    p = ranks ** -alpha
    p /= p.sum()
    us, its, tss = [], [], []
    for w in range(windows):
        u = (w * users_per
             + rng.integers(0, users_per, events_per)).astype(np.int64)
        i = (rng.choice(n_items, size=events_per, p=p)
             + w * drift) % n_items
        t = w * window_ms + np.sort(rng.integers(0, window_ms, events_per))
        us.append(u)
        its.append(i.astype(np.int64))
        tss.append(t.astype(np.int64))
    return (np.concatenate(us), np.concatenate(its),
            np.concatenate(tss))


def _rescale_arm() -> dict:
    """Autoscale-seam arm (ISSUE 15): pairs/s across the load-forced
    2→4 gang rescale on the churn stream.

    A real 2-worker CPU gang (the autoscaler is gang machinery; the arm
    must not fight the throughput bench for the chip, so it pins
    ``JAX_PLATFORMS=cpu`` like the other subprocess arms) ingests the
    churn stream with delay faults billed into three consecutive window
    walls — the same injection the chaos capstone uses — and a scale-up
    at ``--autoscale-trip-windows 2``. Scale-down is disabled (clear
    threshold beyond the stream) so the arm isolates ONE seam. From
    worker 0's journal: the rescale count, the **seam stall** (drain
    record to the first post-resume window — relaunch + jax init +
    cross-topology restore + first dispatch), **windows-to-recover**
    (post-resume windows until the wall drops back under twice the
    pre-seam median — recompile warm-up), and pre/post/overall pairs/s.
    """
    import tempfile

    windows = int(os.environ.get("BENCH_RESCALE_WINDOWS", 24))
    users_per = int(os.environ.get("BENCH_RESCALE_USERS_PER", 60))
    events_per = int(os.environ.get("BENCH_RESCALE_EVENTS_PER", 800))
    u, i, t = _longtail_churn_stream(
        windows=windows, users_per=users_per, events_per=events_per,
        n_items=4000, alpha=1.07, drift=100, seed=5, window_ms=100)
    work = tempfile.mkdtemp(prefix="bench-rescale-")
    try:
        csv = os.path.join(work, "in.csv")
        with open(csv, "w") as fh:
            for uu, ii, tt in zip(u.tolist(), i.tolist(), t.tolist()):
                fh.write(f"{uu},{ii},{tt}\n")
        jpath = os.path.join(work, "journal.jsonl")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=1")
        proc = subprocess.run(
            [sys.executable, "-m", "tpu_cooccurrence.cli",
             "-i", csv, "-ws", "100", "-s", "0xC0FFEE",
             "--backend", "sparse", "--num-shards", "2",
             "--checkpoint-dir", os.path.join(work, "ck"),
             "--checkpoint-every-windows", "1",
             "--checkpoint-retain", "100",
             "--gang-workers", "2", "--gang-heartbeat-s", "1",
             "--collective-timeout-s", "60", "--restart-delay-ms", "0",
             "--journal", jpath,
             "--degrade", "--degrade-window-wall-s", "2.0",
             "--degrade-trip-windows", "3",
             "--autoscale", "on", "--autoscale-min-workers", "2",
             "--autoscale-max-workers", "4",
             "--autoscale-trip-windows", "2",
             "--autoscale-clear-windows", "100000",
             "--autoscale-cooldown-windows", "2",
             "--inject-fault", "window_fire@0:3:delay_ms:2500",
             "--inject-fault", "window_fire@0:4:delay_ms:2500",
             "--inject-fault", "window_fire@0:5:delay_ms:2500",
             "--fault-state-dir", os.path.join(work, "faults")],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"rescale arm gang exited rc={proc.returncode}: "
                f"{proc.stderr[-500:]}")
        with open(jpath + ".p0") as f:
            recs = [json.loads(line) for line in f if line.strip()]
        wrecs = [r for r in recs if "seq" in r]
        scale = [r for r in recs if "autoscale" in r]
        if not scale or not wrecs:
            raise RuntimeError("rescale arm journal has no seam")
        drain = scale[0]
        pre = [r for r in wrecs if r["seq"] <= drain["window"]]
        post = sorted((r for r in wrecs if r["seq"] > drain["window"]),
                      key=lambda r: r["seq"])
        seam_stall = round(post[0]["wall_unix"] - drain["wall_unix"], 3)
        # Injected delays are load, not measurement: drop the delayed
        # windows (wall over the 2.0 s overload threshold the arm
        # configures) from the pre-seam baseline, or the recovery
        # cutoff would sit above every post-seam window and the metric
        # could never read anything but 0.
        pre_walls = sorted(
            w for w in (r["sample_seconds"] + r["score_seconds"]
                        for r in pre) if w < 2.0)
        baseline = (pre_walls[len(pre_walls) // 2] if pre_walls
                    else 0.05)
        recover = 0
        for r in post:
            if (r["sample_seconds"] + r["score_seconds"]
                    <= max(2 * baseline, 0.05)):
                break
            recover += 1

        def _rate(rs):
            span = rs[-1]["wall_unix"] - rs[0]["wall_unix"]
            return round(sum(r["pairs"] for r in rs) / max(span, 1e-9),
                         1)

        return {
            "ok": True,
            "events": int(len(u)),
            "windows": len(wrecs),
            "rescales": len(scale),
            "from_to": [int(drain["from"]), int(drain["to"])],
            "seam_stall_seconds": seam_stall,
            "windows_to_recover": recover,
            "pairs_per_sec": {
                "pre_seam": _rate(pre) if len(pre) > 1 else None,
                "post_seam": _rate(post) if len(post) > 1 else None,
                "overall": _rate(wrecs),
            },
        }
    finally:
        import shutil

        shutil.rmtree(work, ignore_errors=True)


def _fused_gang_arm() -> dict:
    """Fused-vs-chained gang A/B (ISSUE 16): one launch per worker.

    Three real 2-worker CPU gangs (multi-controller sharded sparse —
    the production topology, pinned to ``JAX_PLATFORMS=cpu`` like the
    other subprocess arms) ingest the same steady-keyed stream (fixed
    event population repeated per window, so the pair population
    stabilizes after window 1 and the fused path owns the steady
    state):

    * ``--fused-window off`` — the chained two-launch baseline;
    * ``--fused-window on`` — the one-launch fused window; per-worker
      dispatch splits and bucket compiles from each worker's journal;
    * ``--fused-window on`` + the ISSUE-15 load-forced 2→4 rescale —
      the **seam-recompile cost**: the first post-seam window must
      route chained (cold plans), and the fresh topology's bucket
      recompile count and seam stall ride the arm.
    """
    import tempfile

    import numpy as np

    windows = int(os.environ.get("BENCH_FUSED_GANG_WINDOWS", 14))
    events_per = int(os.environ.get("BENCH_FUSED_GANG_EVENTS_PER", 500))
    rng = np.random.default_rng(16)
    base_u = rng.integers(0, 8, events_per)
    base_i = rng.integers(0, 64, events_per)
    work = tempfile.mkdtemp(prefix="bench-fused-gang-")
    try:
        csv = os.path.join(work, "in.csv")
        with open(csv, "w") as fh:
            for w in range(windows):
                for uu, ii in zip(base_u.tolist(), base_i.tolist()):
                    fh.write(f"{uu},{ii},{w * 100 + 50}\n")
            fh.write(f"0,9999,{windows * 100 + 50}\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=1")

        def gang_run(tag, fused, seam):
            jpath = os.path.join(work, f"journal-{tag}.jsonl")
            argv = [sys.executable, "-m", "tpu_cooccurrence.cli",
                    "-i", csv, "-ws", "100", "-s", "0xC0FFEE",
                    "--backend", "sparse", "--num-shards", "2",
                    "--gang-workers", "2", "--gang-heartbeat-s", "1",
                    "--collective-timeout-s", "60",
                    "--restart-delay-ms", "0",
                    "--fused-window", fused, "--journal", jpath]
            if seam:
                argv += ["--checkpoint-dir", os.path.join(work, "ck"),
                         "--checkpoint-every-windows", "1",
                         "--checkpoint-retain", "100",
                         "--degrade", "--degrade-window-wall-s", "2.0",
                         "--degrade-trip-windows", "3",
                         "--autoscale", "on",
                         "--autoscale-min-workers", "2",
                         "--autoscale-max-workers", "4",
                         "--autoscale-trip-windows", "2",
                         "--autoscale-clear-windows", "100000",
                         "--autoscale-cooldown-windows", "2",
                         "--inject-fault", "window_fire@0:3:delay_ms:2500",
                         "--inject-fault", "window_fire@0:4:delay_ms:2500",
                         "--inject-fault", "window_fire@0:5:delay_ms:2500",
                         "--fault-state-dir", os.path.join(work, "faults")]
            proc = subprocess.run(argv, env=env, cwd=REPO,
                                  capture_output=True, text=True,
                                  timeout=600)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"fused-gang arm ({tag}) exited "
                    f"rc={proc.returncode}: {proc.stderr[-500:]}")
            out = {}
            for p in ("p0", "p1"):
                with open(f"{jpath}.{p}") as f:
                    out[p] = [json.loads(line) for line in f
                              if line.strip()]
            return out

        def _rate(recs):
            wrecs = sorted((r for r in recs if "seq" in r),
                           key=lambda r: r["seq"])
            span = wrecs[-1]["wall_unix"] - wrecs[0]["wall_unix"]
            return (sum(r["pairs"] for r in wrecs) / max(span, 1e-9),
                    wrecs)

        def _split(wrecs):
            flags = [r.get("fused", 0) for r in wrecs]
            return {"fused": int(sum(flags)),
                    "chained": int(len(flags) - sum(flags)),
                    "bucket_compiles": int(
                        wrecs[-1].get("fused_compiles", 0))}

        chained = gang_run("chained", "off", seam=False)
        fused = gang_run("fused", "on", seam=False)
        c_rate, _ = _rate(chained["p0"])
        f_rate, _ = _rate(fused["p0"])
        per_worker = {p: _split(_rate(fused[p])[1]) for p in fused}
        if not any(s["fused"] for s in per_worker.values()):
            raise RuntimeError(
                "fused-gang arm: no worker ever took the fused path")

        seam = gang_run("seam", "on", seam=True)
        recs0 = seam["p0"]
        scale = [r for r in recs0 if "autoscale" in r]
        if not scale:
            raise RuntimeError("fused-gang seam run never rescaled")
        drain = scale[0]
        _, wrecs = _rate(recs0)
        post = [r for r in wrecs if r["seq"] > drain["window"]]
        return {
            "ok": True,
            "windows": windows,
            "pairs_per_sec_chained": round(c_rate, 1),
            "pairs_per_sec_fused": round(f_rate, 1),
            "vs_chained": round(f_rate / max(c_rate, 1e-9), 3),
            "per_worker_dispatches": per_worker,
            "seam": {
                "from_to": [int(drain["from"]), int(drain["to"])],
                "stall_seconds": round(
                    post[0]["wall_unix"] - drain["wall_unix"], 3),
                # Cold plans: the window after the seam must not fuse.
                "first_post_seam_fused": int(post[0].get("fused", 0)),
                # What the fresh topology paid to re-specialize.
                "recompiles_post_seam": int(
                    post[-1].get("fused_compiles", 0)),
            },
        }
    finally:
        import shutil

        shutil.rmtree(work, ignore_errors=True)


def _checkpoint_arm(sp_u, sp_i, sp_t, window_ms: int = 100) -> dict:
    """Full-vs-incremental checkpoint A/B on the churn stream (PR 12).

    Three ingest runs feed window-aligned slices and poll
    ``state/checkpoint.LAST_COMMIT`` after each, so every generation's
    committed bytes/seconds land in the arm (not just the last):

    * ``full@fine`` vs ``incr@fine`` — same cadence, so the
      commit-bytes ratio is apples-to-apples (the acceptance headline:
      median incremental generation ≪ the full rewrite);
    * ``full@coarse`` — the cadence expensive full commits force in
      practice; its crash-replay tail is what the incremental run's
      fine cadence eliminates.

    Restore-to-first-window is measured for real: restore from the
    newest generation, replay the events ingested after that commit,
    stop at the first fired window.
    """
    import statistics
    import tempfile

    import numpy as np

    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.job import CooccurrenceJob
    from tpu_cooccurrence.observability import LEDGER
    from tpu_cooccurrence.observability.registry import REGISTRY
    from tpu_cooccurrence.state import checkpoint as ckpt

    # The coarse cadence models what expensive full rewrites force in
    # practice: a rational interval scales with commit cost, and the
    # measured full-vs-delta gap is ~10x bytes / ~2x seconds (plus
    # whatever the durable-storage link multiplies it by).
    fine = int(os.environ.get("BENCH_CKPT_EVERY_FINE", 2))
    coarse = int(os.environ.get("BENCH_CKPT_EVERY_COARSE", 16))
    bounds = np.searchsorted(
        sp_t, np.arange(window_ms, int(sp_t[-1]) + 2 * window_ms,
                        window_ms))

    def cfg_kw(d, incremental, every):
        return dict(window_size=window_ms, seed=0xC0FFEE, item_cut=500,
                    user_cut=500, backend=Backend.SPARSE,
                    checkpoint_dir=d, checkpoint_every_windows=every,
                    checkpoint_retain=10_000,
                    checkpoint_incremental=incremental,
                    checkpoint_compact_ratio=0.5)

    # Both arms "crash" at the SAME mid-stream point — deliberately LATE
    # in a coarse checkpoint cycle (the expected-case crash position:
    # uniformly random arrival lands ~coarse/2 windows past the last
    # coarse commit; we pin coarse-2 for determinism): each arm restores
    # from ITS newest commit and replays the input ingested after it —
    # the replay-tail difference IS the cadence difference cheap
    # commits buy.
    crash_at = max((len(bounds) // coarse) * coarse - 2, coarse)

    def ingest(incremental, every):
        import shutil

        REGISTRY.reset()
        LEDGER.reset()
        ckpt.LAST_COMMIT = None
        d = tempfile.mkdtemp(prefix="bench-ckpt-")
        job = CooccurrenceJob(Config(**cfg_kw(d, incremental, every)))
        commits, idx_at = [], []
        crash = None
        last_gen = 0
        lo = 0
        for w, hi in enumerate(bounds):
            if hi > lo:
                job.add_batch(sp_u[lo:hi], sp_i[lo:hi], sp_t[lo:hi])
                lo = hi
            c = ckpt.LAST_COMMIT
            if c is not None and c["gen"] != last_gen:
                last_gen = c["gen"]
                commits.append(dict(c))
                idx_at.append(hi)
            if w == crash_at and crash is None:
                # Snapshot the checkpoint dir as of the crash point.
                shutil.copytree(d, d + "-crash")
                crash = (d + "-crash", idx_at[-1] if idx_at else 0,
                         job.windows_fired)
        job.finish()
        c = ckpt.LAST_COMMIT
        if c is not None and c["gen"] != last_gen:
            commits.append(dict(c))
            idx_at.append(len(sp_u))
        return d, job, commits, crash

    def restore_to_first_window(crash, incremental, every):
        """(first-window seconds, catch-up seconds, replayed windows):
        restore from the crash snapshot, replay the input ingested
        after its newest commit until (a) the first window fires and
        (b) the run is back AT the crash point — (b) is where the fine
        cadence cheap commits buy pays off (shorter replay tail)."""
        snap_dir, resume_idx, fired_at_crash = crash
        REGISTRY.reset()
        LEDGER.reset()
        t0 = time.monotonic()
        job = CooccurrenceJob(Config(**cfg_kw(snap_dir, incremental,
                                              every)))
        job.restore()
        w0 = job.windows_fired
        first_window_s = None
        replayed = 0
        lo = resume_idx
        for hi in bounds:
            if hi <= lo:
                continue
            job.add_batch(sp_u[lo:hi], sp_i[lo:hi], sp_t[lo:hi])
            replayed += 1
            lo = hi
            if first_window_s is None and job.windows_fired > w0:
                first_window_s = time.monotonic() - t0
            if job.windows_fired >= fired_at_crash:
                break
        catch_up_s = time.monotonic() - t0
        job.abort()
        return first_window_s or catch_up_s, catch_up_s, replayed

    d_full, j_full, commits_full, crash_full = ingest(False, fine)
    d_incr, _j_incr, commits_incr, crash_incr = ingest(True, fine)
    d_coarse, _j_coarse, _commits_coarse, crash_coarse = ingest(
        False, coarse)

    full_bytes = [c["bytes"] for c in commits_full]
    delta_bytes = [c["bytes"] for c in commits_incr
                   if c["kind"] == "delta"]
    coarse_restore, coarse_catch, coarse_replay = \
        restore_to_first_window(crash_coarse, False, coarse)
    incr_restore, incr_catch, incr_replay = restore_to_first_window(
        crash_incr, True, fine)
    import shutil

    for path in (d_full, d_incr, d_coarse, crash_full[0],
                 crash_incr[0], crash_coarse[0]):
        shutil.rmtree(path, ignore_errors=True)
    med = statistics.median
    return {
        "events": len(sp_u),
        "windows": j_full.windows_fired,
        "every_fine": fine,
        "every_coarse": coarse,
        "generations_full": len(commits_full),
        "generations_incremental": len(commits_incr),
        "delta_generations": len(delta_bytes),
        "compactions": sum(
            1 for i, c in enumerate(commits_incr[1:], 1)
            if c["kind"] == "full"
            and commits_incr[i - 1]["kind"] == "delta"),
        "chain_len_max": max(
            (c["chain_len"] for c in commits_incr), default=0),
        "full_commit_bytes_median": med(full_bytes) if full_bytes else 0,
        "incr_commit_bytes_median": (med(delta_bytes)
                                     if delta_bytes else 0),
        # The acceptance headline: median incremental generation vs the
        # median full rewrite at the SAME cadence.
        "commit_bytes_ratio": round(
            med(delta_bytes) / max(med(full_bytes), 1), 4)
        if delta_bytes and full_bytes else None,
        "full_commit_seconds_median": round(
            med([c["seconds"] for c in commits_full]), 4)
        if commits_full else 0,
        "incr_commit_seconds_median": round(
            med([c["seconds"] for c in commits_incr
                 if c["kind"] == "delta"]), 4) if delta_bytes else 0,
        # Crash-replay comparison: full checkpoints at the coarse
        # cadence their cost forces vs incremental at the fine one.
        "restore_to_first_window_seconds": {
            "full_coarse": round(coarse_restore, 3),
            "incremental": round(incr_restore, 3),
        },
        "restore_catch_up_seconds": {
            "full_coarse": round(coarse_catch, 3),
            "incremental": round(incr_catch, 3),
        },
        "replay_windows": {
            "full_coarse": coarse_replay,
            "incremental": incr_replay,
        },
    }


def _uplink_per_window(latency: dict) -> float:
    """Mean host->device bytes per fired window, from the run's
    ``cooc_window_uplink_bytes`` histogram summary (TransferLedger-fed:
    the fused-vs-chained uplink comparison the basket format exists
    for)."""
    h = (latency or {}).get("cooc_window_uplink_bytes") or {}
    count = h.get("count") or 0
    return round(h.get("sum", 0.0) / count, 1) if count else 0.0


def _record_onchip(value: float, vs_baseline: float, backend: str,
                   pipeline_depth: int, occupancy: dict,
                   latency: dict = None, degradation: dict = None,
                   fused: dict = None, compression: dict = None,
                   serving: dict = None, spill: dict = None,
                   fused_sparse: dict = None,
                   checkpoint: dict = None,
                   fleet: dict = None,
                   rescale: dict = None,
                   fused_gang: dict = None,
                   regression: dict = None) -> None:
    """Append a successful on-chip measurement to the bench history.

    ``pipeline_depth`` and the per-stage occupancy ride along so the
    overlap win (host-busy% + score-busy% > 100) is visible in the
    trajectory, not just in a single run's stdout; ``latency`` carries
    the per-window p50/p95/p99 summaries for the same reason — tail
    regressions must be visible across PRs; ``degradation`` carries the
    shed/quarantine counters so a throughput number earned by shedding
    load is marked as such in the trajectory; ``fused`` carries the
    fused-vs-chained A/B (pairs/s ratio, dispatch counts, per-window
    uplink bytes) so the one-dispatch window's win is visible in
    ``bench_history.jsonl``.
    """
    entry = {"ts": time.strftime("%Y-%m-%d %H:%M:%S"),
             "pairs_per_sec": value, "vs_baseline": vs_baseline,
             "backend": backend, "pipeline_depth": pipeline_depth,
             "occupancy": occupancy}
    if latency:
        entry["latency"] = latency
    if degradation:
        entry["degradation"] = degradation
    if fused:
        entry["fused"] = fused
    if compression:
        # The PR-7 A/B: uplink_bytes_raw / uplink_bytes_encoded /
        # host_index_rss_bytes and effective-cells-per-byte per dtype,
        # trajectory-visible like the fused arm.
        entry["compression"] = compression
    if serving:
        # The PR-8 storm: qps + query p50/p95/p99 against a live
        # ingesting job — the user-facing metric every later perf PR
        # moves, trajectory-visible like the other arms.
        entry["serving"] = serving
    if spill:
        # The PR-9 tiered-state A/B: effective rows per HBM byte off/on,
        # eviction/promotion counters, hot-row hit rate and the
        # bit-identity verdict — the elastic-state headline numbers.
        entry["spill"] = spill
    if fused_sparse:
        # The PR-11 fused-SPARSE A/B: one-dispatch sparse window vs the
        # chained sparse path (pairs/s ratio, per-window uplink bytes,
        # bucket compile counts) — trajectory-visible like the dense
        # fused arm, CPU-neutrality included.
        entry["fused_sparse"] = fused_sparse
    if checkpoint:
        # The PR-12 incremental-checkpoint A/B: full-vs-delta commit
        # bytes + seconds per generation on the churn stream, and the
        # restore-to-first-window comparison — the commit-bandwidth and
        # restart-replay headline numbers.
        entry["checkpoint"] = checkpoint
    if fleet:
        # The ISSUE-13 serving-fleet storm: 1-vs-N replica qps +
        # aggregate scaling over the live delta log, and the kill-one
        # chaos verdict (errors after drain, relaunch re-sync) —
        # trajectory-visible like every other arm, ok:false when the
        # arm degraded.
        entry["fleet"] = fleet
    if rescale:
        # The ISSUE-15 autoscale seam: pairs/s across the load-forced
        # 2→4 gang rescale (seam stall seconds, windows-to-recover,
        # rescale count) — the cost of scaling must stay trajectory-
        # visible, or a "free" rescale that quietly stalls a minute
        # would never be caught.
        entry["rescale"] = rescale
    if fused_gang:
        # The ISSUE-16 fused-SHARDED A/B: one launch per worker vs the
        # chained two-launch gang on the steady-keyed stream (pairs/s
        # ratio, per-worker dispatch splits, bucket compiles, and the
        # 2→4 seam's recompile cost) — trajectory-visible like the
        # single-process fused arms.
        entry["fused_gang"] = fused_gang
    if regression:
        # The ISSUE-17 regression gate's verdict (bench.regress):
        # whether THIS run's tracked metrics sat inside the history's
        # noise bands when it landed. flatten() skips this subtree, so
        # a recorded verdict never bands future verdicts.
        entry["regression"] = regression
    with open(_HISTORY, "a") as f:
        f.write(json.dumps(entry) + "\n")


def measure() -> None:
    """Child mode: measure on whatever platform this process gets.

    Prints the one JSON line; exit code 0 iff the measurement completed.
    The parent enforces the wall-clock deadline from outside.
    """
    from tpu_cooccurrence.xla_cache import enable_compilation_cache

    enable_compilation_cache()
    if os.environ.get("BENCH_EXPECT_ACCEL"):
        # The parent probed an accelerator; a CPU number here would be
        # reported as a chip number. Fail instead.
        import jax

        if jax.default_backend() == "cpu":
            sys.stderr.write("bench: expected an accelerator but jax "
                             "found only the cpu\n")
            return 1

    from tpu_cooccurrence.io.synthetic import zipfian_interactions

    n_events = int(os.environ.get("BENCH_EVENTS", 400_000))
    n_items = int(os.environ.get("BENCH_ITEMS", 20_000))
    pipeline_depth = int(os.environ.get("BENCH_PIPELINE_DEPTH", 0))
    # Optional flight recorder for the measured runs (BENCH_JOURNAL=path):
    # the three measured runs append to one JSONL, and its path rides the
    # output line so the artifact is findable from the BENCH_* record.
    journal = os.environ.get("BENCH_JOURNAL") or None
    users, items, ts = zipfian_interactions(
        n_events, n_items=n_items, n_users=5_000, alpha=1.1, seed=3,
        events_per_ms=200)

    # Untimed warmup on the full stream: populates the jit caches for every
    # pad bucket the measured run will hit, so the metric is steady-state
    # throughput rather than one-time XLA compile latency.
    run("device", users, items, ts, num_items=n_items, window_ms=100,
        pipeline_depth=pipeline_depth)

    # Median of three measured runs; the occupancy/latency published are
    # the median run's.
    samples = []
    for _ in range(3):
        pairs, elapsed, occupancy, latency, degradation, _, _, _ = run(
            "device", users, items, ts, num_items=n_items, window_ms=100,
            pipeline_depth=pipeline_depth, journal=journal)
        samples.append((pairs / max(elapsed, 1e-9), occupancy, latency,
                        degradation))
    samples.sort(key=lambda s: s[0])
    pairs_per_sec, occupancy, latency, degradation = samples[1]

    # Fused-window A/B arm (--fused-window auto): on a real chip this is
    # the one-dispatch window program; on CPU auto resolves OFF and the
    # arm re-measures the chained path (vs_chained ~ 1.0, zero fused
    # dispatches). Same methodology as the chained arm — its own
    # untimed warmup (the main warmup ran chained, and the fused shape
    # ladder's first compiles must not bill the timed runs), the same
    # journal setting, and the median of three —
    # vs_chained is a headline number, not a smoke probe. Per-window
    # uplink bytes come from the TransferLedger via the uplink
    # histogram, so the basket-vs-COO wire cut is a measured number.
    run("device", users, items, ts, num_items=n_items, window_ms=100,
        pipeline_depth=pipeline_depth, fused_window="auto")
    f_samples = []
    for _ in range(3):
        f_pairs, f_elapsed, _, f_latency, _, f_dispatches, _, _ = run(
            "device", users, items, ts, num_items=n_items, window_ms=100,
            pipeline_depth=pipeline_depth, journal=journal,
            fused_window="auto")
        f_samples.append((f_pairs / max(f_elapsed, 1e-9), f_latency,
                          f_dispatches))
    f_samples.sort(key=lambda s: s[0])
    f_rate, f_latency, f_dispatches = f_samples[1]
    fused_info = {
        "mode": "auto",
        "pairs_per_sec": round(f_rate, 1),
        "vs_chained": round(f_rate / max(pairs_per_sec, 1e-9), 3),
        "uplink_bytes_per_window": _uplink_per_window(f_latency),
        "chained_uplink_bytes_per_window": _uplink_per_window(latency),
        **f_dispatches,
    }

    # Compression A/B arm (sparse backend): raw int32 slab + raw wire vs
    # the PR-7 compressed default (int16 cells with wide-promotion +
    # packed delta/bit-packed uplink + bitmap row index). Same
    # methodology as the fused arm — per-arm untimed warmup, median of
    # three — on a truncated stream (the sparse CPU path is slower than
    # dense and the arm measures *wire/footprint* ratios, which converge
    # long before throughput medians do). Ledger-measured: the uplink
    # cut and the effective-cells-per-slab-byte pair are the tentpole's
    # headline numbers.
    comp_events = min(len(users),
                      int(os.environ.get("BENCH_COMPRESS_EVENTS", 120_000)))
    cu, ci, ct = users[:comp_events], items[:comp_events], ts[:comp_events]

    def _comp_arm(wire, cell):
        run("sparse", cu, ci, ct, num_items=n_items, window_ms=100,
            wire_format=wire, cell_dtype=cell)  # warmup (compiles)
        arm = []
        for _ in range(3):
            c_pairs, c_elapsed, _, _, _, _, c_wire, _ = run(
                "sparse", cu, ci, ct, num_items=n_items, window_ms=100,
                wire_format=wire, cell_dtype=cell)
            arm.append((c_pairs / max(c_elapsed, 1e-9), c_wire))
        arm.sort(key=lambda s: s[0])
        return arm[1]

    raw_rate, raw_wire = _comp_arm("raw", "int32")
    pkd_rate, pkd_wire = _comp_arm("packed", "int16")

    def _cells_per_byte(w):
        return round(w["slab_live_cells"] / max(w["slab_device_bytes"], 1),
                     4)

    windows_pkd = max(pkd_wire["windows"], 1)
    compression = {
        "events": comp_events,
        "pairs_per_sec_raw": round(raw_rate, 1),
        "pairs_per_sec_packed": round(pkd_rate, 1),
        "vs_raw": round(pkd_rate / max(raw_rate, 1e-9), 3),
        # Ledger-measured per-window uplink pair: what the raw layout
        # would have shipped vs what the packed encoder actually shipped
        # (same run, so the two describe identical windows).
        "uplink_bytes_raw": round(
            pkd_wire["uplink_bytes_raw"] / windows_pkd, 1),
        "uplink_bytes_encoded": round(
            pkd_wire["uplink_bytes_encoded"] / windows_pkd, 1),
        "uplink_cut": round(
            pkd_wire["uplink_bytes_raw"]
            / max(pkd_wire["uplink_bytes_encoded"], 1), 2),
        "host_index_rss_bytes": pkd_wire["host_index_rss_bytes"],
        "host_index_rss_bytes_raw_arm": raw_wire["host_index_rss_bytes"],
        "effective_cells_per_byte": {
            "int32": _cells_per_byte(raw_wire),
            "int16": _cells_per_byte(pkd_wire),
        },
    }

    # Fused-SPARSE A/B arm (--fused-window auto on the sparse backend):
    # chained vs fused over the same truncated stream as the compression
    # arm, compressed defaults on BOTH arms (int16 cells + packed wire —
    # the fused program decodes the packed uplink in its prologue, so
    # the two levers compose under measurement). On a real chip this is
    # the one-dispatch sparse window; on CPU auto resolves OFF and the
    # arm re-measures the chained path — the CPU-neutrality check
    # (vs_chained ~ 1.0, zero fused dispatches), exactly like the dense
    # fused arm. Per-arm untimed warmup, median of three; per-window
    # uplink bytes ride the ledger-fed histogram, bucket compile counts
    # ride the shape-specialization gauge.
    def _sparse_fused_arm(fused):
        run("sparse", cu, ci, ct, num_items=n_items, window_ms=100,
            wire_format="packed", cell_dtype="int16",
            fused_window=fused)  # warmup (compiles)
        arm = []
        for _ in range(3):
            s_pairs, s_elapsed, _, s_lat, _, s_disp, s_wire, _ = run(
                "sparse", cu, ci, ct, num_items=n_items, window_ms=100,
                wire_format="packed", cell_dtype="int16",
                fused_window=fused)
            arm.append((s_pairs / max(s_elapsed, 1e-9), s_lat, s_disp,
                        s_wire))
        arm.sort(key=lambda s: s[0])
        return arm[1]

    sc_rate, sc_lat, _sc_disp, sc_wire = _sparse_fused_arm("off")
    sf_rate, sf_lat, sf_disp, sf_wire = _sparse_fused_arm("auto")
    sf_windows = max(sf_wire["windows"], 1)
    fused_sparse = {
        "mode": "auto",
        "pairs_per_sec_chained": round(sc_rate, 1),
        "pairs_per_sec_fused": round(sf_rate, 1),
        "vs_chained": round(sf_rate / max(sc_rate, 1e-9), 3),
        "uplink_bytes_per_window": _uplink_per_window(sf_lat),
        "chained_uplink_bytes_per_window": _uplink_per_window(sc_lat),
        "uplink_bytes_encoded_per_window": round(
            sf_wire["uplink_bytes_encoded"] / sf_windows, 1),
        **sf_disp,
    }

    # Tiered-state (spill) A/B arm (PR 9): the SAME long-tail churn
    # stream through the sparse backend with tiering off vs on. The
    # headline pair is deterministic footprint, not timing — effective
    # rows per HBM byte (rows managed / device slab bytes; rows managed
    # is identical across arms by construction) and the hot-row hit
    # rate — so one run per arm suffices; and the results digest pins
    # the bit-identity claim (spill/promote is exact movement). The
    # stream mixes user-cohort churn with catalog drift: the two
    # production shapes that actually create cold rows (see
    # _longtail_churn_stream).
    sp_windows = int(os.environ.get("BENCH_SPILL_WINDOWS", 60))
    sp_u, sp_i, sp_t = _longtail_churn_stream(
        windows=sp_windows, users_per=150, events_per=2500,
        n_items=60_000, alpha=1.07, drift=400, seed=11, window_ms=100)
    sp_thr = int(os.environ.get("BENCH_SPILL_THRESHOLD", 4))

    def _spill_arm(threshold, frac):
        s_pairs, s_elapsed, _, _, _, _, s_wire, s_spill = run(
            "sparse", sp_u, sp_i, sp_t, num_items=60_000, window_ms=100,
            spill_threshold_windows=threshold,
            spill_target_hbm_frac=frac)
        return s_pairs / max(s_elapsed, 1e-9), s_wire, s_spill

    off_rate, off_wire, off_spill = _spill_arm(0, 0.5)
    on_rate, on_wire, on_spill = _spill_arm(sp_thr, 0.0)

    def _rows_per_byte(sp, w):
        return sp["rows_managed"] / max(w["slab_device_bytes"], 1)

    spill_info = {
        "events": len(sp_u),
        "threshold_windows": sp_thr,
        "rows_managed": on_spill["rows_managed"],
        "slab_device_bytes_off": off_wire["slab_device_bytes"],
        "slab_device_bytes_on": on_wire["slab_device_bytes"],
        "effective_rows_per_hbm_byte": {
            "off": round(_rows_per_byte(off_spill, off_wire), 8),
            "on": round(_rows_per_byte(on_spill, on_wire), 8),
        },
        "rows_per_hbm_byte_gain": round(
            _rows_per_byte(on_spill, on_wire)
            / max(_rows_per_byte(off_spill, off_wire), 1e-12), 3),
        "spill_evictions_total": on_spill["evictions_total"],
        "promotions_total": on_spill["promotions_total"],
        "hot_row_hit_rate": round(
            1.0 - on_spill["promotions_total"]
            / max(on_spill["touches_total"], 1), 4),
        "arena_bytes": on_spill["arena_bytes"],
        "resident_rows": on_spill["resident_rows"],
        "pairs_per_sec_off": round(off_rate, 1),
        "pairs_per_sec_on": round(on_rate, 1),
        # The whole point: exact movement, never approximation.
        "identical_topk": (on_spill["results_digest"]
                           == off_spill["results_digest"]),
    }

    # Incremental-checkpoint arm (PR 12): the SAME long-tail churn
    # stream (cold rows = churn a fraction of accumulated state — the
    # regime incremental commits exist for), full-vs-incremental at the
    # same fine cadence for the commit-bytes ratio, plus the
    # restore-to-first-window comparison: a full-checkpoint run is
    # forced onto a COARSE cadence by its commit cost, so a crash
    # replays more input; the incremental run checkpoints every other
    # window and resumes almost immediately.
    try:
        ckpt_info = _checkpoint_arm(sp_u, sp_i, sp_t, window_ms=100)
    except Exception as exc:
        ckpt_info = {"error": f"{type(exc).__name__}: {exc}"}

    # Query-storm arm (PR-8 serving plane): closed-loop qps + query
    # latency tails from a keep-alive HTTP pool against a live ingesting
    # job (million-user id space). Host-side plane, so the arm runs
    # identically on the chip and on the CPU; it must never kill
    # the throughput bench it rides along with.
    try:
        serving_storm = query_storm()
    except Exception as exc:
        # ok: false — the degraded arm must be RECORDED as degraded in
        # bench JSON + history, not read as a silently absent block.
        serving_storm = {"ok": False,
                         "error": f"{type(exc).__name__}: {exc}"}

    # Replicated-serving fleet arm (ISSUE 13): 1-vs-3 stateless read
    # replicas (cooc-replica subprocesses) tailing the same live
    # incremental-checkpoint delta log, client subprocesses hammering
    # each replica (client CPU out of this process's GIL so the fleet's
    # aggregate is server-bound), plus the kill-one chaos case: a
    # replica dies mid-storm, its client drains to a survivor with zero
    # failed queries after the drain, and the fleet supervisor's
    # relaunched replica re-syncs from checkpoint + delta tail to the
    # live generation.
    try:
        fleet_storm = _fleet_storm()
    except Exception as exc:
        fleet_storm = {"ok": False,
                       "error": f"{type(exc).__name__}: {exc}"}

    # Autoscale-seam arm (ISSUE 15): pairs/s across a load-forced 2→4
    # gang rescale — seam stall seconds, windows-to-recover and the
    # rescale count, from the gang's own journal.
    try:
        rescale_info = _rescale_arm()
    except Exception as exc:
        rescale_info = {"ok": False,
                        "error": f"{type(exc).__name__}: {exc}"}

    # Fused-gang arm (ISSUE 16): chained-vs-fused A/B at
    # --gang-workers 2 — one launch per worker, per-worker dispatch
    # splits, bucket compiles, and the 2→4 seam-recompile cost.
    try:
        fused_gang_info = _fused_gang_arm()
    except Exception as exc:
        fused_gang_info = {"ok": False,
                           "error": f"{type(exc).__name__}: {exc}"}

    # Baseline: the exact host (oracle) backend on the same stream, cached
    # in .bench_baseline.json on first run.
    baseline_path = os.path.join(REPO, ".bench_baseline.json")
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            baseline = json.load(f)["pairs_per_sec"]
    else:
        b_pairs, b_elapsed, _, _, _, _, _, _ = run("oracle", users, items, ts,
                                             num_items=n_items,
                                             window_ms=100)
        baseline = b_pairs / max(b_elapsed, 1e-9)
        with open(baseline_path, "w") as f:
            json.dump({"pairs_per_sec": baseline}, f)

    import jax

    backend = jax.default_backend()  # what the measured runs actually used
    out = {
        "metric": "item-pairs/sec (Zipfian basket stream, device backend)",
        "value": round(pairs_per_sec, 1),
        "unit": "pairs/s",
        "vs_baseline": round(pairs_per_sec / max(baseline, 1e-9), 3),
        "pipeline_depth": pipeline_depth,
        "occupancy": occupancy,
        "latency": latency,
        "degradation": degradation,
        "fused": fused_info,
        "fused_sparse": fused_sparse,
        "compression": compression,
        "spill": spill_info,
        "checkpoint": ckpt_info,
        "serving": serving_storm,
        "fleet": fleet_storm,
        "rescale": rescale_info,
        "fused_gang": fused_gang_info,
    }
    if journal:
        out["journal"] = journal
    # Regression gate (bench.regress, ISSUE-17): band this run's
    # tracked metrics against the same-backend history BEFORE the run
    # is appended to it; the verdict rides the bench JSON and (on-chip)
    # the history entry itself. Gate failures never fail the bench —
    # the verify skill's post-bench step is where exit 1 bites.
    try:
        from tpu_cooccurrence.bench import regress as _regress

        candidate = dict(out)
        candidate["pairs_per_sec"] = out["value"]
        candidate["backend"] = backend
        out["regression"] = _regress.evaluate(
            _regress.read_history(_HISTORY), candidate)
    except Exception as exc:  # pragma: no cover - defensive
        out["regression"] = {"ok": True, "error": str(exc)}
    dev = jax.devices()[0]
    out["platform"] = dev.platform
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    if backend != "cpu":
        _record_onchip(out["value"], out["vs_baseline"], backend,
                       pipeline_depth, occupancy, latency, degradation,
                       fused_info, compression, serving_storm, spill_info,
                       fused_sparse, ckpt_info, fleet_storm,
                       rescale_info, fused_gang_info,
                       regression=out.get("regression"))
    print(json.dumps(out))


#: Known-benign XLA stderr noise: the CPU AOT machine-feature mismatch
#: warning ("Target machine feature +prefer-no-gather is not supported
#: ...", plus its feature-list and SIGILL-caveat lines) that every CPU
#: measurement child emits and that previously flooded the captured
#: bench tail in BENCH_r0*.json, burying the `parsed` context. A line
#: containing any of these markers is withheld from the live stderr
#: stream and surfaced instead as a count + sample in the JSON line's
#: ``stderr_noise`` debug field — suppressed from the tail, not lost.
BENIGN_STDERR_MARKERS = (
    "+prefer-no-gather",
    "Machine type used for XLA:CPU compilation",
    "This could lead to execution errors such as SIGILL",
)


def _is_benign_stderr(line: str) -> bool:
    return any(m in line for m in BENIGN_STDERR_MARKERS)


def _pump_stderr(pipe, noise: dict) -> None:
    """Forward a child's stderr line-by-line (hang diagnostics must
    stay live), withholding the known-benign XLA noise into ``noise``."""
    for line in pipe:
        if _is_benign_stderr(line):
            noise["lines"] += 1
            if noise["sample"] is None:
                noise["sample"] = line.strip()[:160]
            continue
        sys.stderr.write(line)
        sys.stderr.flush()


def _run_child(env: dict, deadline_s: float):
    """One measurement child under a hard deadline. Returns the JSON
    line it printed, or None on timeout/failure/garbage output.

    stderr streams through live (jax warnings, job logs, hang
    diagnostics — same discipline as the supervisor's), minus the
    known-benign XLA noise (``BENIGN_STDERR_MARKERS``), which is folded
    into the JSON line's ``stderr_noise`` debug field instead of
    flooding whatever captured this process's tail.
    """
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--measure"],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
    except OSError:
        return None
    noise = {"lines": 0, "sample": None}
    out_buf = []
    pump = threading.Thread(target=_pump_stderr,
                            args=(proc.stderr, noise), daemon=True)
    # stdout is drained on a thread too: the deadline must bound the
    # child's WALL time (proc.wait below), and a main-thread read() on a
    # hung child would block past any deadline.
    drain = threading.Thread(target=lambda: out_buf.append(
        proc.stdout.read()), daemon=True)
    pump.start()
    drain.start()
    try:
        rc = proc.wait(timeout=deadline_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        return None
    pump.join(timeout=10)
    drain.join(timeout=10)
    out = out_buf[0] if out_buf else ""
    if rc != 0:
        return None
    for line in reversed((out or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if noise["lines"]:
                obj["stderr_noise"] = {"suppressed_lines": noise["lines"],
                                       "sample": noise["sample"]}
                line = json.dumps(obj)
            return line
    return None


def main() -> None:
    # --pipeline-depth N (default 0 = serial): the execution-mode knob
    # under measurement; flows to the measurement children via env so the
    # parent stays argv-compatible with the driver's bare invocation.
    argv = sys.argv[1:]
    if "--storm-client" in argv:
        # Fleet-arm client child: hammer one replica URL, fail over to
        # an optional survivor URL on connection loss, print one JSON
        # line. Kept out of the parent so client CPU never shares a GIL
        # with orchestration (or with another client).
        i = argv.index("--storm-client")
        try:
            url = argv[i + 1]
            seconds = float(argv[i + 2])
            threads = int(argv[i + 3])
            fallback = argv[i + 4] if len(argv) > i + 4 else None
        except (IndexError, ValueError):
            sys.stderr.write("bench: --storm-client URL SECONDS THREADS "
                             "[FALLBACK_URL]\n")
            return 2
        print(json.dumps(storm_client(url, seconds, threads, fallback)))
        return 0
    if "--pipeline-depth" in argv:
        i = argv.index("--pipeline-depth")
        try:
            depth = int(argv[i + 1])
        except (IndexError, ValueError):
            sys.stderr.write("bench: --pipeline-depth needs an integer\n")
            return 2
        if depth not in (0, 1, 2):
            # Fail here, not minutes later as an opaque all-children-
            # failed artifact after the backend probe has run.
            sys.stderr.write(
                f"bench: --pipeline-depth must be 0, 1 or 2, got {depth}\n")
            return 2
        os.environ["BENCH_PIPELINE_DEPTH"] = str(depth)
    if "--measure" in argv:
        return measure()

    # Parent: never imports jax; the probe child exits before the
    # measurement child starts, so exactly one process holds the chip.
    env = dict(os.environ)
    env.pop("BENCH_EXPECT_ACCEL", None)
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        line = _run_child(env, CPU_DEADLINE_S)  # a CPU run, asked for
    else:
        probed = probe_backend(240.0)
        if probed in (None, "cpu"):
            line = None
            sys.stderr.write(
                f"bench: expected an accelerator, the probe found "
                f"{probed or 'none (hung or crashed)'}; set "
                f"JAX_PLATFORMS=cpu for a CPU run\n")
        else:
            line = _run_child(dict(env, BENCH_EXPECT_ACCEL="1"),
                              ACCEL_DEADLINE_S)
    if line is not None:
        print(line)
        return 0
    print(json.dumps({
        "metric": "item-pairs/sec (Zipfian basket stream, device backend)",
        "value": 0.0, "unit": "pairs/s", "vs_baseline": 0.0,
        "platform": "error", "error": "the measurement did not complete"}))
    return 1


if __name__ == "__main__":
    sys.exit(main())
