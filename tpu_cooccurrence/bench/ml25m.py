"""Full MovieLens-25M-shape assessment: the <60 s north-star check.

BASELINE.json's second target: "full MovieLens-25M item-item matrix in
<60 s on a TPU v5e-8". This runner measures it honestly instead of
extrapolating from the 500k-event stand-in slice (VERDICT round 1, weak
item 3):

* the FULL 25M-event, 59k-item, 162.5k-user shape (real ratings.csv when
  ``MOVIELENS_25M`` points at it; otherwise the shape-matched Zipfian
  stand-in — labeled), streamed through the production job in bounded
  chunks, sliding windows + top-k (benchmark config 3's setup);
* the backend that carries that vocabulary on one chip: dense device,
  reference-style int16 counts (7.0 GB HBM at 59,047 items);
* a stated, formula-explicit projection to v5e-8 from the single-chip
  measurement: the sharded backend splits every device stage (scatter
  update, gather+LLR+top-K) across 8 item-sharded chips with one psum
  per window (`parallel/sharded.py`), while host-side sampling is not
  sharded in the single-controller runtime — so
  ``projected = host_seconds + device_seconds / 8 + windows * psum_lat``.
  Host and device seconds are separated by the job's per-window step
  timer. The psum term's point estimate is the stated on-pod allowance
  (PSUM_LATENCY_DEFAULT_S — ICI all-reduce of the [59k] row-sum vector
  is sub-millisecond on v5e); the reported ``[low, high]`` range uses
  zero exposed latency as the floor and twice the point estimate as the
  ceiling — both constants and their provenance are in the JSON.

``--host-only`` runs the identical stream through sampling with a null
scorer — the host-side floor any backend pays; useful on CPU-only boxes
(this container's 1 core) and for separating the two budget halves.

Usage:
    python -m tpu_cooccurrence.bench.ml25m [--events N] [--host-only]
"""

from __future__ import annotations

import argparse
import contextlib
import json
import time

from ..config import Backend, Config
from ..job import CooccurrenceJob
from ..metrics import OBSERVED_COOCCURRENCES
from ..state.results import TopKBatch
from .configs import _movielens_25m

# Fallback per-window ICI all-reduce latency for the v5e-8 projection
# when no measured sharded overhead exists yet: one psum of an int32
# [59k] row-sum vector (~250 KB) per fired window. v5e ICI moves that in
# tens of microseconds; 200 us is a deliberately fat allowance for
# launch + sync skew.
PSUM_LATENCY_DEFAULT_S = 200e-6


def _latest_row(name: str, required_key: str):
    """Latest usable TPU_ROUND2.jsonl row of ``name`` carrying the key
    (``onchip_row``: ok and not tagged with a non-TPU platform — a CPU
    smoke row must not become a projection constant)."""
    from .tpu_round2 import OUT, onchip_row

    latest = None
    try:
        with open(OUT) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if (obj.get("name") == name and onchip_row(obj)
                        and required_key in obj):
                    latest = obj
    except OSError:
        pass
    return latest


def measured_sharded_overhead():
    """(seconds_per_window, source) for the projection's point estimate
    (VERDICT r4, Next #7): the sharded-pallas-1chip stage times the SAME
    windows through the unsharded sparse scorer and a 1-device-mesh
    sharded one on the real chip; the difference is the measured
    shard_map+psum wrapper cost per window at the config-3 row-sum
    scale. Present => the projection cites zero assumed constants.
    Returns (None, reason) before any capture."""
    latest = _latest_row("sharded-pallas-1chip",
                         "sharded_overhead_ms_per_window")
    if latest is not None:
        return (latest["sharded_overhead_ms_per_window"] / 1e3,
                "measured 1-chip shard_map+psum overhead per window "
                f"({latest.get('ts', '?')})")
    return None, "no sharded-pallas-1chip capture yet"

N_EVENTS_FULL = 25_000_000


class NullScorer:
    """Swallows pair deltas: isolates the host-side (sampling) floor."""

    last_dispatched_rows = 0

    def __init__(self, top_k: int) -> None:
        self.top_k = top_k

    def process_window(self, ts, pairs) -> TopKBatch:
        return TopKBatch.empty(self.top_k)

    def flush(self) -> TopKBatch:
        return TopKBatch.empty(self.top_k)


@contextlib.contextmanager
def sparse_device_mocked():
    """Patch the sparse scorer's device dispatches to host no-ops.

    ``--host-only --backend sparse`` then measures the TRUE sparse host
    floor — sampling + windowing + slab index + update/meta packing —
    which NullScorer (sampling only) understates. Each stub returns its
    donated inputs unchanged, so no device work is enqueued and the
    scorer's host-side control flow runs exactly as in production.
    (Round 3's 25.2 s measurement used ad-hoc mocks that never landed
    in-repo; this makes the number reproducible.)
    """
    import tpu_cooccurrence.state.sparse_scorer as ss

    saved = {}

    def patch(name, fn):
        saved[name] = getattr(ss, name)
        setattr(ss, name, fn)

    patch("_apply_update",
          lambda cnt, dst, rs, upd, bounds: (cnt, dst, rs))
    patch("_apply_moves_update",
          lambda cnt, dst, rs, mv, upd, bounds, L: (cnt, dst, rs))
    patch("_score_into_table", lambda tbl, *a, **k: tbl)
    patch("_score_window_into_table", lambda tbl, *a, **k: tbl)
    patch("_compact_gather", lambda cnt, dst, gmap, cap: (cnt, dst))
    try:
        yield
    finally:
        for name, fn in saved.items():
            setattr(ss, name, fn)


def measure_full(n_events: int, host_only: bool, chunk: int = 2_000_000,
                 backend: Backend = Backend.DEVICE) -> dict:
    """The MEASUREMENT half of :func:`run_full`: run the stream, return
    the base result row plus the unrounded stage seconds the projection
    needs. Split from :func:`project_v5e8` so consumers that only vary
    the projection *constants* (the capture file) can share one
    measured run — the projection is arithmetic over this dict and the
    tracked JSONL, never a re-measurement.

    ``backend``: DEVICE is the dense int16 carrier; SPARSE scores only
    nonzero cells (~60x fewer at this shape — 54M pairs over a 59k vocab
    leave most of each dense row empty) at the price of host index work,
    so the chip decides which carries config 3 (bench/tpu_round2.py
    measures both)."""
    users, items, ts, standin_model = _movielens_25m(limit=n_events)
    n = len(users)
    dense = backend == Backend.DEVICE
    cfg = Config(window_size=4000, window_slide=1000, seed=3,
                 item_cut=500, user_cut=500, backend=backend,
                 count_dtype="int16" if dense else "int32",
                 num_items=int(items.max()) + 1 if dense else 0)
    # --host-only: sampling-only floor (NullScorer) for the dense
    # carrier; for the sparse carrier the honest floor also includes
    # the slab index + packing host work, so the REAL scorer runs with
    # its device dispatches stubbed to no-ops.
    mock_sparse = host_only and not dense
    ctx = sparse_device_mocked() if mock_sparse else contextlib.nullcontext()
    with ctx:
        job = CooccurrenceJob(
            cfg, scorer=(NullScorer(cfg.top_k)
                         if host_only and not mock_sparse else None))
        start = time.monotonic()
        for lo in range(0, n, chunk):
            hi = min(lo + chunk, n)
            job.add_batch(users[lo:hi], items[lo:hi], ts[lo:hi])
        job.finish()
        seconds = time.monotonic() - start
    pairs = job.counters.get(OBSERVED_COOCCURRENCES)
    summary = job.step_timer.summary()
    host_s = summary["sample_seconds"]
    device_s = summary["score_seconds"]
    windows = summary["windows"]
    out = {
        "name": ("ml25m-full" + ("-hostonly" if host_only else "")
                 + ("" if dense else "-sparse")),
        "backend": ("sparse-device-mocked" if mock_sparse
                    else "null" if host_only else cfg.backend.value),
        "events": n,
        "pairs": int(pairs),
        "windows": int(windows),
        "seconds": round(seconds, 2),
        "pairs_per_sec": round(pairs / max(seconds, 1e-9), 1),
        "host_sample_seconds": round(host_s, 2),
        "device_score_seconds": round(device_s, 2),
        "synthetic_standin": standin_model is not None,
        **({"standin_model": standin_model} if standin_model else {}),
    }
    return {"out": out, "host_s": host_s, "device_s": device_s,
            "windows": windows, "seconds": seconds,
            "host_only": host_only}


def project_v5e8(measured: dict) -> dict:
    """The PROJECTION half of :func:`run_full`: fold the v5e-8
    projection (constants from the tracked capture JSONL, arithmetic
    over the measured stage seconds) into a copy of the measured row.
    Host-only floors carry no projection, exactly as before."""
    out = dict(measured["out"])
    host_s = measured["host_s"]
    device_s = measured["device_s"]
    windows = measured["windows"]
    seconds = measured["seconds"]
    if not measured["host_only"]:
        overhead_s, overhead_src = measured_sharded_overhead()
        # Point estimate: the measured 1-chip shard_map+psum wrapper
        # cost per window when a capture exists (VERDICT r4 Next #7 —
        # zero assumed constants), else the stated on-pod allowance.
        # The upper bound doubles it; the lower bound is collectives
        # fully overlapped with compute.
        if overhead_s is not None:
            psum_s = overhead_s
            point_src = overhead_src
        else:
            psum_s = PSUM_LATENCY_DEFAULT_S
            point_src = "assumed on-pod allowance (point estimate)"
        projected = host_s + device_s / 8 + windows * psum_s
        proj_low = host_s + device_s / 8
        proj_high = host_s + device_s / 8 + windows * 2 * psum_s
        out["v5e8_projected_seconds"] = round(projected, 2)
        out["v5e8_projected_range"] = [round(proj_low, 2),
                                       round(proj_high, 2)]
        out["psum_latency_s"] = psum_s
        out["psum_latency_source"] = point_src
        out["psum_latency_upper_s"] = 2 * psum_s
        out["psum_latency_upper_source"] = "2x the point estimate"
        out["v5e8_projection"] = (
            "host + device/8 + windows*psum: "
            f"{host_s:.1f} + {device_s:.1f}/8 + "
            f"{windows}*{psum_s*1e6:.0f}us "
            f"[upper: {2 * psum_s * 1e6:.0f}us]")
        out["under_60s_single_chip"] = seconds < 60
        out["under_60s_v5e8_projected"] = projected < 60
        # Secondary projection: at the calibrated workload the HOST term
        # binds (round 5: 52 s dense floor vs device/8), and the
        # framework's --partition-sampling splits exactly that term
        # across the pod host's worker processes (u % P partitioning;
        # correctness pinned by tests/test_multihost.py and the
        # randomized multihost sweeps). Its LINEAR host scaling is
        # arithmetic, not a measurement — this box has one core — so
        # the row is labeled and kept separate from the primary
        # projection, which assumes no host partitioning at all.
        out["v5e8_partitioned_projected_seconds"] = round(
            host_s / 8 + device_s / 8 + windows * psum_s, 2)
        out["v5e8_partitioned_note"] = (
            "host/8 + device/8 + windows*psum under --partition-sampling"
            " (8 worker processes on the pod host); host scaling assumed"
            " linear — unmeasurable on this 1-core box")
    return out


def run_full(n_events: int, host_only: bool, chunk: int = 2_000_000,
             backend: Backend = Backend.DEVICE) -> dict:
    """Measure + project in one call (the CLI entry point's form)."""
    return project_v5e8(measure_full(n_events, host_only, chunk, backend))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--events", type=int, default=N_EVENTS_FULL)
    ap.add_argument("--host-only", action="store_true",
                    help="measure the host floor only (dense: sampling "
                         "via a null scorer; sparse: the real scorer "
                         "with device dispatches stubbed)")
    ap.add_argument("--backend", type=Backend, default=Backend.DEVICE,
                    choices=[Backend.DEVICE, Backend.SPARSE],
                    metavar="{device,sparse}")
    args = ap.parse_args()
    print(json.dumps(run_full(args.events, args.host_only,
                              backend=args.backend)), flush=True)


if __name__ == "__main__":
    main()
