#!/usr/bin/env python3
"""Chip smoke: the production streaming path end to end on a TPU.

Each phase drives one benchmark workload (``tpu_cooccurrence/bench/
configs.py``) through ``CooccurrenceJob(Config(...))`` -- the path
``cli.main`` runs -- in this process, which owns the chip. Each phase is
checked against the float64 ``--backend oracle`` run of the same events:
the three cross-backend counters exactly, top-K scores to 1e-4 relative
(ids where the score gaps leave no near-tie).

    python chip_smoke.py             # one chip: dense, dense-fused,
                                     # sparse, sparse-fused
    python chip_smoke.py --chips 4   # only sparse --num-shards 4

One JSON line per phase, then as the last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Off the chip (``JAX_PLATFORMS=cpu``), on a failed phase or on a parity
miss it exits non-zero and never prints ``ok``.

The oracle runs are host-only; they run in CPU-pinned child processes
(``JAX_PLATFORMS=cpu``) beside the device phases, so no child ever
touches the chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

#: One-chip phases in run order. Dense runs first, so its peak HBM is
#: the int16 C alone.
PHASES = ("dense", "dense-fused", "sparse", "sparse-fused")
#: The --chips 4 phase: config 4 on the sharded sparse mesh.
SHARDED = "sharded"

#: Events (baskets for config 5) per workload. Config 5 is cut from
#: 20,000 baskets: the parity reference here is the program's own
#: ``--backend oracle`` run (``OracleJob``), which rescores every row
#: of the catalog it touches each window, so its cost grows with the
#: square of the basket count (133 s at 4,000 on one host core) and
#: 20,000 would not fit the run's time limit. (The benchmark's
#: reference, ``benchmark/reference/oracle.py``, scores a sample of rows
#: and is linear in the baskets.)
SIZES = {"config3": 500_000, "config4": 200_000, "config5": 6_000}

#: Which workload each phase streams, and its device-side settings.
WORKLOAD = {"dense": "config3", "dense-fused": "config5",
            "sparse": "config4", "sparse-fused": "config4",
            SHARDED: "config4"}
SETTINGS = {"dense": {"pallas": "auto"},
            "dense-fused": {"fused_window": "on"},
            "sparse": {},
            "sparse-fused": {"fused_window": "on"},
            SHARDED: {"num_shards": 4}}

#: The counters a device backend must reproduce exactly.
EXACT_COUNTERS = ("ItemRowRescorerRescoredItems",
                  "RowSumProcessWindowRowSum",
                  "UserInteractionCounterObservedCooccurrences")
RTOL = 1e-4
ATOL = 1e-3
#: Scores closer than this may swap order between float32 and float64.
TIE_GAP = 1e-2


def workload(key: str, size: Optional[int] = None, oracle: bool = False):
    """The benchmark workload ``key`` at ``size`` events (baskets)."""
    from tpu_cooccurrence.bench import configs
    from tpu_cooccurrence.config import Backend

    size = size or SIZES[key]
    if key == "config3":
        return configs.config3_workload(
            Backend.ORACLE if oracle else Backend.DEVICE, limit=size)
    if key == "config4":
        return configs.config4_workload(
            Backend.ORACLE if oracle else Backend.SPARSE, n_events=size)
    if key == "config5":
        return configs.config5_workload(
            Backend.ORACLE if oracle else Backend.DEVICE, n_baskets=size)
    raise ValueError(f"unknown workload {key!r}")


def _counters(job) -> Dict[str, int]:
    return {name: int(job.counters.get(name)) for name in EXACT_COUNTERS}


def oracle_reference(key: str, size: Optional[int] = None) -> dict:
    """Counters and final top-K of the float64 oracle backend."""
    from tpu_cooccurrence.job import CooccurrenceJob

    w = workload(key, size, oracle=True)
    start = time.monotonic()
    job = CooccurrenceJob(w.config)
    job.add_batch(w.users, w.items, w.ts)
    job.finish()
    return {"counters": _counters(job),
            "latest": {k: list(v) for k, v in job.latest.snapshot().items()},
            "wall_s": time.monotonic() - start}


def _pin_cpu() -> None:
    """Oracle children: host only, never the chip."""
    os.environ["JAX_PLATFORMS"] = "cpu"


def run_phase(phase: str, size: Optional[int] = None, **overrides) -> dict:
    """Drive one phase on the default device; return its measurements.

    ``overrides`` replace Config fields (the CPU rehearsal shrinks the
    dense capacity and forces interpret-mode kernels with them)."""
    import jax

    from tpu_cooccurrence import native
    from tpu_cooccurrence.job import CooccurrenceJob
    from tpu_cooccurrence.ops.donation import donate_argnums
    from tpu_cooccurrence.xla_cache import cache_dir

    w = workload(WORKLOAD[phase], size)
    cfg = dataclasses.replace(w.config, **{**SETTINGS[phase], **overrides})
    start = time.monotonic()
    job = CooccurrenceJob(cfg)
    job.add_batch(w.users, w.items, w.ts)
    job.finish()
    wall = time.monotonic() - start
    scorer = job.scorer
    dev = jax.devices()[0]
    out = {
        "phase": phase,
        "workload": w.name,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "backend": cfg.backend.value,
        "events": int(len(w.users)),
        "windows": int(job.windows_fired),
        "pairs": int(job.counters.get(EXACT_COUNTERS[2])),
        "wall_s": wall,
        "first_window_s": (job.step_timer.windows[0].seconds
                           if job.step_timer.windows else None),
        "pallas": bool(getattr(scorer, "use_pallas", False)),
        "fused": bool(getattr(scorer, "use_fused", False)),
        "interpret": bool(getattr(scorer, "_pallas_interpret", False)),
        "fixed_shapes": getattr(scorer, "fixed_shapes", None),
        "donation": bool(donate_argnums(0)),
        "compile_cache": cache_dir(),
        "native_lib": native.get_lib() is not None,
    }
    if cfg.num_shards > 1:
        out["shard_devices"] = _shard_devices(scorer)
    out.update(_memory(scorer))
    out["counters"] = _counters(job)
    out["latest"] = {k: list(v) for k, v in job.latest.snapshot().items()}
    del job, scorer
    gc.collect()
    return out


def _shard_devices(scorer) -> List[str]:
    """Where each slab shard lives: one distinct device per shard."""
    shards = scorer.cnt.addressable_shards
    devices = sorted({str(s.device) for s in shards})
    if len(devices) != scorer.n_shards or len(shards) != scorer.n_shards:
        raise AssertionError(
            f"{scorer.n_shards} slab shards live on {devices}")
    return devices


def _memory(scorer) -> dict:
    """HBM while the phase's state is still alive (process-wide peaks)."""
    import jax

    per_device = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        per_device[str(d)] = {
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    first = next(iter(per_device.values()))
    out = {"bytes_in_use": first["bytes_in_use"],
           "peak_bytes_in_use": first["peak_bytes_in_use"]}
    if len(per_device) > 1:
        out["per_device"] = per_device
    return out


def compare(got: dict, ref: dict) -> Tuple[bool, str]:
    """Parity verdict of a device run against the oracle reference."""
    for name in EXACT_COUNTERS:
        if got["counters"][name] != ref["counters"][name]:
            return False, (f"{name}: {got['counters'][name]} != oracle "
                           f"{ref['counters'][name]}")
    a, b = got["latest"], ref["latest"]
    if set(a) != set(b):
        return False, (f"row sets differ: {len(set(a) - set(b))} extra, "
                       f"{len(set(b) - set(a))} missing")
    for item, o in b.items():
        p = a[item]
        if len(p) != len(o):
            return False, f"row {item}: {len(p)} entries vs oracle {len(o)}"
        for pos, ((pj, ps), (oj, os_)) in enumerate(zip(p, o)):
            if not math.isclose(ps, os_, rel_tol=RTOL, abs_tol=ATOL):
                return False, f"row {item}[{pos}]: {ps} vs oracle {os_}"
            # Ids must agree wherever no near-tie can reorder them; the
            # last slot also near-ties the unseen (K+1)th score.
            last = pos == len(o) - 1
            clear = not last and all(
                abs(os_ - o[q][1]) > TIE_GAP
                for q in (pos - 1, pos + 1) if 0 <= q < len(o))
            if clear and pj != oj:
                return False, f"row {item}[{pos}]: id {pj} vs oracle {oj}"
    return True, f"{len(b)} rows match"


def _oracle_pool(keys):
    """Start the oracle runs in CPU-pinned children; {key: AsyncResult}."""
    import multiprocessing

    pool = multiprocessing.get_context("spawn").Pool(
        processes=len(keys), initializer=_pin_cpu)
    return pool, {k: pool.apply_async(oracle_reference, (k, SIZES[k]))
                  for k in keys}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the sharded "
                         "sparse path over four chips")
    args = ap.parse_args(argv)

    from tpu_cooccurrence.xla_cache import enable_compilation_cache

    enable_compilation_cache()  # before the first compile
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: JAX found no TPU (platform {platform!r}); this "
            f"script measures the chip and does not run elsewhere\n")
        return 2
    if len(devices) < args.chips:
        sys.stderr.write(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {len(devices)}\n")
        return 2

    phases = (SHARDED,) if args.chips == 4 else PHASES
    keys = sorted({WORKLOAD[p] for p in phases})
    pool, refs = _oracle_pool(keys)
    ok = True
    try:
        results = {}
        for phase in phases:
            try:
                results[phase] = run_phase(phase)
            except Exception as exc:  # report, then fail the run
                ok = False
                results[phase] = {"phase": phase, "error": repr(exc)}
                print(json.dumps(results[phase]), flush=True)
        for phase in phases:
            res = results[phase]
            if "error" in res:
                continue
            ref = refs[WORKLOAD[phase]].get()
            good, detail = compare(res, ref)
            ok &= good
            res.pop("latest")
            res["parity"] = {"ok": good, "detail": detail,
                             "oracle_counters": ref["counters"],
                             "oracle_wall_s": ref["wall_s"]}
            print(json.dumps(res), flush=True)
    finally:
        pool.terminate()
        pool.join()
    if not ok:
        sys.stderr.write("chip_smoke: FAILED\n")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
