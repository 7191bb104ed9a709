"""Round-2 TPU measurement pass: every pending on-chip number, one run.

Captures the round-2 TPU-gated measurements in one sitting and appends
JSON lines to ``TPU_ROUND2.jsonl`` at the repo root (one object per
measurement, with failures recorded rather than aborting the pass). The
first ``benchmark`` PR replaces it with the cell table (ROADMAP A0/D1):

1. ml25m-sparse / ml25m-full — the two config-3 carrier candidates,
                      25M events + v5e-8 projection (bench/ml25m.py).
2. sparse-pallas / sharded-pallas-1chip / pallas-bench — kernel-vs-XLA
                      A/Bs with on-hardware parity checks.
3. configs          — the five BASELINE.md benchmark configs.

Each measurement can run alone via ``--only NAME``.

(config4-hybrid was the round-1 carrier comparison row; the hybrid
backend lost it 2.2x on-chip and was retired round 3.)

Usage (on a TPU-attached interpreter — no JAX_PLATFORMS override):
    python -m tpu_cooccurrence.bench.tpu_round2 [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

#: TPU_ROUND2_OUT overrides the artifact path — for CPU smoke tests of
#: the measurement machinery (which must not pollute the tracked JSONL
#: with CPU rows).
OUT = os.environ.get("TPU_ROUND2_OUT") or os.path.join(
    os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "TPU_ROUND2.jsonl")


def emit(obj: dict) -> None:
    obj["ts"] = time.strftime("%Y-%m-%d %H:%M:%S")
    with open(OUT, "a") as f:
        f.write(json.dumps(obj) + "\n")
    print(json.dumps(obj), flush=True)


def onchip_row(r: dict) -> bool:
    """Shared predicate for TPU_ROUND2.jsonl readers (summarize.py,
    ml25m.py): an ok row is usable as an on-chip number unless its
    platform tag says otherwise. A CPU smoke run whose TPU_ROUND2_OUT
    override was lost must poison neither the summary nor the
    projection constants. Historic rows predate the tag and pass
    untagged — their capture sessions were TPU-only."""
    if not r.get("ok"):
        return False
    platform = r.get("jax_platform")
    return platform is None or platform == "tpu"


def _backend_tag() -> dict:
    """Per-row platform provenance: a measurement run alone via
    ``--only`` writes no env row, so without this tag a row can't be
    told apart from an accidental CPU run. The key is ``jax_platform``, NOT ``backend``: several
    measurement dicts already carry a ``backend`` field meaning the
    *job* backend ("sparse", "device-int16", ...) which summarize.py
    keys on — the platform tag must neither be shadowed by it nor
    shadow it. Reads only jax's CACHED default backend: the error path
    of a measurement that died before any dispatch must not start a
    backend. Uninitialized ⇒ no tag, honestly."""
    try:
        from jax._src import xla_bridge

        backend = xla_bridge._default_backend  # cached; None if uninit
        return {} if backend is None else {"jax_platform": backend.platform}
    except Exception:  # pragma: no cover - private-API drift
        return {}


def guard(name: str):
    def deco(fn):
        def run(*a, **k):
            start = time.monotonic()
            try:
                res = dict(fn(*a, **k))
                # The measurement NAME is the pass's identity; an inner
                # BenchResult's own "name" must not shadow it (it did
                # through round 3 — config4 rows landed as
                # "zipfian-1M-items"; summarize.py accepts both).
                if "name" in res:
                    res["config"] = res.pop("name")
                emit({"name": name, "ok": True, **_backend_tag(),
                      "wall_s": round(time.monotonic() - start, 1), **res})
                return True
            except Exception as exc:  # record and continue the pass
                emit({"name": name, "ok": False, **_backend_tag(),
                      "error": repr(exc),
                      "trace": traceback.format_exc()[-1500:]})
                return False
        return run
    return deco


@guard("config5-sparse")
def config5_sparse(quick: bool) -> dict:
    """Instacart shape on the sparse backend (50k vocab): the same
    nonzero-cells-only argument as ml25m-sparse — the chip picks the
    config-5 carrier."""
    from ..config import Backend
    from .configs import config5_instacart

    if quick:
        # Quick mode exists to sanity-check the chip cheaply; the
        # Instacart shape takes minutes (same rule as all_configs).
        return {"skipped": "config 5 takes minutes; run without --quick"}
    # Single measured run (chip time is the scarce resource): this
    # shape runs minutes, so the one-time jit compile it absorbs is
    # noise, not signal.
    return config5_instacart(backend=Backend.SPARSE).as_dict()


@guard("ml25m-full")
def ml25m_full(quick: bool) -> dict:
    from .ml25m import run_full

    return run_full(2_000_000 if quick else 25_000_000, host_only=False)


@guard("ml25m-sparse")
def ml25m_sparse(quick: bool) -> dict:
    """The sparse carrier candidate: scores only nonzero cells (~60x
    fewer than dense at this shape) for more host index work — the chip
    decides which backend carries config 3."""
    from ..config import Backend
    from .ml25m import run_full

    return run_full(2_000_000 if quick else 25_000_000, host_only=False,
                    backend=Backend.SPARSE)


@guard("sparse-pallas")
def sparse_pallas(quick: bool) -> dict:
    """A/B the sparse rectangle scorer: XLA gather+LLR+top_k vs the fused
    Pallas kernel, at the fixed-shape rectangle sizes config 4 actually
    dispatches (VERDICT r3, Next #2 — pre-built so a 247x-style cliff
    like dense int16's costs a measurement, not a chip session). The
    result decides whether SparseDeviceScorer's pallas auto rule stays
    OFF for int32 slabs or flips on."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..state.sparse_scorer import (SparseDeviceScorer, _score_slab,
                                       _score_slab_pallas, fixed_block)

    rng = np.random.default_rng(0)
    num_items = 1 << 20 if not quick else 1 << 16  # config-4 vocab scale
    top_k = 10
    row_sums = jnp.asarray(rng.integers(1, 1 << 20, num_items),
                           dtype=jnp.int32)
    observed = np.float32(1e9)
    budget = SparseDeviceScorer.FIXED_BUDGET
    row_cap = SparseDeviceScorer.FIXED_ROW_CAP

    def timeit(fn, n=5):
        jax.block_until_ready(fn())  # compile
        start = time.monotonic()
        for _ in range(n):
            jax.block_until_ready(fn())
        return (time.monotonic() - start) / n

    def parity(a, b) -> dict:
        """On-HARDWARE parity of two packed [2, S, K] results. CPU
        interpret mode already pins this; re-checking compiled-on-chip
        catches Mosaic miscompiles (a known class: carried-scratch/
        bitcast issues appear only at real grid sizes — see
        ops/pallas_score.py)."""
        from ..ops.pallas_score import topk_parity
        from ..state.results import unpack_ids

        a, b = np.asarray(a), np.asarray(b)
        ok, mism = topk_parity(a[0], unpack_ids(a[1]),
                               b[0], unpack_ids(b[1]))
        return {"scores_allclose": ok, "id_mismatches": mism}

    by_rect = {}
    for R in (256, 1024, 4096):
        S = fixed_block(R, budget, row_cap)
        if quick:
            S = min(S, 512)
        # Rows at ~R/2 occupancy (post-pow-4-bucketing typical fill).
        lens = rng.integers(R // 4, R + 1, S).astype(np.int32)
        starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
        cap = int(lens.sum()) + 8
        cnt = jnp.asarray(rng.integers(0, 50, cap), dtype=jnp.int32)
        dst = jnp.asarray(rng.integers(0, num_items, cap), dtype=jnp.int32)
        meta = np.zeros((3, S), dtype=np.int32)
        meta[0] = rng.choice(num_items, S, replace=False)
        meta[1] = starts
        meta[2] = lens
        meta_j = jnp.asarray(meta)
        xla_out = _score_slab(cnt, dst, row_sums, meta_j, observed,
                              top_k=top_k, R=R)
        xla_s = timeit(lambda: _score_slab(
            cnt, dst, row_sums, meta_j, observed, top_k=top_k, R=R))
        try:
            interp = jax.default_backend() != "tpu"
            pl_out = _score_slab_pallas(cnt, dst, row_sums, meta_j,
                                        observed, top_k=top_k, R=R,
                                        interpret=interp)
            pl_s = timeit(lambda: _score_slab_pallas(
                cnt, dst, row_sums, meta_j, observed, top_k=top_k, R=R,
                interpret=interp))
            by_rect[f"R{R}xS{S}"] = {
                "xla_ms": round(xla_s * 1e3, 2),
                "pallas_ms": round(pl_s * 1e3, 2),
                "pallas_speedup": round(xla_s / pl_s, 3),
                "parity": parity(xla_out, pl_out),
            }
        except Exception as exc:
            by_rect[f"R{R}xS{S}"] = {
                "xla_ms": round(xla_s * 1e3, 2),
                "pallas_error": repr(exc)[:200],
            }
    return {"count_dtype": "int32", "vocab": num_items,
            "by_rect": by_rect}


@guard("sharded-pallas-1chip")
def sharded_pallas_1chip(quick: bool) -> dict:
    """End-to-end validation of the kernel-inside-shard_map paths on ONE
    real chip (a 1-device mesh): both sharded backends run --pallas on
    vs off on the same stream and the results must match. This proves
    compile+execute+parity of the exact shard_map+pallas programs a pod
    would run (the CPU tests only ever exercise them interpreted)."""
    import numpy as np

    from ..parallel.mesh import make_mesh
    from ..parallel.sharded import ShardedScorer
    from ..parallel.sharded_sparse import ShardedSparseScorer
    from ..sampling.reservoir import PairDeltaBatch

    rng = np.random.default_rng(3)
    n, items = (20_000, 256) if quick else (60_000, 512)
    src = rng.integers(0, items, n).astype(np.int64)
    dst = rng.integers(0, items, n).astype(np.int64)
    keep = src != dst
    pairs = PairDeltaBatch(src[keep], dst[keep],
                           np.ones(int(keep.sum()), dtype=np.int32))
    mesh = make_mesh(1)

    def compare(mk):
        out = {}
        for pl in ("on", "off"):
            sc = mk(pl)
            sc.process_window(0, pairs)
            batches = [sc.flush(), sc.flush()]
            out[pl] = {int(r): (v.copy(), i.copy())
                       for b in batches
                       for r, i, v in zip(b.rows, b.idx, b.vals)}
        from ..ops.pallas_score import topk_parity
        from ..state.results import unpack_ids

        rows_match = set(out["on"]) == set(out["off"])
        common = sorted(set(out["on"]) & set(out["off"]))
        if not common:
            # Disjoint/empty row sets ARE the parity failure this check
            # exists to catch — report it, don't crash on np.stack([]).
            return {"rows": len(out["off"]), "rows_on": len(out["on"]),
                    "rows_match": rows_match, "scores_allclose": False,
                    "id_mismatches": -1}
        v_on = np.stack([out["on"][r][0] for r in common])
        i_on = np.stack([out["on"][r][1] for r in common])
        v_off = np.stack([out["off"][r][0] for r in common])
        i_off = np.stack([out["off"][r][1] for r in common])
        ok, id_mism = topk_parity(v_off, i_off, v_on, i_on)
        return {"rows": len(out["off"]), "rows_match": rows_match,
                "scores_allclose": ok, "id_mismatches": id_mism}

    # VERDICT r4 Next #7: the shard_map+psum wrapper's per-window cost,
    # measured on the one real device at the config-3 row-sum scale —
    # the same windows through the unsharded sparse scorer and a
    # 1-device-mesh sharded one; the difference is the wrapper term
    # (shard_map launch + the per-window row-sum psum a pod pays) the
    # v5e-8 projection previously covered with an assumed allowance.
    from ..state.sparse_scorer import SparseDeviceScorer

    vocab = 59_047  # config 3's calibrated ML-25M vocabulary
    n_w = 3 if quick else 6
    per_w = 10_000 if quick else 30_000
    r2 = np.random.default_rng(7)
    windows = []
    for w in range(n_w + 1):
        s = r2.integers(0, vocab, per_w).astype(np.int64)
        d = r2.integers(0, vocab, per_w).astype(np.int64)
        k = s != d
        windows.append((w, PairDeltaBatch(
            s[k], d[k], np.ones(int(k.sum()), dtype=np.int32))))

    def step_time(sc):
        sc.process_window(*windows[0])  # compile + first-touch growth
        sc.flush()
        start = time.monotonic()
        for w, p in windows[1:]:
            sc.process_window(w, p)
        sc.flush()  # deferred results: the fetch closes the timing
        return (time.monotonic() - start) / n_w

    t_plain = step_time(SparseDeviceScorer(10, defer_results=True,
                                           fixed_score="on"))
    t_sharded = step_time(ShardedSparseScorer(10, mesh=mesh,
                                              defer_results=True,
                                              fixed_score="on"))
    return {
        "sharded_dense_int16": compare(lambda pl: ShardedScorer(
            items, 10, mesh=mesh, count_dtype="int16", use_pallas=pl)),
        "sharded_sparse": compare(lambda pl: ShardedSparseScorer(
            10, mesh=mesh, defer_results=True, fixed_score="on",
            use_pallas=pl)),
        "step_ms_per_window_unsharded": round(t_plain * 1e3, 2),
        "step_ms_per_window_sharded_1dev": round(t_sharded * 1e3, 2),
        "sharded_overhead_ms_per_window": round(
            max(0.0, t_sharded - t_plain) * 1e3, 3),
        "overhead_vocab": vocab,
        "overhead_pairs_per_window": per_w,
    }


@guard("pallas-bench")
def pallas_bench(quick: bool) -> dict:
    """The kernel's target case: int16 counts at a max-vocab shape, where
    the XLA path's transient f32 score matrix doubles working HBM."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..ops.device_scorer import _score
    from ..ops.pallas_score import pallas_score_topk

    num_items = 20_480 if quick else 61_440  # multiple of the 512 tile
    s = 2048 if quick else 8192
    top_k = 10
    rng = np.random.default_rng(0)
    C = jnp.asarray(rng.integers(0, 50, (num_items, num_items)),
                    dtype=jnp.int16)
    row_sums = jnp.asarray(rng.integers(1, 1 << 20, num_items),
                           dtype=jnp.int32)
    rows = jnp.asarray(rng.integers(0, num_items, s), dtype=jnp.int32)
    observed = np.float32(1e9)

    def timeit(fn, n=5):
        fn()  # compile
        start = time.monotonic()
        for _ in range(n):
            jax.block_until_ready(fn())
        return (time.monotonic() - start) / n

    xla_s = timeit(lambda: _score(C, row_sums, rows, observed,
                                  top_k=top_k, packed=True))
    # Tile sweep: wider tiles amortize the sequential top-K merge (and its
    # per-tile threshold check) at the cost of a bigger VMEM working set.
    pallas_ms = {}
    for tile in (512, 1024, 2048):
        if num_items % tile:
            continue
        try:
            pl_s = timeit(lambda: pallas_score_topk(
                C, row_sums, rows, observed, top_k=top_k, tile=tile,
                packed=True))
            pallas_ms[str(tile)] = round(pl_s * 1e3, 2)
        except Exception as exc:
            pallas_ms[str(tile)] = f"failed: {exc!r}"[:200]
    best = min((v for v in pallas_ms.values() if isinstance(v, float)),
               default=None)
    return {"shape": [s, num_items], "count_dtype": "int16",
            "xla_ms": round(xla_s * 1e3, 2),
            "pallas_ms_by_tile": pallas_ms,
            "pallas_speedup": (round(xla_s * 1e3 / best, 3)
                               if best else None)}


@guard("configs")
def all_configs(quick: bool) -> dict:
    from .configs import ALL_CONFIGS

    # --quick runs only the two small configs (config 4 already ran as
    # its own measurement).
    fns = [fn for _name, fn in ALL_CONFIGS]
    if quick:
        fns = fns[:2]
    return {"results": [fn().as_dict() for fn in fns]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small shapes (sanity, not headline numbers)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of measurement names")
    args = ap.parse_args()
    # The carrier rows run before anything long; sparse-pallas decides
    # the config-4 carrier kernel in the same sitting.
    passes = {
        "ml25m-sparse": ml25m_sparse,
        "sparse-pallas": sparse_pallas,
        "ml25m-full": ml25m_full,
        "sharded-pallas-1chip": sharded_pallas_1chip,
        "config5-sparse": config5_sparse,
        "pallas-bench": pallas_bench,
        "configs": all_configs,
    }
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(passes)
        if unknown:
            ap.error(f"unknown measurement(s) {sorted(unknown)}; "
                     f"choose from {sorted(passes)}")
    # Persistent compile cache before the first compile (xla_cache.py).
    from ..xla_cache import enable_compilation_cache

    enable_compilation_cache()
    import jax

    # One env row per full pass, not one per --only run.
    if only is None:
        emit({"name": "env", "ok": True,
              "devices": [str(d) for d in jax.devices()],
              "backend": jax.default_backend(), "quick": args.quick})
    all_ok = True
    for name, fn in passes.items():
        if only is None or name in only:
            all_ok = bool(fn(args.quick)) and all_ok
    # A failed measurement must not exit 0.
    if not all_ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
