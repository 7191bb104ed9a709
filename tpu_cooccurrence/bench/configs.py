"""The five BASELINE.md benchmark configurations.

| # | Config                                                        |
|---|---------------------------------------------------------------|
| 1 | batch word co-occurrence on tiny text file (local, CPU)       |
| 2 | MovieLens-100K user->item baskets, tumbling count window      |
| 3 | MovieLens-25M sessions, sliding time window + top-k           |
| 4 | Zipfian synthetic basket stream (1M items, a=1.1), 8 shards   |
| 5 | Instacart order-product baskets, incremental streaming update |

Real dataset files are used when present (paths via env:
``MOVIELENS_100K``, ``MOVIELENS_25M``, ``INSTACART_ORDERS``/
``INSTACART_ORDER_PRODUCTS``); otherwise shape-matched synthetic stand-ins
are generated (this environment has no network egress), and the report
labels them as such.

Metric: item-pairs/sec = ObservedCooccurrences / wall-clock (BASELINE.md).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from ..config import Backend, Config
from ..io import synthetic
from ..job import CooccurrenceJob
from ..metrics import OBSERVED_COOCCURRENCES

TINY_TEXT = """the quick brown fox jumps over the lazy dog
pack my box with five dozen liquor jugs
how vexingly quick daft zebras jump
the five boxing wizards jump quickly
sphinx of black quartz judge my vow
the quick onyx goblin jumps over the lazy dwarf
"""


@dataclasses.dataclass
class BenchResult:
    name: str
    backend: str
    events: int
    pairs: int
    seconds: float
    synthetic_standin: bool
    #: Which synthetic model produced the stand-in stream (None for real
    #: files): "zipf" (legacy shape-matched Zipf) or "calibrated-v1"
    #: (marginals fitted to the dataset's published spectra — see
    #: docs/calibrated_standins.md).
    standin_model: Optional[str] = None

    @property
    def pairs_per_sec(self) -> float:
        return self.pairs / max(self.seconds, 1e-9)

    def as_dict(self) -> Dict:
        return {
            "name": self.name,
            "backend": self.backend,
            "events": self.events,
            "pairs": self.pairs,
            "seconds": round(self.seconds, 3),
            "pairs_per_sec": round(self.pairs_per_sec, 1),
            "synthetic_standin": self.synthetic_standin,
            **({"standin_model": self.standin_model}
               if self.standin_model else {}),
        }


@dataclasses.dataclass
class Workload:
    """One benchmark configuration's stream and job config, not yet run:
    ``chip_smoke.py`` drives the same workload on the chip and on the
    oracle backend."""
    name: str
    config: Config
    users: np.ndarray
    items: np.ndarray
    ts: np.ndarray
    #: None = real (or non-stand-in) input; a string names the
    #: synthetic model that stands in for a real dataset.
    standin_model: Optional[str] = None


def _run(w: Workload) -> BenchResult:
    job = CooccurrenceJob(w.config)
    start = time.monotonic()
    job.add_batch(w.users, w.items, w.ts)
    job.finish()
    seconds = time.monotonic() - start
    return BenchResult(w.name, w.config.backend.value, len(w.users),
                       job.counters.get(OBSERVED_COOCCURRENCES), seconds,
                       w.standin_model is not None, w.standin_model)


def config1_tiny_text(backend: Backend = Backend.DEVICE) -> BenchResult:
    """Batch word co-occurrence on a tiny text (one window, skip-cuts)."""
    users, items, ts = synthetic.word_cooccurrence_stream(TINY_TEXT * 50)
    n_items = int(items.max()) + 1
    cfg = Config(window_size=1_000_000, skip_cuts=True, seed=1,
                 backend=backend, num_items=n_items)
    return _run(Workload("tiny-text-batch", cfg, users, items, ts))


def _movielens_100k() -> Tuple:
    """(users, items, ts, standin_model): model is None for real files —
    the helper that picks the generator owns the provenance label."""
    path = os.environ.get("MOVIELENS_100K", "")
    if path and os.path.exists(path):
        (users, items, ts), = synthetic.movielens_interactions(path)
        return users, items, ts, None
    # Stand-in calibrated to the published ML-100K marginals (943
    # users x 1,682 movies, top-3 movie counts, >=20 ratings/user).
    users, items, ts = synthetic.ml100k_calibrated()
    return users, items, ts, "calibrated-v1"


def config2_ml100k(backend: Backend = Backend.DEVICE) -> BenchResult:
    users, items, ts, model = _movielens_100k()
    cfg = Config(window_size=4000, seed=2, item_cut=500, user_cut=500,
                 backend=backend, num_items=int(items.max()) + 1)
    return _run(Workload("ml-100k-tumbling", cfg, users, items, ts, model))


def _movielens_25m(limit: Optional[int]) -> Tuple:
    path = os.environ.get("MOVIELENS_25M", "")
    if path and os.path.exists(path):
        (users, items, ts), = synthetic.movielens_interactions(path)
        if limit:
            users, items, ts = users[:limit], items[:limit], ts[:limit]
        return users, items, ts, None
    n = limit or 2_000_000
    # Stand-in calibrated to the published ML-25M marginals (162,541
    # users x 59,047 movies, near-tied top movies at ~81.5k ratings,
    # >=20 ratings/user) — a plain Zipf alpha misses the real head by
    # construction (docs/calibrated_standins.md has the deltas).
    users, items, ts = synthetic.ml25m_calibrated(n)
    return users, items, ts, "calibrated-v1"


def _dense_cfg_extras(backend: Backend, items) -> Dict:
    """int16 counts whenever a dense (device/sharded) backend carries the
    config — that is what fits these vocabularies on chip."""
    dense = backend in (Backend.DEVICE, Backend.SHARDED)
    return {
        "count_dtype": "int16" if dense else "int32",
        "num_items": int(items.max()) + 1 if dense else 0,
    }


def config3_workload(backend: Backend = Backend.DEVICE,
                     limit: Optional[int] = 500_000) -> Workload:
    """59k-item vocab (the calibrated stand-in carries ML-25M's real
    59,047 movies): a dense int32 C (13.9 GB) misses one chip's HBM,
    but reference-style int16 counts (7.0 GB) fit — so the dense device
    backend carries this config instead of the host-matrix hybrid."""
    users, items, ts, model = _movielens_25m(limit)
    cfg = Config(window_size=4000, window_slide=1000, seed=3,
                 item_cut=500, user_cut=500, backend=backend,
                 **_dense_cfg_extras(backend, items))
    return Workload("ml-25m-sliding", cfg, users, items, ts, model)


def config3_ml25m_sliding(backend: Backend = Backend.DEVICE,
                          limit: Optional[int] = 500_000) -> BenchResult:
    return _run(config3_workload(backend, limit))


def config4_workload(backend: Backend = Backend.SPARSE,
                     n_events: int = 1_000_000) -> Workload:
    """1M-item Zipfian stream. Dense device state is infeasible at this
    vocabulary; the device-resident sparse slab backend carries it (the
    host-matrix hybrid remains as the fallback comparison point)."""
    users, items, ts = synthetic.zipfian_interactions(
        n_events, n_items=1_000_000, n_users=100_000, alpha=1.1, seed=4,
        events_per_ms=200)
    cfg = Config(window_size=100, seed=4, item_cut=500, user_cut=500,
                 backend=backend)
    return Workload("zipfian-1M-items", cfg, users, items, ts)


def config4_zipfian_1m(backend: Backend = Backend.SPARSE,
                       n_events: int = 1_000_000) -> BenchResult:
    return _run(config4_workload(backend, n_events))


def _instacart(n_baskets: Optional[int] = None) -> Tuple:
    orders = os.environ.get("INSTACART_ORDERS", "")
    order_products = os.environ.get("INSTACART_ORDER_PRODUCTS", "")
    if orders and os.path.exists(orders) and os.path.exists(order_products):
        (users, items, ts), = synthetic.instacart_interactions(
            orders, order_products)
        return users, items, ts, None
    # Stand-in calibrated to the published Instacart marginals (user
    # order counts 4..100 mean 16.6, basket sizes mean ~10 median 8,
    # Banana-headed product spectrum). Scale via BENCH_BASKETS;
    # persistent histories make the pair volume grow quadratically in
    # per-user interactions.
    if n_baskets is None:
        n_baskets = int(os.environ.get("BENCH_BASKETS", 20_000))
    users, items, ts = synthetic.instacart_calibrated(n_baskets)
    return users, items, ts, "calibrated-v1"


def config5_workload(backend: Backend = Backend.DEVICE,
                     n_baskets: Optional[int] = None) -> Workload:
    """~50k-item vocab: int16 counts (5 GB dense C) keep this on the dense
    device backend."""
    users, items, ts, model = _instacart(n_baskets)
    cfg = Config(window_size=1000, seed=5, item_cut=500, user_cut=500,
                 backend=backend, **_dense_cfg_extras(backend, items))
    return Workload("instacart-incremental", cfg, users, items, ts, model)


def config5_instacart(backend: Backend = Backend.DEVICE) -> BenchResult:
    return _run(config5_workload(backend))


ALL_CONFIGS: List[Tuple[str, Callable[[], BenchResult]]] = [
    ("1-tiny-text", config1_tiny_text),
    ("2-ml100k", config2_ml100k),
    ("3-ml25m-sliding", config3_ml25m_sliding),
    ("4-zipfian-1M", config4_zipfian_1m),
    ("5-instacart", config5_instacart),
]


def run_all() -> Iterator[BenchResult]:
    for _name, fn in ALL_CONFIGS:
        yield fn()


def main() -> None:
    import json

    # Stream each result as it completes (config 3-5 take minutes each).
    for res in run_all():
        print(json.dumps(res.as_dict()), flush=True)


if __name__ == "__main__":
    main()
