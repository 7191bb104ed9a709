"""The one general generator: a configuration's event stream from its
``stream`` parameters and the run's ``--seed``.

Copied from ``tpu_cooccurrence/io/synthetic.py`` (``calibrated_interactions``
and ``zipfian_interactions``), so the yardstick stays put when the program
changes. Two kinds, named by ``stream["generator"]``:

* ``calibrated``: item popularity Zipf-Mandelbrot ``(r + q)^-s``, per-user
  activity a clipped log-normal, user ids by exact multiplicity (largest
  remainder), shuffled over the stream. The activity vector -- the sizes
  of the work -- is drawn from the fixed ``sizes_seed``; ``--seed`` draws
  the items and the order, so every seed does the same amount of work.
* ``zipf``: items Zipf(alpha) over ``n_items``, users uniform. The
  stream's structure -- which rank and which user each event has -- is
  drawn from the fixed ``sizes_seed``; ``--seed`` relabels items and
  users by a permutation of each, so every seed does the same work on
  other ids.

Timestamps come from the traffic, not from here (``timestamps``).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def rng(seed: int, salt: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), salt])


def sample_items(weights: np.ndarray, n: int,
                 gen: np.random.Generator) -> np.ndarray:
    """``n`` iid draws from normalised ``weights`` by inverse CDF."""
    cdf = np.cumsum(weights)
    cdf[-1] = 1.0
    return np.searchsorted(cdf, gen.random(n)).astype(np.int64)


def _exact_multiplicities(weights: np.ndarray, total: int) -> np.ndarray:
    expected = total * (weights / weights.sum())
    base = np.floor(expected).astype(np.int64)
    rem = total - int(base.sum())
    if rem > 0:
        base[np.argsort(-(expected - base), kind="stable")[:rem]] += 1
    return base


def calibrated(p: dict, events: int, seed: int):
    r = np.arange(1, p["n_items"] + 1, dtype=np.float64)
    w = (r + p["item_q"]) ** (-p["item_s"])
    gen = rng(seed)
    items = sample_items(w / w.sum(), events, gen)
    sizes = np.random.default_rng(p["sizes_seed"])
    activity = np.clip(np.exp(sizes.normal(p["user_mu"], p["user_sigma"],
                                           p["n_users"])),
                       p["user_lo"], p["user_hi"])
    users = np.repeat(np.arange(p["n_users"], dtype=np.int64),
                      _exact_multiplicities(activity, events))
    gen.shuffle(users)
    return users, items


def zipf(p: dict, events: int, seed: int):
    w = np.arange(1, p["n_items"] + 1, dtype=np.float64) ** (-p["alpha"])
    sizes = rng(p["sizes_seed"])
    ranks = sample_items(w / w.sum(), events, sizes)
    slots = sizes.integers(0, p["n_users"], events, dtype=np.int64)
    gen = rng(seed)
    items = gen.permutation(p["n_items"])[ranks]
    users = gen.permutation(p["n_users"])[slots]
    return users, items


GENERATORS = {"calibrated": calibrated, "zipf": zipf}


def generate(stream: dict, events: int, seed: int
             ) -> Tuple[np.ndarray, np.ndarray]:
    """(users, items) of the configuration's stream, ``events`` long."""
    return GENERATORS[stream["generator"]](stream, events, seed)


def timestamps(events: int, events_per_s: float) -> np.ndarray:
    """Event time in ms of each event at a fixed rate: event ``i`` is due
    ``i / events_per_s`` seconds into the stream."""
    return (np.arange(events, dtype=np.int64) * 1000
            // int(events_per_s)).astype(np.int64)
