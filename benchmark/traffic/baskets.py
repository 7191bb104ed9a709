"""Basket replay: an order history, one order (basket) at a time, each
order's products one event each under the order's ``(user, ts)``.

The stream is a copy of ``tpu_cooccurrence/io/synthetic.py``'s
``instacart_calibrated``, with its draws split in two. The fixed
``sizes_seed`` draws the sizes of the work: each user's order count (a
clipped log-normal, users by exact multiplicity), the order of the
baskets and each basket's size. ``--seed`` draws the products (Zipf-
Mandelbrot over the catalog, i.i.d.), so every seed does the same amount
of work on other products. Basket ``b`` is due at ``b x ms_per_basket``.

The job is fed one tumbling window per ``add_batch``: batches are cut at
window boundaries, so a basket is never split and each call fires the
window before it. Parameters (the cell file's ``traffic``):
``warmup_batches``, the untimed prefix that fills the state and warms up
the cell's shapes; ``batches_per_s``, fixed work: the window feeds
``round(--seconds x batches_per_s)`` windows and ends when their results
are on the device. The window continues the stream where the warm-up
stopped, and ends early if the stream does.
"""

from __future__ import annotations

import jax
import numpy as np

from benchmark.traffic import stream as streams


def _lognormal(gen, n: int, mu: float, sigma: float, lo: float,
               hi: float) -> np.ndarray:
    return np.clip(np.exp(gen.normal(mu, sigma, n)), lo, hi)


def generate(p: dict, seed: int):
    """(users, items, ts, basket_sizes) of the configuration's stream."""
    sizes = np.random.default_rng(p["sizes_seed"])
    orders = _lognormal(sizes, p["n_users"], p["orders_mu"],
                        p["orders_sigma"], p["orders_lo"], p["orders_hi"])
    basket_users = np.repeat(
        np.arange(p["n_users"], dtype=np.int64),
        streams._exact_multiplicities(orders, p["n_orders"]))
    sizes.shuffle(basket_users)
    basket_sizes = np.rint(_lognormal(
        sizes, p["n_orders"], p["basket_mu"], p["basket_sigma"],
        p["basket_lo"], p["basket_hi"])).astype(np.int64)
    users = np.repeat(basket_users, basket_sizes)
    ts = np.repeat(np.arange(p["n_orders"], dtype=np.int64)
                   * int(p["ms_per_basket"]), basket_sizes)
    r = np.arange(1, p["n_products"] + 1, dtype=np.float64)
    w = (r + p["item_q"]) ** (-p["item_s"])
    items = streams.sample_items(w / w.sum(), int(basket_sizes.sum()),
                                 streams.rng(seed))
    return users, items, ts, basket_sizes


def window_bounds(basket_sizes: np.ndarray, baskets_per_window: int
                  ) -> np.ndarray:
    """Event offsets at which each tumbling window starts, and the end."""
    starts = np.concatenate([[0], np.cumsum(basket_sizes)])
    return starts[::baskets_per_window].tolist() + (
        [] if len(basket_sizes) % baskets_per_window == 0
        else [int(starts[-1])])


def drive(run) -> None:
    p, s = run.traffic, run.spec.config["stream"]
    run.users, run.items, run.ts, sizes = generate(s, run.args.seed)
    run.mark("stream")
    per_window = int(run.spec.config["job"]["window_size"]) // int(
        s["ms_per_basket"])
    bounds = window_bounds(sizes, per_window)
    n = len(bounds) - 1  # windows in the stream
    warm = int(p["warmup_batches"])
    fixed = max(1, round(run.args.seconds * float(p["batches_per_s"])))
    with jax.profiler.TraceAnnotation("warm-up"):
        run.make_job()
        for k in range(min(warm, n)):
            run.ingest(bounds[k], bounds[k + 1])
    run.sync()
    run.open_window()
    k = min(warm, n)
    while k < min(warm + fixed, n):
        run.ingest(bounds[k], bounds[k + 1])
        k += 1
    run.close_window()
    run.consumed = bounds[k]
