"""Closed-loop replay: the configuration's stream at its own event time,
fed to ``add_batch`` in fixed batches as fast as it returns.

Parameters (the cell file's ``traffic``): ``batch_events``, the events of
one ``add_batch`` call; ``warmup_batches``, the untimed prefix that fills
the state and warms up the cell's shapes; ``batches_per_s`` (optional),
fixed work: the window feeds ``round(--seconds x batches_per_s)`` batches
and ends when their results are on the device, so every run does the
same windows of the stream however long they take (the rate is set so
that the window lasts about ``--seconds`` on the code that set it).
Without it the window feeds batches until ``--seconds`` have passed.
``prewarm`` (optional, with fixed work), drive a scratch job, thrown away
after, through exactly the events the run will feed: a program whose
shapes grow with its state (the sparse slab's capacities and buckets)
would otherwise compile inside the window.
``await_results`` (optional), send the next batch only once the last
one's windows are on the device, as a consumer that commits each window
before it reads the next. The window continues the stream where the
warm-up stopped, and ends early if the stream does.
"""

from __future__ import annotations

import gc

import jax


def drive(run) -> None:
    p, stream = run.traffic, run.spec.config["stream"]
    n = int(stream["events"])
    run.users, run.items = run.stream.generate(stream, n, run.args.seed)
    run.ts = run.stream.timestamps(n, stream["events_per_s"])
    run.mark("stream")
    batch, warm = int(p["batch_events"]), int(p["warmup_batches"])
    fixed = None
    if "batches_per_s" in p:
        fixed = max(1, round(run.args.seconds * float(p["batches_per_s"])))
    with jax.profiler.TraceAnnotation("warm-up"):
        if p.get("prewarm"):
            scratch = run.make_job(scratch=True)
            for lo in range(0, min(n, (warm + fixed) * batch), batch):
                scratch.add_batch(run.users[lo:lo + batch],
                                  run.items[lo:lo + batch],
                                  run.ts[lo:lo + batch])
            del scratch
            gc.collect()
            run.mark("prewarm")
        run.make_job()
        lo = 0
        for _ in range(warm):
            run.ingest(lo, lo + batch)
            lo += batch
    run.sync()
    run.open_window()
    fed = 0
    while lo < n and (fed < fixed if fixed else not run.window_over()):
        run.ingest(lo, min(lo + batch, n))
        lo, fed = min(lo + batch, n), fed + 1
        if p.get("await_results"):
            run.sync()
    run.close_window()
    run.consumed = lo
