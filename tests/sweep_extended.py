"""Out-of-suite extended randomized sweep (run manually after major
changes — docs/ARCHITECTURE.md testing strategy):

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tests/sweep_extended.py [--trials 30] [--seed-base 0xA11CE]

Samples the config space (tumbling/sliding, cuts on/off, random top-k
including > vocab, random streams) and checks a wide backend-variant
matrix against the float64 oracle through the in-suite protocol
(identical counters; scores to tolerance; gap-gated exact ids). Round 4
provenance: seed family 0xA11CE caught the vocab-smaller-than-top-K
dense crash (fixed + pinned in tests/test_pipeline.py); families
0xA11CE and 0xB0B then ran 240 runs clean.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402


def _checkpoint_trial(trial, rng, kw, slide, users, items, ts,
                      assert_latest_close, Backend, Config):
    """Randomized mid-stream checkpoint/restore equivalence: restore at
    a random split point and finish — results must match an
    uninterrupted run for every backend."""
    import tempfile

    import numpy as np

    from tpu_cooccurrence.job import CooccurrenceJob

    split = int(len(users) * float(rng.uniform(0.3, 0.7)))
    fails = 0
    for backend, extra in (("oracle", {}), ("sparse", {}),
                           ("device", {}),
                           ("sparse", {"num_shards": 4})):
        with tempfile.TemporaryDirectory() as ck:
            cfg = Config(backend=Backend(backend), window_slide=slide,
                         development_mode=True, checkpoint_dir=ck,
                         **dict(kw, **extra))
            try:
                ref = CooccurrenceJob(Config(
                    backend=Backend(backend), window_slide=slide,
                    development_mode=True, **dict(kw, **extra)))
                ref.add_batch(users, items, ts)
                ref.finish()
                a = CooccurrenceJob(cfg)
                a.add_batch(users[:split], items[:split], ts[:split])
                a.checkpoint()
                b = CooccurrenceJob(cfg)
                b.restore()
                b.add_batch(users[split:], items[split:], ts[split:])
                b.finish()
                assert (ref.counters.as_dict() == b.counters.as_dict()
                        ), "counters diverge"
                r = {i: ref.latest[i] for i in ref.latest}
                g = {i: b.latest[i] for i in b.latest}
                assert set(r) == set(g), "item sets diverge"
                for item in r:
                    np.testing.assert_allclose(
                        np.array([v for _, v in g[item]]),
                        np.array([v for _, v in r[item]]),
                        rtol=1e-6, atol=1e-6)
            except Exception as exc:
                fails += 1
                print(f"CKPT TRIAL {trial} {backend} {extra} "
                      f"split={split}: {exc!r}"[:300], flush=True)
    return fails


def _multihost_trial(trial, rng, kw, slide, users, items, ts, tmpdir):
    """One randomized 2-process multi-controller run vs the in-process
    8-shard reference: merged disjoint row partitions must reproduce
    the single-process results exactly."""
    import json
    import socket
    import subprocess

    import numpy as np

    from tpu_cooccurrence.config import Backend, Config
    from test_pipeline import run_production

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(repo, "tests", "multihost_worker.py")
    backend = ["sharded", "sparse"][trial % 2]
    partition = bool(rng.integers(0, 2))
    n_items_cap = int(items.max()) + 1
    stream = os.path.join(tmpdir, f"s{trial}.npz")
    np.savez(stream, users=users, items=items, ts=ts)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=repo + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    procs, outs = [], []
    for pid in range(2):
        spec = dict(kw, stream=stream, coordinator=coordinator,
                    num_processes=2, process_id=pid, phase="full",
                    backend=backend, num_shards=8, num_items=n_items_cap,
                    partition_sampling=partition, window_slide=slide)
        spec_p = os.path.join(tmpdir, f"spec{trial}-{pid}.json")
        out_p = os.path.join(tmpdir, f"out{trial}-{pid}.json")
        with open(spec_p, "w") as f:
            json.dump(spec, f)
        outs.append(out_p)
        procs.append(subprocess.Popen(
            [sys.executable, worker, spec_p, out_p], env=env, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    results = []
    for p, out_p in zip(procs, outs):
        stdout, stderr = p.communicate(timeout=300)
        if p.returncode != 0:
            print(f"MH TRIAL {trial} {backend} ps={partition}: worker "
                  f"rc={p.returncode}: {stderr[-300:]}", flush=True)
            return 1
        with open(out_p) as f:
            results.append(json.load(f))
    merged = {}
    for res in results:
        for item, top in res["latest"].items():
            if int(item) in merged:
                print(f"MH TRIAL {trial}: row {item} from two processes",
                      flush=True)
                return 1
            merged[int(item)] = [(int(j), s) for j, s in top]
    ref = run_production(
        Config(**kw, backend=Backend(backend), num_shards=8,
               num_items=n_items_cap, window_slide=slide),
        users, items, ts)
    ok = set(merged) == set(ref.latest)
    if ok:
        for item in merged:
            a = np.array([v for _, v in merged[item]])
            b = np.array([v for _, v in ref.latest[item]])
            if len(a) != len(b) or not np.allclose(a, b, rtol=1e-6,
                                                   atol=1e-6):
                ok = False
                break
    if not ok:
        print(f"MH TRIAL {trial} {backend} ps={partition}: results "
              f"diverge from single-process reference", flush=True)
        return 1
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trials", type=int, default=30)
    ap.add_argument("--seed-base", type=lambda s: int(s, 0),
                    default=0xA11CE)
    ap.add_argument("--checkpoint", action="store_true",
                    help="mid-stream checkpoint/restore equivalence "
                         "instead of the backend matrix")
    ap.add_argument("--multihost", action="store_true",
                    help="randomized 2-process multi-controller runs vs "
                         "the in-process reference")
    args = ap.parse_args()

    from tpu_cooccurrence.config import Backend, Config
    from test_pipeline import assert_latest_close, run_production

    fails = 0
    for trial in range(args.trials):
        rng = np.random.default_rng(args.seed_base + trial)
        n = int(rng.integers(200, 2500))
        n_users = int(rng.integers(2, 50))
        n_items = int(rng.integers(4, 200))
        users = rng.integers(0, n_users, n).astype(np.int64)
        items = rng.integers(0, n_items, n).astype(np.int64)
        ts = np.cumsum(rng.integers(0, 4, n)).astype(np.int64)
        kw = dict(window_size=int(rng.integers(3, 60)),
                  seed=int(rng.integers(0, 2**31)),
                  item_cut=int(rng.integers(1, 12)),
                  user_cut=int(rng.integers(1, 8)),
                  top_k=int(rng.integers(1, 14)),
                  skip_cuts=bool(rng.integers(0, 2)))
        slide = None
        if trial % 4 == 0:
            base = int(rng.integers(2, 10))
            kw["window_size"] = base * int(rng.integers(2, 5))
            slide = base
        if args.checkpoint:
            fails += _checkpoint_trial(trial, rng, kw, slide, users,
                                       items, ts, assert_latest_close,
                                       Backend, Config)
            if trial % 10 == 9:
                print(f"trial {trial + 1}/{args.trials} done", flush=True)
            continue
        if args.multihost:
            import tempfile

            # The worker spec carries neither of these; drop them from
            # the reference config too so both sides run identically.
            kw.pop("skip_cuts", None)
            kw.pop("top_k", None)
            with tempfile.TemporaryDirectory() as td:
                fails += _multihost_trial(trial, rng, kw, slide,
                                          users, items, ts, td)
            if trial % 5 == 4:
                print(f"trial {trial + 1}/{args.trials} done", flush=True)
            continue
        oracle = run_production(
            Config(backend=Backend.ORACLE, window_slide=slide,
                   development_mode=True, **kw), users, items, ts)
        ref = {i: oracle.latest[i] for i in oracle.latest}
        variants = [
            ("device", {"num_items": n_items}),
            ("device", {"num_items": n_items, "count_dtype": "int16"}),
            ("sparse", {}),
            ("sparse", {"num_shards": 8}),
            ("sparse", {"pallas": "on"}),
            ("sharded", {"num_items": n_items, "num_shards": 8}),
            ("sharded", {"num_shards": 4}),  # derive-from-data
        ]
        for backend, extra in variants:
            cfg = Config(backend=Backend(backend), window_slide=slide,
                         development_mode=True, **dict(kw, **extra))
            try:
                job = run_production(cfg, users, items, ts)
                assert job.counters.as_dict() == oracle.counters.as_dict()
                assert_latest_close(
                    ref, {i: job.latest[i] for i in job.latest},
                    rtol=2e-4, atol=2e-4)
            except Exception as exc:  # record all, fail at end
                fails += 1
                print(f"TRIAL {trial} {backend} {extra}: {exc!r}"[:300],
                      flush=True)
        if trial % 10 == 9:
            print(f"trial {trial + 1}/{args.trials} done", flush=True)
    print("FAILURES:", fails)
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
