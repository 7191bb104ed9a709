"""The comparison that decides ``correct``.

What the timed path produced -- the job's final top-K rows, read through
its own result path after the window, and its three exact counters -- is
held against the plain reference (``oracle.py``) replaying the same
events. The reference expands and scores only a sample of rows, drawn
from the seed, with the cell's longest rows always in it; the cuts, the
reservoir, the row sums and the counters it keeps for every item.

Numbers compared, each against its limit in the cell file's ``check``:

* ``counters_off``: how many of the three exact counters differ;
* ``rows_off``: rows the program rescored that the reference did not,
  and the other way round;
* ``ids_off``: top-K ids that differ where the reference's scores leave
  no near-tie to reorder them, and rows of a different length;
* ``score_gap``: the widest gap of a top-K score,
  ``|program - reference| / (|reference| + 1)``.

``control=1`` puts the reference, computed one precision lower than the
configuration states, in the program's place.
"""

from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from benchmark.reference import oracle

#: Scores closer than this (relative, as ``score_gap``) are a near-tie
#: whose order float rounding may swap.
TIE = 1e-3
#: The precision one below each that a configuration states.
LOWER = {"float64": np.float32}

Rows = Dict[int, List[Tuple[int, float]]]


class Result:
    """The program's results, taken off the job before it is freed."""

    def __init__(self, counters: Dict[str, int], rows: List[int], snap,
                 consumed: int) -> None:
        self.counters = counters
        self.rows = rows
        self.snap = snap
        self.consumed = consumed

    @classmethod
    def of(cls, job, consumed: int) -> "Result":
        snap = job.latest.snapshot()
        return cls({c: int(job.counters.get(c)) for c in oracle.EXACT_COUNTERS},
                   [int(r) for r in snap], snap, consumed)

    def latest(self, rows) -> Rows:
        return {r: [(int(i), float(s)) for i, s in self.snap[r]]
                for r in rows if r in self.snap}


def sample_rows(run, result: Result) -> List[int]:
    """The rows compared: the cell's longest rows (its most frequent
    items), and a sample of the others drawn from the seed."""
    chk = run.spec.workload["check"]
    have = set(result.rows)
    freq = np.bincount(run.items[: result.consumed])
    longest = [int(i) for i in np.argsort(-freq, kind="stable")
               if int(i) in have][: chk["longest_rows"]]
    pool = sorted(have - set(longest))
    gen = run.stream.rng(run.args.seed, salt=1)
    n = min(chk["sample_rows"], len(pool))
    return longest + [int(r) for r in gen.choice(pool, n, replace=False)]


def reference(config: dict, rows) -> "oracle._Scoring":
    job = config["job"]
    if job.get("window_slide"):
        return oracle.SlidingReference(
            job["window_size"], job["window_slide"], job["item_cut"],
            job["user_cut"], job["top_k"], rows=rows)
    return oracle.TumblingReference(
        job["window_size"], job["item_cut"], job["user_cut"],
        job["top_k"], job["seed"], rows=rows)


def score_numbers(got: Rows, want: Rows, nxt: Dict[int, float]
                  ) -> Tuple[int, float]:
    """(ids_off, score_gap) of ``got`` against the reference's ``want``;
    ``nxt`` holds each row's (K+1)-th score, which the last slot may tie
    with."""
    ids_off, gap = 0, 0.0
    for row, ref in want.items():
        mine = got.get(row)
        if mine is None or len(mine) != len(ref):
            ids_off += 1
            continue
        scores = [s for _i, s in ref]
        if nxt.get(row) is not None:
            scores.append(nxt[row])
        for pos, ((gi, gs), (ri, rs)) in enumerate(zip(mine, ref)):
            gap = max(gap, abs(gs - rs) / (abs(rs) + 1.0))
            near = [scores[q] for q in (pos - 1, pos + 1)
                    if 0 <= q < len(scores)]
            if gi != ri and all(abs(rs - s) > TIE * (abs(rs) + 1.0)
                                for s in near):
                ids_off += 1
    return ids_off, gap


def compare(run, result: Result, control: int = 0):
    """(correct, checks): each number compared beside its limit."""
    cfg = run.spec.config
    limits = run.spec.workload["check"]["limits"]
    rows = sample_rows(run, result)
    start = time.monotonic()
    ref = reference(cfg, set(rows))
    n = result.consumed
    for u, i, t in zip(run.users[:n].tolist(), run.items[:n].tolist(),
                       run.ts[:n].tolist()):
        ref.process(u, i, t)
    ref.finish()
    if control:
        got = ref.latest(LOWER[cfg["score_precision"]])
        counters, have = dict(ref.counters), set(ref.rescored)
    else:
        got = result.latest(rows)
        counters, have = result.counters, set(result.rows)
    want = ref.latest()
    ids_off, gap = score_numbers(got, want, ref.next_scores)
    numbers = {
        "counters_off": sum(counters[c] != ref.counters[c]
                            for c in oracle.EXACT_COUNTERS),
        "rows_off": len(have ^ ref.rescored),
        "ids_off": ids_off,
        "score_gap": gap,
    }
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = all(v <= limits[k] for k, v in numbers.items())
    run.reference_s = time.monotonic() - start
    run.rows_compared = len(want)
    return correct, checks
