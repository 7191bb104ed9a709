"""The dense chained update scatters each COO chunk in ``SCATTER_STEP``
steps over the chunk's live lanes only (``ops/device_scorer._update_coo``
and ``_update_coo_u16``, a loop with a traced trip count): ``C`` and the
row sums equal a one-shot ``C.at[src, dst].add`` of the same folded COO,
bit for bit, and the window's counts say how many lanes the steps were
shaped for (``scatter_lanes``) and how many were live
(``scatter_live``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_cooccurrence.ops import device_scorer as ds
from tpu_cooccurrence.ops.aggregate import aggregate_window_coo
from tpu_cooccurrence.sampling.reservoir import PairDeltaBatch

STEP = ds.SCATTER_STEP
#: 65,536 cells: room for a full 2-step bucket of distinct cells.
ITEMS = 256
#: A folded cell delta past int16: the window ships the int32 COO.
WIDE = 40_000


def _window(rng, n, wire, first=None):
    """One window whose fold is ``n`` distinct cells, each cell's delta
    split over two pairs so the fold has work to do. ``wire="i32"`` puts
    the last cell's delta past int16, so the window cannot ride as
    uint16; ``first`` puts the first delta in cell ``(0, 0)``."""
    cells = rng.choice(np.arange(1, ITEMS * ITEMS), size=n, replace=False)
    delta = rng.choice(np.array([-2, -1, 1, 2, 3], np.int32), size=n)
    if wire == "i32":
        delta[-1] = WIDE
    if first is not None:
        cells[0], delta[0] = 0, first
    half = delta // 2
    src, dst = cells // ITEMS, cells % ITEMS
    return PairDeltaBatch(np.concatenate([src, src]).astype(np.int64),
                          np.concatenate([dst, dst]).astype(np.int64),
                          np.concatenate([half, delta - half]))


def _one_shot(C, row_sums, pairs):
    """The window's folded COO applied in one scatter-add."""
    src, dst, delta = aggregate_window_coo(pairs.src, pairs.dst,
                                           pairs.delta)
    delta = jnp.asarray(delta.astype(np.int32))
    return (C.at[src, dst].add(delta.astype(C.dtype)),
            row_sums.at[src].add(delta))


def _lanes(chunks):
    return sum(STEP * -(-n // STEP) for n in chunks)


#: case -> (live lanes of each window, wire, max_pairs_per_step, the
#: first cell's folded delta of each window). The live counts take every
#: step boundary: one lane, one short of a step, a step, one past it,
#: and the whole 2-step pow2 bucket.
SIZES = {f"n{n}": n for n in (1, STEP - 1, STEP, STEP + 1, 2 * STEP)}
CASES = {f"{size}-{wire}": ([n], wire, 1 << 20, [None])
         for size, n in SIZES.items() for wire in ("u16", "i32")}
CASES.update({
    # A window over max_pairs_per_step: two chunks, 2 steps and 1.
    "two-chunks-u16": ([2 * STEP + 100], "u16", STEP + STEP // 2, [None]),
    "two-chunks-i32": ([2 * STEP + 100], "i32", STEP + STEP // 2, [None]),
    # One cell driven past int16 over two windows: int16 counts wrap
    # like the reference's shorts, the same in every step.
    "wrap-u16": ([STEP + 7, 3], "u16", 1 << 20, [30_000, 5_000]),
    "wrap-i32": ([5, STEP + 7], "i32", 1 << 20, [30_000, 5_000]),
})


@pytest.mark.parametrize("count_dtype", ["int16", "int32"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_stepped_update_matches_one_shot_scatter(monkeypatch, case,
                                                 count_dtype):
    sizes, wire, max_pairs, firsts = CASES[case]
    shipped = []
    for name, tag in (("_update_coo", "i32"), ("_update_coo_u16", "u16")):
        def spy(*args, _real=getattr(ds, name), _tag=tag, **kw):
            shipped.append(_tag)
            return _real(*args, **kw)
        monkeypatch.setattr(ds, name, spy)
    rng = np.random.default_rng(sum(map(ord, case)))
    scorer = ds.DeviceScorer(ITEMS, top_k=10, use_pallas="off",
                             count_dtype=count_dtype,
                             max_pairs_per_step=max_pairs,
                             defer_results=True)
    C, row_sums = jnp.zeros_like(scorer.C), jnp.zeros_like(scorer.row_sums)
    for n, first in zip(sizes, firsts):
        pairs = _window(rng, n, wire, first)
        C, row_sums = _one_shot(C, row_sums, pairs)
        scorer.process_window(0, pairs)
        chunks = [min(max_pairs, n - lo) for lo in range(0, n, max_pairs)]
        counts = scorer.stage_clock.counts
        assert counts["scatter_live"] == n
        assert counts["scatter_lanes"] == _lanes(chunks)
        assert shipped[-len(chunks):] == [wire] * len(chunks)
    np.testing.assert_array_equal(np.asarray(scorer.C), np.asarray(C))
    np.testing.assert_array_equal(np.asarray(scorer.row_sums),
                                  np.asarray(row_sums))
    if case.startswith("wrap"):
        wrapped = 35_000 - (1 << 16) if count_dtype == "int16" else 35_000
        assert int(np.asarray(scorer.C)[0, 0]) == wrapped
