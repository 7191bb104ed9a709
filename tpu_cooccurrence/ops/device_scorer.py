"""Single-device JAX scoring backend.

The TPU-idiomatic replacement of hot loops 3+4 (SURVEY §3.3-3.4): per window,
the COO pair-delta batch is scatter-added into a dense item x item count
matrix ``C`` (the AᵀA delta application), row sums are derived as a
segment-sum by source row, and every updated row is LLR-scored and top-K'd
in one vectorized pass:

  * scatter-add     — replaces ItemRowAggregator.java:26-31 + the rescorer's
                      per-entry ``addTo`` merge (:172-177)
  * segment row sums — replaces RowSumAggregator.java:15-38 (+ derivation
                      argument in ``sampling/reservoir.py``)
  * vectorized LLR  — replaces the scalar loop at
                      ItemRowRescorerTwoInputStreamOperator.java:199-223
  * ``lax.top_k``   — replaces IntDoublePriorityQueue (tie-breaking differs:
                      lowest column index wins among equal scores; the
                      reference keeps the earlier-inserted entry)

Dynamic shapes are bucketed to powers of two so XLA compiles a bounded set
of programs (SURVEY §7 "hard parts": padding/bucketing of COO buffers).
Padded pair slots carry ``delta == 0`` at indices (0, 0) — a scatter-add of
zero is a no-op. Padded row slots score row 0 and are dropped on host.

Counts are int32 by default (the reference uses Java short16 with silent
wraparound — we deliberately widen, SURVEY §7); ``count_dtype="int16"``
opts back into reference-style shorts, halving HBM so the dense matrix
reaches ~90k-item vocabularies, wraparound included. Row sums are int32
always. LLR runs in float32 via the stable ``log1p`` form (``ops/llr.py``);
``observed`` is tracked exactly on host and fed per step as a float32
scalar.
"""

from __future__ import annotations

import collections
import contextlib
import functools
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..metrics import Counters, RESCORED_ITEMS, ROW_SUM_PROCESS_WINDOW
from .. import tuning  # noqa: E402  (registry: stdlib-only)
from ..observability import LEDGER, StageClock
from ..observability.registry import REGISTRY
from ..robustness import faults
from ..sampling.reservoir import BasketBatch, PairDeltaBatch
from ..state.results import TopKBatch, pack_ids, unpack_ids
from .aggregate import (aggregate_window_coo, distinct_sorted,
                        narrow_deltas_int32)
from .donation import donate_argnums
from .llr import llr_stable


#: The pow2/pow4 plan high-water floor: every dispatch shape
#: rounds up to at least this many rows (registry-declared so
#: the autotune plane can move it).
_POW2_PAD_MIN = int(tuning.default("pow2_pad_min"))


def pad_pow2(n: int, minimum: int = _POW2_PAD_MIN) -> int:
    size = minimum
    while size < n:
        size *= 2
    return size


def pad_pow4(n: int, minimum: int = _POW2_PAD_MIN) -> int:
    """Power-of-4 bucket: ≤4x padding waste, 2x fewer compiled programs.

    Scatter/score work on padded slots is cheap device time; each distinct
    shape is an XLA compile (seconds on the chip), so a coarser
    bucket ladder wins for streaming workloads whose per-window sizes vary.
    """
    size = minimum
    while size < n:
        size *= 4
    return size


#: Most lanes one fused window's basket rectangle (ops bucket x basket
#: bucket, two lanes a cell) may expand to: an 8 MB int32 uplink at this
#: bound; a larger window takes the chained path.
FUSED_MAX_LANES = 1 << 22
#: Most rows one fused window rescores with the Pallas kernel: a pow4
#: bucket whose padding blocks the kernel skips (``dense_topk``'s
#: ``live``), so no ``[S, I]`` working set bounds it.
FUSED_MAX_ROWS = 1 << 16
#: Deferred fused windows one scorer lets wait on the device. A window's
#: uplinked basket block stays in HBM until its program runs, and the
#: fused path leaves the host so little to do that it runs ahead to the
#: runtime's own limit of queued programs: 0.1 GB of blocks at the
#: Instacart cell's shapes (v5e). Two keep the device fed, one running
#: while the next waits.
FUSED_IN_FLIGHT = 2


def pallas_auto(count_dtype: np.dtype, backend: str, top_k: int = 1) -> bool:
    """Default kernel choice for ``--pallas auto``, from on-chip measurement.

    int16 counts on a real TPU: the fused Pallas scorer, decisively — the
    XLA gather+LLR+top_k path collapses at int16 (44.3s vs the kernel's
    0.18s on [8192, 61440], a 247x gap on a v5e). int32: XLA, which wins
    ~5x there (23ms vs 120ms on [8192, 20480] — lax.top_k lowers to an
    efficient built-in selection while the in-kernel merge is
    VPU-sequential per tile). Off-TPU the
    kernel only runs interpreted (test/debug), never by default. A
    ``top_k`` beyond the kernel's output lane width falls back to XLA
    (explicit ``--pallas on`` still reports the hard limit instead).
    These numbers were measured before this round, on older code; they
    await a benchmark cell (ROADMAP A1).
    """
    from .pallas_score import _K_PAD

    return (backend == "tpu" and np.dtype(count_dtype).itemsize == 2
            and top_k <= _K_PAD)


def resolve_pallas_flag(use_pallas: str, count_dtype, top_k: int) -> bool:
    """Resolve an ``auto|on|off`` --pallas request for a DENSE scorer
    (single-chip or sharded): the measured :func:`pallas_auto` rule,
    with the top-k-overflow fallback warned rather than silent."""
    if use_pallas == "auto":
        backend = jax.default_backend()
        on = pallas_auto(count_dtype, backend, top_k)
        if not on and pallas_auto(count_dtype, backend):
            import logging

            from .pallas_score import _K_PAD

            logging.getLogger("tpu_cooccurrence").warning(
                "--top-k %d exceeds the fused kernel's %d-lane output; "
                "falling back to the XLA scorer, which is much slower "
                "at int16 counts (measured 247x before this round)",
                top_k, _K_PAD)
        return on
    if use_pallas in ("on", "off"):
        return use_pallas == "on"
    raise ValueError(f"use_pallas must be auto|on|off, got {use_pallas!r}")


def resolve_fused_flag(fused_window: str) -> bool:
    """Resolve an ``auto|on|off`` --fused-window request.

    ``auto`` is the on-chip gate: the fused one-dispatch window only
    engages on a real TPU, where per-window dispatch count and uplink
    bytes are wall-clock. Off-TPU the score kernel would run
    interpreted — a debug path, not a fast path — so a CPU run stays on
    the chained scatter+score pipeline ('on' still forces it for parity
    tests). The benchmark's basket cell (``instacart-baskets.replay``,
    tumbling reservoir windows on the dense backend) runs it 'on': 15%
    more pairs per second than the chained path on a v5e. The default
    stays 'off' because the flag also selects the sparse backend's fused
    window, which no cell has measured a win for.
    """
    if fused_window not in ("auto", "on", "off"):
        raise ValueError(
            f"fused_window must be auto|on|off, got {fused_window!r}")
    if fused_window == "auto":
        return jax.default_backend() == "tpu"
    return fused_window == "on"


def score_row_budget(num_items: int, cap: int) -> int:
    """Rows per chained score call: ``[S, I]`` int32 ≲ 1 GB.

    The XLA scorer gathers ``C[rows]`` into an ``[S, I]`` int32 block and
    scores it into ``[S, I]`` float32, so the budget bounds that working
    set well under one chip's 16 GB. The Pallas kernel holds no such
    block (it DMAs each row's 8-row group of ``C`` itself): there the
    budget only sets the chained path's rows per call, and the fused
    window scores up to ``FUSED_MAX_ROWS`` in one program.
    """
    budget_rows = max(64, (1 << 28) // max(num_items, 1))
    return min(cap, 1 << (budget_rows.bit_length() - 1))


def fit_count_dtype(arr, dtype: np.dtype) -> np.ndarray:
    """Cast checkpointed counts to a scorer's dtype.

    Widening is always safe (no scan); narrowing (int32 checkpoint ->
    int16 run) scans for out-of-range values instead of silently wrapping.
    """
    arr = np.asarray(arr)
    if arr.dtype == dtype:
        return arr
    if not np.can_cast(arr.dtype, dtype, casting="safe"):
        info = np.iinfo(dtype)
        if arr.size and (arr.min() < info.min or arr.max() > info.max):
            raise ValueError(
                f"checkpoint counts exceed {np.dtype(dtype).name} range — "
                f"restore with --count-dtype {arr.dtype.name}")
    return arr.astype(dtype)


def _apply_coo(C, row_sums, src, dst, delta, num_items: int):
    # C may be int16 (reference-style short counts, --count-dtype int16 —
    # halves HBM so the dense backend reaches ~90k-item vocabularies; cell
    # wraparound then matches the reference's documented silent-overflow
    # behavior, ItemRowAggregator.java:16). Row sums stay int32 always:
    # they grow far past 2^15.
    with jax.named_scope("scatter"):
        C = C.at[src, dst].add(delta.astype(C.dtype))
        rs_delta = jnp.zeros((num_items,), dtype=jnp.int32).at[src].add(
            delta)
        return C, row_sums + rs_delta


@functools.partial(jax.jit, donate_argnums=donate_argnums(0, 1), static_argnames=("num_items",))
def _update(C, row_sums, src, dst, delta, num_items: int):
    return _apply_coo(C, row_sums, src, dst, delta, num_items)


#: Entries one step of a stepped scatter takes (``_stepped_apply``):
#: COO lanes on the chained path, pairs (two lanes each) in the fused
#: window. A padding lane costs the scatter as much as a live one (about
#: 113 ns each on a v5e at the Instacart catalog), so both dense update
#: paths scatter only the steps their live entries fill: at most one
#: step's worth of padding a COO chunk or window, and a small scatter
#: working set. The chained path's COO buckets are powers of two of at
#: least this many lanes, so its steps never run past the buffer.
SCATTER_STEP = 1 << 14


def _stepped_apply(C, row_sums, n, lanes, num_items: int):
    """Scatter-add ``n`` entries into ``C`` and the row sums
    ``SCATTER_STEP`` at a time, for as many steps as they fill: a loop
    with a traced trip count, so one program serves every ``n`` its
    buffer can hold, and ``C`` stays the loop's carry (updated in place
    under donation). ``lanes(t)`` gives step ``t``'s lanes as ``(src,
    dst, delta)``; those of the last step past ``n`` must be no-ops."""

    def step(t, state):
        return _apply_coo(*state, *lanes(t), num_items)

    steps = (n + SCATTER_STEP - 1) // SCATTER_STEP
    return jax.lax.fori_loop(0, steps, step, (C, row_sums))


def _coo_step(coo, t):
    """Step ``t``'s ``SCATTER_STEP`` lanes of a packed ``[3, N]`` COO."""
    return jax.lax.dynamic_slice_in_dim(coo, t * SCATTER_STEP, SCATTER_STEP,
                                        axis=1)


@functools.partial(jax.jit, donate_argnums=donate_argnums(0, 1), static_argnames=("num_items",))
def _update_coo(C, row_sums, coo, n, num_items: int):
    """Scatter-apply the first ``n`` lanes of a packed ``[3, N]`` (src,
    dst, delta) COO block, in steps (``_stepped_apply``).

    Packing the three arrays into one host buffer costs one host->device
    transfer instead of three — on a latency-bound link transfer count
    matters as much as bytes.
    """
    return _stepped_apply(C, row_sums, n,
                          lambda t: _coo_step(coo, t), num_items)


@functools.partial(jax.jit, donate_argnums=donate_argnums(0, 1), static_argnames=("num_items",))
def _update_coo_u16(C, row_sums, coo, n, num_items: int):
    """Scatter-apply the first ``n`` lanes of a packed ``[3, N]`` uint16
    COO block (half the bytes), in steps.

    Used only when the vocab fits 2^16 (the caller checks ``num_items`` —
    int16-count runs can exceed that, and then ship int32 blocks); deltas
    ride as uint16 two's complement and are sign-extended step by step.
    The caller also falls back to the int32 block when a window's
    aggregated cell delta leaves int16 range.
    """

    def lanes(t):
        src, dst, delta = _coo_step(coo, t)
        return (src.astype(jnp.int32), dst.astype(jnp.int32),
                delta.astype(jnp.int16).astype(jnp.int32))  # sign-extend

    return _stepped_apply(C, row_sums, n, lanes, num_items)


@functools.partial(jax.jit, static_argnames=("n",))
def _grow_dense(C, row_sums, n: int):
    """Re-allocate the dense state to an ``n x n`` capacity (auto-derive)."""
    old = C.shape[0]
    newC = jnp.zeros((n, n), C.dtype).at[:old, :old].set(C)
    new_rs = jnp.zeros((n,), row_sums.dtype).at[:old].set(row_sums)
    return newC, new_rs


def topk_padded(scores, top_k: int):
    """``lax.top_k`` tolerating vocabularies SMALLER than K: the missing
    lanes pad with (-inf, 0), which every consumer already filters (the
    reference's heap simply holds fewer entries in this regime)."""
    k_eff = min(top_k, scores.shape[-1])
    vals, idx = jax.lax.top_k(scores, k_eff)
    if k_eff < top_k:
        pad = top_k - k_eff
        vals = jnp.concatenate(
            [vals, jnp.full(vals.shape[:-1] + (pad,), -jnp.inf,
                            vals.dtype)], axis=-1)
        idx = jnp.concatenate(
            [idx, jnp.zeros(idx.shape[:-1] + (pad,), idx.dtype)], axis=-1)
    return vals, idx


def _score_body(C, row_sums, rows, observed, top_k: int,
                packed: bool = False):
    # Shared between the chained `_score` jit and the fused window
    # program (`_fused_window_emit`/`_defer`): one body, so the two
    # dispatch shapes cannot drift numerically — the fused path's
    # bit-identical-to-chained contract rides on this.
    with jax.named_scope("gather"):
        counts = C[rows]  # [S, I] int32
        rs = row_sums.astype(jnp.float32)
        rsi = rs[rows][:, None]
    with jax.named_scope("score"):
        k11 = counts.astype(jnp.float32)
        rsj = rs[None, :]
        k12 = rsi - k11
        k21 = rsj - k11
        k22 = observed + k11 - k12 - k21
        scores = llr_stable(k11, k12, k21, k22)
        scores = jnp.where(counts != 0, scores, -jnp.inf)
        vals, idx = topk_padded(scores, top_k)
    if packed:
        # One fused [2, S, K] float32 result => a single device->host fetch.
        return jnp.stack([vals, pack_ids(idx)])
    return vals, idx


_score = functools.partial(jax.jit, static_argnames=("top_k", "packed"))(
    _score_body)


def _basket_lanes(block, step, n_pairs, num_items: int, basket_width: int):
    """Step ``step``'s ``SCATTER_STEP`` pairs of a window's basket
    uplink, as COO lanes, forward then mirrored.

    ``block`` is the packed ``[N, W + 4]`` int32 uplink: the basket
    rectangle plus the (new, len, skip, sign) meta columns; op ``r``
    emits the pairs ``new[r] <-> basket[r, c]`` for ``c < len[r]``,
    ``c != skip[r]``, each with delta ``sign[r]``. Pair ``k`` of the
    window is the ``k``-th such cell in op order. Lanes past the
    window's ``n_pairs`` are sent past the last row, where the scatter
    drops them.
    """
    w = basket_width
    lens, skips = block[:, w + 1], block[:, w + 2]
    per_op = lens - ((skips >= 0) & (skips < lens)).astype(jnp.int32)
    starts = jnp.cumsum(per_op) - per_op
    meta = jnp.stack([starts, skips, block[:, w + 3], block[:, w]], axis=1)
    base = step * SCATTER_STEP
    k = base + jnp.arange(SCATTER_STEP, dtype=jnp.int32)
    # Each pair's op: the ops that start before the step, plus one mark
    # at every op's first pair inside it, summed up.
    at = starts - base
    marks = jnp.zeros((SCATTER_STEP,), jnp.int32).at[
        jnp.where(at >= 0, at, SCATTER_STEP)].add(1, mode="drop")
    op = jnp.sum(at < 0, dtype=jnp.int32) + jnp.cumsum(marks) - 1
    first, skip, sign, new = meta[op].T
    col = k - first
    col = col + ((skip >= 0) & (col >= skip)).astype(jnp.int32)
    partner = block[op, jnp.minimum(col, w - 1)]
    live = k < n_pairs
    src = jnp.concatenate([jnp.where(live, new, num_items),
                           jnp.where(live, partner, num_items)])
    return src, jnp.concatenate([partner, new]), jnp.concatenate([sign,
                                                                  sign])


def _fused_apply_baskets(C, row_sums, block, n_pairs, num_items: int,
                         basket_width: int):
    """Expansion + scatter half of the fused window program: the
    window's ``n_pairs`` pairs, expanded from the basket uplink on the
    device (``_basket_lanes``) and scatter-added into ``C`` and the row
    sums ``SCATTER_STEP`` pairs at a time, for as many steps as they
    fill (a loop with a traced trip count: one program serves every pair
    count the rectangle can hold)."""
    return _stepped_apply(
        C, row_sums, n_pairs,
        lambda t: _basket_lanes(block, t, n_pairs, num_items, basket_width),
        num_items)


def _fused_score_packed(C, row_sums, rows, live, observed, top_k: int,
                        use_pallas: bool, tile: int, interpret: bool):
    """Score half of the fused program: the SAME math as the chained
    path — ``_score_body`` when the Pallas score kernel is off, the
    shared ``pallas_score.dense_topk`` core when it is on — so fused and
    chained results are bitwise equal, not just close. With the kernel,
    the blocks of rows past the first ``live`` are skipped."""
    if not use_pallas:
        return _score_body(C, row_sums, rows, observed, top_k, packed=True)
    from .pallas_score import dense_topk

    # The caller pads rows to a pow4 bucket (a row-block multiple).
    vals, idx = dense_topk(C, rows, row_sums, observed, top_k=top_k,
                           tile=tile, interpret=interpret, live=live)
    # Value-space id packing, exactly like pallas_score_topk(packed=True).
    return jnp.stack([vals[:, :top_k], idx[:, :top_k]])


@functools.partial(jax.jit, donate_argnums=donate_argnums(0, 1),
                   static_argnames=("num_items", "basket_width", "top_k",
                                    "use_pallas", "tile", "interpret"))
def _fused_window_emit(C, row_sums, block, n_pairs, rows, live, observed, *,
                       num_items: int, basket_width: int, top_k: int,
                       use_pallas: bool, tile: int, interpret: bool):
    """ONE-dispatch fused window (streaming-results form): on-chip
    basket expansion + count scatter + row-sum maintenance + LLR rescore
    + per-row top-K, one XLA program per (ops-bucket, basket-bucket,
    rows-bucket) shape triple. Replaces the chained path's separate
    update and score dispatches and its 3x-wider COO uplink."""
    C, row_sums = _fused_apply_baskets(C, row_sums, block, n_pairs,
                                       num_items, basket_width)
    packed = _fused_score_packed(C, row_sums, rows, live, observed, top_k,
                                 use_pallas, tile, interpret)
    return C, row_sums, packed


@functools.partial(jax.jit, donate_argnums=donate_argnums(0, 1, 2),
                   static_argnames=("num_items", "basket_width", "top_k",
                                    "use_pallas", "tile", "interpret"))
def _fused_window_defer(C, row_sums, tbl, block, n_pairs, rows, live,
                        scatter_rows, observed, *, num_items: int,
                        basket_width: int, top_k: int, use_pallas: bool,
                        tile: int, interpret: bool):
    """Deferred-results form of :func:`_fused_window_emit`: the packed
    top-K scatters into the device-resident results table inside the
    same program — a steady-state window is literally one dispatch and
    zero result downlink. Padded score rows carry the ``_SENT_ROW``
    sentinel and drop out of the scatter. The last output is a scalar
    the host can wait on for the window (every other output is donated
    to the next dispatch)."""
    C, row_sums = _fused_apply_baskets(C, row_sums, block, n_pairs,
                                       num_items, basket_width)
    packed = _fused_score_packed(C, row_sums, rows, live, observed, top_k,
                                 use_pallas, tile, interpret)
    return (C, row_sums, tbl.at[:, scatter_rows].set(packed, mode="drop"),
            row_sums[0])


def check_coo_chunk(coo: np.ndarray, n: int) -> None:
    """Pad-slot invariant guard for packed COO chunks (regression).

    The chained path's correctness under padding rests on two facts: a
    chunk's ``n`` real entries fit its padded buffer (a chunk larger
    than ``max_pairs_per_step``'s bucket must never silently truncate),
    and every pad slot carries the ``(0, 0) delta == 0`` triple whose
    scatter-add is a no-op. Both held by construction until someone
    reuses buffers; this check makes a violation an error at the
    window that caused it, not a silently-wrong count matrix. O(pad)
    over a buffer the caller just wrote — noise next to the fold.
    """
    if n > coo.shape[1]:
        raise AssertionError(
            f"COO chunk holds {n} entries but its padded buffer is only "
            f"{coo.shape[1]} wide — entries would be silently truncated")
    if n < coo.shape[1] and coo[:, n:].any():
        raise AssertionError(
            "COO pad slots must stay (0, 0) delta == 0: a nonzero pad "
            "slot would scatter garbage into C")


# Result-table scatter sentinel for padded score rows: >= any vocab
# capacity, dropped by mode="drop". Padded rows may not scatter under
# their gather stand-in (row 0) — that would overwrite item 0's entry
# with scores from a *later* matrix state than its last real emission.
_SENT_ROW = np.int32(2**31 - 1)


@functools.partial(jax.jit, donate_argnums=donate_argnums(0))
def _scatter_packed(tbl, packed, scatter_rows):
    return tbl.at[:, scatter_rows].set(packed, mode="drop")


@jax.jit
def _gather_packed(tbl, rows):
    return tbl[:, rows]


@functools.partial(jax.jit, static_argnames=("items_cap", "top_k"))
def _empty_table(items_cap: int, top_k: int):
    return jnp.full((2, items_cap, top_k), -jnp.inf, jnp.float32)


@functools.partial(jax.jit, static_argnames=("items_cap",))
def _resized_table(tbl, items_cap: int):
    m = min(items_cap, tbl.shape[1])
    return _empty_table(items_cap, tbl.shape[2]).at[:, :m].set(tbl[:, :m])


class DeferredResultsTable:
    """Device-resident latest-results table for deferred-results scorers.

    Final-state consumption mode (no ``--emit-updates``): each window's
    score dispatch scatters its packed ``[2, S_pad, K]`` top-K block into
    ``tbl`` (``[2, items_cap, K]`` float32 on device) instead of
    returning it to the host; :meth:`drain` fetches only the rows
    scattered since the last drain, in one exact-bytes gather. Per-window
    result downlink drops to zero — on a high-latency link the dominant
    wall cost of large windows. Shared by the dense and sparse scorers;
    the sparse scorer fuses the scatter into its scoring jit and so
    reassigns :attr:`tbl` directly (it is donated there).

    The caller owns — and must absorb — every drained row: rows fetched
    earlier persist in the job's ``LatestResults``, which keeps periodic
    checkpoints incremental (O(rows since last drain), not O(all rows)).
    """

    def __init__(self, top_k: int, items_cap: int) -> None:
        self.top_k = top_k
        self.tbl = None  # lazy: allocated at the first scoring dispatch
        self.dirty = np.zeros(items_cap, dtype=bool)

    def resize(self, items_cap: int) -> int:
        """Track a vocab-capacity change, preserving entries and marks.
        Returns the number of device programs launched (0 or 1)."""
        m = min(items_cap, len(self.dirty))
        dirty = np.zeros(items_cap, dtype=bool)
        dirty[:m] = self.dirty[:m]
        self.dirty = dirty
        if self.tbl is None or self.tbl.shape[1] == items_cap:
            return 0
        self.tbl = _resized_table(self.tbl, items_cap=items_cap)
        return 1

    def ensure(self) -> int:
        """Allocate the device table (before a window's first scatter).
        Returns the number of device programs launched (0 or 1)."""
        if self.tbl is not None:
            return 0
        self.tbl = _empty_table(items_cap=len(self.dirty), top_k=self.top_k)
        return 1

    def scatter(self, packed, scatter_rows: np.ndarray) -> None:
        """Scatter one packed block; padded entries must carry a sentinel
        index (``_SENT_ROW``), not their row-0 gather stand-in."""
        self.tbl = _scatter_packed(self.tbl, packed,
                                   jnp.asarray(scatter_rows))

    def mark(self, rows: np.ndarray) -> None:
        self.dirty[rows] = True

    def drain(self, float_ids: bool = False):
        """Fetch rows scored since the last drain as a :class:`TopKBatch`.

        ``float_ids``: ids were packed as float *values* (the Pallas
        kernel's encoding) rather than by :func:`pack_ids`.
        """
        from ..state.results import TopKBatch, unpack_ids

        rows = np.flatnonzero(self.dirty)
        if self.tbl is None or len(rows) == 0:
            return TopKBatch.empty(self.top_k)
        n = len(rows)
        rows_pad = np.zeros(pad_pow2(n, minimum=16), np.int32)
        rows_pad[:n] = rows
        LEDGER.up("drain-rows", rows_pad)
        host = np.asarray(_gather_packed(self.tbl, jnp.asarray(rows_pad)))
        LEDGER.down("results-drain", host)
        # Clear marks only once the host copy is in hand: a transient
        # fetch failure must leave the rows dirty so a retrying caller
        # can still drain them.
        self.dirty[rows] = False
        idx = (host[1, :n].astype(np.int32) if float_ids
               else unpack_ids(host[1, :n]))
        return TopKBatch(rows.astype(np.int32), idx, host[0, :n])

    def reset(self, items_cap: int) -> None:
        """Restart empty (restore path: pre-checkpoint rows already live
        in the job's LatestResults, flushed before every save)."""
        self.tbl = None
        self.dirty = np.zeros(items_cap, dtype=bool)


class DeviceScorer:
    """Dense sharless device backend over a fixed item-vocab capacity."""

    # Column-tile width for the fused kernel. Swept on-chip at the int16
    # max-vocab shape [8192, 61440] before this round (awaits a cell):
    # 2048 -> 179ms, 1024 -> 224ms, 512 -> 300ms — wider tiles amortize
    # the sequential top-K merge, and the (16, 2048) int16 block is still
    # far under VMEM.
    PALLAS_TILE = 2048

    def __init__(self, num_items: int, top_k: int,
                 counters: Optional[Counters] = None,
                 max_score_rows_per_call: int = 8192,
                 max_pairs_per_step: int = 1 << 20,
                 use_pallas: str = "auto",
                 count_dtype: str = "int32",
                 device=None,
                 defer_results: bool = False,
                 fused_window: str = "off") -> None:
        if count_dtype not in ("int32", "int16"):
            raise ValueError(f"count_dtype must be int32|int16, got {count_dtype}")
        self.count_dtype = np.dtype(count_dtype)
        self.top_k = top_k
        self.counters = counters if counters is not None else Counters()
        self._max_score_rows_cap = max_score_rows_per_call
        self.max_pairs_per_step = max_pairs_per_step
        self.use_pallas = resolve_pallas_flag(use_pallas, self.count_dtype,
                                              top_k)
        # Fused one-dispatch window path (--fused-window): the sampler
        # uplinks baskets instead of expanded COO and expansion + count
        # update + rescore + top-K run as one program per shape triple.
        # The job enables basket emission iff this resolved True.
        self.use_fused = resolve_fused_flag(fused_window)
        # Basket uplinks are the DENSE fused path's wire format (the
        # device expands them, _basket_lanes); the sparse fused path
        # consumes aggregated deltas instead and leaves this False.
        self.wants_baskets = self.use_fused
        # One scalar per deferred fused window still on the device, oldest
        # first (FUSED_IN_FLIGHT bounds them).
        self._fused_queue: collections.deque = collections.deque()
        # Which path the LAST process_window dispatch took — the
        # journal's ``fused`` field and /healthz read it.
        self.last_dispatch_fused = False
        # Tracing plane: per-window stage seconds (index / uplink-encode
        # / rescore; the job carves the rest of score_seconds into
        # dispatch) and counts (launches, score_cells, live_cells; with
        # the Pallas kernel fetch_cells; on the chained update
        # scatter_lanes and scatter_live).
        self.stage_clock = StageClock()
        self._fused_dispatches = REGISTRY.gauge(
            "cooc_fused_dispatches_total",
            help="windows dispatched through the fused one-dispatch "
                 "window program")
        self._chained_dispatches = REGISTRY.gauge(
            "cooc_chained_dispatches_total",
            help="windows dispatched through the chained "
                 "scatter+score path")
        # Off-TPU the kernel can only run interpreted (test/debug use).
        self._pallas_interpret = jax.default_backend() != "tpu"
        # num_items == 0: derive the vocab from the data — start at a
        # modest capacity and double C whenever a window's max dense id
        # outgrows it (amortized O(final) copy work). An explicit
        # num_items stays a hard capacity (the job enforces it).
        self.auto_capacity = num_items <= 0
        if self.auto_capacity:
            num_items = pad_pow2(max(1 << 10, top_k))
        if self.use_pallas:
            # Pad the vocab so the Pallas column-tile grid divides evenly;
            # the extra columns stay zero and are masked out of scoring.
            self.num_items = ((num_items + self.PALLAS_TILE - 1)
                              // self.PALLAS_TILE) * self.PALLAS_TILE
        else:
            self.num_items = num_items
        self.num_items_logical = num_items
        # Bound each score call's [S, I] working set so vocab-ceiling
        # configurations don't OOM; the result-fetch pipeline hides the
        # extra per-chunk round trips.
        self.max_score_rows = score_row_budget(self.num_items,
                                               self._max_score_rows_cap)
        self.device = device
        num_items = self.num_items
        with jax.default_device(device) if device is not None else contextlib.nullcontext():
            self.C = jnp.zeros((num_items, num_items),
                               dtype=jnp.dtype(self.count_dtype.name))
            self.row_sums = jnp.zeros((num_items,), dtype=jnp.int32)
        self.observed = 0  # exact, host-side (int), fed to kernels as f32
        # Result pipeline: window results are fetched one window late so the
        # device->host copy (latency-bound) overlaps the next window's
        # host sampling and device dispatch. ``flush()``
        # returns the final in-flight window.
        self._pending: Optional[List] = None
        self.last_dispatched_rows = 0
        # scorer_breaker fault-site ordinal (robustness plane): counts
        # this scorer's process_window calls so chaos tests can fail a
        # specific dispatch and trip the circuit breaker wrapper.
        self._breaker_seq = 0
        # Deferred-results mode (final-state consumption, no streaming):
        # see DeferredResultsTable.
        self.defer_results = bool(defer_results)
        self._results = (DeferredResultsTable(top_k, self.num_items)
                         if self.defer_results else None)

    def _ensure_capacity(self, max_id: int) -> None:
        if max_id < self.num_items:
            return
        if not self.auto_capacity:
            raise ValueError(
                f"item id {max_id} exceeds --num-items capacity "
                f"{self.num_items_logical}")
        n = self.num_items
        while n <= max_id:
            n *= 2
        self.stage_clock.add("launches")
        self.C, self.row_sums = _grow_dense(self.C, self.row_sums, n=n)
        self.num_items = self.num_items_logical = n
        self.max_score_rows = score_row_budget(n, self._max_score_rows_cap)
        if self._results is not None:
            self.stage_clock.add("launches", self._results.resize(n))

    def process_window(self, ts: int, pairs) -> TopKBatch:
        self._breaker_seq += 1
        if faults.PLAN is not None:
            # The breaker's trip input: an injected exception here is a
            # failed device dispatch the ScorerCircuitBreaker absorbs.
            faults.PLAN.fire("scorer_breaker", seq=self._breaker_seq)
        self.last_dispatched_rows = 0
        self.last_dispatch_fused = False
        clk = self.stage_clock
        clk.reset()
        if isinstance(pairs, BasketBatch):
            if self.use_fused:
                routed = self._try_fused(ts, pairs)
                if routed is not None:
                    return routed
                clk.add("fused_windows", 0)
            # Not fused-routable (oversized window / kernel limit) or
            # fused resolved off: expand host-side and run the chained
            # path — the same pair multiset, so results are identical.
            pairs = pairs.to_pairs()
        if len(pairs) == 0:
            if self.defer_results:
                # Nothing in flight; results wait for the final flush.
                return TopKBatch.empty(self.top_k)
            # No new dispatch this window — drain any completed in-flight
            # results now instead of withholding them behind idle windows.
            return self.flush()
        with clk.stage("index"):
            self._ensure_capacity(int(max(pairs.src.max(),
                                          pairs.dst.max())))
            src, dst, agg_delta = aggregate_window_coo(
                pairs.src, pairs.dst, pairs.delta)
            agg_delta = narrow_deltas_int32(agg_delta)
            rows = distinct_sorted(src)

        # Bounded COO buckets: chunk to max_pairs_per_step, pad each chunk to
        # a power of two of at least SCATTER_STEP lanes (recompile guard,
        # SURVEY §7 "dynamic shapes"). pow-2 (not the score path's pow-4):
        # post-aggregation sizes sit in a narrow steady-state band, so the
        # finer ladder costs few extra compiles (amortized by the on-disk
        # XLA cache) and halves the worst-case transfer padding. The device
        # scatters only the SCATTER_STEP steps the chunk's n live lanes
        # fill (n rides as a traced scalar, so the bucket alone keys the
        # program); the rest of the last step is the (0, 0) delta 0
        # padding, a no-op. The chunk ships as one packed [3, N] buffer
        # (one transfer, not three).
        # uint16 wire format halves transfer bytes whenever the vocab and
        # the window's cell deltas allow it (bytes on the link are
        # wall-clock).
        with clk.stage("uplink-encode"):
            use_u16 = (self.num_items <= (1 << 16)
                       and len(agg_delta) > 0
                       and int(agg_delta.min()) >= -(1 << 15)
                       and int(agg_delta.max()) < (1 << 15))
            for lo in range(0, len(src), self.max_pairs_per_step):
                n = min(len(src) - lo, self.max_pairs_per_step)
                pad = pad_pow2(n, minimum=SCATTER_STEP)
                if use_u16:
                    coo = np.zeros((3, pad), dtype=np.uint16)
                    coo[2, :n] = agg_delta[lo: lo + n].astype(
                        np.int16).view(np.uint16)
                    update = _update_coo_u16
                else:
                    coo = np.zeros((3, pad), dtype=np.int32)
                    coo[2, :n] = agg_delta[lo: lo + n]
                    update = _update_coo
                coo[0, :n] = src[lo: lo + n]
                coo[1, :n] = dst[lo: lo + n]
                check_coo_chunk(coo, n)
                clk.add("launches")
                clk.add("scatter_lanes", SCATTER_STEP * -(-n // SCATTER_STEP))
                clk.add("scatter_live", n)
                LEDGER.up("coo", coo)
                self.C, self.row_sums = update(
                    self.C, self.row_sums, coo, np.int32(n),
                    num_items=self.num_items)

        window_sum = int(pairs.delta.sum())
        self.observed += window_sum
        self.counters.add(ROW_SUM_PROCESS_WINDOW, window_sum)

        self.counters.add(RESCORED_ITEMS, len(rows))
        self.last_dispatched_rows = len(rows)
        self._chained_dispatches.add(1)
        if self.defer_results:
            clk.add("launches", self._results.ensure())
        chunks: List[Tuple[np.ndarray, int, object]] = []
        with clk.stage("rescore"):
            for lo in range(0, len(rows), self.max_score_rows):
                chunk = rows[lo: lo + self.max_score_rows]
                s = len(chunk)
                pad_s = min(pad_pow4(s, minimum=64), self.max_score_rows)
                rows_padded = np.zeros(pad_s, dtype=np.int32)
                rows_padded[:s] = chunk
                self._count_scored(s, rows_padded)
                LEDGER.up("score-rows", rows_padded)
                if self.use_pallas:
                    from .pallas_score import pallas_score_topk

                    packed = pallas_score_topk(
                        self.C, self.row_sums, jnp.asarray(rows_padded),
                        np.float32(self.observed), top_k=self.top_k,
                        tile=self.PALLAS_TILE,
                        interpret=self._pallas_interpret, packed=True)
                else:
                    packed = _score(self.C, self.row_sums, rows_padded,
                                    np.float32(self.observed),
                                    top_k=self.top_k, packed=True)
                if self.defer_results:
                    # Padded entries gather row 0 but must NOT scatter
                    # there.
                    scatter_rows = np.full(pad_s, _SENT_ROW, dtype=np.int32)
                    scatter_rows[:s] = chunk
                    clk.add("launches")
                    self._results.scatter(packed, scatter_rows)
                    continue
                if hasattr(packed, "copy_to_host_async"):
                    packed.copy_to_host_async()
                chunks.append((chunk, s, packed))
        if self.defer_results:
            self._results.mark(rows)
            return TopKBatch.empty(self.top_k)
        prev, self._pending = self._pending, chunks
        return (self._materialize(prev) if prev is not None
                else TopKBatch.empty(self.top_k))

    def _count_scored(self, rows: int, rows_padded: np.ndarray) -> None:
        """One scoring program over ``rows_padded`` rows of the catalog
        width, the first ``rows`` of them live: the window's launch and
        cell counts, and with the Pallas kernel the cells it fetches from
        ``C`` (whole 8-row groups; over ``live_cells``, the fetch's read
        amplification)."""
        clk = self.stage_clock
        clk.add("launches")
        clk.add("score_cells", len(rows_padded) * self.num_items)
        clk.add("live_cells", rows * self.num_items)
        if self.use_pallas:
            from .pallas_score import fetch_cells

            clk.add("fetch_cells", fetch_cells(rows_padded, self.num_items))

    def _try_fused(self, ts: int, b: BasketBatch) -> Optional[TopKBatch]:
        """Run one window through the fused one-dispatch program, or
        return ``None`` when the window is not fused-routable — the
        caller then expands host-side and takes the chained path, which
        produces identical results (same pair multiset, same score
        math). Not routable: zero-pair windows (the chained empty-window
        contract applies), windows whose basket rectangle's lanes exceed
        ``FUSED_MAX_LANES``, rescore sets beyond ``FUSED_MAX_ROWS`` (one
        score chunk without the Pallas kernel, whose ``[S, I]`` gather
        the chunk bounds), and configurations the Pallas score kernel
        itself rejects on the chained path (vocab > 2^24, K > lane
        width) — the chained path raises the canonical error for those.
        """
        per_op = b.pairs_per_op()
        n_pairs = int(per_op.sum())
        if n_pairs == 0:
            return None
        if self.use_pallas:
            from .pallas_score import _K_PAD

            if self.top_k > _K_PAD or self.num_items > (1 << 24):
                return None
        clk = self.stage_clock
        with clk.stage("index"):
            valid = b._valid()
            active = per_op > 0
            self._ensure_capacity(int(max(b.new_items[active].max(),
                                          b.baskets[valid].max())))
            n_ops = b.n_ops
            n_cap = pad_pow2(n_ops, minimum=64)
            l_cap = pad_pow2(max(int(b.baskets.shape[1]), 1), minimum=128)
            if 2 * n_cap * l_cap > FUSED_MAX_LANES:
                # The uplinked rectangle would pass its bound: oversized
                # windows stay chained, where the COO upload is chunked.
                return None
            # Rescore set: every item touched by an emitted pair — the
            # union of active star items and valid basket cells, exactly
            # the chained path's distinct_sorted(src) set (np.unique sorts).
            rows = np.unique(np.concatenate([
                b.new_items[active].astype(np.int64),
                b.baskets[valid].astype(np.int64)])).astype(np.int32)
            if len(rows) > (FUSED_MAX_ROWS if self.use_pallas
                            else self.max_score_rows):
                return None

        with clk.stage("uplink-encode"):
            # Single packed uplink: basket rectangle + 4 meta columns. Pad
            # ops carry (len 0, sign 0) — zero expanded lanes. Basket cells
            # beyond each op's len ride up unspecified and are never read
            # (the expansion reads each op's first len cells), same
            # contract as the sampler's storage.
            blockbuf = np.zeros((n_cap, l_cap + 4), dtype=np.int32)
            w = b.baskets.shape[1]
            if w:
                blockbuf[:n_ops, :w] = b.baskets
            blockbuf[:, l_cap + 2] = -1
            blockbuf[:n_ops, l_cap] = b.new_items
            blockbuf[:n_ops, l_cap + 1] = b.lens
            blockbuf[:n_ops, l_cap + 2] = b.skips
            blockbuf[:n_ops, l_cap + 3] = b.signs

        # Exact host-side observed tracking, identical to the chained
        # path's pairs.delta.sum(): each op contributes 2 * sign * pairs.
        window_sum = int((2 * b.signs.astype(np.int64) * per_op).sum())
        self.observed += window_sum
        self.counters.add(ROW_SUM_PROCESS_WINDOW, window_sum)
        self.counters.add(RESCORED_ITEMS, len(rows))
        self.last_dispatched_rows = len(rows)
        self.last_dispatch_fused = True
        self._fused_dispatches.add(1)

        s = len(rows)
        # One pow4 bucket of rows per program. The Pallas kernel skips
        # the blocks past the live rows, so it scores (and counts) only
        # those; the XLA scorer scores every padded row.
        pad_s = pad_pow4(s, minimum=64)
        if not self.use_pallas:
            pad_s = min(pad_s, self.max_score_rows)
        rows_padded = np.zeros(pad_s, dtype=np.int32)
        rows_padded[:s] = rows
        from .pallas_score import BLOCK_ROWS

        scored = (-(-s // BLOCK_ROWS) * BLOCK_ROWS if self.use_pallas
                  else pad_s)
        self._count_scored(s, rows_padded[:scored])
        clk.add("fused_windows")
        clk.add("expand_lanes",
                2 * SCATTER_STEP * -(-n_pairs // SCATTER_STEP))
        clk.add("expand_live", 2 * n_pairs)
        observed = np.float32(self.observed)
        live, pairs = np.int32(s), np.int32(n_pairs)
        if self.defer_results:
            self.stage_clock.add("launches", self._results.ensure())
            # Padded entries gather row 0 but must NOT scatter there.
            scatter_rows = np.full(pad_s, _SENT_ROW, dtype=np.int32)
            scatter_rows[:s] = rows
            LEDGER.up_basket("fused-window", blockbuf, rows_padded,
                             scatter_rows)
            while len(self._fused_queue) >= FUSED_IN_FLIGHT:
                self._fused_queue.popleft().block_until_ready()
            (self.C, self.row_sums, self._results.tbl,
             done) = _fused_window_defer(
                self.C, self.row_sums, self._results.tbl, blockbuf, pairs,
                rows_padded, live, scatter_rows, observed,
                num_items=self.num_items, basket_width=l_cap,
                top_k=self.top_k, use_pallas=self.use_pallas,
                tile=self.PALLAS_TILE, interpret=self._pallas_interpret)
            self._fused_queue.append(done)
            self._results.mark(rows)
            return TopKBatch.empty(self.top_k)
        LEDGER.up_basket("fused-window", blockbuf, rows_padded)
        self.C, self.row_sums, packed = _fused_window_emit(
            self.C, self.row_sums, blockbuf, pairs, rows_padded, live,
            observed,
            num_items=self.num_items, basket_width=l_cap,
            top_k=self.top_k, use_pallas=self.use_pallas,
            tile=self.PALLAS_TILE, interpret=self._pallas_interpret)
        if hasattr(packed, "copy_to_host_async"):
            packed.copy_to_host_async()
        # Same one-window-behind result pipeline as the chained path.
        prev, self._pending = self._pending, [(rows, s, packed)]
        return (self._materialize(prev) if prev is not None
                else TopKBatch.empty(self.top_k))

    def flush(self) -> TopKBatch:
        """Emit the final in-flight window's results (end of pipeline).

        Deferred mode: drain rows scored since the last flush from the
        device table in one exact-bytes gather (the caller owns — and must
        absorb — the returned rows; see SparseDeviceScorer.flush)."""
        if self.defer_results:
            # Pallas packs ids as float values; XLA as an int32 bitcast.
            return self._results.drain(float_ids=self.use_pallas)
        prev, self._pending = self._pending, None
        return (self._materialize(prev) if prev is not None
                else TopKBatch.empty(self.top_k))

    def _materialize(self, chunks) -> TopKBatch:
        rows_l, idx_l, vals_l = [], [], []
        for chunk, s, packed in chunks:
            host = np.asarray(packed)  # single [2, S, K] fetch
            LEDGER.down("results", host)
            rows_l.append(chunk)
            vals_l.append(host[0, :s])
            if self.use_pallas:
                # Pallas packs ids as float values (see pallas_score.py).
                idx_l.append(host[1, :s].astype(np.int32))
            else:
                idx_l.append(unpack_ids(host[1, :s]))
        return TopKBatch.concatenate(rows_l, idx_l, vals_l, self.top_k)

    # -- checkpoint ------------------------------------------------------

    def checkpoint_state(self) -> dict:
        return {
            "C": np.asarray(self.C),
            "row_sums": np.asarray(self.row_sums),
            "observed": np.asarray([self.observed], dtype=np.int64),
        }

    def restore_state(self, st: dict) -> None:
        ck = fit_count_dtype(st["C"], self.count_dtype)
        if self.auto_capacity and ck.shape[0] > self.num_items:
            # Derived-capacity scorers adopt the checkpoint's size —
            # re-applying the Pallas tile rounding the constructor performs
            # (the checkpoint may come from a non-pallas run whose capacity
            # is not a tile multiple).
            n = ck.shape[0]
            if self.use_pallas:
                n = ((n + self.PALLAS_TILE - 1)
                     // self.PALLAS_TILE) * self.PALLAS_TILE
            self.num_items = self.num_items_logical = n
            self.max_score_rows = score_row_budget(self.num_items,
                                                   self._max_score_rows_cap)
        if ck.shape != (self.num_items, self.num_items):
            # Vocab padding differs between runs when the pallas setting
            # changes (the kernel pads to tile multiples). Both layouts hold
            # the same logical vocab, so translate: slice a larger padded
            # checkpoint / zero-extend a smaller one — after verifying no
            # live counts fall outside this scorer's capacity.
            n = ck.shape[0]
            if (n > self.num_items
                    and (ck[self.num_items:].any()
                         or ck[:, self.num_items:].any())):
                raise ValueError(
                    f"checkpoint C shape {ck.shape} holds counts beyond this "
                    f"scorer's capacity {self.num_items} — restore with "
                    f"--num-items >= the checkpointing run's")
            fitted = np.zeros((self.num_items, self.num_items),
                              dtype=self.count_dtype)
            m = min(n, self.num_items)
            fitted[:m, :m] = ck[:m, :m]
            ck = fitted
            rs = np.zeros((self.num_items,), dtype=np.int32)
            rs[:m] = np.asarray(st["row_sums"], dtype=np.int32)[:m]
        else:
            rs = np.asarray(st["row_sums"], dtype=np.int32)
        self.C = jnp.asarray(ck)
        self.row_sums = jnp.asarray(rs)
        self.observed = int(st["observed"][0])
        # In-flight results belong to windows after the checkpoint; a
        # restore that rolls back must not emit them.
        self._pending = None
        if self._results is not None:
            self._results.reset(self.num_items)
