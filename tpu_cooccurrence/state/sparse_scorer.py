"""Device-resident sparse backend: HBM slab matrix + host-side index.

The TPU-first answer to the 1M-item regime (benchmark config 4), where a
dense item x item ``C`` is infeasible and the hybrid backend's
ship-rows-per-window design drowns in host<->device transfer: the
co-occurrence matrix *values* live permanently in device HBM and only the
window's aggregated deltas travel up / packed top-K results travel down.
Per window that is a few hundred KB instead of the hybrid's padded
[S, R] count rectangles — on a bandwidth/latency-bound link
(DCN-attached hosts in general) transfer volume is the whole game.

Design (no reference analogue — the reference delegates all state to
Flink's heap, ``ItemRowRescorerTwoInputStreamOperator.java:33-37``):

* **Host keeps the index, device keeps the data.** The host maintains the
  sorted packed-key array of all matrix cells (:class:`SlabIndex`) plus,
  per cell, the *device slot* its count lives in. Every placement
  decision (slot assignment, row growth, compaction) is host-computed
  numpy; the device never needs data-dependent control flow — every
  kernel is a fixed-shape scatter/gather jit, exactly what XLA wants.
* **Per-row slab allocation.** Each item row owns a contiguous device
  region with power-of-two capacity. New cells append at ``start+len``;
  an outgrown row is relocated by an on-device gather/scatter (the move
  *instructions* — old start, new start, length — are the only upload).
  Freed regions are reclaimed by an infrequent whole-heap compaction.
* **Scoring reads HBM, not the wire.** Updated rows are scored in
  length-bucketed ``[S_pad, R]`` rectangles gathered *on device* from the
  slab (``cnt``/``dst`` arrays), with row sums resident too; only the
  packed ``[2, S, K]`` result is fetched, one window late (same
  result pipeline as the other device backends).

Per-cell device cost: 8 bytes (int32 count + int32 partner id) + amortized
slack from power-of-two row caps — ~16 GB HBM holds ~1e9 cells, far above
any stream the cuts (fMax/kMax, ``Configuration.java:151-152``) admit.

Tie-breaking among equal scores: ``lax.top_k`` keeps the lowest slot
index, i.e. the earliest-*inserted* cell of the row — which matches the
reference's heap behavior (it keeps the earlier entry) rather than the
dense backend's lowest-item-id rule. All cross-backend tests compare ids
only where score gaps exceed tolerance.

:class:`SlabIndex` is row-id-space agnostic so the multi-chip backend
(``parallel/sharded_sparse.py``) can keep one index per shard over
shard-local row ids and slots.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import tuning
from ..metrics import Counters, RESCORED_ITEMS, ROW_SUM_PROCESS_WINDOW
from ..observability import LEDGER, StageClock
from ..observability.registry import REGISTRY
from ..robustness import faults
from ..ops.aggregate import (AggregatedPairs, aggregate_window_coo,
                             distinct_sorted, merge_sorted_insert,
                             narrow_deltas_int32)
from ..ops.device_scorer import (DeferredResultsTable, pad_pow2, pad_pow4,
                                 split_upload_auto)
from ..ops.donation import donate_argnums
from ..ops.llr import llr_stable
from ..sampling.reservoir import PairDeltaBatch, _ragged_arange
from .results import TopKBatch, pack_ids, unpack_ids

# Scatter index sentinel: >= any capacity, dropped by mode="drop".
_SENT = np.int32(2**31 - 1)


def _moves_body(cnt, dst, mv, L: int):
    """Relocate outgrown rows inside the slab (trace body).

    ``mv``: [3, Mv] int32 (old_start, new_start, len); padded rows carry
    len == 0. Reads and writes never overlap: new regions are freshly
    allocated past the heap end or in compacted space.
    """
    old_start, new_start, ln = mv[0], mv[1], mv[2]
    with jax.named_scope("slab-update"):
        col = jnp.arange(L, dtype=jnp.int32)[None, :]
        valid = col < ln[:, None]
        src_idx = jnp.where(valid, old_start[:, None] + col, 0)
        out_idx = jnp.where(valid, new_start[:, None] + col, _SENT)
        cnt = cnt.at[out_idx.ravel()].set(cnt[src_idx].ravel(), mode="drop")
        dst = dst.at[out_idx.ravel()].set(dst[src_idx].ravel(), mode="drop")
        return cnt, dst


def _update_body(cnt, dst, row_sums, upd, bounds):
    """Apply one window's state changes (trace body).

    ``upd``: [2, N] int32 — three concatenated sections along axis 1
    (boundaries in ``bounds``; intra-section padding uses sentinel
    indices, dropped by the scatters):

      [0, b0)   new cells:   (slot, partner item id) — writes ``dst``,
                zeroes ``cnt`` (slots may hold stale bytes from a freed
                region)
      [b0, b1)  cell deltas: (slot, +/-count) — scatter-add into ``cnt``
      [b1, N)   row sums:    (item, +/-sum)   — scatter-add into
                ``row_sums``

    Section order matters: new-cell zeroing must precede the delta add.
    The device-side stage name (op metadata in a profiler trace) is
    ``slab-update``.
    """
    with jax.named_scope("slab-update"):
        cnt, dst = _apply_cells(cnt, dst, upd, bounds)
        pos = jnp.arange(upd.shape[1], dtype=jnp.int32)
        rs_idx = jnp.where(pos >= bounds[1], upd[0], _SENT)
        row_sums = row_sums.at[rs_idx].add(
            jnp.where(pos >= bounds[1], upd[1], 0), mode="drop")
        return cnt, dst, row_sums


_apply_update = functools.partial(jax.jit, donate_argnums=donate_argnums(0, 1, 2))(
    _update_body)


@functools.partial(jax.jit, donate_argnums=donate_argnums(0, 1, 2))
def _apply_update_chunked(cnt, dst, row_sums, upd_parts, bounds):
    """_apply_update with the update buffer arriving as K separate
    transfers; the concatenate is device-side and fuses away."""
    return _update_body(cnt, dst, row_sums,
                        jnp.concatenate(upd_parts, axis=1), bounds)


@functools.partial(jax.jit, donate_argnums=donate_argnums(0, 1, 2), static_argnames=("L",))
def _apply_moves_update_chunked(cnt, dst, row_sums, mv, upd_parts, bounds,
                                L: int):
    cnt, dst = _moves_body(cnt, dst, mv, L)
    return _update_body(cnt, dst, row_sums,
                        jnp.concatenate(upd_parts, axis=1), bounds)


@functools.partial(jax.jit, donate_argnums=donate_argnums(0, 1, 2),
                   static_argnames=("n_pad",))
def _apply_update_packed(cnt, dst, row_sums, words_i, words_v, header, *,
                         n_pad: int):
    """_apply_update with the window buffer arriving in the compressed
    wire format (state/wire.py: per-section delta + zigzag + bit-pack);
    the decode prologue is gathers/shifts/cumsums feeding the SAME
    ``_update_body`` scatter unchanged."""
    from .wire import decode_update

    upd, bounds = decode_update(words_i, words_v, header, n_pad)
    return _update_body(cnt, dst, row_sums, upd, bounds)


@functools.partial(jax.jit, donate_argnums=donate_argnums(0, 1, 2),
                   static_argnames=("n_pad", "L"))
def _apply_moves_update_packed(cnt, dst, row_sums, mv, words_i, words_v,
                               header, *, n_pad: int, L: int):
    from .wire import decode_update

    cnt, dst = _moves_body(cnt, dst, mv, L)
    upd, bounds = decode_update(words_i, words_v, header, n_pad)
    return _update_body(cnt, dst, row_sums, upd, bounds)


@functools.partial(jax.jit, donate_argnums=donate_argnums(2, 3))
def _promote_cells(cnt, dst, cnt_w, dst_w, src_slots, dst_slots):
    """Move promoted rows' cells from the narrow slab into the wide
    int32 side-table (``src_slots`` padded with 0 — a safe gather —
    ``dst_slots`` padded with the sentinel, dropped). The cast widens,
    so it is exact for any narrow cell."""
    vals = cnt[src_slots].astype(jnp.int32)
    cnt_w = cnt_w.at[dst_slots].set(vals, mode="drop")
    dst_w = dst_w.at[dst_slots].set(dst[src_slots], mode="drop")
    return cnt_w, dst_w


@functools.partial(jax.jit, donate_argnums=donate_argnums(0, 1, 2), static_argnames=("L",))
def _apply_moves_update(cnt, dst, row_sums, mv, upd, bounds, L: int):
    """Row relocations + the window update in ONE dispatch.

    Zipfian streams relocate rows nearly every window (hot rows keep
    outgrowing their pow-2 caps), so fusing the two kernels removes a
    per-window dispatch — on a high-latency link each dispatch is wall
    time. Moves run first: the window's new-cell slots already assume the
    relocated layout.

    Trade-off, deliberate: the fused program is keyed by the cartesian
    (mv_pad, L, n_pad) where the split kernels were keyed by the two
    sums — more cold-start compiles, amortized by the coarse pow-4
    ladders and the on-disk XLA cache, in exchange for one fewer
    dispatch on nearly every window."""
    cnt, dst = _moves_body(cnt, dst, mv, L)
    return _update_body(cnt, dst, row_sums, upd, bounds)


def _apply_cells(cnt, dst, upd, bounds):
    """New-cell + delta sections of an update buffer (shared with the
    sharded backend, whose row sums update separately — replicated).

    The delta add narrows to the slab's cell dtype (a no-op for int32
    slabs): exact by the promotion invariant — a row still on a narrow
    slab has row sum < 2^(w-1), so every cell value and window delta it
    can see fits the dtype (state/wire.cell_promote_threshold).
    """
    idx, val = upd[0], upd[1]
    pos = jnp.arange(upd.shape[1], dtype=jnp.int32)
    is_new = pos < bounds[0]
    is_delta = (pos >= bounds[0]) & (pos < bounds[1])
    new_idx = jnp.where(is_new, idx, _SENT)
    dst = dst.at[new_idx].set(val, mode="drop")
    cnt = cnt.at[new_idx].set(0, mode="drop")
    d_idx = jnp.where(is_delta, idx, _SENT)
    cnt = cnt.at[d_idx].add(
        jnp.where(is_delta, val, 0).astype(cnt.dtype), mode="drop")
    return cnt, dst


def gather_rect(cnt, dst, row_sums, meta, R: int):
    """XLA rectangle gather shared by the XLA and Pallas scorers.

    Returns ``(k11i, valid, ds, rsj, rsi)``: counts [S, R] int32, the
    live-cell mask (zero cells — cancelled counts — are not scored),
    partner ids (0 where invalid), partner row sums f32 (0 where
    invalid), and the scored rows' own sums as an f32 column. One
    definition so the kernel's drop-in contract cannot drift from
    ``_score_rect``'s masking rules.
    """
    rowids, starts, lens = meta[0], meta[1], meta[2]
    col = jnp.arange(R, dtype=jnp.int32)[None, :]
    in_row = col < lens[:, None]
    idx = jnp.where(in_row, starts[:, None] + col, 0)
    k11i = jnp.where(in_row, cnt[idx], 0)
    valid = k11i != 0
    ds = jnp.where(valid, dst[idx], 0)
    rsj = jnp.where(valid, row_sums[ds], 0).astype(jnp.float32)
    rsi = row_sums[rowids].astype(jnp.float32)[:, None]
    return k11i, valid, ds, rsj, rsi


def _score_rect(cnt, dst, row_sums, meta, observed, top_k: int, R: int):
    """LLR + top-K over one length bucket of updated rows (trace body).

    ``meta``: [3, S_pad] int32 (row id, slab start, row len); padded rows
    carry len == 0 and score all -inf. ``meta[0]`` row ids index
    ``row_sums`` (global id space); starts index the local slab.
    """
    with jax.named_scope("score"):
        k11i, valid, ds, rsj, rsi = gather_rect(cnt, dst, row_sums, meta,
                                                R)
        k11 = k11i.astype(jnp.float32)
        k12 = rsi - k11
        k21 = rsj - k11
        k22 = observed + k11 - k12 - k21
        scores = llr_stable(k11, k12, k21, k22)
        scores = jnp.where(valid, scores, -jnp.inf)
        vals, kidx = jax.lax.top_k(scores, top_k)
        ids = jnp.take_along_axis(ds, kidx, axis=1)
        return jnp.stack([vals, pack_ids(ids)])


_score_slab = functools.partial(jax.jit, static_argnames=("top_k", "R"))(
    _score_rect)


@functools.partial(jax.jit, static_argnames=("top_k", "R", "interpret"))
def _score_slab_pallas(cnt, dst, row_sums, meta, observed, *,
                       top_k: int, R: int, interpret: bool = False):
    """Jitted fused-kernel counterpart of :data:`_score_slab` (pipelined,
    non-deferred path): same packed [2, S, K] return."""
    from ..ops.pallas_score import pallas_score_rect

    return pallas_score_rect(cnt, dst, row_sums, meta, observed,
                             top_k=top_k, R=R, interpret=interpret)


def _rect_into_table(tbl, cnt, dst, row_sums, meta, observed,
                     top_k: int, R: int, pallas: bool = False,
                     interpret: bool = False):
    """Score one rectangle and scatter it into the results table (trace
    body shared by the per-bucket and fused-window dispatch forms).
    ``pallas`` routes the rectangle through the fused LLR+top-K kernel
    (``ops/pallas_score.pallas_score_rect``, same packed wire format);
    the scatter is identical either way. Device-side stage names:
    ``score`` (the XLA scorer; a Pallas kernel keeps its own name, as a
    custom call takes the innermost scope's) and ``table``."""
    if pallas:
        from ..ops.pallas_score import pallas_score_rect

        packed = pallas_score_rect(cnt, dst, row_sums, meta, observed,
                                   top_k=top_k, R=R, interpret=interpret)
    else:
        packed = _score_rect(cnt, dst, row_sums, meta, observed, top_k, R)
    with jax.named_scope("table"):
        rowids = jnp.where(meta[2] > 0, meta[0], _SENT)
        return tbl.at[:, rowids].set(packed, mode="drop")


@functools.partial(jax.jit, donate_argnums=donate_argnums(0),
                   static_argnames=("top_k", "R", "pallas", "interpret"))
def _score_into_table(tbl, cnt, dst, row_sums, meta, observed, *,
                      top_k: int, R: int, pallas: bool = False,
                      interpret: bool = False):
    """Score one length bucket and scatter the packed result straight into
    the device-resident latest-results table (``[2, items_cap, K]``) —
    nothing returns to the host. The deferred-results mode's whole point:
    on a high-latency link the per-window result downlink (tens of MB on
    large windows) disappears; the host fetches the table once at flush.
    """
    return _rect_into_table(tbl, cnt, dst, row_sums, meta, observed,
                            top_k, R, pallas, interpret)


@functools.partial(jax.jit, donate_argnums=donate_argnums(0),
                   static_argnames=("top_k", "plan", "interpret"))
def _score_window_into_table(tbl, cnt, dst, row_sums, meta_all, observed, *,
                             top_k: int, plan, interpret: bool = False):
    """ALL of a window's scoring in one dispatch (fixed-shape mode).

    ``plan``: static tuple of ``(R, S, offset, pallas)`` rectangles;
    ``meta_all`` is their [3, sum(S)] concatenation (one upload). Fixed
    shapes make the rectangle sizes pure functions of R, and the caller
    dispatches a monotone high-water set of buckets (empty ones as
    all-padding), so the plan only ever GROWS — at most one program per
    bucket the stream ever occupied (measured: 3 over both benchmark
    streams), and the per-window dispatch count drops from
    one-per-bucket to one. ``pallas`` per rectangle: wide buckets can
    ride the fused kernel while narrow ones stay XLA, inside the same
    dispatch."""
    for R, S, off, use_pl in plan:
        meta = jax.lax.slice(meta_all, (0, off), (3, off + S))
        tbl = _rect_into_table(tbl, cnt, dst, row_sums, meta, observed,
                               top_k, R, use_pl, interpret)
    return tbl


def _fused_sparse_body(cnt, dst, row_sums, tbl, reg_start, reg_len, upd,
                       bounds, reg_upd, rows_all, observed, top_k: int,
                       plan, interpret: bool):
    """ONE-dispatch fused sparse window (trace body shared by the packed
    and raw wire forms).

    Stages, in order, all inside one program:

      1. ``_update_body``   — the window's new-cell / delta / row-sum
                              scatter (Insum-style indirect addressing
                              into slab cells; pad lanes carry the
                              sentinel no-op scatter, exactly like the
                              chained upload).
      2. registry sync      — ``reg_upd`` ([3, Rp]: row, start, len;
                              sentinel-padded) scatters the host
                              registry's dirty rows into the
                              device-resident (start, len) mirror, so
                              stage 3 resolves rows to slab rectangles
                              without a per-window meta upload.
      3. bucketed rescore   — for each static ``plan`` rectangle, the
                              touched rows' (start, len) are GATHERED
                              from the device mirror (the on-device
                              registry probe) and the SHARED score body
                              (``_score_rect`` / ``pallas_score_rect``)
                              scatters packed top-K into the results
                              table. Pad slots carry ``_SENT`` row ids:
                              their gathers clamp harmlessly and their
                              scatter drops, mirroring the chained
                              path's len==0 padding.

    Sharing ``_update_body`` and ``_rect_into_table`` with the chained
    dispatches is the bit-parity argument: the fused window cannot
    drift numerically because there is no second implementation.
    """
    cnt, dst, row_sums = _update_body(cnt, dst, row_sums, upd, bounds)
    with jax.named_scope("slab-update"):
        reg_start = reg_start.at[reg_upd[0]].set(reg_upd[1], mode="drop")
        reg_len = reg_len.at[reg_upd[0]].set(reg_upd[2], mode="drop")
    for R, S, off, use_pl in plan:
        rowids = jax.lax.slice(rows_all, (off,), (off + S,))
        meta = jnp.stack([rowids, reg_start[rowids], reg_len[rowids]])
        tbl = _rect_into_table(tbl, cnt, dst, row_sums, meta, observed,
                               top_k, R, use_pl, interpret)
    return cnt, dst, row_sums, tbl, reg_start, reg_len


@functools.partial(jax.jit,
                   donate_argnums=donate_argnums(0, 1, 2, 3, 4, 5),
                   static_argnames=("n_pad", "top_k", "plan", "interpret"))
def _fused_sparse_window_packed(cnt, dst, row_sums, tbl, reg_start, reg_len,
                                words_i, words_v, header, reg_upd, rows_all,
                                observed, *, n_pad: int, top_k: int, plan,
                                interpret: bool = False):
    """Packed-wire form: the PR-7 bit-packed uplink is decoded by the
    ``decode_update`` prologue (gathers/shifts/uint32-wraparound cumsums)
    INSIDE the fused program, feeding the same scatter — wire compression
    and fusion compose instead of excluding each other."""
    from .wire import decode_update

    upd, bounds = decode_update(words_i, words_v, header, n_pad)
    return _fused_sparse_body(cnt, dst, row_sums, tbl, reg_start, reg_len,
                              upd, bounds, reg_upd, rows_all, observed,
                              top_k, plan, interpret)


@functools.partial(jax.jit,
                   donate_argnums=donate_argnums(0, 1, 2, 3, 4, 5),
                   static_argnames=("top_k", "plan", "interpret"))
def _fused_sparse_window_raw(cnt, dst, row_sums, tbl, reg_start, reg_len,
                             upd, bounds, reg_upd, rows_all, observed, *,
                             top_k: int, plan, interpret: bool = False):
    """Raw-wire form (``--wire-format raw``): the update buffer ships
    uncompressed, the rest of the program is identical."""
    return _fused_sparse_body(cnt, dst, row_sums, tbl, reg_start, reg_len,
                              upd, bounds, reg_upd, rows_all, observed,
                              top_k, plan, interpret)


@functools.partial(jax.jit, static_argnames=("n",))
def _grow(arr, n: int):
    # No donation: the output is a different buffer size, so XLA could
    # never reuse the input allocation anyway.
    return jnp.zeros((n,), arr.dtype).at[: arr.shape[0]].set(arr)


@functools.partial(jax.jit, donate_argnums=donate_argnums(0, 1), static_argnames=("cap",))
def _compact_gather(cnt, dst, gmap, cap: int):
    """Rebuild the slab through a host-supplied gather map (compaction)."""
    return (jnp.zeros((cap,), cnt.dtype).at[: gmap.shape[0]].set(cnt[gmap]),
            jnp.zeros((cap,), dst.dtype).at[: gmap.shape[0]].set(dst[gmap]))


class SlabCapacityError(ValueError):
    """Slab/registry capacity crossed the int32 slot space (2^31 cells).

    A permanent configuration error (the cell-addressing wire format is
    int32 by design): the CLI maps it to the supervisor's EX_CONFIG so a
    restart loop is never spent on a stream that cannot fit. Raised by
    the growth paths instead of silently wrapping through
    ``.astype(np.int32)`` as the pre-guard code did.
    """


def _pow2ceil(x: np.ndarray, minimum: int) -> np.ndarray:
    v = np.maximum(x, minimum).astype(np.int64)
    out = 1 << np.ceil(np.log2(v)).astype(np.int64)
    if int(out.max(initial=0)) >= 2**31:
        raise SlabCapacityError(
            f"capacity growth to {int(out.max())} cells crosses the int32 "
            f"slot space (2^31); the sparse backend's cell addressing is "
            f"int32 — shard the stream (--num-shards) instead")
    return out.astype(np.int32)


def _pad_words(words: np.ndarray) -> np.ndarray:
    """Pad an encoded word stream to a pow2 transfer bucket with at
    least one trailing guard word (the jit decode gathers word+1)."""
    out = np.zeros(pad_pow2(len(words) + 1, minimum=256), dtype=np.uint32)
    out[: len(words)] = words
    return out


def resolve_fixed_shapes(fixed_shapes, defer_results: bool) -> bool:
    """Resolve a fixed-shape request (None = env TPU_COOC_FIXED_SCORE or
    auto) and enforce the defer-only contract — shared by the
    single-device and sharded sparse scorers."""
    if fixed_shapes is None:
        env = tuning.env_read("TPU_COOC_FIXED_SCORE", "auto")
        env = env.strip().lower()
        if env in ("1", "on", "true", "yes"):
            fixed_shapes = True
        elif env in ("0", "off", "false", "no"):
            fixed_shapes = False
        elif env in ("auto", ""):
            # Fixed rectangles only make sense when results stay on
            # device: the pipelined path fetches each packed block, and
            # a full [2, s_block, K] fetch per bucket would ship
            # megabytes of padding over the very link this mode exists
            # to spare.
            fixed_shapes = (jax.default_backend() == "tpu"
                            and defer_results)
        else:
            raise ValueError(
                f"TPU_COOC_FIXED_SCORE must be 0/1/auto, got {env!r}")
    if fixed_shapes and not defer_results:
        # An explicit request that cannot take effect must not be
        # silently downgraded — a fixed-vs-variable A/B would then
        # compare two identical variable runs.
        raise ValueError(
            "fixed-shape scoring needs deferred results (it is "
            "incompatible with --emit-updates: the per-window result "
            "fetch would ship the padded rectangles)")
    return bool(fixed_shapes)


def fixed_block(R: int, budget: int, row_cap: int) -> int:
    """Fixed-mode rectangle rows for bucket width ``R``: budget-bounded,
    upload-capped, and >= the top_k-compatible minimum."""
    return max(min(budget // R, row_cap), 16)


def ladder_bits(ladder: int) -> int:
    """Validate a score-bucket ladder base (power of two >= 2) and return
    its log2. The single owner of the ladder contract — scorers validate
    through this at construction, and :func:`bucket_r` / :func:`score_buckets` share it so bucket rounding and rectangle widths
    cannot drift apart."""
    k = ladder.bit_length() - 1
    if k < 1 or ladder != (1 << k):
        raise ValueError(
            f"score ladder must be a power of two >= 2, got {ladder} "
            f"(TPU_COOC_SCORE_LADDER)")
    return k


def bucket_r(b: int, min_r: int, ladder: int) -> int:
    """Rectangle width of bucket ``b``: ``min_r * ladder^b``."""
    return min_r << (ladder_bits(ladder) * b)


def score_buckets(lens: np.ndarray, min_r: int, ladder: int = 4):
    """Length buckets: bucket b scores rows at ``R = bucket_r(b)`` (the
    smallest b with R >= len). Returns (bucket-per-row, order sorted
    by bucket). Integer math, exact at powers:
    ``shift = ceil(len / 2^floor(log2 min_r)) - 1``;
    ``b = ceil(log2(shift+1) / k)`` for ``ladder = 2^k`` via frexp's
    exponent (``frexp(s)[1] = floor(log2 s) + 1``, ``frexp(0) = 0``).

    The ladder trades padded device compute for dispatch count: pow-4
    (default) pads rows <=4x and yields ~5-6 dispatches per window on a
    Zipfian length mix; pow-16 pads <=16x (device-only work) but about
    halves the dispatches — the better point when every dispatch pays a
    high-latency link round trip (remote coordinators).
    """
    k = ladder_bits(ladder)
    shift = (np.maximum(lens, 1) - 1) >> (min_r.bit_length() - 1)
    bucket = (np.frexp(shift.astype(np.float64))[1] + k - 1) // k
    return bucket, np.argsort(bucket, kind="stable")


# -- row registries -----------------------------------------------------
#
# The per-row slab placement record (start, len, cap). Two storage
# strategies behind one batch API:
#
#   dense   — the original three int32 arrays over the whole row space
#             (12 B per *possible* row, O(1) everything).
#   bitmap  — SMASH-style: a one-bit-per-row occupancy bitmap plus a
#             per-64-bit-word rank directory (exclusive popcount prefix
#             sums — the hierarchical index), with (start, len, cap)
#             packed densely over *occupied* rows in row-id order.
#             Membership and field gathers are O(1) per row (word rank +
#             in-word popcount); host RSS is 2 bits per possible row +
#             12 B per occupied row — at 1M possible rows with a sparse
#             vocabulary this is an order of magnitude under dense
#             (pinned by tests/test_slab_registry.py).
#
# Default: bitmap (env TPU_COOC_ROW_INDEX=dense opts out for A/B).


if hasattr(np, "bitwise_count"):  # numpy >= 2.0
    def _popcount(words: np.ndarray) -> np.ndarray:
        return np.bitwise_count(words)
else:  # portable fallback: byte-table popcount over the uint8 view
    _POP8 = np.asarray([bin(i).count("1") for i in range(256)],
                       dtype=np.uint8)

    def _popcount(words: np.ndarray) -> np.ndarray:
        return _POP8[words.view(np.uint8).reshape(-1, 8)].sum(
            axis=1).astype(np.uint64)


class _RegistryDirtyLog:
    """Dirty-row tracking shared by both registry layouts.

    The fused sparse window keeps a DEVICE-resident mirror of the
    (start, len) columns (``SparseDeviceScorer`` reg views) so the
    scoring half of the one-dispatch program can resolve rows to slab
    rectangles without a per-window meta upload. The mirror syncs by
    delta: every host-side registry mutation logs its rows here, and
    the next fused dispatch uplinks exactly those rows' (start, len).
    Off (``None``) unless the fused path enables it — the steady-state
    chained path pays nothing.
    """

    #: Logged-entry bound: past this the log collapses to the all-dirty
    #: flag (next fused window does one full occupied-rows resync).
    #: Bounds memory when the fused path is enabled but windows route
    #: chained indefinitely (e.g. every touched row went wide) — the
    #: log would otherwise grow by one array per window forever.
    DIRTY_CAP = 1 << 20

    def __init__(self) -> None:
        self._dirty_log = None  # None = tracking off
        self._dirty_count = 0
        self._all_dirty = False

    def enable_dirty_log(self) -> None:
        if self._dirty_log is None:
            self._dirty_log = []

    def _mark_dirty(self, rows) -> None:
        if self._dirty_log is None or self._all_dirty or not len(rows):
            return
        self._dirty_log.append(np.asarray(rows, dtype=np.int64))
        self._dirty_count += len(rows)
        if self._dirty_count > self.DIRTY_CAP:
            self._mark_all_dirty()

    def _mark_all_dirty(self) -> None:
        if self._dirty_log is not None:
            self._all_dirty = True
            self._dirty_log.clear()
            self._dirty_count = 0

    def drain_dirty(self):
        """``(rows, all_dirty)`` accumulated since the last drain. With
        ``all_dirty`` the caller must resync every occupied row (the
        wholesale-rebuild paths — restore, reset — and a capped log)."""
        all_d = self._all_dirty
        if all_d or self._dirty_log is None or not self._dirty_log:
            rows = np.zeros(0, dtype=np.int64)
        elif len(self._dirty_log) == 1:
            rows = np.unique(self._dirty_log[0])
        else:
            rows = np.unique(np.concatenate(self._dirty_log))
        if self._dirty_log is not None:
            self._dirty_log.clear()
        self._dirty_count = 0
        self._all_dirty = False
        return rows, all_d


class DenseRowRegistry(_RegistryDirtyLog):
    """Original dense triple: three int32 arrays over the row space."""

    kind = "dense"

    def __init__(self, rows_capacity: int) -> None:
        super().__init__()
        cap = max(int(rows_capacity), 64)
        self.start = np.zeros(cap, dtype=np.int32)
        self.length = np.zeros(cap, dtype=np.int32)
        self.cap = np.zeros(cap, dtype=np.int32)

    @property
    def rows_cap(self) -> int:
        return len(self.start)

    @property
    def nbytes(self) -> int:
        return self.start.nbytes + self.length.nbytes + self.cap.nbytes

    def ensure(self, max_row: int) -> None:
        if max_row < self.rows_cap:
            return
        new_cap = int(_pow2ceil(np.asarray([max_row + 1]), 1024)[0])
        for name in ("start", "length", "cap"):
            old = getattr(self, name)
            grown = np.zeros(new_cap, dtype=old.dtype)
            grown[: len(old)] = old
            setattr(self, name, grown)

    def get(self, rows: np.ndarray):
        rows = np.asarray(rows, dtype=np.int64)
        if len(rows) and int(rows.max()) >= self.rows_cap:
            # Beyond-capacity rows read as absent (0, 0, 0).
            safe = np.minimum(rows, self.rows_cap - 1)
            in_r = rows < self.rows_cap
            return (np.where(in_r, self.start[safe], 0).astype(np.int32),
                    np.where(in_r, self.length[safe], 0).astype(np.int32),
                    np.where(in_r, self.cap[safe], 0).astype(np.int32))
        return self.start[rows], self.length[rows], self.cap[rows]

    def update(self, rows: np.ndarray, start=None, length=None,
               cap=None) -> None:
        rows = np.asarray(rows, dtype=np.int64)
        if len(rows):
            self.ensure(int(rows.max()))
        self._mark_dirty(rows)
        if start is not None:
            self.start[rows] = start
        if length is not None:
            self.length[rows] = length
        if cap is not None:
            self.cap[rows] = cap

    def clear(self, rows: np.ndarray) -> None:
        rows = np.asarray(rows, dtype=np.int64)
        rows = rows[rows < self.rows_cap]
        self._mark_dirty(rows)
        self.start[rows] = 0
        self.length[rows] = 0
        self.cap[rows] = 0

    def occupied(self) -> np.ndarray:
        return np.flatnonzero(self.cap > 0).astype(np.int32)

    def reset(self) -> None:
        self._mark_all_dirty()
        self.start[:] = 0
        self.length[:] = 0
        self.cap[:] = 0


class BitmapRowRegistry(_RegistryDirtyLog):
    """Bitmap + rank directory + packed per-occupied-row fields.

    ``bits`` holds one occupancy bit per possible row; ``rank`` holds the
    exclusive popcount prefix sum per 64-bit word (the hierarchy level
    that makes rank O(1): packed position of row r =
    ``rank[r >> 6] + popcount(bits[r >> 6] below bit r)``). The packed
    field arrays stay in row-id order; batch inserts merge new rows per
    window (one ``np.insert`` pass, mirroring the sorted cell index's
    merge cadence). Rows are never removed — ``clear`` zeroes the fields
    (a freed row costs 12 packed bytes until a rebuild), matching the
    dense registry's observable behavior exactly.
    """

    kind = "bitmap"

    def __init__(self, rows_capacity: int) -> None:
        super().__init__()
        cap = max(int(rows_capacity), 64)
        cap = int(_pow2ceil(np.asarray([cap]), 64)[0])
        self.bits = np.zeros(cap // 64, dtype=np.uint64)
        self.rank = np.zeros(cap // 64, dtype=np.int64)
        self.start = np.zeros(0, dtype=np.int32)
        self.length = np.zeros(0, dtype=np.int32)
        self.cap = np.zeros(0, dtype=np.int32)

    @property
    def rows_cap(self) -> int:
        return len(self.bits) * 64

    @property
    def nbytes(self) -> int:
        return (self.bits.nbytes + self.rank.nbytes + self.start.nbytes
                + self.length.nbytes + self.cap.nbytes)

    def ensure(self, max_row: int) -> None:
        if max_row < self.rows_cap:
            return
        new_cap = int(_pow2ceil(np.asarray([max_row + 1]), 1024)[0])
        n_words = new_cap // 64
        grown = np.zeros(n_words, dtype=np.uint64)
        grown[: len(self.bits)] = self.bits
        self.bits = grown
        self.rank = np.zeros(n_words, dtype=np.int64)
        self._rebuild_rank()  # appended words inherit the running rank

    def _rebuild_rank(self) -> None:
        pc = _popcount(self.bits).astype(np.int64)
        np.cumsum(pc[:-1], out=self.rank[1:])
        self.rank[0] = 0

    def _pos(self, rows: np.ndarray):
        """(packed position, occupied) per row — O(1) membership.
        Beyond-capacity rows report unoccupied."""
        in_r = rows < self.rows_cap
        w = np.minimum(rows >> 6, len(self.bits) - 1)
        b = (rows & 63).astype(np.uint64)
        wbits = self.bits[w]
        occ = ((wbits >> b) & np.uint64(1)).astype(bool) & in_r
        below = wbits & ((np.uint64(1) << b) - np.uint64(1))
        return self.rank[w] + _popcount(below).astype(np.int64), occ

    def get(self, rows: np.ndarray):
        rows = np.asarray(rows, dtype=np.int64)
        pos, occ = self._pos(rows)
        s = np.zeros(len(rows), dtype=np.int32)
        ln = np.zeros(len(rows), dtype=np.int32)
        c = np.zeros(len(rows), dtype=np.int32)
        p = pos[occ]
        s[occ] = self.start[p]
        ln[occ] = self.length[p]
        c[occ] = self.cap[p]
        return s, ln, c

    def update(self, rows: np.ndarray, start=None, length=None,
               cap=None) -> None:
        """Batch insert-or-update. ``rows`` must be unique and sorted
        ascending (every caller passes ``np.unique`` output) so the
        packed arrays keep their row-id order through one insert pass."""
        rows = np.asarray(rows, dtype=np.int64)
        if not len(rows):
            return
        self.ensure(int(rows.max()))
        self._mark_dirty(rows)
        pos, occ = self._pos(rows)
        new = rows[~occ]
        if len(new):
            ins = pos[~occ]  # positions in the PRE-insert packed arrays
            self.start = np.insert(self.start, ins, 0)
            self.length = np.insert(self.length, ins, 0)
            self.cap = np.insert(self.cap, ins, 0)
            np.bitwise_or.at(self.bits, new >> 6,
                             np.uint64(1) << (new & 63).astype(np.uint64))
            self._rebuild_rank()
            pos, _occ = self._pos(rows)
        if start is not None:
            self.start[pos] = start
        if length is not None:
            self.length[pos] = length
        if cap is not None:
            self.cap[pos] = cap

    def clear(self, rows: np.ndarray) -> None:
        rows = np.asarray(rows, dtype=np.int64)
        self._mark_dirty(rows)
        pos, occ = self._pos(rows)
        p = pos[occ]
        self.start[p] = 0
        self.length[p] = 0
        self.cap[p] = 0

    def occupied(self) -> np.ndarray:
        ids = np.flatnonzero(np.unpackbits(
            self.bits.view(np.uint8), bitorder="little"))
        return ids[self.cap > 0].astype(np.int32)

    def reset(self) -> None:
        self._mark_all_dirty()
        self.bits[:] = 0
        self.rank[:] = 0
        self.start = np.zeros(0, dtype=np.int32)
        self.length = np.zeros(0, dtype=np.int32)
        self.cap = np.zeros(0, dtype=np.int32)


def make_row_registry(rows_capacity: int, kind: Optional[str] = None):
    """Row-registry factory: ``kind`` or env ``TPU_COOC_ROW_INDEX``
    (default bitmap — the compressed index is the production layout;
    dense remains for A/B and as the reference implementation)."""
    if kind is None:
        kind = tuning.env_read("TPU_COOC_ROW_INDEX", "bitmap").strip().lower()
    if kind == "dense":
        return DenseRowRegistry(rows_capacity)
    if kind == "bitmap":
        return BitmapRowRegistry(rows_capacity)
    raise ValueError(
        f"TPU_COOC_ROW_INDEX must be bitmap or dense, got {kind!r}")


class _RowField:
    """Read-only vectorized view of one registry column — compatibility
    shim for callers that indexed the old dense arrays directly
    (``index.row_start[rows]``). Scalar in, scalar out."""

    def __init__(self, reg, field: int) -> None:
        self._reg = reg
        self._field = field

    def __getitem__(self, rows):
        scalar = np.isscalar(rows) or getattr(rows, "ndim", 1) == 0
        out = self._reg.get(np.atleast_1d(np.asarray(rows)))[self._field]
        return out[0] if scalar else out

    def __len__(self) -> int:
        return self._reg.rows_cap


@dataclasses.dataclass
class AllocPlan:
    """Device-facing output of one window's :meth:`SlabIndex.apply`."""

    mv: Optional[np.ndarray]      # [3, Mv_pad] int32 move instructions
    mv_len: int                   # static rectangle width for the move kernel
    slots: np.ndarray             # slab slot per window cell (d_key order)
    new_sel: np.ndarray           # bool per window cell: newly inserted

    @property
    def n_new(self) -> int:
        return int(self.new_sel.sum())


class SlabIndex:
    """Sorted-key cell index + per-row slab registry + allocator.

    Row-id-space agnostic: callers pack keys as ``row << 32 | dst`` in
    whatever row space they shard by (global for the single-device
    backend, shard-local for the sharded one). Slots are offsets into the
    caller's slab arrays; the index never touches a device.

    Invariant the allocator and compactor rely on: a row's live slots are
    always exactly ``[start, start + len)`` (appends are contiguous and
    cells are never removed), so within-row slot offsets are dense.

    Per-row placement lives in a pluggable row registry (default: the
    SMASH-style bitmap + rank index, ``BitmapRowRegistry``); the old
    dense-array access pattern stays available through the read-only
    ``row_start`` / ``row_len`` / ``row_cap`` views.
    """

    def __init__(self, rows_capacity: int = 1 << 10,
                 row_index: Optional[str] = None) -> None:
        self.g_key = np.zeros(0, dtype=np.int64)
        self.g_slot = np.zeros(0, dtype=np.int32)
        self.rows = make_row_registry(rows_capacity, row_index)
        self.heap_end = 0
        self.garbage = 0  # cells in freed (moved-out) regions
        self.compactions = 0

    def __len__(self) -> int:
        return len(self.g_key)

    @property
    def rows_cap(self) -> int:
        return self.rows.rows_cap

    @property
    def row_start(self) -> _RowField:
        return _RowField(self.rows, 0)

    @property
    def row_len(self) -> _RowField:
        return _RowField(self.rows, 1)

    @property
    def row_cap(self) -> _RowField:
        return _RowField(self.rows, 2)

    @property
    def nbytes(self) -> int:
        """Host RSS of the index structures (registry + cell index) —
        the ``cooc_host_index_rss_bytes`` gauge and the bench's
        ``host_index_rss_bytes`` field read this."""
        return self.rows.nbytes + self.g_key.nbytes + self.g_slot.nbytes

    def ensure_rows(self, max_row: int) -> None:
        self.rows.ensure(max_row)

    def apply(self, d_key: np.ndarray) -> AllocPlan:
        """Classify one window's (sorted unique) cell keys against the
        index, allocate slots for the new ones (recording relocations of
        outgrown rows), and insert them. Returns the device-facing plan;
        the caller dispatches moves BEFORE any cell writes and must size
        its slab to ``heap_end`` beforehand."""
        pos = np.searchsorted(self.g_key, d_key)
        if len(self.g_key):
            safe = np.minimum(pos, len(self.g_key) - 1)
            exists = self.g_key[safe] == d_key
        else:
            exists = np.zeros(len(d_key), dtype=bool)
        new_key = d_key[~exists]
        mv = None
        mv_len = 0
        new_slots = np.zeros(0, dtype=np.int32)
        if len(new_key):
            mv, mv_len, new_slots = self._allocate(new_key)
        slots = np.empty(len(d_key), dtype=np.int32)
        slots[exists] = self.g_slot[pos[exists]]
        if len(new_key):
            slots[~exists] = new_slots
            self.g_key, self.g_slot = merge_sorted_insert(
                self.g_key, self.g_slot, pos[~exists], new_key, new_slots)
        return AllocPlan(mv, mv_len, slots, ~exists)

    def _shift_moved(self, rows: np.ndarray, old_starts: np.ndarray,
                     lens: np.ndarray, new_starts: np.ndarray,
                     disjoint: bool = False) -> None:
        """Re-point the index at relocated rows' new slots (their g_key
        segment is contiguous in the sorted layout).

        ``disjoint``: every new region lies beyond the old heap end
        (the _allocate growth case, never compaction's overlapping
        re-lay) — a hint subclasses use to pick an in-place fast path;
        this sorted implementation edits only g_slot values and needs
        no distinction."""
        seg_lo = np.searchsorted(self.g_key, rows.astype(np.int64) << 32)
        idx = np.repeat(seg_lo, lens) + _ragged_arange(lens)
        self.g_slot[idx] += np.repeat(new_starts - old_starts, lens)

    def keys_and_slots(self):
        """(sorted packed cell keys, matching slots) — the checkpoint
        view. The sorted index holds exactly this already."""
        return self.g_key, self.g_slot

    def _allocate(self, new_key: np.ndarray):
        n_src = (new_key >> 32).astype(np.int64)
        rows_new, first_idx, counts = np.unique(
            n_src, return_index=True, return_counts=True)
        rows_new32 = rows_new.astype(np.int32)
        self.ensure_rows(int(rows_new32.max()))
        r_start, r_len, r_cap = self.rows.get(rows_new)
        need = r_len + counts.astype(np.int32)
        grow_mask = need > r_cap
        mv = None
        mv_len = 0
        if grow_mask.any():
            grow_rows = rows_new32[grow_mask]
            new_caps = _pow2ceil(need[grow_mask], minimum=4)
            new_end = self.heap_end + int(new_caps.astype(np.int64).sum())
            if new_end >= 2**31:
                raise SlabCapacityError(
                    f"slab heap growth to {new_end} cells crosses the "
                    f"int32 slot space (2^31); shard the stream "
                    f"(--num-shards) instead")
            offs = (self.heap_end
                    + np.concatenate([[0], np.cumsum(new_caps)[:-1]])
                    ).astype(np.int32)
            self.heap_end = new_end
            old_start = r_start[grow_mask].copy()
            old_len = r_len[grow_mask].copy()
            self.garbage += int(r_cap[grow_mask].sum())
            moved = old_len > 0
            if moved.any():
                # Growth offsets start at the old heap_end: disjoint.
                self._shift_moved(grow_rows[moved], old_start[moved],
                                  old_len[moved], offs[moved],
                                  disjoint=True)
                mv_count = int(moved.sum())
                mv_len = int(pad_pow4(int(old_len[moved].max()), minimum=8))
                mv_pad = pad_pow4(mv_count, minimum=8)
                mv = np.zeros((3, mv_pad), dtype=np.int32)
                mv[0, :mv_count] = old_start[moved]
                mv[1, :mv_count] = offs[moved]
                mv[2, :mv_count] = old_len[moved]
            self.rows.update(grow_rows, start=offs, cap=new_caps)
        # Append slots: start + len + within-row rank (new_key is sorted,
        # so same-row entries are contiguous and rank is positional).
        rank = (np.arange(len(new_key))
                - np.repeat(first_idx, counts)).astype(np.int32)
        k_start, k_len, _ = self.rows.get(n_src)
        new_slots = (k_start + k_len + rank).astype(np.int32)
        self.rows.update(rows_new32, length=need)
        return mv, mv_len, new_slots

    def needs_compaction(self, min_heap: int) -> bool:
        # Threshold at 1/3: pure cap-doubling alone converges to garbage
        # just UNDER half the heap (sum of freed caps 4+8+..+C/2 = C-4 per
        # row vs live cap C), so a 1/2 threshold would never fire.
        return self.garbage * 3 > self.heap_end and self.heap_end > min_heap

    def _adopt_alloc(self, rows: np.ndarray, lens: np.ndarray) -> np.ndarray:
        """Allocate fresh contiguous regions for currently-absent ``rows``
        (sorted unique) and register them; returns the cell slots in the
        caller's per-row cell order. Shared by both index layouts'
        :meth:`adopt_rows`."""
        rows = np.asarray(rows, dtype=np.int64)
        lens32 = np.asarray(lens, dtype=np.int32)
        self.ensure_rows(int(rows.max()))
        caps = _pow2ceil(lens32, minimum=4)
        new_end = self.heap_end + int(caps.astype(np.int64).sum())
        if new_end >= 2**31:
            raise SlabCapacityError(
                f"slab heap growth to {new_end} cells crosses the int32 "
                f"slot space (2^31); shard the stream (--num-shards) "
                f"instead")
        starts = (self.heap_end
                  + np.concatenate([[0], np.cumsum(caps)[:-1]])
                  ).astype(np.int32)
        self.heap_end = new_end
        self.rows.update(rows, start=starts, length=lens32, cap=caps)
        return (np.repeat(starts, lens32)
                + _ragged_arange(lens32)).astype(np.int32)

    def adopt_rows(self, rows: np.ndarray, keys: np.ndarray,
                   lens: np.ndarray) -> np.ndarray:
        """Re-insert absent rows' cells with their given per-row order
        PRESERVED (``keys`` concatenated per row in within-row slab
        order, ``lens`` per row). The tiered store's promotion path: the
        re-promoted row must reproduce its pre-spill slab layout because
        top-K tie-breaking among equal scores is slot-ordered — a
        key-ordered re-insert (what :meth:`apply` would do) could flip
        ties against the spill-off run. Returns the slots, keys-aligned
        — valid until the next :meth:`apply` (which may relocate an
        adopted row that outgrows its capacity; re-resolve through
        :meth:`lookup` afterwards).
        """
        slots = self._adopt_alloc(rows, lens)
        if len(keys):
            order = np.argsort(keys, kind="stable")
            sk = keys[order]
            ss = slots[order]
            pos = np.searchsorted(self.g_key, sk)
            self.g_key, self.g_slot = merge_sorted_insert(
                self.g_key, self.g_slot, pos, sk, ss)
        return slots

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """Current slots of keys KNOWN to be present. The promotion
        path resolves its cells' slots through this AFTER the window's
        :meth:`apply` — apply may have relocated an adopted row (a new
        cell outgrowing the fresh capacity), and a slot captured at
        adopt time would then point into the freed region."""
        pos = np.searchsorted(self.g_key, keys)
        if len(keys):
            safe = np.minimum(pos, max(len(self.g_key) - 1, 0))
            if (len(self.g_key) == 0 or (pos >= len(self.g_key)).any()
                    or not np.array_equal(self.g_key[safe], keys)):
                raise KeyError("lookup of absent cell keys — promotion "
                               "contract violated")
        return self.g_slot[pos].astype(np.int32)

    def row_cells(self, rows: np.ndarray):
        """Live cells of ``rows`` as ``(keys, slots)``, rows concatenated
        in order (keys sorted within each row — the sorted layout's
        per-row segments are key-ordered). The promotion path reads a
        row's cells through this before handing them to the wide index."""
        lo = np.searchsorted(self.g_key, rows.astype(np.int64) << 32)
        _s, lens, _c = self.rows.get(rows)
        idx = np.repeat(lo, lens) + _ragged_arange(lens)
        return self.g_key[idx], self.g_slot[idx]

    def free_rows(self, rows: np.ndarray) -> None:
        """Drop rows and their cells from the index (cell-dtype promotion
        moved them to the wide side-table): the slab region becomes
        garbage for the next compaction and the keys are really deleted,
        so a freed key can re-insert later as a fresh cell (the
        compaction-reinsertion edge case, tests/test_slab_registry.py).
        Promotions are rare (Zipf head only); the O(total) segment
        delete is off the steady-state path."""
        _s, lens, cap = self.rows.get(rows)
        self.garbage += int(cap.sum())
        lo = np.searchsorted(self.g_key, rows.astype(np.int64) << 32)
        idx = np.repeat(lo, lens) + _ragged_arange(lens)
        self.g_key = np.delete(self.g_key, idx)
        self.g_slot = np.delete(self.g_slot, idx)
        self.rows.clear(rows)

    def compact(self) -> np.ndarray:
        """Defragment: re-lay rows contiguously (row-id order). Returns
        the slot-space gather map (new slab = old slab[gmap]); updates the
        index in place. The caller runs the device gather."""
        alloc = self.rows.occupied()
        old_starts, lens, _caps = self.rows.get(alloc)
        new_caps = _pow2ceil(lens, minimum=4)
        new_starts = np.concatenate(
            [[0], np.cumsum(new_caps)[:-1]]).astype(np.int32)
        new_end = int(new_caps.sum())
        within = _ragged_arange(lens).astype(np.int32)
        # Gather map in slot order; slots of a row are exactly
        # [start, start+len), so the map is dense per row.
        gmap = np.zeros(max(new_end, 1), dtype=np.int32)
        gmap[np.repeat(new_starts, lens) + within] = (
            np.repeat(old_starts, lens) + within)
        # Re-point the index at the compacted layout (the hook reads all
        # old positions before writing, so overlapping old/new regions of
        # different rows are safe).
        self._shift_moved(alloc, old_starts, lens, new_starts)
        self.rows.update(alloc, start=new_starts, cap=new_caps)
        self.heap_end = new_end
        self.garbage = 0
        self.compactions += 1
        return gmap

    def rebuild_from_keys(self, keys: np.ndarray) -> np.ndarray:
        """Reset to a fresh contiguous layout for ``keys`` (sorted packed
        cell keys, e.g. from a checkpoint). Returns the slot per key."""
        rows_all = (keys >> 32).astype(np.int64)
        self.rows.reset()
        if len(keys) == 0:
            self.g_key = keys.copy()
            self.g_slot = np.zeros(0, dtype=np.int32)
            self.heap_end = 0
            self.garbage = 0
            return self.g_slot
        self.ensure_rows(int(rows_all.max()))
        rows_u, counts = np.unique(rows_all, return_counts=True)
        rows_u32 = rows_u.astype(np.int32)
        caps = _pow2ceil(counts.astype(np.int32), minimum=4)
        starts = np.concatenate([[0], np.cumsum(caps)[:-1]]).astype(np.int32)
        self.rows.update(rows_u32, start=starts,
                         length=counts.astype(np.int32), cap=caps)
        self.heap_end = int(caps.sum())
        self.garbage = 0
        self.g_key = keys.copy()
        self.g_slot = (np.repeat(starts, counts)
                       + _ragged_arange(counts)).astype(np.int32)
        return self.g_slot



class HashSlabIndex(SlabIndex):
    """Native hash-table cell index: O(window cells) per window.

    The sorted base index pays an O(total cells) merge every window —
    measured at 90 s of a 463 s full-ML-25M CPU run once the matrix held
    14M cells. This variant keys cells in a C++ open-addressing table
    (``native/slab_hash.cpp``) plus a slot -> key reverse array (needed to
    re-point moved rows, which the sorted layout found by segment); the
    sorted view the checkpoints want is built on demand. Same public
    interface and allocator as the base class; use
    :func:`make_slab_index` to pick the best available implementation.
    """

    GROW_NUM, GROW_DEN = 3, 2  # grow when 3*n > 2*cap (load ~0.67)

    def __init__(self, rows_capacity: int = 1 << 10,
                 table_capacity: int = 1 << 14) -> None:
        from ..native import _ptr8, _ptr32, _ptr64, get_lib

        super().__init__(rows_capacity)
        self._p64, self._p32, self._p8 = _ptr64, _ptr32, _ptr8
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError(
                "HashSlabIndex needs the native library; use "
                "make_slab_index() to fall back to the sorted index")
        self._cap = int(table_capacity)
        if self._cap < 2 or self._cap & (self._cap - 1):
            raise ValueError(
                f"table_capacity must be a power of two >= 2, got "
                f"{table_capacity} (the probe mask is capacity - 1)")
        self._tkeys = np.full(self._cap, -1, dtype=np.int64)
        self._tvals = np.zeros(self._cap, dtype=np.int32)
        self._n = 0
        self.slot_key = np.full(1 << 10, -1, dtype=np.int64)
        self._moved_rows = np.zeros(0, dtype=np.int64)  # last _shift_moved

    def __len__(self) -> int:
        return self._n

    @staticmethod
    def _check_probe(exhausted: int) -> None:
        """Fail loudly on a bounded-probe exhaustion (contract violation:
        promised-present key absent, or a table the caller never grew)."""
        if exhausted:
            raise RuntimeError(
                f"slab hash probe exhausted the table for {exhausted} "
                f"keys — cell-index contract violated (corrupted reverse "
                f"map or un-grown table)")

    def _grow_table(self, need: int) -> None:
        if self.GROW_NUM * need <= self.GROW_DEN * self._cap:
            return
        cap = self._cap
        while self.GROW_NUM * need > self.GROW_DEN * cap:
            cap *= 2
        live = self._tkeys != -1
        keys = np.ascontiguousarray(self._tkeys[live])
        vals = np.ascontiguousarray(self._tvals[live])
        self._cap = cap
        self._tkeys = np.full(cap, -1, dtype=np.int64)
        self._tvals = np.zeros(cap, dtype=np.int32)
        self._check_probe(self._lib.slab_hash_insert(
            self._p64(self._tkeys), self._p32(self._tvals), cap - 1,
            self._p64(keys), self._p32(vals), len(keys)))

    def _ensure_slot_key(self, need: int) -> None:
        if need <= len(self.slot_key):
            return
        n = len(self.slot_key)
        while n < need:
            n *= 2
        grown = np.full(n, -1, dtype=np.int64)
        grown[: len(self.slot_key)] = self.slot_key
        self.slot_key = grown

    def apply(self, d_key: np.ndarray) -> AllocPlan:
        d_key = np.ascontiguousarray(d_key, dtype=np.int64)
        # The stale-slot re-probe below is only valid for rows moved by
        # THIS window's _allocate; drop last window's record up front so
        # staleness can never leak across windows.
        self._moved_rows = np.zeros(0, dtype=np.int64)
        n = len(d_key)
        slots = np.empty(n, dtype=np.int32)
        is_new = np.empty(n, dtype=np.uint8)
        self._check_probe(self._lib.slab_hash_lookup(
            self._p64(self._tkeys), self._p32(self._tvals), self._cap - 1,
            self._p64(d_key), n, self._p32(slots), self._p8(is_new)))
        new_sel = is_new.view(bool)
        new_key = d_key[new_sel]
        mv = None
        mv_len = 0
        if len(new_key):
            mv, mv_len, new_slots = self._allocate(new_key)
            slots[new_sel] = new_slots
            self._ensure_slot_key(self.heap_end)
            self.slot_key[new_slots] = new_key
            self._grow_table(self._n + len(new_key))
            new_slots = np.ascontiguousarray(new_slots)
            self._check_probe(self._lib.slab_hash_insert(
                self._p64(self._tkeys), self._p32(self._tvals),
                self._cap - 1, self._p64(new_key), self._p32(new_slots),
                len(new_key)))
            self._n += len(new_key)
            if mv is not None and not new_sel.all():
                # Allocation relocated rows, so the pre-allocation lookup
                # above returned stale slots for existing cells of MOVED
                # rows (the sorted index reads g_slot AFTER the shift) —
                # re-probe exactly those against the updated table.
                # Relocations fire nearly every window on Zipfian
                # streams, so the re-probe is masked to the moved rows'
                # cells, not the whole window.
                ex_pos = np.flatnonzero(~new_sel)
                # Membership via a dense row mask, not np.isin: isin
                # sorts both sides (O(n log n) per window) and this
                # line sits on the per-window hot path. Every existing
                # cell's row was registered through ensure_rows at
                # first insertion, so row ids index row_start-sized
                # arrays by the class invariant.
                mask = np.zeros(len(self.row_start), dtype=bool)
                mask[self._moved_rows] = True
                stale = ex_pos[mask[d_key[ex_pos] >> 32]]
                if len(stale):
                    ex_keys = np.ascontiguousarray(d_key[stale])
                    ex_slots = np.empty(len(ex_keys), dtype=np.int32)
                    scratch = np.empty(len(ex_keys), dtype=np.uint8)
                    self._check_probe(self._lib.slab_hash_lookup(
                        self._p64(self._tkeys), self._p32(self._tvals),
                        self._cap - 1, self._p64(ex_keys), len(ex_keys),
                        self._p32(ex_slots), self._p8(scratch)))
                    slots[stale] = ex_slots
        return AllocPlan(mv, mv_len, slots, new_sel.copy())

    def _shift_moved(self, rows: np.ndarray, old_starts: np.ndarray,
                     lens: np.ndarray, new_starts: np.ndarray,
                     disjoint: bool = False) -> None:
        # The reverse map recovers the moved cells' keys (the sorted
        # index found them by key-segment instead).
        self._moved_rows = rows  # apply() re-probes only these rows' cells
        self._ensure_slot_key(self.heap_end)
        if disjoint:
            # Growth relocations (every window on Zipfian streams): one
            # C pass copies each row's reverse-map keys and re-points
            # the table, skipping the ragged index/gather temporaries
            # below. Only valid when no new region overlaps an old one
            # — guaranteed by _allocate (offsets start at heap_end).
            self._check_probe(self._lib.slab_shift_rows(
                self._p64(self._tkeys), self._p32(self._tvals),
                self._cap - 1, self._p64(self.slot_key),
                self._p32(np.ascontiguousarray(old_starts,
                                               dtype=np.int32)),
                self._p32(np.ascontiguousarray(new_starts,
                                               dtype=np.int32)),
                self._p32(np.ascontiguousarray(lens, dtype=np.int32)),
                len(lens)))
            return
        old_idx = np.repeat(old_starts, lens) + _ragged_arange(lens)
        keys = np.ascontiguousarray(self.slot_key[old_idx])
        new_idx = (np.repeat(new_starts, lens)
                   + _ragged_arange(lens)).astype(np.int32)
        self.slot_key[new_idx] = keys
        self._check_probe(self._lib.slab_hash_update(
            self._p64(self._tkeys), self._p32(self._tvals), self._cap - 1,
            self._p64(keys), self._p32(np.ascontiguousarray(new_idx)),
            len(keys)))

    def rebuild_from_keys(self, keys: np.ndarray) -> np.ndarray:
        slots = super().rebuild_from_keys(keys)
        # The base rebuilt the registry and the sorted arrays; the hash
        # variant keeps the table + reverse map instead.
        keys = np.ascontiguousarray(self.g_key)
        slots = np.ascontiguousarray(self.g_slot)
        self.g_key = np.zeros(0, dtype=np.int64)
        self.g_slot = np.zeros(0, dtype=np.int32)
        cap = 1 << 14
        while self.GROW_NUM * len(keys) > self.GROW_DEN * cap:
            cap *= 2
        self._cap = cap
        self._tkeys = np.full(self._cap, -1, dtype=np.int64)
        self._tvals = np.zeros(self._cap, dtype=np.int32)
        if len(keys):
            self._check_probe(self._lib.slab_hash_insert(
                self._p64(self._tkeys), self._p32(self._tvals),
                self._cap - 1, self._p64(keys), self._p32(slots), len(keys)))
        self._n = len(keys)
        self.slot_key = np.full(max(1 << 10, _pow2ceil(
            np.asarray([max(self.heap_end, 1)]), 1024)[0]), -1,
            dtype=np.int64)
        if len(keys):
            self.slot_key[slots] = keys
        return slots

    def keys_and_slots(self):
        live = self._tkeys != -1
        keys = self._tkeys[live]
        slots = self._tvals[live]
        order = np.argsort(keys, kind="stable")
        return keys[order], slots[order]

    @property
    def nbytes(self) -> int:
        return (self.rows.nbytes + self._tkeys.nbytes + self._tvals.nbytes
                + self.slot_key.nbytes)

    def row_cells(self, rows: np.ndarray):
        """Hash-layout override: recover keys through the reverse map
        (insertion order within a row; the caller sorts jointly)."""
        starts, lens, _ = self.rows.get(rows)
        idx = np.repeat(starts, lens) + _ragged_arange(lens)
        return self.slot_key[idx].copy(), idx.astype(np.int32)

    def adopt_rows(self, rows: np.ndarray, keys: np.ndarray,
                   lens: np.ndarray) -> np.ndarray:
        """Hash-layout override: same preserved-order contract as the
        sorted base (see its docstring); the table and reverse map take
        the place of the sorted merge."""
        slots = self._adopt_alloc(rows, lens)
        if not len(keys):
            return slots
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        self._ensure_slot_key(self.heap_end)
        self.slot_key[slots] = keys
        self._grow_table(self._n + len(keys))
        slots_c = np.ascontiguousarray(slots)
        self._check_probe(self._lib.slab_hash_insert(
            self._p64(self._tkeys), self._p32(self._tvals), self._cap - 1,
            self._p64(keys), self._p32(slots_c), len(keys)))
        self._n += len(keys)
        return slots

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """Hash-layout override of the present-keys slot resolve."""
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        slots = np.empty(len(keys), dtype=np.int32)
        missing = np.empty(len(keys), dtype=np.uint8)
        self._check_probe(self._lib.slab_hash_lookup(
            self._p64(self._tkeys), self._p32(self._tvals), self._cap - 1,
            self._p64(keys), len(keys), self._p32(slots),
            self._p8(missing)))
        if missing.view(bool).any():
            raise KeyError("lookup of absent cell keys — promotion "
                           "contract violated")
        return slots

    def free_rows(self, rows: np.ndarray) -> None:
        """Hash-layout override: the open-addressing table has no
        tombstones, so deletion rebuilds it minus the dead keys —
        promotions are rare enough that the rebuild is off the
        steady-state path."""
        starts, lens, cap = self.rows.get(rows)
        self.garbage += int(cap.sum())
        idx = np.repeat(starts, lens) + _ragged_arange(lens)
        dead = self.slot_key[idx]
        self.slot_key[idx] = -1
        live = self._tkeys != -1
        tk, tv = self._tkeys[live], self._tvals[live]
        keep = ~np.isin(tk, dead)
        tk = np.ascontiguousarray(tk[keep])
        tv = np.ascontiguousarray(tv[keep])
        self._tkeys = np.full(self._cap, -1, dtype=np.int64)
        self._tvals = np.zeros(self._cap, dtype=np.int32)
        if len(tk):
            self._check_probe(self._lib.slab_hash_insert(
                self._p64(self._tkeys), self._p32(self._tvals),
                self._cap - 1, self._p64(tk), self._p32(tv), len(tk)))
        self._n = len(tk)
        self.rows.clear(rows)


def make_slab_index(rows_capacity: int = 1 << 10) -> SlabIndex:
    """Best available cell index: the native hash table, else sorted."""
    from ..native import get_lib

    if get_lib() is not None:
        return HashSlabIndex(rows_capacity=rows_capacity)
    return SlabIndex(rows_capacity=rows_capacity)


class SparseDeviceScorer:
    """Single-device scorer over a :class:`SlabIndex`-managed HBM slab."""

    # Pipelined mode (pipeline.py) may hand this scorer pre-folded
    # AggregatedPairs — the producer thread runs the per-cell fold, and
    # process_window starts at slot allocation. Bit-identical either way
    # (the fold is the same aggregate_window_coo call).
    accepts_aggregated = True

    # Per-score-chunk padded-cell budget. Padding is device compute only —
    # it never crosses the wire in this backend — so the budget is sized
    # for HBM transients ([S, R] gather + scores), not transfer, and the
    # length ladder is coarse (default pow-4; TPU_COOC_SCORE_LADDER):
    # fewer dispatches beats tighter padding when every dispatch pays
    # link round-trip latency.
    SCORE_BUDGET = 1 << 24
    # Fixed-shape mode budget (smaller: every window pays the full padded
    # rectangle, and its meta upload is wire bytes — see fixed_shapes).
    FIXED_BUDGET = 1 << 22
    # Per-bucket row cap in fixed-shape mode: bounds the [3, S_cap] meta
    # upload (12 B/row; 65536 rows = 768 KB) that every window ships.
    FIXED_ROW_CAP = 1 << 16

    def __init__(self, top_k: int, counters: Optional[Counters] = None,
                 development_mode: bool = False,
                 capacity: int = 1 << 16,
                 items_capacity: int = 1 << 10,
                 compact_min_heap: int = 1 << 16,
                 score_ladder: Optional[int] = None,
                 defer_results: bool = False,
                 fixed_shapes: Optional[bool] = None,
                 use_pallas: str = "auto",
                 cell_dtype: str = "int32",
                 wire_format: str = "raw",
                 spill_threshold_windows: int = 0,
                 spill_target_hbm_frac: float = 0.5,
                 fused_window: str = "off") -> None:
        from .wire import CELL_DTYPES, cell_promote_threshold

        if cell_dtype not in CELL_DTYPES:
            raise ValueError(
                f"cell_dtype must be one of {sorted(CELL_DTYPES)}, got "
                f"{cell_dtype!r}")
        if wire_format not in ("raw", "packed"):
            raise ValueError(
                f"wire_format must be raw or packed, got {wire_format!r}")
        self.cell_dtype = cell_dtype
        self._cnt_dtype = CELL_DTYPES[cell_dtype]
        # Narrow-cell promotion bound (None for int32): a row whose sum
        # reaches it moves to the wide int32 side-table BEFORE this
        # window's deltas apply, so narrow cells can never saturate and
        # scores stay bit-identical to an int32 slab.
        self.promote_threshold = cell_promote_threshold(cell_dtype)
        self.wire_packed = wire_format == "packed"
        self.top_k = top_k
        # Bucket-ladder base for the scoring dispatches (see score_buckets).
        # Env-tunable so high-latency links can trade padding for fewer
        # round trips without a config/API change.
        self.score_ladder = int(score_ladder if score_ladder is not None
                                else tuning.env_read(
                                    "TPU_COOC_SCORE_LADDER", 4))
        ladder_bits(self.score_ladder)  # validate at construction
        self.counters = counters if counters is not None else Counters()
        self.development_mode = development_mode
        self.index = make_slab_index(rows_capacity=items_capacity)
        self.items_cap = int(items_capacity)
        self.row_sums_host = np.zeros(self.items_cap, dtype=np.int64)
        self.compact_min_heap = int(compact_min_heap)
        self.capacity = int(capacity)
        self.cnt = jnp.zeros(self.capacity, dtype=self._cnt_dtype)
        self.dst = jnp.zeros(self.capacity, dtype=jnp.int32)
        self.row_sums = jnp.zeros(self.items_cap, dtype=jnp.int32)
        self.observed = 0
        # Exact live-cell count (dead promoted index entries excluded) —
        # feeds cooc_slab_live_cells and the bench's cells-per-byte.
        self.live_cells = 0
        # Wide int32 side-table (narrow cell dtypes only): its own
        # SlabIndex over the same row-id space plus a private slab pair.
        # Rows promote in whole — a row is entirely narrow or entirely
        # wide — so scoring stays per-row and the shared kernels run
        # unchanged over whichever slab pair holds the row.
        if self.promote_threshold is not None:
            self.index_w = make_slab_index(rows_capacity=items_capacity)
            self.capacity_w = 1 << 10
            self.cnt_w = jnp.zeros(self.capacity_w, dtype=jnp.int32)
            self.dst_w = jnp.zeros(self.capacity_w, dtype=jnp.int32)
            self.wide_rows = np.zeros(self.items_cap, dtype=bool)
        else:
            self.index_w = None
        self._plan_buckets_w = {}
        # One-window-deep result pipeline (see ops/device_scorer.py).
        self._pending: Optional[List] = None
        self.last_dispatched_rows = 0
        # scorer_breaker fault-site ordinal (see ops/device_scorer.py).
        self._breaker_seq = 0
        # Deferred-results mode: each score dispatch scatters its top-K
        # into a device-resident [2, items_cap, K] table instead of
        # returning it; ``flush()`` fetches the table's touched rows once.
        # This is the final-state consumption mode (no --emit-updates):
        # per-window result transfer drops to zero, which on a DCN link
        # is most of a large window's wall time. The
        # reference has no analogue (its sink is a no-op, results ride the
        # accumulator dump — FlinkCooccurrences.java:169-181).
        self.defer_results = bool(defer_results)
        self._results = (DeferredResultsTable(top_k, self.items_cap)
                         if self.defer_results else None)
        # Fixed-shape scoring: pad every bucket's meta to a constant
        # per-bucket row cap so each window re-dispatches the SAME
        # compiled programs — one compile per bucket ever, steady ~1
        # dispatch per occupied bucket, no pow-4 shape ladder. The padded
        # rows are dead device compute (bounded by FIXED_BUDGET) and a
        # bounded meta upload; the win is dispatch/compile-count, which
        # is what a high-latency link and a freshly-started process
        # actually pay for. Default: on for real TPUs, off elsewhere
        # (CPU tests would crawl through the padding); env
        # TPU_COOC_FIXED_SCORE=0/1 overrides.
        self.fixed_shapes = resolve_fixed_shapes(fixed_shapes,
                                                 self.defer_results)
        # bucket -> high-water chunk count (monotone plan: the fused
        # program's static plan only ever grows, so compile count stays
        # bounded even when a bucket occasionally overflows s_block).
        self._plan_buckets = {}
        # Fused-kernel routing for wide rectangles (--pallas): see
        # ops/pallas_score.resolve_sparse_pallas_flag (the measured
        # rationale lives there, once, for both sparse scorers).
        from ..ops.pallas_score import resolve_sparse_pallas_flag

        self.use_pallas = resolve_sparse_pallas_flag(use_pallas)
        self._pallas_interpret = jax.default_backend() != "tpu"
        # Fused one-dispatch window (--fused-window on the SPARSE
        # backend): steady-state windows run wire decode + update
        # scatter + registry sync + rescore + results scatter as ONE
        # program (_fused_sparse_window_*). Deferred results only — the
        # whole point is that nothing returns per window; config rejects
        # an explicit 'on' with --emit-updates, 'auto' degrades to
        # chained. Relocation / promotion / spill-re-promotion windows
        # route chained per window (same bit-identical results: the
        # fused body IS the chained body, fused).
        from ..ops.device_scorer import resolve_fused_flag

        self.use_fused = self.defer_results and resolve_fused_flag(
            fused_window)
        # The sparse fused path consumes aggregated deltas (the host
        # fold owns slot allocation); it never wants basket uplinks.
        self.wants_baskets = False
        # Which path the LAST process_window dispatch took — the
        # journal's ``fused`` field and /healthz read it.
        self.last_dispatch_fused = False
        # Tracing plane: per-window stage seconds (index / uplink-encode
        # / rescore) the job carves into journal span tuples — the
        # unattributed remainder of score_seconds becomes "dispatch" —
        # and counts (launches, score_cells, live_cells).
        self.stage_clock = StageClock()
        self._fused_dispatches = REGISTRY.gauge(
            "cooc_fused_dispatches_total",
            help="windows dispatched through the fused one-dispatch "
                 "window program")
        self._chained_dispatches = REGISTRY.gauge(
            "cooc_chained_dispatches_total",
            help="windows dispatched through the chained "
                 "scatter+score path")
        self._bucket_compiles = REGISTRY.gauge(
            "cooc_fused_bucket_compilations_total",
            help="distinct fused-window program shapes dispatched "
                 "(per-bucket shape-specialization compile churn)")
        # Static-shape keys the fused path has dispatched: each is one
        # XLA compile (pow2/pow4 ladders bound the set).
        self._fused_shapes = set()
        if self.use_fused:
            # Host side of the device registry mirror: every registry
            # mutation logs its rows; each fused dispatch uplinks the
            # dirty rows' (start, len) as a delta sync.
            self.index.rows.enable_dirty_log()
            self.reg_start = jnp.zeros(self.items_cap, dtype=jnp.int32)
            self.reg_len = jnp.zeros(self.items_cap, dtype=jnp.int32)
        # Elastic-state placement policy (state/store.py): tiered
        # cold-row spill when --spill-threshold-windows is set, direct
        # (everything device-resident) otherwise. The store owns the
        # checkpoint-blob round trip either way.
        from .store import make_store

        self.store = make_store(self, spill_threshold_windows,
                                spill_target_hbm_frac)

    def _rect_pallas(self, R: int) -> bool:
        """Whether bucket width ``R`` routes through the fused kernel
        (ops/pallas_score.rect_routed — the shared routing rule)."""
        from ..ops.pallas_score import rect_routed

        return rect_routed(self.use_pallas, R, self.top_k, self.items_cap)

    # Back-compat introspection used by tests.
    @property
    def heap_end(self) -> int:
        return self.index.heap_end

    @property
    def compactions(self) -> int:
        return self.index.compactions

    # -- capacity management --------------------------------------------

    def _ensure_items(self, max_id: int) -> None:
        if max_id >= (1 << 31) - 1:
            raise ValueError("sparse backend supports item ids < 2^31 - 1")
        if max_id < self.items_cap:
            return
        new_cap = int(_pow2ceil(np.asarray([max_id + 1]), 1024)[0])
        grown = np.zeros(new_cap, dtype=np.int64)
        grown[: len(self.row_sums_host)] = self.row_sums_host
        self.row_sums_host = grown
        clk = self.stage_clock
        clk.add("launches")
        self.row_sums = _grow(self.row_sums, n=new_cap)
        if self.index_w is not None:
            wide = np.zeros(new_cap, dtype=bool)
            wide[: len(self.wide_rows)] = self.wide_rows
            self.wide_rows = wide
        if self.use_fused:
            # Zero-extension preserves the synced (start, len) entries;
            # new rows read len 0 until their first registry sync.
            clk.add("launches")
            self.reg_start = _grow(self.reg_start, n=new_cap)
            clk.add("launches")
            self.reg_len = _grow(self.reg_len, n=new_cap)
        self.items_cap = new_cap
        if self._results is not None:
            clk.add("launches", self._results.resize(new_cap))

    def _ensure_heap(self, need_end: int) -> None:
        if need_end <= self.capacity:
            return
        new_cap = self.capacity
        while new_cap < need_end:
            new_cap *= 2
        self.stage_clock.add("launches")
        self.cnt = _grow(self.cnt, n=new_cap)
        self.stage_clock.add("launches")
        self.dst = _grow(self.dst, n=new_cap)
        self.capacity = new_cap

    def _ensure_heap_w(self, need_end: int) -> None:
        if need_end <= self.capacity_w:
            return
        new_cap = self.capacity_w
        while new_cap < need_end:
            new_cap *= 2
        self.stage_clock.add("launches")
        self.cnt_w = _grow(self.cnt_w, n=new_cap)
        self.stage_clock.add("launches")
        self.dst_w = _grow(self.dst_w, n=new_cap)
        self.capacity_w = new_cap

    # -- the window step --------------------------------------------------

    def process_window(self, ts: int, pairs: PairDeltaBatch):
        self._breaker_seq += 1
        if faults.PLAN is not None:
            # The breaker's trip input (see ops/device_scorer.py).
            faults.PLAN.fire("scorer_breaker", seq=self._breaker_seq)
        self.last_dispatched_rows = 0
        self.last_dispatch_fused = False
        self.stage_clock.reset()
        if len(pairs) == 0:
            if self.defer_results:
                # Idle window: results are intentionally held on device for
                # the end-of-stream/checkpoint flush (the drain itself is
                # incremental — dirty rows only — but draining on every
                # idle window would still cost a dispatch + downlink for
                # rows nobody asked for yet).
                return TopKBatch.empty(self.top_k)
            # No new dispatch — drain any completed in-flight results now.
            return self.flush()
        clk = self.stage_clock
        # The window's host bookkeeping before any upload: the index
        # stage.
        with clk.stage("index"):
            # Tiered-state spill step (state/store.py; no-op for the
            # direct store): advance the window clock and move rows that
            # went cold to the host arena, BEFORE any index op — the
            # freed regions become garbage the compaction below can
            # reclaim this window.
            self.store.tick()
            # Reclaim freed slab regions once they dominate the heap.
            # Runs between windows only: mid-window the move/update
            # instructions already carry concrete slab addresses.
            if self.index.needs_compaction(self.compact_min_heap):
                gmap = self.index.compact()
                gmap_pad = np.zeros(min(pad_pow2(len(gmap),
                                                 minimum=1 << 10),
                                        self.capacity), dtype=np.int32)
                gmap_pad[: len(gmap)] = gmap
                LEDGER.up("compact-gather", gmap_pad)
                clk.add("launches")
                self.cnt, self.dst = _compact_gather(
                    self.cnt, self.dst, gmap_pad, cap=self.capacity)
            if (self.index_w is not None
                    and self.index_w.needs_compaction(self.compact_min_heap)):
                gmap = self.index_w.compact()
                gmap_pad = np.zeros(min(pad_pow2(len(gmap),
                                                 minimum=1 << 10),
                                        self.capacity_w), dtype=np.int32)
                gmap_pad[: len(gmap)] = gmap
                LEDGER.up("compact-gather-wide", gmap_pad)
                clk.add("launches")
                self.cnt_w, self.dst_w = _compact_gather(
                    self.cnt_w, self.dst_w, gmap_pad, cap=self.capacity_w)
            self._ensure_items(int(max(pairs.src.max(), pairs.dst.max())))
            if isinstance(pairs, AggregatedPairs):
                src_d, d_val, d_key = pairs.src, pairs.delta, pairs.key
            else:
                src_d, _, d_val, d_key = aggregate_window_coo(
                    pairs.src, pairs.dst, pairs.delta.astype(np.int64),
                    return_key=True)
            d_val32 = narrow_deltas_int32(d_val)

            # Row sums first (watermark ordering, reference
            # ItemRowRescorerTwoInputStreamOperator.java:116-142). The
            # host mirror is exact (int64); the device copy feeds the
            # k21 gathers.
            rows = distinct_sorted(src_d)
            row_ends = np.searchsorted(src_d, rows, side="right")
            cum = np.concatenate([[0], np.cumsum(d_val)])
            rs_delta = cum[row_ends] - cum[np.searchsorted(src_d, rows)]
            self.row_sums_host[rows] += rs_delta
            if self.row_sums_host[rows].max(initial=0) >= 2**31:
                raise ValueError("row sum exceeds int32 range")
            # Fold-invariant: the per-cell aggregated deltas sum to
            # exactly the raw per-pair deltas (both int64), so either
            # input form works.
            window_sum = int(d_val.sum())
            self.observed += window_sum
            self.counters.add(ROW_SUM_PROCESS_WINDOW, window_sum)

            # Spill-tier re-promotion FIRST (before the narrow->wide
            # check and before any delta applies): touched rows resident
            # in the host arena re-enter the slab index with their
            # within-row order preserved; their cell values ride this
            # window's update upload as extra new-cell + delta entries —
            # no extra dispatch.
            promo_n, promo_w = self.store.promote_touched(rows)
            # Incremental-checkpoint dirty feed (state/delta.py): the
            # SAME touched-rows set the recency clock stamps — one dirty
            # source, two consumers. No-op unless
            # --checkpoint-incremental armed it.
            self.store.note_touched(rows)
            # Narrow-cell promotion, then the per-slab split: a cell
            # routes by its row's residency, decided BEFORE this
            # window's deltas apply.
            if self.index_w is not None:
                self._promote_rows(rows)
                cell_wide = self.wide_rows[src_d]
            else:
                cell_wide = None
        # Fused routing gate: steady-state all-narrow windows with no
        # spill re-promotion take the one-dispatch program; promotion /
        # wide-touching / re-promotion windows (and, inside
        # _fused_window, relocation windows and explicit upload-split
        # requests) route chained — per window, bit-identically.
        plan = None
        fused_done = False
        if (self.use_fused and promo_n is None and promo_w is None
                and (cell_wide is None or not cell_wide.any())):
            fused_done, plan = self._fused_window(d_key, d_val32,
                                                  rows, rs_delta)
        if fused_done:
            if self.development_mode:
                self._check_row_sums(rows)
            self.counters.add(RESCORED_ITEMS, len(rows))
            self.last_dispatched_rows = len(rows)
            self.last_dispatch_fused = True
            self._fused_dispatches.add(1)
            self._record_state_gauges()
            # Deferred results only: this window's top-K was scattered
            # into the device table inside the fused program.
            return TopKBatch.empty(self.top_k)

        self._chained_dispatches.add(1)
        split = cell_wide is not None and (cell_wide.any()
                                           or promo_w is not None)
        # Slot allocation is index work; a plan from a fused attempt
        # that bailed AFTER allocation (relocation window / explicit
        # upload-split request) must not be applied twice.
        with clk.stage("index"):
            if split:
                key_n, key_w = d_key[~cell_wide], d_key[cell_wide]
                plan_n = self.index.apply(key_n)
                plan_w = self.index_w.apply(key_w)
            elif plan is None:
                plan = self.index.apply(d_key)
        with clk.stage("uplink-encode"):
            if split:
                self._window_update(key_n, d_val32[~cell_wide], rows,
                                    rs_delta, plan_n, wide=False,
                                    promo=promo_n)
                self._window_update(key_w, d_val32[cell_wide], rows[:0],
                                    rs_delta[:0], plan_w, wide=True,
                                    promo=promo_w)
            else:
                self._window_update(d_key, d_val32, rows, rs_delta, plan,
                                    wide=False, promo=promo_n)

        if self.development_mode:
            self._check_row_sums(rows)

        # Score every updated row, length-bucketed (padding is device-only).
        self.counters.add(RESCORED_ITEMS, len(rows))
        self.last_dispatched_rows = len(rows)
        if self.index_w is not None and self.wide_rows[rows].any():
            wmask = self.wide_rows[rows]
            chunks = self._dispatch_scoring(rows[~wmask], wide=False)
            chunks += self._dispatch_scoring(rows[wmask], wide=True)
        else:
            chunks = self._dispatch_scoring(rows)
        self._record_state_gauges()

        prev, self._pending = self._pending, chunks
        return (self._materialize(prev) if prev is not None
                else TopKBatch.empty(self.top_k))

    def _promote_rows(self, rows: np.ndarray) -> None:
        """Promote rows whose (already-updated) sum crossed the narrow
        bound: move their cells to the wide side-table before this
        window's deltas touch them — saturation can never be observed."""
        thr = self.promote_threshold
        sel = (self.row_sums_host[rows] >= thr) & ~self.wide_rows[rows]
        if not sel.any():
            return
        newly = rows[sel]
        self.wide_rows[newly] = True
        keys, slots = self.index.row_cells(newly)
        self.index.free_rows(newly)
        if not len(keys):
            return  # first-ever window already past the bound: no cells yet
        order = np.argsort(keys, kind="stable")
        keys = keys[order]
        slots = slots[order].astype(np.int32)
        plan_w = self.index_w.apply(keys)
        self._ensure_heap_w(self.index_w.heap_end)
        m = len(keys)
        m_pad = pad_pow2(m, minimum=64)
        src = np.zeros(m_pad, dtype=np.int32)
        src[:m] = slots
        dsts = np.full(m_pad, _SENT, dtype=np.int32)
        dsts[:m] = plan_w.slots
        LEDGER.up("promote-cells", src, dsts)
        self.stage_clock.add("launches")
        self.cnt_w, self.dst_w = _promote_cells(
            self.cnt, self.dst, self.cnt_w, self.dst_w, src, dsts)

    def _window_update(self, d_key: np.ndarray, d_val32: np.ndarray,
                       rows: np.ndarray, rs_delta: np.ndarray,
                       plan: AllocPlan, wide: bool = False,
                       promo=None) -> None:
        """Pack and dispatch one slab's window update under ``plan``,
        the slots ``index.apply`` allocated for ``d_key``. The narrow
        dispatch also carries the shared row-sum section (row sums are
        slab-independent); the wide dispatch's is empty.

        ``promo`` — tiered-store re-promotion extras ``(cell_keys,
        dst_vals, cnt_vals)``: each promoted cell rides the SAME upload
        as one new-cell entry (sets its partner id, zeroes the slot)
        plus one delta entry (adds its spilled count back) — exact
        movement with no extra dispatch. Slots are resolved AFTER
        ``apply`` (a promoted row gaining a new cell this window may be
        relocated by it); they are disjoint from apply's new-cell slots,
        and a promoted slot also receiving a window delta is fine: the
        delta section's scatter-adds commute."""
        index = self.index_w if wide else self.index
        if wide:
            self._ensure_heap_w(index.heap_end)
            cnt_t, dst_t = self.cnt_w, self.dst_w
        else:
            self._ensure_heap(index.heap_end)
            cnt_t, dst_t = self.cnt, self.dst
        self.live_cells += plan.n_new

        upd, bounds, n = self._pack_update(index, plan, d_key, d_val32,
                                           rows, rs_delta, promo)
        n_pad = upd.shape[1]
        lbl = "update-wide" if wide else "update"
        self.stage_clock.add("launches")

        # An explicit upload-split request (TPU_COOC_UPLOAD_CHUNKS /
        # _CHUNK_KB) pins the raw chunked path — the two wire levers are
        # alternatives, and an operator A/B-ing chunk sizes must not
        # silently measure the packed encoder instead.
        parts = split_upload_auto(upd) if not wide else None
        if parts is None and self.wire_packed:
            from .wire import encode_update

            words_i, words_v, header = encode_update(upd, bounds, n)
            wi = _pad_words(words_i)
            wv = _pad_words(words_v)
            if plan.mv is not None:
                LEDGER.up("update-moves", plan.mv)
                LEDGER.up_encoded(lbl + "-packed",
                                  upd.nbytes + bounds.nbytes, wi, wv, header)
                cnt_t, dst_t, self.row_sums = _apply_moves_update_packed(
                    cnt_t, dst_t, self.row_sums, plan.mv, wi, wv, header,
                    n_pad=n_pad, L=plan.mv_len)
            else:
                LEDGER.up_encoded(lbl + "-packed",
                                  upd.nbytes + bounds.nbytes, wi, wv, header)
                cnt_t, dst_t, self.row_sums = _apply_update_packed(
                    cnt_t, dst_t, self.row_sums, wi, wv, header, n_pad=n_pad)
        else:
            if parts is not None:
                # Ledger mirrors the actual transfer pattern: one event
                # per chunk plus the small metadata buffers (same byte
                # total as the monolithic event).
                for p in parts:
                    LEDGER.up("update-chunk", p)
            if plan.mv is not None:
                if parts is not None:
                    LEDGER.up("update-meta", bounds, plan.mv)
                    cnt_t, dst_t, self.row_sums = _apply_moves_update_chunked(
                        cnt_t, dst_t, self.row_sums, plan.mv,
                        parts, bounds, L=plan.mv_len)
                else:
                    LEDGER.up(lbl, upd, bounds, plan.mv)
                    cnt_t, dst_t, self.row_sums = _apply_moves_update(
                        cnt_t, dst_t, self.row_sums, plan.mv, upd,
                        bounds, L=plan.mv_len)
            else:
                if parts is not None:
                    LEDGER.up("update-meta", bounds)
                    cnt_t, dst_t, self.row_sums = _apply_update_chunked(
                        cnt_t, dst_t, self.row_sums, parts, bounds)
                else:
                    LEDGER.up(lbl, upd, bounds)
                    cnt_t, dst_t, self.row_sums = _apply_update(
                        cnt_t, dst_t, self.row_sums, upd, bounds)
        if wide:
            self.cnt_w, self.dst_w = cnt_t, dst_t
        else:
            self.cnt, self.dst = cnt_t, dst_t

    def _pack_update(self, index, plan: AllocPlan, d_key: np.ndarray,
                     d_val32: np.ndarray, rows: np.ndarray,
                     rs_delta: np.ndarray, promo):
        """THE window update-buffer layout (new cells | deltas | row
        sums, sentinel padding, pow4 transfer bucket) — single owner,
        shared by the chained and fused dispatch forms so the wire
        layout cannot drift between them. Returns ``(upd, bounds, n)``.

        ``promo`` as in :meth:`_window_update` (the fused path always
        passes ``None`` — re-promotion windows route chained)."""
        if promo is not None:
            p_keys, p_dst, p_vals = promo
            p_slots = index.lookup(p_keys)
        else:
            p_slots = p_dst = p_vals = np.zeros(0, dtype=np.int32)
        n_pn = plan.n_new
        n_promo = len(p_slots)
        n_new = n_pn + n_promo
        n_d, n_rs = len(d_key) + n_promo, len(rows)
        n = n_new + n_d + n_rs
        n_pad = pad_pow4(n, minimum=1 << 12)
        upd = np.full((2, n_pad), _SENT, dtype=np.int32)
        upd[1] = 0
        if n_pn:
            upd[0, :n_pn] = plan.slots[plan.new_sel]
            upd[1, :n_pn] = (d_key[plan.new_sel]
                             & 0xFFFFFFFF).astype(np.int32)
        if n_promo:
            upd[0, n_pn: n_new] = p_slots
            upd[1, n_pn: n_new] = p_dst
            upd[0, n_new: n_new + n_promo] = p_slots
            upd[1, n_new: n_new + n_promo] = p_vals
        upd[0, n_new + n_promo: n_new + n_d] = plan.slots
        upd[1, n_new + n_promo: n_new + n_d] = d_val32
        upd[0, n_new + n_d: n] = rows
        upd[1, n_new + n_d: n] = rs_delta.astype(np.int32)
        bounds = np.asarray([n_new, n_new + n_d], dtype=np.int32)
        return upd, bounds, n

    def _bump_fixed_plan(self, plan_buckets: dict, bucket: np.ndarray,
                         min_r: int) -> None:
        """Raise the monotone (bucket -> chunk-count) high-water plan to
        cover this window's bucket occupancy — single owner of the
        fixed-shape plan rule, shared by the chained fixed-mode dispatch
        and the fused window so their plans cannot drift."""
        for b, n_rows in zip(*[u.tolist() for u in
                               np.unique(bucket, return_counts=True)]):
            R = bucket_r(b, min_r, self.score_ladder)
            S = fixed_block(R, self.FIXED_BUDGET, self.FIXED_ROW_CAP)
            plan_buckets[b] = max(plan_buckets.get(b, 0), -(-n_rows // S))

    @property
    def fused_compilations(self) -> int:
        """Distinct fused-program static shapes dispatched so far (=
        XLA compiles of the fused window; the journal's per-window
        ``fused_compiles`` field)."""
        return len(self._fused_shapes)

    def _note_fused_shape(self, key) -> None:
        """Track distinct fused-program static shapes (= XLA compiles):
        the per-bucket shape-specialization churn gauge."""
        if key not in self._fused_shapes:
            self._fused_shapes.add(key)
            self._bucket_compiles.set(len(self._fused_shapes))

    def _fused_window(self, d_key: np.ndarray, d_val32: np.ndarray,
                      rows: np.ndarray, rs_delta: np.ndarray):
        """Dispatch one steady-state window through the fused
        one-dispatch program. Returns ``(handled, pre_plan)``:
        ``(True, None)`` when the window ran fused, ``(False, plan)``
        when it must route chained — the allocation already happened, so
        the chained ``_window_update`` receives the plan instead of
        re-applying it.

        Not fused-routable (decided here, after allocation): relocation
        windows (``plan.mv`` — the fused program carries no move
        kernel; moves stay fused with the CHAINED update instead) and
        windows under an explicit upload-split request
        (TPU_COOC_UPLOAD_CHUNKS/_CHUNK_KB pins the raw chunked path —
        an operator A/B-ing chunk sizes must not silently measure the
        fused program). The caller gates promotion / wide-row / spill
        re-promotion windows before allocation.
        """
        clk = self.stage_clock
        with clk.stage("index"):
            plan = self.index.apply(d_key)
            if plan.mv is None:
                self._ensure_heap(self.index.heap_end)
        if plan.mv is not None:
            return False, plan

        # The update upload: cells, deltas and row sums, then the
        # registry mirror's delta sync and the wire encoding.
        with clk.stage("uplink-encode"):
            upd, bounds, n = self._pack_update(self.index, plan, d_key,
                                               d_val32, rows, rs_delta, None)
            n_pad = upd.shape[1]
            if split_upload_auto(upd) is not None:
                return False, plan
            # Registry delta sync: rows whose host (start, len) changed
            # since the device mirror last synced — this window's
            # new-cell rows plus anything a chained window / compaction
            # / spill touched in between. Sentinel-padded,
            # scatter-dropped.
            dirty, all_dirty = self.index.rows.drain_dirty()
            if all_dirty:
                dirty = self.index.rows.occupied().astype(np.int64)
            n_reg = len(dirty)
            reg_pad = pad_pow2(n_reg, minimum=256)
            reg_upd = np.full((3, reg_pad), _SENT, dtype=np.int32)
            if n_reg:
                r_start, r_len, _c = self.index.rows.get(dirty)
                reg_upd[0, :n_reg] = dirty
                reg_upd[1, :n_reg] = r_start
                reg_upd[2, :n_reg] = r_len
            if self.wire_packed:
                from .wire import encode_update

                words_i, words_v, header = encode_update(upd, bounds, n)
                wi = _pad_words(words_i)
                wv = _pad_words(words_v)
        self.live_cells += plan.n_new

        # Monotone scoring plan (the fixed-shape mode's rule, shared
        # _plan_buckets): every (bucket, chunk-rank) ever occupied
        # dispatches — absent ones as all-padding rectangles — so the
        # static plan only grows and compile count stays bounded by the
        # final plan's rectangle count. Per-row independence of
        # _score_rect makes chunking/padding parity-neutral. The bump is
        # index work and the rectangles are scoring work, as on the
        # chained path (_dispatch_scoring).
        with clk.stage("index"):
            _s, lens_h, _c = self.index.rows.get(rows)
            min_r = max(16, self.top_k)
            bucket, order = score_buckets(lens_h, min_r, self.score_ladder)
            self._bump_fixed_plan(self._plan_buckets, bucket, min_r)

        with clk.stage("rescore"):
            b_sorted = bucket[order]
            plan_t = []
            segs = []
            off = 0
            for b in sorted(self._plan_buckets):
                R = bucket_r(b, min_r, self.score_ladder)
                S = fixed_block(R, self.FIXED_BUDGET, self.FIXED_ROW_CAP)
                lo = int(np.searchsorted(b_sorted, b))
                hi = int(np.searchsorted(b_sorted, b, side="right"))
                rows_b = rows[order[lo:hi]]
                for c in range(self._plan_buckets[b]):
                    chunk = rows_b[c * S: (c + 1) * S]
                    seg = np.full(S, _SENT, dtype=np.int32)
                    seg[: len(chunk)] = chunk
                    segs.append(seg)
                    plan_t.append((R, S, off, self._rect_pallas(R)))
                    off += S
            rows_all = np.concatenate(segs)
            plan_t = tuple(plan_t)
        self._count_scored(plan_t, lens_h)

        clk.add("launches", self._results.ensure())
        observed = np.float32(self.observed)
        if self.wire_packed:
            LEDGER.up_encoded("fused-window-packed",
                              upd.nbytes + bounds.nbytes, wi, wv, header)
            LEDGER.up("fused-window-meta", reg_upd, rows_all)
            self._note_fused_shape(
                ("packed", n_pad, len(wi), len(wv), reg_pad, plan_t))
            (self.cnt, self.dst, self.row_sums, self._results.tbl,
             self.reg_start, self.reg_len) = _fused_sparse_window_packed(
                self.cnt, self.dst, self.row_sums, self._results.tbl,
                self.reg_start, self.reg_len, wi, wv, header, reg_upd,
                rows_all, observed, n_pad=n_pad, top_k=self.top_k,
                plan=plan_t, interpret=self._pallas_interpret)
        else:
            LEDGER.up("fused-window", upd, bounds, reg_upd, rows_all)
            self._note_fused_shape(("raw", n_pad, reg_pad, plan_t))
            (self.cnt, self.dst, self.row_sums, self._results.tbl,
             self.reg_start, self.reg_len) = _fused_sparse_window_raw(
                self.cnt, self.dst, self.row_sums, self._results.tbl,
                self.reg_start, self.reg_len, upd, bounds, reg_upd,
                rows_all, observed, top_k=self.top_k, plan=plan_t,
                interpret=self._pallas_interpret)
        self._results.mark(rows)
        return True, None

    def _record_state_gauges(self) -> None:
        """Per-window state-footprint gauges (the compression layer's
        headline numbers: host index RSS, device slab bytes, live cells)."""
        rss = self.index.nbytes
        slab = self.cnt.nbytes + self.dst.nbytes
        if self.index_w is not None:
            rss += self.index_w.nbytes + self.wide_rows.nbytes
            slab += self.cnt_w.nbytes + self.dst_w.nbytes
        REGISTRY.gauge(
            "cooc_host_index_rss_bytes",
            help="host-side slab index footprint (registry + cell "
                 "index), refreshed per window").set(rss)
        REGISTRY.gauge(
            "cooc_slab_device_bytes",
            help="device slab allocation (cnt + dst, narrow and wide)"
        ).set(slab)
        REGISTRY.gauge(
            "cooc_slab_live_cells",
            help="live matrix cells across narrow and wide slabs"
        ).set(self.live_cells)
        self.store.record_gauges()

    def _dispatch_scoring(self, rows: np.ndarray,
                          wide: bool = False) -> List[Tuple]:
        """Score ``rows`` out of one slab pair (``wide`` routes promoted
        rows through the int32 side-table; the kernels are dtype- and
        buffer-polymorphic, so both residencies share every program)."""
        if wide:
            index, cnt, dst = self.index_w, self.cnt_w, self.dst_w
            plan_buckets = self._plan_buckets_w
        else:
            index, cnt, dst = self.index, self.cnt, self.dst
            plan_buckets = self._plan_buckets
        if len(rows) == 0 and not plan_buckets:
            return []
        clk = self.stage_clock
        with clk.stage("index"):
            # One registry pass (the _RowField views are the compat shim
            # for external callers; this is the per-window hot path).
            starts, lens, _caps = index.rows.get(rows)
            min_r = max(16, self.top_k)  # lax.top_k needs k <= R
            bucket, order = score_buckets(lens, min_r, self.score_ladder)
            if self.fixed_shapes:
                # Monotone plan: dispatch every (bucket, chunk-rank) ever
                # occupied (absent ones as all-padding rectangles), so
                # the fused program's static plan only grows — no churn
                # from per-window bucket subsets OR from a bucket
                # occasionally overflowing its per-dispatch row cap.
                self._bump_fixed_plan(plan_buckets, bucket, min_r)
        with clk.stage("rescore"):
            b_sorted = bucket[order]
            if self.defer_results:
                clk.add("launches", self._results.ensure())
            chunks: List[Tuple[np.ndarray, int, object]] = []
            # Fixed mode: (R, S, chunk) rectangles of one window dispatch.
            rects: List[Tuple[int, int, np.ndarray]] = []
            pos = 0
            while pos < len(order):
                b = int(b_sorted[pos])
                end = int(np.searchsorted(b_sorted, b, side="right"))
                R = bucket_r(b, min_r, self.score_ladder)
                if self.fixed_shapes:
                    s_block = fixed_block(R, self.FIXED_BUDGET,
                                          self.FIXED_ROW_CAP)
                else:
                    s_block = max(self.SCORE_BUDGET // R, 16)
                for lo in range(pos, end, s_block):
                    chunk = order[lo: min(lo + s_block, end)]
                    s = len(chunk)
                    if self.fixed_shapes:
                        # Fixed mode: always the full per-bucket
                        # rectangle, collected into ONE window dispatch
                        # below.
                        rects.append((R, s_block, chunk))
                        continue
                    # pow-4 row padding: each (R, s_pad) combination is
                    # one trace + compile per process; a coarse ladder
                    # keeps the program count (and per-process retrace
                    # time) small.
                    s_pad = min(pad_pow4(s, minimum=16), s_block)
                    self._count_scored(((R, s_pad),), lens[chunk])
                    meta = np.zeros((3, s_pad), dtype=np.int32)
                    meta[0, :s] = rows[chunk]
                    meta[1, :s] = starts[chunk]
                    meta[2, :s] = lens[chunk]
                    LEDGER.up("bucket-meta", meta)
                    if self.defer_results:
                        # Fused: the scatter rides the scoring dispatch
                        # (the table is donated in and reassigned).
                        self._results.tbl = _score_into_table(
                            self._results.tbl, cnt, dst,
                            self.row_sums, meta, np.float32(self.observed),
                            top_k=self.top_k, R=R,
                            pallas=self._rect_pallas(R),
                            interpret=self._pallas_interpret)
                        continue
                    score = (_score_slab_pallas if self._rect_pallas(R)
                             else _score_slab)
                    kw = ({"interpret": self._pallas_interpret}
                          if self._rect_pallas(R) else {})
                    packed = score(cnt, dst, self.row_sums,
                                   meta, np.float32(self.observed),
                                   top_k=self.top_k, R=R, **kw)
                    if hasattr(packed, "copy_to_host_async"):
                        packed.copy_to_host_async()
                    chunks.append((rows[chunk], s, packed))
                pos = end
            if self.fixed_shapes:
                # Top up to the high-water plan: every (bucket,
                # chunk-rank) ever seen dispatches, absent ones as
                # all-padding.
                have = {}
                for R, _S, _c in rects:
                    have[R] = have.get(R, 0) + 1
                for b, n_chunks in plan_buckets.items():
                    R = bucket_r(b, min_r, self.score_ladder)
                    S = fixed_block(R, self.FIXED_BUDGET,
                                    self.FIXED_ROW_CAP)
                    for _ in range(n_chunks - have.get(R, 0)):
                        rects.append((R, S, order[:0]))
            if rects:
                # One packed [3, sum(S)] meta upload + one dispatch for
                # the whole window (fixed mode is defer-only, enforced at
                # construction). Canonical R order keeps the plan
                # identical regardless of which buckets were empty this
                # window.
                self._count_scored(rects, lens)
                rects.sort(key=lambda t: t[0])
                total = sum(S for _R, S, _c in rects)
                meta_all = np.zeros((3, total), dtype=np.int32)
                plan = []
                off = 0
                for R, S, chunk in rects:
                    s = len(chunk)
                    meta_all[0, off: off + s] = rows[chunk]
                    meta_all[1, off: off + s] = starts[chunk]
                    meta_all[2, off: off + s] = lens[chunk]
                    plan.append((R, S, off, self._rect_pallas(R)))
                    off += S
                LEDGER.up("window-meta", meta_all)
                self._results.tbl = _score_window_into_table(
                    self._results.tbl, cnt, dst, self.row_sums,
                    meta_all, np.float32(self.observed),
                    top_k=self.top_k, plan=tuple(plan),
                    interpret=self._pallas_interpret)
        if self.defer_results:
            self._results.mark(rows)
        return chunks

    def _count_scored(self, rects, lens: np.ndarray) -> None:
        """One scoring program over ``rects`` (``(R, S, ...)``
        rectangles, a fixed plan's all-padding ones included) for rows
        of lengths ``lens``: the window's launch and cell counts."""
        clk = self.stage_clock
        clk.add("launches")
        clk.add("score_cells", sum(r[0] * r[1] for r in rects))
        clk.add("live_cells", int(lens.sum()))

    def _check_row_sums(self, rows: np.ndarray) -> None:
        """Dev-mode invariant: slab row contents sum to the tracked row sum
        (reference check, ItemRowRescorerTwoInputStreamOperator.java:183-193)."""
        cnt = np.asarray(self.cnt).astype(np.int64)
        cnt_w = (np.asarray(self.cnt_w) if self.index_w is not None
                 else None)
        for r in rows.tolist():
            if self.index_w is not None and self.wide_rows[r]:
                s, ln = self.index_w.row_start[r], self.index_w.row_len[r]
                actual = int(cnt_w[s: s + ln].sum())
            else:
                s, ln = self.index.row_start[r], self.index.row_len[r]
                actual = int(cnt[s: s + ln].sum())
            if actual != int(self.row_sums_host[r]):
                raise AssertionError(
                    f"Item row {int(self.row_sums_host[r])} does not match "
                    f"actual row sum {actual} (item {r})")

    # -- results ----------------------------------------------------------

    def flush(self) -> TopKBatch:
        if self.defer_results:
            return self._results.drain()
        prev, self._pending = self._pending, None
        return (self._materialize(prev) if prev is not None
                else TopKBatch.empty(self.top_k))

    def _materialize(self, chunks) -> TopKBatch:
        rows_l, idx_l, vals_l = [], [], []
        for rows, s, packed in chunks:
            host = np.asarray(packed)  # single [2, S_pad, K] fetch
            LEDGER.down("results", host)
            rows_l.append(rows)
            vals_l.append(host[0, :s])
            idx_l.append(unpack_ids(host[1, :s]))
        return TopKBatch.concatenate(rows_l, idx_l, vals_l, self.top_k)

    # -- checkpoint -------------------------------------------------------

    def checkpoint_state(self) -> dict:
        """Canonical snapshot via the state store (state/store.py): the
        tiered store merges spilled arena cells back into the blob, the
        direct store passes through — either way the format is the
        canonical one and files are interchangeable across stores."""
        return self.store.checkpoint_state()

    def restore_state(self, st: dict) -> None:
        self.store.restore_state(st)

    def _device_checkpoint_state(self) -> dict:
        """Canonical sparse-matrix snapshot of the DEVICE-resident rows —
        same keys as the hybrid backend, so checkpoints are
        interchangeable between the two (and between cell dtypes:
        narrow/wide residency is an in-memory layout, not a checkpoint
        concern)."""
        keys, slots = self.index.keys_and_slots()
        if self.index_w is not None:
            # free_rows deletes promoted rows' narrow entries; the mask
            # filter is defensive belt-and-braces on top of that.
            live = ~self.wide_rows[(keys >> 32).astype(np.int64)]
            keys, slots = keys[live], slots[live]
        if len(slots):
            # Gather live cells ON DEVICE so the fetch is nnz values, not
            # the whole slab (capacity >= 2x nnz from pow-2 slack+garbage).
            # The ledger books the NARROW fetched array — widening to
            # int64 happens host-side and never crosses the wire.
            LEDGER.up("checkpoint-slots", slots)
            fetched = np.asarray(self.cnt[jnp.asarray(slots)])
            LEDGER.down("checkpoint-cells", fetched)
            vals = fetched.astype(np.int64)
        else:
            vals = np.zeros(0, np.int64)
        if self.index_w is not None:
            keys_w, slots_w = self.index_w.keys_and_slots()
            if len(slots_w):
                LEDGER.up("checkpoint-slots", slots_w)
                fetched_w = np.asarray(self.cnt_w[jnp.asarray(slots_w)])
                LEDGER.down("checkpoint-cells", fetched_w)
                vals_w = fetched_w.astype(np.int64)
                keys = np.concatenate([keys, keys_w])
                vals = np.concatenate([vals, vals_w])
                order = np.argsort(keys, kind="stable")
                keys, vals = keys[order], vals[order]
        nz = vals != 0
        return {
            "rows_key": keys[nz],
            "rows_cnt": vals[nz].astype(np.int64),
            "row_sums": self.row_sums_host.copy(),
            "observed": np.asarray([self.observed], dtype=np.int64),
        }

    def _device_restore_state(self, st: dict) -> None:
        from .wire import checked_narrow

        key = st["rows_key"]
        cnt_vals = st["rows_cnt"]
        max_id = int(max((key >> 32).max(initial=0),
                         int((key & 0xFFFFFFFF).max(initial=0))))
        # Size host registries/capacities directly — the device arrays are
        # rebuilt wholesale below, so the _ensure_* grow-copy kernels would
        # only produce buffers we immediately discard.
        if max_id >= self.items_cap:
            new_cap = int(_pow2ceil(np.asarray([max_id + 1]), 1024)[0])
            self.row_sums_host = np.zeros(new_cap, dtype=np.int64)
            self.items_cap = new_cap
        rs = np.asarray(st["row_sums"], dtype=np.int64)
        if len(rs) > self.items_cap and rs[self.items_cap:].any():
            # Row-sum == sum of the row's cells (dev-mode invariant), so a
            # nonzero sum beyond the max cell id is a corrupt checkpoint.
            raise ValueError("checkpoint row sums extend past its cells")
        self.row_sums_host = np.zeros(self.items_cap, dtype=np.int64)
        m = min(len(rs), self.items_cap)
        self.row_sums_host[:m] = rs[:m]
        if self.index_w is not None:
            # Residency from the restored sums: any row at/past the bound
            # goes wide (a once-promoted row whose sum has since dropped
            # back under the bound fits narrow again — every cell is at
            # most the current sum — so the threshold rule is exact).
            self.wide_rows = self.row_sums_host >= self.promote_threshold
            wide_cells = self.wide_rows[(key >> 32).astype(np.int64)]
            key_w, cnt_w_vals = key[wide_cells], cnt_vals[wide_cells]
            key, cnt_vals = key[~wide_cells], cnt_vals[~wide_cells]
            slots_w = self.index_w.rebuild_from_keys(key_w)
            self.capacity_w = 1 << 10
            while self.capacity_w < self.index_w.heap_end:
                self.capacity_w *= 2
            cnt_w_host = np.zeros(self.capacity_w, dtype=np.int32)
            dst_w_host = np.zeros(self.capacity_w, dtype=np.int32)
            cnt_w_host[slots_w] = cnt_w_vals.astype(np.int32)
            dst_w_host[slots_w] = (key_w & 0xFFFFFFFF).astype(np.int32)
            LEDGER.up("restore-slab", cnt_w_host, dst_w_host)
            self.cnt_w = jnp.asarray(cnt_w_host)
            self.dst_w = jnp.asarray(dst_w_host)
        slots = self.index.rebuild_from_keys(key)
        while self.capacity < self.index.heap_end:
            self.capacity *= 2
        cnt_host = np.zeros(self.capacity, dtype=self._cnt_dtype)
        dst_host = np.zeros(self.capacity, dtype=np.int32)
        cnt_host[slots] = checked_narrow(cnt_vals, self._cnt_dtype)
        dst_host[slots] = (key & 0xFFFFFFFF).astype(np.int32)
        LEDGER.up("restore-slab", cnt_host, dst_host)
        self.cnt = jnp.asarray(cnt_host)
        self.dst = jnp.asarray(dst_host)
        self.row_sums = jnp.asarray(self.row_sums_host.astype(np.int32))
        self.observed = int(st["observed"][0])
        self.live_cells = len(st["rows_key"])
        # In-flight results belong to windows after the checkpoint.
        self._pending = None
        if self._results is not None:
            self._results.reset(self.items_cap)
        self._plan_buckets = {}
        self._plan_buckets_w = {}
        if self.use_fused:
            # Fresh device registry mirror for the rebuilt index; the
            # registry reset above marked everything dirty, so the next
            # fused window resyncs every occupied row.
            self.reg_start = jnp.zeros(self.items_cap, dtype=jnp.int32)
            self.reg_len = jnp.zeros(self.items_cap, dtype=jnp.int32)
