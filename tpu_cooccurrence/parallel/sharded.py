"""Multi-chip sharded scoring backend (``shard_map`` over an item mesh).

Distribution design (SURVEY §2.6, §7.6 — the TPU-native replacement of the
reference's keyed Netty shuffle + broadcast):

  * ``C`` (item x item counts) is **row-sharded** over the ``items`` mesh
    axis, interleaved: global row ``r`` lives on shard ``r % D`` at local
    row ``r // D`` — the analogue of ``keyBy(item)`` partitioned operator
    state. Item ids follow popularity (a stream's ranks, a first-seen
    vocab), so contiguous row blocks would put the hot rows, and most of
    every window's rescoring and scatter, on shard 0; dealt round robin,
    each shard gets a quarter of them (a 4-shard mesh). Columns keep the
    global order.
  * ``row_sums`` is **replicated** in global order — the analogue of the
    broadcast row-sum stream every rescorer subtask mirrors
    (``ItemRowRescorerTwoInputStreamOperator.java:33``, broadcast at
    ``FlinkCooccurrences.java:163``). Each shard computes a partial row-sum
    delta from its pair slice and the full update is an ``lax.psum`` over
    ICI — replacing the keyed shuffle + re-broadcast round-trip.
  * pair deltas and rows-to-score are **pre-partitioned by owner on host**
    (the hash-shuffle analogue: one stable sort of the window's owners,
    instead of a network shuffle), so each chip receives and processes
    only its slice. Each shard scatters its live lanes only, in the dense
    scorer's steps (``ops/device_scorer._stepped_apply``).
  * top-K is shard-local: each shard owns its rows outright, so no
    cross-chip merge is needed (SURVEY §7 "sharded top-K"); only the
    replicated row sums and the scalar ``observed`` total require
    cross-chip agreement.
  * ``C`` and the row sums are created on the devices; checkpoints hold
    ``C`` in global row order, as the dense single-chip scorer's do.

Works identically on a virtual CPU mesh
(``--xla_force_host_platform_device_count``) and real TPU meshes.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..metrics import Counters, RESCORED_ITEMS, ROW_SUM_PROCESS_WINDOW
from ..observability import LEDGER, StageClock
from ..observability.registry import REGISTRY, log_buckets
from ..state.results import TopKBatch, pack_ids, unpack_ids
from ..ops.aggregate import (aggregate_window_coo, distinct_sorted,
                             narrow_deltas_int32)
from ..ops.llr import llr_stable
from ..ops.device_scorer import (FUSED_MAX_ROWS, SCATTER_STEP, _coo_step,
                                 _stepped_apply, pad_pow2,
                                 resolve_pallas_flag, score_row_budget,
                                 topk_padded)
from ..ops.donation import donate_argnums
from ..sampling.reservoir import PairDeltaBatch
from .mesh import (ITEM_AXIS, make_mesh, pad_to_multiple,
                   shard_map_maybe_relaxed)


#: Row-count ladder for the dispatch-size histogram: 1 .. 2^24 rows.
ROWS_BUCKETS = log_buckets(1.0, 2.0 ** 24)

#: Most COO lanes one shard takes in one update program. A window's
#: lanes up to it ride one power-of-two bucket; a larger window ships
#: chunks of exactly this many lanes a shard, so one program serves every
#: large window (a bucket crossed inside a measured stretch would compile
#: there). The scatter runs the live lanes' steps only, so a chunk's
#: padding costs uplink bytes and no device time.
SHARD_CHUNK = 1 << 17


def _record_shard_metrics(n_rows: int, per_shard_counts) -> None:
    """Per-dispatch distribution metrics shared by both sharded backends.

    ``cooc_scorer_dispatch_rows`` is the per-window scored-row
    distribution (the padded-rectangle driver); the imbalance gauge is
    max/mean owned rows across shards — 1.0 is a perfectly balanced
    dispatch, and a sustained high value means one chip's rows gate every
    window (the sharded analogue of a straggler subtask).
    """
    REGISTRY.histogram(
        "cooc_scorer_dispatch_rows", ROWS_BUCKETS,
        help="distinct rows dispatched for scoring per window").observe(
            max(n_rows, 1))
    counts = np.asarray(per_shard_counts, dtype=np.float64)
    mean = counts.mean()
    if mean > 0:
        REGISTRY.gauge(
            "cooc_shard_row_imbalance",
            help="max/mean owned scored rows across shards "
                 "(1.0 = balanced)").set(float(counts.max() / mean))


def partition_by_owner(owners: np.ndarray, cols: Sequence[np.ndarray],
                       n_shards: int, cap: int, minimum: int
                       ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Bucket a window's entries by owning shard: ``cols`` (each ``[M]``,
    entry ``i`` owned by shard ``owners[i]``) into one int32 ``[D,
    len(cols), W]`` block, each shard's entries first in their order,
    then zeros. One stable sort of the owners serves every column.

    Returns ``(block, counts, width)``: the entries per shard, and the
    lanes a shard takes per program — one power-of-two bucket (at least
    ``minimum``) while the fullest shard's entries fit ``cap``, else
    ``cap``, with ``W`` a multiple of it.
    """
    owners = owners.astype(np.min_scalar_type(max(n_shards - 1, 0)))
    counts = np.bincount(owners, minlength=n_shards)
    most = int(counts.max()) if len(owners) else 0
    width = pad_pow2(most, minimum=minimum) if most <= cap else cap
    block = np.zeros((n_shards, len(cols), -(-max(most, 1) // width) * width),
                     dtype=np.int32)
    order = np.argsort(owners, kind="stable")
    shard = owners[order]
    slot = np.arange(len(order)) - (np.cumsum(counts) - counts)[shard]
    for k, col in enumerate(cols):
        block[shard, k, slot] = col[order]
    return block, counts, width


class ShardedScorer:
    """Item-row-sharded dense co-occurrence state over a 1-D device mesh."""

    #: Initial per-shard row capacity in derive-from-data mode
    #: (``num_items == 0``): the vocab grows with the stream like the
    #: dense backend's, doubling on overflow.
    AUTO_INITIAL_ROWS = 64

    #: Column-tile width for the fused kernel (same measured choice as
    #: DeviceScorer.PALLAS_TILE — swept on-chip before this round).
    PALLAS_TILE = 2048

    def __init__(self, num_items: int, top_k: int, num_shards: Optional[int] = None,
                 counters: Optional[Counters] = None,
                 mesh: Optional[Mesh] = None,
                 max_score_rows_per_call: int = 8192,
                 count_dtype: str = "int32",
                 use_pallas: str = "auto") -> None:
        if count_dtype not in ("int32", "int16"):
            raise ValueError(f"count_dtype must be int32|int16, got {count_dtype}")
        self.count_dtype = np.dtype(count_dtype)
        self.mesh = mesh if mesh is not None else make_mesh(num_shards)
        self.n_shards = self.mesh.devices.size
        # Fused-kernel routing: same auto rule (and top-k-overflow
        # warning) as the dense single-chip scorer — the kernel exactly
        # when int16 counts meet a real TPU (XLA collapsed 247x there,
        # measured before this round), per shard inside the shard_map
        # body. With pallas on, the vocab pads so the kernel's column
        # grid divides evenly and each shard's rows fill whole 8-row
        # fetch groups.
        self.use_pallas = resolve_pallas_flag(use_pallas, self.count_dtype,
                                              top_k)
        self._pallas_interpret = jax.default_backend() != "tpu"
        self._pad_unit = (math.lcm(8 * self.n_shards, self.PALLAS_TILE)
                          if self.use_pallas else self.n_shards)
        self.num_items_logical = num_items
        self.auto_grow = num_items <= 0
        if self.auto_grow:
            if jax.process_count() > 1:
                raise ValueError(
                    "multi-host sharded runs need --num-items: the vocab "
                    "capacity must agree across processes before any "
                    "window fires")
            num_items = self.AUTO_INITIAL_ROWS * self.n_shards
        self.top_k = top_k
        self.counters = counters if counters is not None else Counters()
        self._max_score_rows_per_call = max_score_rows_per_call
        self.observed = 0  # exact host-side total
        # One-window-deep result pipeline (see ops/device_scorer.py): the
        # device->host fetch of window N's top-K overlaps window N+1's host
        # sampling and dispatch; ``flush()`` drains the tail.
        self._pending: Optional[List] = None
        self.last_dispatched_rows = 0
        # Tracing plane, as the dense scorer's: per-window stage seconds
        # (index / uplink-encode / rescore) and counts (launches,
        # score_cells, live_cells, scatter_lanes, scatter_live; and
        # shard_rows, shard_rows_max: the rows scored over all shards and
        # on the fullest one).
        self.stage_clock = StageClock()

        from .distributed import put_global

        self._put_global = put_global
        self._build(num_items)
        self.C, self.row_sums = self._new_state()

    def _new_state(self):
        """Zero ``C`` and row sums, created on the devices, each shard its
        own block: no host copy of the num_items^2 state ever exists."""
        n, dtype = self.num_items, jnp.dtype(self.count_dtype.name)
        return jax.jit(
            lambda: (jnp.zeros((n, n), dtype), jnp.zeros((n,), jnp.int32)),
            out_shardings=(self._rows_sharding,
                           NamedSharding(self.mesh, P())))()

    @property
    def _rows_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(ITEM_AXIS, None))

    def _build(self, num_items: int) -> None:
        """(Re)build the capacity-dependent pieces: shard geometry and the
        jitted ``shard_map`` programs (their row arithmetic closes over the
        per-shard row count)."""
        self.num_items = pad_to_multiple(num_items, self._pad_unit)
        self.rows_per_shard = self.num_items // self.n_shards
        # Rows per shard of one scoring program: the Pallas kernel holds
        # no [S, I] working set and skips the blocks past a shard's live
        # rows, so one program a window; the XLA scorer's [S, I] gather
        # bounds its calls.
        self.max_score_rows = (FUSED_MAX_ROWS if self.use_pallas
                               else score_row_budget(
                                   self.num_items,
                                   self._max_score_rows_per_call))
        top_k = self.top_k
        n_shards = self.n_shards
        rows_per_shard_c = self.rows_per_shard

        def _update(C_loc, row_sums, coo, n):
            # Per-shard [1, 3, L] slice (local rows, global columns,
            # deltas) and its live lane count [1]: the shard scatters its
            # live lanes' steps into its block and its local row sums,
            # which land at their global rows (r = local * D + shard) for
            # the psum.
            C_loc, rs_loc = _stepped_apply(
                C_loc, jnp.zeros((rows_per_shard_c,), jnp.int32), n[0],
                lambda t: _coo_step(coo[0], t), rows_per_shard_c)
            shard = jax.lax.axis_index(ITEM_AXIS)
            rs_part = jnp.zeros((rows_per_shard_c, n_shards), jnp.int32
                                ).at[:, shard].set(rs_loc).reshape(-1)
            return C_loc, row_sums + jax.lax.psum(rs_part, ITEM_AXIS)

        use_pallas = self.use_pallas
        interpret = self._pallas_interpret
        tile = self.PALLAS_TILE

        def _score(C_loc, row_sums, rows, live, observed):
            rows = rows[0]
            local = rows // n_shards
            if use_pallas:
                from ..ops.pallas_score import pallas_score_topk_local

                # Fused LLR+top-K per shard, the blocks past the shard's
                # live rows skipped; ids ride as float values (decoded
                # with astype in _materialize, like the dense single-chip
                # pallas path).
                packed = pallas_score_topk_local(
                    C_loc, row_sums, rows, local, observed, top_k=top_k,
                    tile=tile, interpret=interpret, live=live[0])
                return packed[None]
            counts = C_loc[local]  # [S, I] (shard-local rows)
            k11 = counts.astype(jnp.float32)
            rs = row_sums.astype(jnp.float32)
            rsi = rs[rows][:, None]
            rsj = rs[None, :]
            k12 = rsi - k11
            k21 = rsj - k11
            k22 = observed + k11 - k12 - k21
            scores = llr_stable(k11, k12, k21, k22)
            scores = jnp.where(counts != 0, scores, -jnp.inf)
            # topk_padded: a vocab smaller than K pads with -inf/0.
            vals, idx = topk_padded(scores, top_k)
            # Pack per shard into [1, 2, S, K] f32 => one fetchable buffer.
            return jnp.stack([vals, pack_ids(idx)])[None]

        # The vma check is off: the update's loop carries per-shard row
        # sums that start invariant, and the kernel's outputs carry no
        # vma annotation.
        self._update = jax.jit(shard_map_maybe_relaxed(
            _update, self.mesh,
            (P(ITEM_AXIS, None), P(), P(ITEM_AXIS), P(ITEM_AXIS)),
            (P(ITEM_AXIS, None), P()), relaxed=True),
            donate_argnums=donate_argnums(0, 1))
        self._score = jax.jit(shard_map_maybe_relaxed(
            _score, self.mesh,
            (P(ITEM_AXIS, None), P(), P(ITEM_AXIS), P(ITEM_AXIS), P()),
            P(ITEM_AXIS), relaxed=True))

    def _grow(self, need: int) -> None:
        """Double (at least) the vocab capacity, on the devices.

        Derive-from-data mode only. A row keeps its shard and local row
        (``r % D``, ``r // D``) whatever the capacity, so each shard pads
        its own block with zero rows and columns."""
        old_items, old_rows = self.num_items, self.rows_per_shard
        self._build(max(2 * old_items, int(need)))
        rows, items = self.rows_per_shard - old_rows, self.num_items - old_items
        grow = jax.jit(shard_map_maybe_relaxed(
            lambda c, rs: (jnp.pad(c, ((0, rows), (0, items))),
                           jnp.pad(rs, (0, items))),
            self.mesh, (P(ITEM_AXIS, None), P()), (P(ITEM_AXIS, None), P()),
            relaxed=False))
        self.stage_clock.add("launches")
        self.C, self.row_sums = grow(self.C, self.row_sums)

    # ------------------------------------------------------------------

    def _put(self, block: np.ndarray):
        return self._put_global(block, self.mesh, P(ITEM_AXIS))

    def process_window(self, ts: int, pairs: PairDeltaBatch):
        """One sharded update+score step; returns the *previous* window's
        results as a packed ``TopKBatch`` (one-window-deep pipeline)."""
        self.last_dispatched_rows = 0
        clk = self.stage_clock
        clk.reset()
        if len(pairs) == 0:
            # No new dispatch this window — drain any completed in-flight
            # results now instead of withholding them behind idle windows.
            return self.flush()
        D = self.n_shards
        with clk.stage("index"):
            # Shared per-window cell aggregation (see ops/aggregate.py):
            # the hash-shuffle analogue ships each distinct cell once per
            # window and keeps duplicate indices out of the per-shard
            # scatters.
            src, dst, delta64 = aggregate_window_coo(
                pairs.src, pairs.dst, pairs.delta)
            delta = narrow_deltas_int32(delta64)
            if self.auto_grow:
                max_id = int(max(src.max(), dst.max()))
                if max_id >= self.num_items:
                    self._grow(max_id + 1)
            rows = distinct_sorted(src)

        with clk.stage("uplink-encode"):
            # Owner-partitioned [D, 3, L] (local row, column, delta)
            # blocks, one packed transfer a chunk; padding lanes are
            # (0, 0) delta 0, a no-op the scatter's steps never reach past
            # the last live one.
            coo, lanes, width = partition_by_owner(
                src % D, (src // D, dst, delta), D, SHARD_CHUNK,
                SCATTER_STEP)
            for lo in range(0, coo.shape[2], width):
                live = np.clip(lanes - lo, 0, width).astype(np.int32)
                chunk = np.ascontiguousarray(coo[:, :, lo: lo + width])
                clk.add("launches")
                clk.add("scatter_lanes",
                        SCATTER_STEP * int((-(-live // SCATTER_STEP)).sum()))
                clk.add("scatter_live", int(live.sum()))
                LEDGER.up("coo", chunk, live)
                self.C, self.row_sums = self._update(
                    self.C, self.row_sums, self._put(chunk), self._put(live))

        window_sum = int(pairs.delta.sum())
        self.observed += window_sum
        self.counters.add(ROW_SUM_PROCESS_WINDOW, window_sum)

        self.counters.add(RESCORED_ITEMS, len(rows))
        self.last_dispatched_rows = len(rows)
        with clk.stage("rescore"):
            chunks = self._rescore(rows)
        prev, self._pending = self._pending, chunks
        return (self._materialize(prev) if prev is not None
                else TopKBatch.empty(self.top_k))

    def _rescore(self, rows: np.ndarray) -> List[Tuple]:
        """Dispatch the scoring of ``rows`` (global, sorted), each on its
        shard: one program per ``max_score_rows`` a shard, each shard's
        rows padded to the program's power-of-two bucket. Returns the
        in-flight ``(rows block, live, packed)`` chunks."""
        from ..ops.pallas_score import BLOCK_ROWS

        clk = self.stage_clock
        block, counts, width = partition_by_owner(
            rows % self.n_shards, (rows,), self.n_shards,
            self.max_score_rows, BLOCK_ROWS)
        _record_shard_metrics(len(rows), counts)
        clk.add("shard_rows", len(rows))
        clk.add("shard_rows_max", int(counts.max()))
        chunks = []
        for lo in range(0, block.shape[2], width):
            rb = np.ascontiguousarray(block[:, 0, lo: lo + width])
            live = np.clip(counts - lo, 0, width).astype(np.int32)
            # The kernel scores the live rows' blocks; XLA every row.
            scored = (int((-(-live // BLOCK_ROWS)).sum()) * BLOCK_ROWS
                      if self.use_pallas else rb.size)
            clk.add("launches")
            clk.add("score_cells", scored * self.num_items)
            clk.add("live_cells", int(live.sum()) * self.num_items)
            LEDGER.up("score-rows", rb, live)
            packed = self._score(self.C, self.row_sums, self._put(rb),
                                 self._put(live), np.float32(self.observed))
            if hasattr(packed, "copy_to_host_async"):
                packed.copy_to_host_async()
            chunks.append((rb, live, packed))
        return chunks

    def flush(self):
        """Emit the final in-flight window's results (end of pipeline)."""
        prev, self._pending = self._pending, None
        return (self._materialize(prev) if prev is not None
                else TopKBatch.empty(self.top_k))

    def _materialize(self, chunks):
        """Fetch in-flight [D, 2, S, K] blocks into one packed TopKBatch.

        Iterates *addressable* shards only: single-process that is all of
        them; multi-host each process emits exactly the rows its chips own
        (the analogue of a Flink subtask emitting its key partition).
        """
        rows_l, idx_l, vals_l = [], [], []
        for rb, live, packed in chunks:
            for shard in packed.addressable_shards:
                d = shard.index[0].start or 0
                n_valid = int(live[d])
                if n_valid <= 0:
                    continue
                host = np.asarray(shard.data)[0]  # [2, S, K]
                LEDGER.down("results", host)
                rows_l.append(rb[d, :n_valid])
                vals_l.append(host[0, :n_valid])
                # Pallas packs ids as float values (astype), XLA with
                # results.pack_ids — see ops/pallas_score.py.
                idx_l.append(host[1, :n_valid].astype(np.int32)
                             if self.use_pallas
                             else unpack_ids(host[1, :n_valid]))
        return TopKBatch.concatenate(rows_l, idx_l, vals_l, self.top_k)

    # -- checkpoint ------------------------------------------------------

    @property
    def process_suffix(self) -> str:
        """Checkpoint filename suffix: multi-host runs save per process."""
        return f".p{jax.process_index()}" if jax.process_count() > 1 else ""

    def checkpoint_state(self) -> dict:
        if jax.process_count() > 1:
            # C is sharded across hosts and not fully addressable from any
            # single process; each process snapshots the contiguous block
            # of C's device rows its chips hold (device order is
            # hosts-major, see distributed.make_multihost_mesh), to be
            # restored under the same layout. row_sums is replicated.
            shards = sorted(self.C.addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            c_local = np.concatenate([np.asarray(s.data) for s in shards])
            row_lo = shards[0].index[0].start or 0
            return {
                "C_local": c_local,
                "row_lo": np.asarray([row_lo], dtype=np.int64),
                "row_sums": np.asarray(self.row_sums),
                "observed": np.asarray([self.observed], dtype=np.int64),
            }
        # Global row order: shard d's local row l is global row l*D + d.
        C = np.empty((self.num_items, self.num_items), self.count_dtype)
        for shard in self.C.addressable_shards:
            d = (shard.index[0].start or 0) // self.rows_per_shard
            C[d::self.n_shards] = np.asarray(shard.data)
        return {
            "C": C,
            "row_sums": np.asarray(self.row_sums),
            "observed": np.asarray([self.observed], dtype=np.int64),
        }

    def _fit_count_dtype(self, arr) -> np.ndarray:
        from ..ops.device_scorer import fit_count_dtype

        return fit_count_dtype(arr, self.count_dtype)

    def restore_state(self, st: dict) -> None:
        if "C_local" in st:
            if jax.process_count() == 1:
                raise ValueError(
                    "checkpoint was written by a multi-host run (per-process "
                    "row blocks); restore it under the same process layout")
            c_local = self._fit_count_dtype(st["C_local"])
            row_lo = int(st["row_lo"][0])
            # Validate the snapshot's row block against the rows this
            # process's chips actually own under the current layout — a
            # different process count/placement must fail loudly, not
            # slice garbage.
            spans = [s.index[0] for s in self.C.addressable_shards]
            own_lo = min(sp.start or 0 for sp in spans)
            own_hi = max(sp.stop if sp.stop is not None else self.num_items
                         for sp in spans)
            if row_lo != own_lo or len(c_local) != own_hi - own_lo:
                raise ValueError(
                    f"checkpoint holds rows [{row_lo}, "
                    f"{row_lo + len(c_local)}) but this process owns "
                    f"[{own_lo}, {own_hi}) — restore under the writing "
                    f"run's process layout")

            def _local_block(idx):
                rows = idx[0]
                return c_local[rows.start - row_lo: rows.stop - row_lo,
                               idx[1]]

            self.C = jax.make_array_from_callback(
                (self.num_items, self.num_items), self._rows_sharding,
                _local_block)
        else:
            C = self._fit_count_dtype(st["C"])
            if C.shape[0] != self.num_items:
                # The writing run's capacity (already padded to ITS shard
                # count) may differ from this scorer's — e.g. a restore
                # into a derive-from-data run, or a different mesh size.
                # Rebuild at the larger of the two (never shrink below the
                # configured --num-items: the vocab bound the operator
                # asked for must survive the restore); the rest is zeros.
                self._build(pad_to_multiple(max(C.shape[0], self.num_items),
                                            self._pad_unit))
            D, R = self.n_shards, self.rows_per_shard

            def _shard_block(idx):
                # Shard d's rows are the global rows d, d + D, ...
                own = C[(idx[0].start or 0) // R::D]
                block = np.zeros((R, self.num_items), self.count_dtype)
                block[: len(own), : C.shape[1]] = own
                return block

            self.C = jax.make_array_from_callback(
                (self.num_items, self.num_items), self._rows_sharding,
                _shard_block)
        rs = np.asarray(st["row_sums"], dtype=np.int32)
        if len(rs) != self.num_items:
            grown_rs = np.zeros((self.num_items,), dtype=np.int32)
            grown_rs[: len(rs)] = rs
            rs = grown_rs
        self.row_sums = self._put_global(rs, self.mesh, P())
        self.observed = int(st["observed"][0])
        # In-flight results belong to windows after the checkpoint; a
        # restore that rolls back must not emit them.
        self._pending = None
