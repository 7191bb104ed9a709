"""Multi-chip sharded scoring backend (``shard_map`` over an item mesh).

Distribution design (SURVEY §2.6, §7.6 — the TPU-native replacement of the
reference's keyed Netty shuffle + broadcast):

  * ``C`` (item x item counts) is **row-sharded** over the ``items`` mesh
    axis: shard d owns rows ``[d*R, (d+1)*R)`` — the analogue of
    ``keyBy(item)`` partitioned operator state.
  * ``row_sums`` is **replicated** — the analogue of the broadcast row-sum
    stream every rescorer subtask mirrors
    (``ItemRowRescorerTwoInputStreamOperator.java:33``, broadcast at
    ``FlinkCooccurrences.java:163``). Each shard computes a partial row-sum
    delta from its pair slice and the full update is an ``lax.psum`` over
    ICI — replacing the keyed shuffle + re-broadcast round-trip.
  * pair deltas and rows-to-score are **pre-partitioned by owner on host**
    (the hash-shuffle analogue, but a cheap bucketed sort instead of a
    network shuffle), so each chip receives and processes only its slice.
  * top-K is shard-local: each shard owns its rows outright, so no
    cross-chip merge is needed (SURVEY §7 "sharded top-K"); only the
    replicated row sums and the scalar ``observed`` total require
    cross-chip agreement.

Works identically on a virtual CPU mesh
(``--xla_force_host_platform_device_count``) and real TPU meshes.
"""

from __future__ import annotations

import math
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..metrics import Counters, RESCORED_ITEMS, ROW_SUM_PROCESS_WINDOW
from ..observability.registry import REGISTRY, log_buckets
from ..state.results import TopKBatch, pack_ids, unpack_ids
from ..ops.aggregate import (aggregate_window_coo, distinct_sorted,
                             narrow_deltas_int32)
from ..ops.llr import llr_stable
from ..ops.device_scorer import (pad_pow2, resolve_pallas_flag,
                                 score_row_budget, topk_padded)
from ..ops.donation import donate_argnums
from ..sampling.reservoir import PairDeltaBatch
from .mesh import (ITEM_AXIS, make_mesh, pad_to_multiple,
                   shard_map_maybe_relaxed)


#: Row-count ladder for the dispatch-size histogram: 1 .. 2^24 rows.
ROWS_BUCKETS = log_buckets(1.0, 2.0 ** 24)


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
        # body. With pallas on, the vocab pads to a tile multiple so the
        # kernel's column grid divides evenly.
        self.use_pallas = resolve_pallas_flag(use_pallas, self.count_dtype,
                                              top_k)
        self._pallas_interpret = jax.default_backend() != "tpu"
        self._pad_unit = (math.lcm(self.n_shards, self.PALLAS_TILE)
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

        from .distributed import put_global

        self._put_global = put_global
        self._build(num_items)
        self.C = put_global(
            np.zeros((self.num_items, self.num_items), dtype=self.count_dtype),
            self.mesh, P(ITEM_AXIS, None))
        self.row_sums = put_global(
            np.zeros((self.num_items,), dtype=np.int32), self.mesh, P())

    def _build(self, num_items: int) -> None:
        """(Re)build the capacity-dependent pieces: shard geometry and the
        jitted ``shard_map`` programs (their row arithmetic closes over the
        per-shard row count)."""
        self.num_items = pad_to_multiple(num_items, self._pad_unit)
        self.rows_per_shard = self.num_items // self.n_shards
        # Bound each shard's per-call [S, I] score working set.
        self.max_score_rows = score_row_budget(
            self.num_items, self._max_score_rows_per_call)
        top_k = self.top_k

        num_items_c = self.num_items
        rows_per_shard_c = self.rows_per_shard

        def _update(C_loc, row_sums, coo):
            # Per-shard [1, 3, P] slices arrive owner-partitioned (one packed
            # buffer = one host->device transfer); localize rows.
            src, dst, delta = coo[0, 0], coo[0, 1], coo[0, 2]
            lo = jax.lax.axis_index(ITEM_AXIS) * rows_per_shard_c
            # C may be int16 (--count-dtype, reference-style short counts);
            # row sums stay int32 (see ops/device_scorer._apply_coo).
            C_loc = C_loc.at[src - lo, dst].add(delta.astype(C_loc.dtype))
            rs_part = jnp.zeros((num_items_c,), dtype=jnp.int32).at[src].add(delta)
            row_sums = row_sums + jax.lax.psum(rs_part, ITEM_AXIS)
            return C_loc, row_sums

        use_pallas = self.use_pallas
        interpret = self._pallas_interpret
        tile = self.PALLAS_TILE

        def _score(C_loc, row_sums, rows, observed):
            lo = jax.lax.axis_index(ITEM_AXIS) * rows_per_shard_c
            if use_pallas:
                from ..ops.pallas_score import pallas_score_topk_local

                # Fused LLR+top-K per shard; ids ride as float values
                # (decoded with astype in _materialize, like the dense
                # single-chip pallas path).
                packed = pallas_score_topk_local(
                    C_loc, row_sums, rows[0], lo, observed,
                    top_k=top_k, tile=tile, interpret=interpret)
                return packed[None]
            counts = C_loc[rows[0] - lo]  # [S, I] int32 (shard-local rows)
            k11 = counts.astype(jnp.float32)
            rs = row_sums.astype(jnp.float32)
            rsi = rs[rows[0]][:, None]
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

        self._update = jax.jit(shard_map(
            _update, mesh=self.mesh,
            in_specs=(P(ITEM_AXIS, None), P(), P(ITEM_AXIS)),
            out_specs=(P(ITEM_AXIS, None), P()),
        ), donate_argnums=donate_argnums(0, 1))
        self._score = jax.jit(shard_map_maybe_relaxed(
            _score, self.mesh,
            (P(ITEM_AXIS, None), P(), P(ITEM_AXIS), P()),
            P(ITEM_AXIS), relaxed=use_pallas))

    def _grow(self, need: int) -> None:
        """Double (at least) the vocab capacity and reshard the state.

        Derive-from-data mode only. Growth changes every row's owning
        shard (rows_per_shard changes), so the old state is materialized
        on host, zero-padded, and re-placed under the new geometry — a
        rare event (doubling) whose cost is one full C round-trip,
        exactly like the dense backend's reallocation."""
        old_items = self.num_items
        C_host = np.asarray(self.C)
        rs_host = np.asarray(self.row_sums)
        self._build(max(2 * old_items, int(need)))
        C_new = np.zeros((self.num_items, self.num_items),
                         dtype=self.count_dtype)
        C_new[:old_items, :old_items] = C_host
        rs_new = np.zeros((self.num_items,), dtype=np.int32)
        rs_new[:old_items] = rs_host
        self.C = self._put_global(C_new, self.mesh, P(ITEM_AXIS, None))
        self.row_sums = self._put_global(rs_new, self.mesh, P())

    # ------------------------------------------------------------------

    def _partition_by_owner(self, values: np.ndarray, owners: np.ndarray,
                            pad_min: int, fill: np.ndarray
                            ) -> Tuple[np.ndarray, np.ndarray]:
        """Bucket ``values`` rows into [n_shards, pad] with per-shard counts.

        ``fill`` supplies the padding value per shard (must target a row the
        shard owns, with delta 0 for updates)."""
        counts = np.bincount(owners, minlength=self.n_shards)
        pad = pad_pow2(int(counts.max()) if len(owners) else 0, minimum=pad_min)
        out = np.tile(fill[:, None], (1, pad)).astype(values.dtype)
        order = np.argsort(owners, kind="stable")
        offsets = np.zeros(self.n_shards + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])
        for d in range(self.n_shards):
            sel = order[offsets[d]:offsets[d + 1]]
            out[d, : len(sel)] = values[sel]
        return out, counts

    def process_window(self, ts: int, pairs: PairDeltaBatch):
        """One sharded update+score step; returns the *previous* window's
        results as a packed ``TopKBatch`` (one-window-deep pipeline)."""
        self.last_dispatched_rows = 0
        if len(pairs) == 0:
            # No new dispatch this window — drain any completed in-flight
            # results now instead of withholding them behind idle windows.
            return self.flush()
        # Shared per-window cell aggregation (see ops/aggregate.py): the
        # hash-shuffle analogue ships each distinct cell once per window and
        # keeps duplicate indices out of the per-shard scatters.
        src, dst, delta64 = aggregate_window_coo(
            pairs.src, pairs.dst, pairs.delta)
        delta = narrow_deltas_int32(delta64)
        if self.auto_grow:
            max_id = int(max(src.max(), dst.max()))
            if max_id >= self.num_items:
                self._grow(max_id + 1)
        owners = (src // self.rows_per_shard).astype(np.int64)

        # Owner-partitioned [D, P] blocks; padding rows point at each shard's
        # first owned row with delta 0 (scatter no-op). The three blocks ship
        # as one packed [D, 3, P] buffer (one transfer).
        shard_first_row = (np.arange(self.n_shards, dtype=np.int32)
                           * self.rows_per_shard)
        src_b, _ = self._partition_by_owner(src, owners, 256, shard_first_row)
        dst_b, _ = self._partition_by_owner(dst, owners, 256,
                                            np.zeros(self.n_shards, np.int32))
        delta_b, _ = self._partition_by_owner(delta, owners, 256,
                                              np.zeros(self.n_shards, np.int32))
        coo_b = self._put_global(np.stack([src_b, dst_b, delta_b], axis=1),
                                 self.mesh, P(ITEM_AXIS))

        self.C, self.row_sums = self._update(self.C, self.row_sums, coo_b)

        window_sum = int(pairs.delta.sum())
        self.observed += window_sum
        self.counters.add(ROW_SUM_PROCESS_WINDOW, window_sum)

        rows = distinct_sorted(src)
        self.counters.add(RESCORED_ITEMS, len(rows))
        self.last_dispatched_rows = len(rows)
        row_owners = (rows // self.rows_per_shard).astype(np.int64)
        rows_b, row_counts = self._partition_by_owner(
            rows, row_owners, 64, shard_first_row)
        _record_shard_metrics(len(rows), row_counts)

        # Chunk the padded per-shard row dimension to the HBM budget (both
        # are powers of two, so every chunk is shape-stable).
        chunks: List[Tuple[int, np.ndarray, object]] = []
        for lo in range(0, rows_b.shape[1], self.max_score_rows):
            rb = np.ascontiguousarray(rows_b[:, lo: lo + self.max_score_rows])
            rb_g = self._put_global(rb, self.mesh, P(ITEM_AXIS))
            packed = self._score(self.C, self.row_sums, rb_g,
                                 np.float32(self.observed))
            if hasattr(packed, "copy_to_host_async"):
                packed.copy_to_host_async()
            chunks.append((lo, rb, packed))
        prev, self._pending = self._pending, (row_counts, chunks)
        return (self._materialize(prev) if prev is not None
                else TopKBatch.empty(self.top_k))

    def flush(self):
        """Emit the final in-flight window's results (end of pipeline)."""
        prev, self._pending = self._pending, None
        return (self._materialize(prev) if prev is not None
                else TopKBatch.empty(self.top_k))

    def _materialize(self, pending):
        """Fetch in-flight [D, 2, S, K] blocks into one packed TopKBatch.

        Iterates *addressable* shards only: single-process that is all of
        them; multi-host each process emits exactly the rows its chips own
        (the analogue of a Flink subtask emitting its key partition).
        """
        row_counts, chunks = pending
        rows_l, idx_l, vals_l = [], [], []
        for lo, rb, packed in chunks:
            for shard in packed.addressable_shards:
                d = shard.index[0].start or 0
                host = np.asarray(shard.data)[0]  # [2, S, K]
                n_valid = min(rb.shape[1], int(row_counts[d]) - lo)
                if n_valid <= 0:
                    continue
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
            # single process; each process snapshots the contiguous row
            # block its chips own (device order is hosts-major, see
            # distributed.make_multihost_mesh). row_sums is replicated.
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
        return {
            "C": np.asarray(self.C),
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
            from jax.sharding import NamedSharding

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
                (self.num_items, self.num_items),
                NamedSharding(self.mesh, P(ITEM_AXIS, None)), _local_block)
        else:
            C = self._fit_count_dtype(st["C"])
            if C.shape[0] != self.num_items:
                # The writing run's capacity (already padded to ITS shard
                # count) may differ from this scorer's — e.g. a restore
                # into a derive-from-data run, or a different mesh size.
                # Rebuild at the larger of the two (never shrink below the
                # configured --num-items: the vocab bound the operator
                # asked for must survive the restore) and zero-pad.
                cap = pad_to_multiple(max(C.shape[0], self.num_items),
                                      self._pad_unit)
                self._build(cap)
                grown = np.zeros((self.num_items, self.num_items), C.dtype)
                grown[: C.shape[0], : C.shape[1]] = C
                C = grown
            self.C = self._put_global(C, self.mesh, P(ITEM_AXIS, None))
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
