"""The main path's programs compile for a TPU v5e at real widths.

No chip is attached here: the TPU compiler compiles for a v5e described
through ``jax.experimental.topologies`` (the on-chip-measurement guide,
section 2). That catches what interpret mode cannot -- tiling the chip
refuses, VMEM overuse, a program that does not fit HBM -- at no chip
time. Nothing runs, so these say nothing about results or speed.

The topology is described only inside the module-scoped fixture below:
only one process may load libtpu, and the xdist worker that runs this
file is the one that loads it.
"""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

#: One v5e chip's HBM (Google Cloud, "TPU v5e": 16 GB per chip).
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A TPU executable written to the persistent cache cannot be read
    # back without a chip: keep the cache off around these compiles.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hbm_bytes(compiled) -> int:
    """Per-device bytes one program needs while it runs."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# -- the three Pallas kernels at real widths --------------------------------

def _dense_kernel(one_chip):
    from tpu_cooccurrence.ops.pallas_score import dense_topk

    rows, items = 8192, 61_440
    fn = jax.jit(lambda c, rs, r, obs: dense_topk(
        c, r, rs, obs, top_k=10, tile=2048, interpret=False))
    return fn.lower(_sds((items, items), jnp.int16, one_chip),
                    _sds((items,), jnp.int32, one_chip),
                    _sds((rows,), jnp.int32, one_chip),
                    _sds((), jnp.float32, one_chip)).compile()


def _rect_kernel(one_chip, R):
    from tpu_cooccurrence.ops.pallas_score import pallas_score_rect

    cap, items, S = 1 << 22, 1 << 20, 1024
    fn = jax.jit(lambda c, d, rs, m, o: pallas_score_rect(
        c, d, rs, m, o, top_k=10, R=R, interpret=False))
    return fn.lower(_sds((cap,), jnp.int32, one_chip),
                    _sds((cap,), jnp.int32, one_chip),
                    _sds((items,), jnp.int32, one_chip),
                    _sds((3, S), jnp.int32, one_chip),
                    _sds((), jnp.float32, one_chip)).compile()


@pytest.mark.parametrize("kernel", [
    "dense-int16-8192x61440", "rect-R256", "rect-R1024", "rect-R4096"])
def test_pallas_kernel_compiles(one_chip, kernel):
    if kernel.startswith("dense"):
        compiled = _dense_kernel(one_chip)
    else:
        compiled = _rect_kernel(one_chip, int(kernel[len("rect-R"):]))
    _assert_kernel(compiled)
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES


# -- whole programs of the main path ----------------------------------------

def _redonate(jitted, donate, static):
    """The production jit with the chip's donation: ops/donation.py
    resolved to none here, on the CPU, when the module was imported."""
    return jax.jit(jitted.__wrapped__, donate_argnums=donate,
                   static_argnames=static)


def _dense_update(one_chip, items=61_440, wire=jnp.int32):
    """Config 3's chained dense window, update half: the [3, N] COO
    scatter into the resident int16 C, stepped over the chunk's live
    lanes and donated as on the chip (uint16 COO: the ML-25M cell's)."""
    from tpu_cooccurrence.ops import device_scorer as ds

    fn = _redonate(ds._update_coo if wire == jnp.int32
                   else ds._update_coo_u16, (0, 1), ("num_items",))
    return fn.lower(_sds((items, items), jnp.int16, one_chip),
                    _sds((items,), jnp.int32, one_chip),
                    _sds((3, 1 << 20), wire, one_chip),
                    _sds((), jnp.int32, one_chip),
                    num_items=items).compile()


def _dense_score(one_chip, items=61_440):
    """...and score half: the int16 Pallas scorer over the rows budget."""
    from tpu_cooccurrence.ops import device_scorer as ds
    from tpu_cooccurrence.ops.pallas_score import pallas_score_topk

    rows = ds.score_row_budget(items, 8192)
    return pallas_score_topk.lower(
        _sds((items, items), jnp.int16, one_chip),
        _sds((items,), jnp.int32, one_chip),
        _sds((rows,), jnp.int32, one_chip),
        _sds((), jnp.float32, one_chip),
        top_k=10, tile=ds.DeviceScorer.PALLAS_TILE, interpret=False,
        packed=True).compile()


def _dense_fused(one_chip, n_cap):
    """Config 5's one-dispatch dense window (deferred results) at the
    Instacart cell's steady shapes: 49,688 products padded to the kernel
    tile, ``n_cap`` ops of 512-wide baskets, 16,384 rows rescored."""
    from tpu_cooccurrence.io.synthetic import INSTACART_CALIBRATION
    from tpu_cooccurrence.ops import device_scorer as ds

    tile = ds.DeviceScorer.PALLAS_TILE
    items = -(-INSTACART_CALIBRATION["n_products"] // tile) * tile
    l_cap, rows = 512, 16_384
    statics = ("num_items", "basket_width", "top_k", "use_pallas", "tile",
               "interpret")
    fn = _redonate(ds._fused_window_defer, (0, 1, 2), statics)
    scalar = _sds((), jnp.int32, one_chip)
    return fn.lower(
        _sds((items, items), jnp.int16, one_chip),
        _sds((items,), jnp.int32, one_chip),
        _sds((2, items, 10), jnp.float32, one_chip),
        _sds((n_cap, l_cap + 4), jnp.int32, one_chip), scalar,
        _sds((rows,), jnp.int32, one_chip), scalar,
        _sds((rows,), jnp.int32, one_chip),
        _sds((), jnp.float32, one_chip),
        num_items=items, basket_width=l_cap, top_k=10, use_pallas=True,
        tile=tile, interpret=False).compile()


def _run_config4(**cfg):
    """Config 4's stream through the job on the CPU (the shapes the
    programs below are compiled at come from this run)."""
    from tpu_cooccurrence.bench.configs import config4_workload
    from tpu_cooccurrence.job import CooccurrenceJob

    w = config4_workload(n_events=100_000)
    job = CooccurrenceJob(dataclasses.replace(
        w.config, **{"fused_window": "on", **cfg}))
    job.add_batch(w.users, w.items, w.ts)
    job.finish()
    return job


def _struct(a, sharding):
    a = a if hasattr(a, "shape") else np.asarray(a)
    return _sds(a.shape, a.dtype, sharding)


def _sparse_fused(one_chip, monkeypatch):
    """Config 4's one-dispatch sparse window, at the shapes its stream
    reaches (in the wire format the backend resolves)."""
    from tpu_cooccurrence.state import sparse_scorer as ss

    calls = {}
    for name in ("_fused_sparse_window_packed", "_fused_sparse_window_raw"):
        def spy(*a, _name=name, _real=getattr(ss, name), **k):
            calls[_name] = (_real, a, k)
            return _real(*a, **k)

        monkeypatch.setattr(ss, name, spy)
    _run_config4()
    assert calls, "no window ran fused"
    real, args, kwargs = next(iter(calls.values()))
    return real.lower(*[_struct(a, one_chip) for a in args],
                      **dict(kwargs, interpret=False)).compile()


def _assert_reads_c_in_place(compiled):
    """The kernel fetches its rows from C itself: the program makes no
    int16 value (no copy, slice or gather of C) and holds no [S, I]
    buffer of gathered rows."""
    made = [line.strip()[:120] for line in compiled.as_text().splitlines()
            if re.search(r"= s16\[", line) and "parameter(" not in line]
    assert not made, made
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("program", [
    "dense-update-61440-int16", "dense-update-59392-int16-u16-ml25m",
    "dense-score-61440-int16",
    "dense-score-59392-int16-ml25m", "dense-fused-config5",
    "dense-fused-config5-1024ops", "sparse-fused-config4"])
def test_main_path_program_compiles(one_chip, monkeypatch, program):
    if program.startswith("dense-update"):
        items = int(program.split("-")[2])
        compiled = _dense_update(
            one_chip, items, jnp.uint16 if "u16" in program else jnp.int32)
        m = compiled.memory_analysis()
        # Donation keeps ONE 7.55 GB C resident through the scatter's
        # steps: without the alias the output C alone would double it
        # past the chip's HBM.
        assert m.alias_size_in_bytes >= items ** 2 * 2
        assert m.temp_size_in_bytes < 64 * 2**20
    elif program.startswith("dense-score"):
        # 59,392: the ML-25M benchmark cell's catalog, padded to the tile
        # (4,096 rows a call, tile 2,048).
        compiled = _dense_score(one_chip, int(program.split("-")[2]))
        _assert_kernel(compiled)
        _assert_reads_c_in_place(compiled)
    elif program.startswith("dense-fused-config5"):
        compiled = _dense_fused(one_chip, 1024 if "1024" in program else 2048)
        _assert_kernel(compiled)
        # C is updated in place by steps of the scatter: no copy of it,
        # and no working set that grows with the window's lanes.
        assert compiled.memory_analysis().alias_size_in_bytes >= \
            51_200 ** 2 * 2
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
    else:
        compiled = _sparse_fused(one_chip, monkeypatch)
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES


# -- four chips --------------------------------------------------------------

def test_sharded_sparse_window_compiles_on_2x2(topo, monkeypatch):
    """The --num-shards 4 window step -- the slab update with its
    row-sum psum, then the fixed-shape rescore into the deferred table
    (what the chip runs) -- over a mesh of the four described chips, at
    the shapes config 4's stream reaches on the CPU mesh."""
    from tpu_cooccurrence.parallel.mesh import ITEM_AXIS
    from tpu_cooccurrence.parallel.sharded_sparse import ShardedSparseScorer

    calls = {}
    real_build = ShardedSparseScorer._build_update
    real_score = ShardedSparseScorer._score_window_into_fn

    def record(name, fn):
        def call(*args):
            calls[name] = args
            return fn(*args)
        return call

    def build(self):
        real_build(self)
        self._update = record("update", self._update)

    def score(self, plan):
        calls["plan"] = plan
        return record("score", real_score(self, plan))

    monkeypatch.setattr(ShardedSparseScorer, "_build_update", build)
    monkeypatch.setattr(ShardedSparseScorer, "_score_window_into_fn", score)
    job = _run_config4(num_shards=4, fused_window="off", fixed_score="on")
    monkeypatch.undo()
    assert {"update", "score"} <= set(calls)

    scorer = job.scorer
    mesh = Mesh(np.asarray(topo.devices[:4]), (ITEM_AXIS,))
    scorer.mesh = mesh
    scorer._pallas_interpret = False
    scorer._score_window_fns = {}
    real_build(scorer)

    def structs(args):
        return [_struct(a, NamedSharding(
            mesh, a.sharding.spec if hasattr(a, "sharding") else P()))
            for a in args]

    update = scorer._update.lower(*structs(calls["update"])).compile()
    assert "all-reduce" in update.as_text()  # the row-sum psum on ICI
    score = real_score(scorer, calls["plan"]).lower(
        *structs(calls["score"])).compile()
    for compiled in (update, score):
        assert _hbm_bytes(compiled) < V5E_HBM_BYTES
