"""The trace reduction and the HBM-share arithmetic on a small synthetic
trace (planes, lines and events as ProfileData gives them)."""

from types import SimpleNamespace as NS

import pytest

from benchmark.trace import reduce, roofline

MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def trace():
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[
            ev("window", 10, 100),
            ev("ingest", 10, 60), ev("PjitFunction(_update)", 20, 5),
            ev("sync", 70, 40),
        ])])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_update", 0, 200)]),
        NS(name="XLA Ops", events=[
            ev("fusion.1", 5, 10),           # starts before the window
            ev("%pallas_score_topk.1 = custom-call", 40, 20),
            ev("gather.2", 50, 20),          # overlaps the kernel
            ev("%pallas_score_topk.1 = custom-call", 100, 30),  # ends after the window
        ])])
    other = NS(name="/device:TPU:0 SparseCore 0", lines=[])  # no ops
    return NS(planes=[NS(name="/host:metadata", lines=[]), host, device,
                      other])


def test_union_and_gaps():
    assert reduce.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert reduce.gaps([(2, 3), (5, 9)], 0, 10) == [(0, 2), (3, 5), (9, 10)]


def test_reduce_clips_to_the_window():
    r = reduce.reduce(trace())
    assert r["window_s"] == pytest.approx(0.100)
    # Busy: [10,15) + [40,70) + [100,110) = 45 ms.
    assert r["busy_s"] == pytest.approx(0.045)
    assert reduce.op_seconds(r, ("%pallas_score_topk",)) == pytest.approx(
        0.030)
    assert r["device_ops"][0][0] == "%pallas_score_topk.1 = custom-call"
    idle = dict(r["idle_gaps"])
    # Gaps [15,40) in ingest, [70,100) in sync.
    assert idle["ingest"] == pytest.approx(0.025)
    assert idle["sync"] == pytest.approx(0.030)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_gap_label_names_the_inner_host_event():
    t = trace()
    t.planes[2].lines[1].events = [ev("x", 10, 9), ev("x", 26, 84)]
    idle = dict(reduce.reduce(t)["idle_gaps"])
    assert idle == {"ingest>PjitFunction(_update)": pytest.approx(0.007)}


def test_nothing_to_read_is_none():
    t = trace()
    t.planes = t.planes[:2]  # no device plane
    assert reduce.reduce(t) is None


def test_hbm_share_counts_the_work():
    nbytes = roofline.dense_score_bytes(1000, 59047, 2)
    assert nbytes == 1000 * 59047 * 2 + 59047 * 4 + 1000 * 4
    share = roofline.hbm_share(nbytes, 1e-3, "TPU v5 lite")
    assert share == pytest.approx(100 * nbytes / 819e9 / 1e-3)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
