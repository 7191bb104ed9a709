"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers: busy time as the union of device-op intervals, idle
share, device time per op name, and idle gaps labelled by what the host
was doing.

The window is the harness's own ``window`` span (a
``jax.profiler.TraceAnnotation`` around the measured period), so host and
device times are read on the trace's one clock.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

#: The harness's span around the measured window.
WINDOW_SPAN = "window"
#: Harness spans that label idle gaps (the program has none of its own
#: yet; see PERF.md, Open questions).
HARNESS_SPANS = ("ingest", "sync", "warm-up")
#: Line of a device plane whose events are single device operations.
OPS_LINE = "XLA Ops"
#: Idle gaps labelled one by one, longest first.
MAX_LABELLED = 2000

Interval = Tuple[int, int]  # (start_ns, end_ns)


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``, as ProfileData."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of ``[lo, hi)`` between merged busy ones."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)


def device_planes(pd) -> list:
    """One plane per chip: ``/device:TPU:<n>``, not the chip's other
    planes (``/device:TPU:<n> ...``) that hold no operations."""
    return [p for p in pd.planes if re.fullmatch(r"/device:TPU:\d+", p.name)]


def _device_ops(plane) -> List[Tuple[str, int, int]]:
    lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
    out = []
    for ln in lines:
        out.extend(_events(ln))
    return out


def _host_events(pd) -> List[Tuple[str, int, int]]:
    out = []
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                out.extend(_events(ln))
    return out


class _Host:
    """Host events of the window, for labelling idle gaps."""

    def __init__(self, events: Sequence[Tuple[str, int, int]]) -> None:
        import numpy as np

        self.names = [n for n, _s, _e in events]
        self.start = np.array([s for _n, s, _e in events], dtype=np.int64)
        self.end = np.array([e for _n, _s, e in events], dtype=np.int64)
        self.harness = np.array([n in HARNESS_SPANS for n in self.names])
        self.other = ~self.harness & np.array(
            [n != WINDOW_SPAN for n in self.names])

    def label(self, mid: int) -> str:
        """What the host was doing at ``mid``: the innermost harness
        span, and the innermost other host event inside it, if any."""
        import numpy as np

        live = (self.start <= mid) & (self.end > mid)
        out = []
        for kind in (self.harness, self.other):
            idx = np.flatnonzero(live & kind)
            if len(idx):
                inner = idx[np.argmin(self.end[idx] - self.start[idx])]
                out.append(self.names[inner])
        return ">".join(out) if out else "outside"


def reduce(pd, top: int = 10) -> Optional[Dict]:
    """The window's device numbers, or None when the trace holds no
    window span or no device operation (nothing to read)."""
    host = _host_events(pd)
    spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    planes = device_planes(pd)
    if not spans or not planes:
        return None
    lo, hi = spans[0]
    per_op: Dict[str, float] = defaultdict(float)
    busy_s, merged_per_plane = 0.0, []
    for plane in planes:
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in _device_ops(plane)
               if e > lo and s < hi]
        for n, s, e in ops:
            per_op[n] += (e - s) / 1e9
        merged = union([(s, e) for _n, s, e in ops])
        merged_per_plane.append(merged)
        busy_s += sum(e - s for s, e in merged) / 1e9
    if not per_op:
        return None
    busy_s /= len(planes)  # averaged over the chips used
    window_s = (hi - lo) / 1e9
    labels = _Host([h for h in host if h[2] > lo and h[1] < hi])
    idle: Dict[str, float] = defaultdict(float)
    idle_gaps = sorted(gaps(merged_per_plane[0], lo, hi),
                       key=lambda g: g[0] - g[1])
    for n, (g_lo, g_hi) in enumerate(idle_gaps):
        # Label the longest gaps one by one; the short tail in bulk.
        label = (labels.label((g_lo + g_hi) // 2) if n < MAX_LABELLED
                 else "shorter gaps")
        idle[label] += (g_hi - g_lo) / 1e9
    return {
        "planes": [p.name for p in pd.planes],
        "busy_s": busy_s,
        "window_s": window_s,
        "op_seconds": dict(per_op),
        "device_ops": [[short(op), sec] for op, sec in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:top],
    }


def short(op: str) -> str:
    """An op's name and result shape, without the rest of its HLO text:
    ``%fusion.1 = s16[4096,29696]{...} fusion(...)`` reads
    ``%fusion.1 = s16[4096,29696]``."""
    name, eq, rest = op.partition(" = ")
    return f"{name} = {rest.split('{')[0].split(' ')[0]}" if eq else op


def op_seconds(reduced: Dict, names: Sequence[str]) -> float:
    """Summed device time of the ops whose name contains any of
    ``names``."""
    return sum(s for op, s in reduced["op_seconds"].items()
               if any(n in op for n in names))
