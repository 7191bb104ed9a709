"""The chip's peaks, and the bytes a kernel's work needs.

Counts are of the algorithm's work, from the shapes the run reports --
never of an implementation's padding -- so a share reads the same
whichever kernel does the work.
"""

from __future__ import annotations

import json
import os
from typing import Dict

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> Dict:
    """The published peaks of ``device_kind``; an unknown chip is an
    error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to {_PEAKS} with its source")
    return table[device_kind]


def dense_score_bytes(rows: int, width: int, cell_bytes: int) -> float:
    """HBM bytes of rescoring ``rows`` dense rows of a ``width``-item
    catalog: each row's counts and its row sum are read, every other
    item's row sum once, and K ids and scores are written (K is
    negligible and left out)."""
    return float(rows) * width * cell_bytes + width * 4 + rows * 4


def hbm_share(nbytes: float, seconds: float, device_kind: str) -> float:
    """Percent of the chip's HBM bandwidth that moving ``nbytes`` in
    ``seconds`` uses: the least time the bytes need over the time
    taken."""
    return 100.0 * nbytes / peaks(device_kind)["hbm_bytes_per_s"] / seconds
