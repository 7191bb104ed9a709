"""bench.regress: the bench-history regression gate.

``python -m tpu_cooccurrence.bench.regress`` replays
``bench_history.jsonl`` (one JSON entry per on-chip bench run, appended
by ``bench.py``) and flags metric deltas beyond the history's own noise
band — the gate ROADMAP open item #5 requires before any knob may
self-tune, and the verify skill's post-bench step.

Method: per tracked metric (flattened dotted leaves of the history
entries, e.g. ``serving.qps``), take the history's **median** and
**MAD** (median absolute deviation — robust to the odd outlier run a
shared host produces) and flag the candidate when it lands beyond
``median ± max(mad_k * MAD, rel_floor * |median|)`` on the metric's
BAD side (each tracked metric declares its good direction; a 2x
pairs/s IMPROVEMENT is news, not a regression). The relative floor
keeps a freakishly quiet history (MAD ~ 0) from flagging ordinary
jitter. History entries compare within the same ``backend`` only — CPU
numbers must never band a TPU run.

Exit code: 1 when any tracked metric regresses, 0 otherwise —
including when history is too thin to band (< ``min_history`` prior
entries): a gate that cries wolf on its second-ever run would be
deleted by round three.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

#: Default history file (bench.py's append target), repo-root relative.
DEFAULT_HISTORY = "bench_history.jsonl"

#: Tracked metrics: flattened dotted key -> direction. "higher" = a
#: drop regresses (throughput-like), "lower" = a rise regresses
#: (latency/cost-like). Anything not listed is informational only.
KEY_METRICS: Dict[str, str] = {
    "pairs_per_sec": "higher",
    "vs_baseline": "higher",
    "fused.vs_chained": "higher",
    "fused_sparse.vs_chained": "higher",
    "fused_gang.vs_chained": "higher",
    "compression.rows_per_hbm_byte_gain": "higher",
    "serving.qps": "higher",
    "fleet.aggregate_qps": "higher",
    "serving.query_p99_s": "lower",
    "fleet.query_p99_s": "lower",
    "checkpoint.commit_bytes_ratio": "lower",
    "rescale.seam_stall_seconds": "lower",
}

#: Minimum same-backend prior entries before a metric is banded.
MIN_HISTORY = 3

#: Noise-band half-width: max(MAD_K * MAD, REL_FLOOR * |median|).
MAD_K = 5.0
REL_FLOOR = 0.10


def flatten(entry: dict, prefix: str = "") -> Dict[str, float]:
    """Numeric leaves of a history entry as dotted keys. The embedded
    ``regression`` verdict (this module's own output, recorded back
    into history by bench.py) is skipped — the gate must never band
    its own prior verdicts."""
    out: Dict[str, float] = {}
    for key, value in entry.items():
        if key in ("regression", "ts", "note"):
            continue
        dotted = f"{prefix}{key}"
        if isinstance(value, bool):
            continue
        if isinstance(value, (int, float)):
            out[dotted] = float(value)
        elif isinstance(value, dict):
            out.update(flatten(value, prefix=f"{dotted}."))
    return out


def read_history(path: str) -> List[dict]:
    """History entries, skipping unparseable lines (same torn-tail
    tolerance as the journal readers)."""
    entries: List[dict] = []
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    entry = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(entry, dict):
                    entries.append(entry)
    except OSError:
        pass
    return entries


def _median(values: List[float]) -> float:
    ordered = sorted(values)
    n = len(ordered)
    mid = n // 2
    if n % 2:
        return ordered[mid]
    return (ordered[mid - 1] + ordered[mid]) / 2.0


def evaluate(history: List[dict], candidate: dict,
             min_history: int = MIN_HISTORY, mad_k: float = MAD_K,
             rel_floor: float = REL_FLOOR) -> dict:
    """Band every tracked metric of ``candidate`` against the
    same-backend ``history`` entries. Returns the verdict dict bench.py
    embeds as ``out["regression"]``::

        {"ok": bool, "checked": N, "regressions": [per-metric dicts],
         "insufficient_history": [metric names], "backend": ...}
    """
    backend = str(candidate.get("backend", ""))
    prior = [flatten(e) for e in history
             if str(e.get("backend", "")) == backend]
    cand = flatten(candidate)
    regressions: List[dict] = []
    thin: List[str] = []
    checked = 0
    for metric, direction in KEY_METRICS.items():
        if metric not in cand:
            continue
        series = [p[metric] for p in prior if metric in p]
        if len(series) < min_history:
            thin.append(metric)
            continue
        checked += 1
        med = _median(series)
        mad = _median([abs(v - med) for v in series])
        band = max(mad_k * mad, rel_floor * abs(med))
        value = cand[metric]
        bad = (value < med - band if direction == "higher"
               else value > med + band)
        if bad:
            regressions.append({
                "metric": metric, "value": round(value, 6),
                "median": round(med, 6), "band": round(band, 6),
                "direction": direction, "n_history": len(series),
            })
    return {
        "ok": not regressions,
        "backend": backend,
        "checked": checked,
        "regressions": regressions,
        "insufficient_history": thin,
    }


def evaluate_latest(history: List[dict],
                    min_history: int = MIN_HISTORY) -> Tuple[dict, dict]:
    """CLI form: treat the newest history entry as the candidate and
    band it against everything before it. Returns (candidate,
    verdict)."""
    if not history:
        return {}, {"ok": True, "backend": "", "checked": 0,
                    "regressions": [],
                    "insufficient_history": list(KEY_METRICS)}
    candidate = history[-1]
    return candidate, evaluate(history[:-1], candidate,
                               min_history=min_history)


def render_text(candidate: dict, verdict: dict) -> str:
    lines = [f"bench.regress: backend={verdict['backend'] or '?'} "
             f"checked={verdict['checked']} metric(s)"]
    if candidate.get("ts"):
        lines[0] += f" candidate ts={candidate['ts']}"
    for reg in verdict["regressions"]:
        arrow = "below" if reg["direction"] == "higher" else "above"
        lines.append(
            f"  REGRESSION {reg['metric']}: {reg['value']} is {arrow} "
            f"median {reg['median']} +/- band {reg['band']} "
            f"(n={reg['n_history']})")
    if verdict["insufficient_history"]:
        lines.append(
            "  insufficient history (<%d same-backend entries): %s"
            % (MIN_HISTORY, ", ".join(verdict["insufficient_history"])))
    lines.append("verdict: " + ("OK" if verdict["ok"] else "REGRESSED"))
    return "\n".join(lines) + "\n"


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tpu_cooccurrence.bench.regress",
        description="Replay bench_history.jsonl and flag metric deltas "
                    "beyond the history's noise band (median +/- MAD "
                    "per metric, per backend). Exit 1 on regression.")
    p.add_argument("--history", default=DEFAULT_HISTORY,
                   help="bench history JSONL (default: "
                        f"{DEFAULT_HISTORY} in the cwd)")
    p.add_argument("--candidate", default=None,
                   help="JSON file holding the candidate bench output "
                        "(bench.py's stdout); default: the newest "
                        "history entry")
    p.add_argument("--min-history", type=int, default=MIN_HISTORY,
                   dest="min_history",
                   help="same-backend entries required before a metric "
                        "is banded (thinner history passes the gate)")
    p.add_argument("--format", default="text", choices=("text", "json"),
                   dest="format")
    args = p.parse_args(argv)
    history = read_history(args.history)
    if args.candidate:
        with open(args.candidate, "r", encoding="utf-8") as f:
            candidate = json.load(f)
        # bench.py's stdout names the headline "value"; history names
        # it "pairs_per_sec" — normalize so one metric table serves.
        if "pairs_per_sec" not in candidate and "value" in candidate:
            candidate = dict(candidate)
            candidate["pairs_per_sec"] = candidate["value"]
        verdict = evaluate(history, candidate,
                           min_history=args.min_history)
    else:
        candidate, verdict = evaluate_latest(
            history, min_history=args.min_history)
    if args.format == "json":
        sys.stdout.write(json.dumps(
            {"candidate_ts": candidate.get("ts"), **verdict},
            sort_keys=True) + "\n")
    else:
        sys.stdout.write(render_text(candidate, verdict))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
