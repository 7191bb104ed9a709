"""cooc-trace: offline fleet-trace analysis over run journals.

``python -m tpu_cooccurrence.observability.trace`` merges the journal
JSONL files of a whole fleet — gang workers (``journal.p<i>``), the
single-process job, N read replicas — into one correlated timeline and
answers the questions no single flight recorder can: how long from a
window firing on a worker to its rows being servable from a replica
(end-to-end **freshness**), which stage of the window lifecycle
dominates (per-stage **waterfall**, p50/p95/p99 over the registry's
fixed-log buckets), and where the seams are (fused-vs-chained
fallbacks, autoscale drains, degradation transitions, supervisor
restarts, replica resyncs — all already journaled, here finally
joined).

Join model (see ``journal.py``): every record carries the correlation
trio (``run_id``, ``process_id``, ``attempt``). Window records join to
checkpoint records on (``run_id``, ``process_id``, ``window_seq``);
checkpoint records join to replica records on ``generation``. When the
writer and a separately launched replica carry different run ids, the
generation join still holds — the shared state dir is the namespace —
and the report says so instead of silently dropping the fleet's other
half.

Restart stitching: a supervised restart reuses the journal file in
append mode, so one file can carry several attempts of the same window
ordinals. The merge dedups on (``run_id``, ``process_id``,
``window_seq``), keeping the HIGHEST attempt (the one whose effects
survived), and reports how many pre-crash duplicates it dropped.

Output: ``--format text`` (operator summary), ``--format json`` (the
full analysis dict), ``--format chrome`` (Chrome-trace / Perfetto
``traceEvents`` of the merged timeline — load it at ui.perfetto.dev).

Deliberately jax-free: it imports only the stdlib plus
``observability.registry`` (pure stdlib) and ``observability.journal``
(stdlib), so it runs anywhere the journals land — no accelerator, no
heavyweight deps.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

from .journal import (CORE_STAGES, REPLICA_SPAN_STAGES, SPAN_STAGES,
                      read_records)
from .registry import SECONDS_BUCKETS, Histogram

#: Relative tolerance for the core-span / wall-seconds reconciliation.
RECONCILE_REL_TOL = 0.01

#: Windows shorter than this are skipped by the reconciliation check:
#: at microsecond scale the journal's own field rounding dominates.
RECONCILE_MIN_WALL_S = 1e-3


def classify(rec: dict) -> Optional[str]:
    """Record type by distinguishing key (the journal's own dispatch
    rule) — None for JSON lines that are not journal records."""
    if not isinstance(rec, dict) or "v" not in rec:
        return None
    for key, kind in (("autoscale", "autoscale"), ("replica", "replica"),
                      ("checkpoint", "checkpoint"), ("event", "event")):
        if key in rec:
            return kind
    return "window" if "seq" in rec else None


def discover(paths: List[str]) -> List[str]:
    """Expand directories into their journal files (any ``*.jsonl*``
    basename — covers ``journal.jsonl``, per-worker ``journal.jsonl.p0``
    and replica-fleet suffixes); pass plain files through."""
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for name in sorted(os.listdir(p)):
                if ".jsonl" in name:
                    out.append(os.path.join(p, name))
        else:
            out.append(p)
    return out


def load(files: List[str]) -> Dict[str, List[dict]]:
    """Read + classify every record in ``files``; each record gains a
    ``_src`` key (source basename) for provenance in reports."""
    by_kind: Dict[str, List[dict]] = {
        k: [] for k in ("window", "event", "checkpoint", "autoscale",
                        "replica")}
    for path in files:
        for rec in read_records(path):
            kind = classify(rec)
            if kind is not None:
                rec["_src"] = os.path.basename(path)
                by_kind[kind].append(rec)
    return by_kind


def _ident(rec: dict) -> Tuple[str, int, int]:
    """(run_id, process_id, attempt) with pre-tracing-era defaults."""
    return (str(rec.get("run_id", "")), int(rec.get("process_id", 0)),
            int(rec.get("attempt", 0)))


def dedup_windows(windows: List[dict]) -> Tuple[List[dict], int]:
    """One record per (run_id, process_id, window_seq), keeping the
    highest attempt — a supervised restart replays window ordinals its
    crashed predecessor already journaled, and only the surviving
    attempt's spans belong on the merged timeline. Returns (kept,
    dropped_duplicates)."""
    best: Dict[Tuple[str, int, int], dict] = {}
    dropped = 0
    for rec in windows:
        run_id, process_id, attempt = _ident(rec)
        key = (run_id, process_id, int(rec["seq"]))
        cur = best.get(key)
        if cur is None:
            best[key] = rec
            continue
        dropped += 1
        if attempt > _ident(cur)[2]:
            best[key] = rec
    kept = sorted(best.values(),
                  key=lambda r: (_ident(r)[0], _ident(r)[1],
                                 int(r["seq"])))
    return kept, dropped


def _span_list(rec: dict) -> List[Tuple[str, float, float]]:
    return [(str(s[0]), float(s[1]), float(s[2]))
            for s in rec.get("spans", [])]


def waterfall(windows: List[dict],
              replicas: List[dict]) -> Dict[str, dict]:
    """Per-stage seconds distributions over the merged fleet, via the
    registry's fixed-log bucket histograms (same resolution /metrics
    uses, so offline and online percentiles agree)."""
    hists = {stage: Histogram(stage, SECONDS_BUCKETS)
             for stage in SPAN_STAGES + REPLICA_SPAN_STAGES}
    for rec in list(windows) + list(replicas):
        for stage, _off, secs in _span_list(rec):
            if stage in hists:
                hists[stage].observe(secs)
    return {stage: h.summary() for stage, h in hists.items()
            if h.count}


def reconcile(windows: List[dict]) -> dict:
    """Check the span contract: per window, the core stages
    (``CORE_STAGES``) must sum to ``sample_seconds + score_seconds`` (rel tol
    ``RECONCILE_REL_TOL``; sub-millisecond windows skipped — journal
    field rounding dominates there)."""
    checked = violations = 0
    max_rel_err = 0.0
    for rec in windows:
        spans = _span_list(rec)
        if not spans:
            continue
        wall = float(rec.get("sample_seconds", 0.0)) \
            + float(rec.get("score_seconds", 0.0))
        if wall < RECONCILE_MIN_WALL_S:
            continue
        core = sum(secs for stage, _off, secs in spans
                   if stage in CORE_STAGES)
        checked += 1
        rel = abs(core - wall) / wall
        max_rel_err = max(max_rel_err, rel)
        if rel > RECONCILE_REL_TOL:
            violations += 1
    return {"windows_checked": checked, "violations": violations,
            "max_rel_err": round(max_rel_err, 6),
            "ok": violations == 0}


def freshness(windows: List[dict], checkpoints: List[dict],
              replicas: List[dict]) -> dict:
    """End-to-end freshness: window-fire -> replica-servable.

    A generation becomes servable on a replica at its replica record's
    ``wall_unix`` (post-publish). Its data age anchors at the window
    the commit snapshotted: the checkpoint record's ``window_seq``
    resolves to that window record's ``wall_unix`` on the same (run_id,
    process_id); a checkpoint with no surviving window record (or a
    pre-tracing journal) anchors at the commit's own wall clock. With
    several writers committing the same generation, the EARLIEST anchor
    wins — freshness reports the oldest data in the snapshot.
    """
    window_wall: Dict[Tuple[str, int, int], float] = {}
    for rec in windows:
        run_id, process_id, _ = _ident(rec)
        window_wall[(run_id, process_id, int(rec["seq"]))] = \
            float(rec["wall_unix"])
    gen_fire: Dict[int, float] = {}
    for rec in checkpoints:
        gen = int(rec.get("generation", rec["checkpoint"]))
        run_id, process_id, _ = _ident(rec)
        anchor = float(rec["wall_unix"])
        if "window_seq" in rec:
            anchor = window_wall.get(
                (run_id, process_id, int(rec["window_seq"])), anchor)
        gen_fire[gen] = min(gen_fire.get(gen, anchor), anchor)
    hist = Histogram("freshness", SECONDS_BUCKETS)
    joined = unjoined = 0
    cross_run = False
    writer_runs = {_ident(r)[0] for r in checkpoints}
    for rec in replicas:
        gen = int(rec.get("generation", rec["replica"]))
        fire = gen_fire.get(gen)
        if fire is None:
            unjoined += 1
            continue
        joined += 1
        if _ident(rec)[0] not in writer_runs:
            cross_run = True
        hist.observe(max(0.0, float(rec["wall_unix"]) - fire))
    out = hist.summary()
    out["joined"] = joined
    out["unjoined_replica_records"] = unjoined
    if cross_run:
        # Writer and replica were launched with different run ids; the
        # generation join over the shared state dir still holds, but
        # say so (set TPU_COOC_RUN_ID / --run-id to unify).
        out["cross_run_join"] = True
    return out


def annotations(windows: List[dict], events: List[dict],
                autoscales: List[dict], replicas: List[dict],
                dropped_duplicates: int) -> dict:
    """Seam/fallback annotation: everything already journaled, joined
    into one fleet-level accounting."""
    fused = sum(1 for r in windows if r.get("fused") == 1)
    chained = sum(1 for r in windows if r.get("fused") == 0)
    fallbacks: Dict[str, int] = {}
    for rec in windows:
        reason = rec.get("fallback_reason")
        if reason:
            fallbacks[reason] = fallbacks.get(reason, 0) + 1
    # Ingest-plane seams: partition-ownership reassignment events the
    # rescaled restore journals ("ingest/partition-reassign:N->M") —
    # each marks the gang topology boundary where the merged offset
    # sections were re-derived under new ownership.
    partition_reassigns = [
        {"event": r["event"], "window": r.get("window_seq")}
        for r in sorted(events, key=lambda r: float(r["wall_unix"]))
        if str(r.get("event", "")).startswith("ingest/partition-reassign")]
    degrade_transitions = sum(
        len(r.get("degrade_events", [])) for r in windows) + sum(
        1 for r in events
        if not str(r.get("event", "")).startswith("ingest/"))
    # Restarts: attempts observed per (run_id, process_id) beyond the
    # first — the supervisor threads the ordinal through the env
    # exactly so this census works post-hoc.
    attempts: Dict[Tuple[str, int], set] = {}
    for rec in windows:
        run_id, process_id, attempt = _ident(rec)
        attempts.setdefault((run_id, process_id), set()).add(attempt)
    restarts = sum(len(a) - 1 for a in attempts.values())
    resyncs = max((int(r.get("resyncs", 0)) for r in replicas),
                  default=0)
    # Generation monotonicity per replica slot: resyncs and relaunches
    # both bootstrap FORWARD to the newest checkpoint, so the merged
    # per-slot generation stream must never step back.
    monotone_violations = 0
    last_gen: Dict[Tuple[str, int], int] = {}
    for rec in sorted(replicas, key=lambda r: float(r["wall_unix"])):
        run_id, process_id, _ = _ident(rec)
        gen = int(rec.get("generation", rec["replica"]))
        key = (run_id, process_id)
        if gen < last_gen.get(key, gen):
            monotone_violations += 1
        last_gen[key] = max(gen, last_gen.get(key, gen))
    return {
        "fused_windows": fused,
        "chained_windows": chained,
        "fallback_reasons": fallbacks,
        "degrade_transitions": degrade_transitions,
        "autoscale_drains": [
            {"decision": r["autoscale"], "from": r["from"], "to": r["to"],
             "trigger": r["trigger"], "window": r["window"]}
            for r in sorted(autoscales,
                            key=lambda r: float(r["wall_unix"]))],
        "partition_reassigns": partition_reassigns,
        "restarts": restarts,
        "dropped_duplicate_windows": dropped_duplicates,
        "replica_resyncs": resyncs,
        "replica_generation_monotone": monotone_violations == 0,
    }


def analyze(files: List[str]) -> dict:
    """The full analysis dict (the ``--format json`` payload)."""
    by_kind = load(files)
    windows, dropped = dedup_windows(by_kind["window"])
    return {
        "files": [os.path.basename(f) for f in files],
        "records": {k: len(v) for k, v in by_kind.items()},
        "processes": sorted({f"{r}/p{p}" for r, p, _ in
                             map(_ident, windows + by_kind["replica"])}),
        "waterfall": waterfall(windows, by_kind["replica"]),
        "reconcile": reconcile(windows),
        "freshness": freshness(windows, by_kind["checkpoint"],
                               by_kind["replica"]),
        "annotations": annotations(windows, by_kind["event"],
                                   by_kind["autoscale"],
                                   by_kind["replica"], dropped),
    }


# -- Chrome-trace export -------------------------------------------------

def _chrome_pid(kind: str, process_id: int) -> int:
    # Distinct pid planes keep workers and replicas as separate process
    # tracks in Perfetto (a replica's slot ids overlap the workers').
    return process_id + (1000 if kind == "replica" else 0)


def chrome_trace(files: List[str]) -> dict:
    """Chrome-trace / Perfetto JSON of the merged timeline: one process
    track per fleet slot (replicas offset to their own pid plane), one
    thread track per restart attempt, complete ("X") events per span
    and instant ("i") events for the out-of-band records. Timestamps
    are wall-clock microseconds; a window's spans are laid back-to-back
    ending at its record's ``wall_unix`` (the journal's flush point)."""
    by_kind = load(files)
    windows, _ = dedup_windows(by_kind["window"])
    events: List[dict] = []
    named = set()

    def track(kind: str, rec: dict) -> Tuple[int, int]:
        run_id, process_id, attempt = _ident(rec)
        pid, tid = _chrome_pid(kind, process_id), attempt
        if (pid,) not in named:
            named.add((pid,))
            label = ("replica" if kind == "replica" else "worker")
            name = f"{label} p{process_id}"
            if run_id:
                name += f" run {run_id}"
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "tid": 0, "args": {"name": name}})
        if (pid, tid) not in named:
            named.add((pid, tid))
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid,
                           "args": {"name": f"attempt {attempt}"}})
        return pid, tid

    for kind, recs in (("window", windows),
                       ("replica", by_kind["replica"])):
        for rec in recs:
            spans = _span_list(rec)
            if not spans:
                continue
            pid, tid = track(kind, rec)
            total = sum(secs for _stage, _off, secs in spans)
            t0 = (float(rec["wall_unix"]) - total) * 1e6
            off = 0.0
            args = ({"window_seq": rec["seq"],
                     "fused": rec.get("fused")} if kind == "window"
                    else {"generation": rec.get("generation",
                                                rec["replica"]),
                          "lag": rec.get("lag")})
            for stage, _off, secs in spans:
                events.append({
                    "name": stage, "ph": "X", "cat": kind,
                    "ts": round(t0 + off * 1e6, 3),
                    "dur": round(secs * 1e6, 3),
                    "pid": pid, "tid": tid, "args": args})
                off += secs
    for kind, name_of in (
            ("event", lambda r: f"degrade:{r['event']}"),
            ("checkpoint",
             lambda r: f"checkpoint gen {r['checkpoint']} ({r['kind']})"),
            ("autoscale",
             lambda r: (f"autoscale {r['autoscale']} "
                        f"{r['from']}->{r['to']}"))):
        for rec in by_kind[kind]:
            pid, tid = track(kind, rec)
            events.append({
                "name": name_of(rec), "ph": "i", "s": "p", "cat": kind,
                "ts": round(float(rec["wall_unix"]) * 1e6, 3),
                "pid": pid, "tid": tid})
    events.sort(key=lambda e: e.get("ts", 0.0))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# -- text rendering ------------------------------------------------------

def _fmt_summary(s: dict) -> str:
    if not s.get("count"):
        return "n=0"
    return (f"n={s['count']} p50={s.get('p50', 0):.6f}s "
            f"p95={s.get('p95', 0):.6f}s p99={s.get('p99', 0):.6f}s "
            f"max={s.get('max', 0):.6f}s")


def render_text(analysis: dict) -> str:
    lines = ["cooc-trace: merged fleet timeline", ""]
    rc = analysis["records"]
    lines.append(
        "records: "
        + "  ".join(f"{k}={rc[k]}" for k in ("window", "checkpoint",
                                             "replica", "autoscale",
                                             "event") if rc.get(k)))
    lines.append("processes: " + (", ".join(analysis["processes"])
                                  or "(none)"))
    lines.append("")
    lines.append("stage waterfall (fixed-log buckets):")
    wf = analysis["waterfall"]
    for stage in SPAN_STAGES + REPLICA_SPAN_STAGES:
        if stage in wf:
            lines.append(f"  {stage:<18} {_fmt_summary(wf[stage])}")
    rec = analysis["reconcile"]
    lines.append("")
    lines.append(
        f"span reconciliation: {rec['windows_checked']} windows checked, "
        f"{rec['violations']} violations "
        f"(max rel err {rec['max_rel_err']:.4%}) "
        f"-> {'OK' if rec['ok'] else 'FAIL'}")
    fr = analysis["freshness"]
    lines.append("")
    if fr.get("count"):
        lines.append("end-to-end freshness (window-fire -> "
                     "replica-servable): " + _fmt_summary(fr))
        if fr.get("cross_run_join"):
            lines.append("  note: writer and replica carry different "
                         "run ids; joined on generation over the "
                         "shared state dir")
    else:
        lines.append("end-to-end freshness: no replica records joined "
                     f"({fr.get('unjoined_replica_records', 0)} "
                     "unjoined)")
    an = analysis["annotations"]
    lines.append("")
    lines.append(
        f"seams: fused={an['fused_windows']} "
        f"chained={an['chained_windows']} "
        f"fallbacks={an['fallback_reasons'] or '{}'} "
        f"degrade-transitions={an['degrade_transitions']} "
        f"restarts={an['restarts']} "
        f"dropped-dup-windows={an['dropped_duplicate_windows']} "
        f"replica-resyncs={an['replica_resyncs']}")
    for drain in an["autoscale_drains"]:
        lines.append(
            f"  autoscale {drain['decision']} {drain['from']}->"
            f"{drain['to']} ({drain['trigger']}) @window "
            f"{drain['window']}")
    for seam in an.get("partition_reassigns", []):
        lines.append(
            f"  {seam['event']} @window {seam['window']}")
    if not an["replica_generation_monotone"]:
        lines.append("  WARNING: replica generation stream stepped "
                     "backwards (corrupt merge or clock skew)")
    return "\n".join(lines) + "\n"


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tpu_cooccurrence.observability.trace",
        description="Merge a fleet's run journals into one correlated "
                    "timeline: per-stage waterfall, end-to-end "
                    "freshness, seam annotations, Chrome-trace export.")
    p.add_argument("paths", nargs="*",
                   help="journal files and/or directories to merge")
    p.add_argument("--gang-dir", default=None,
                   help="gang/fleet dir whose journal files to merge "
                        "(alias of passing the directory positionally)")
    p.add_argument("--state-dir", default=None,
                   help="state dir holding writer + replica journals "
                        "(alias of passing the directory positionally)")
    p.add_argument("--format", default="text",
                   choices=("text", "json", "chrome"), dest="format")
    p.add_argument("--out", default=None,
                   help="write output here instead of stdout")
    args = p.parse_args(argv)
    roots = list(args.paths)
    for d in (args.gang_dir, args.state_dir):
        if d:
            roots.append(d)
    files = discover(roots)
    if not files:
        p.error("no journal files found (pass files, a --gang-dir, or "
                "a --state-dir)")
    if args.format == "chrome":
        text = json.dumps(chrome_trace(files))
    elif args.format == "json":
        text = json.dumps(analyze(files), sort_keys=True, indent=2) + "\n"
    else:
        text = render_text(analyze(files))
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text)
    else:
        sys.stdout.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
