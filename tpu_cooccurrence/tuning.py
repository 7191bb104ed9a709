"""The typed TuningParameter registry: every performance knob, declared.

ROADMAP #5's observation: the system has ~a dozen hand-set performance
parameters (pipeline depth, cell/wire dtypes, spill thresholds, compact
ratio, autoscale hysteresis, the ``TPU_COOC_*`` env knobs) and they
lived as scattered literals — an argparse default here, an
``os.environ.get`` fallback there, a pow2 pad floor hardcoded in a
kernel helper. A future autotune plane cannot steer knobs it cannot
enumerate, and cooclint cannot flag an unregistered knob without a
registry to check against. This module is that registry, in the same
shape as ``metrics.CANONICAL_METRICS`` and ``faults.SITES``: a typed
table the owning modules import, and that the analyzer imports as a
truth table (``analysis/rules_tuning.py``).

Contracts enforced by cooclint's ``tuning-registry`` rule:

* every ``TPU_COOC_*`` env var the package reads or mentions must be a
  registered parameter's ``env`` binding;
* package code outside this module never calls
  ``os.environ.get("TPU_COOC_...")`` directly — reads go through
  :func:`env_read` (same semantics as ``os.environ.get``, plus the
  registration check), so the registry always knows the live read
  sites;
* registered flag bindings must exist in ``config.py`` (and dead
  registry rows are flagged from the other side);
* hot-path modules comparing against an integer literal that equals a
  distinctive registered default get flagged — an inlined copy of a
  knob is how a knob stops being tunable.

``config.py`` reads defaults and bounds from here (:func:`default`,
:func:`bounds`), and the README "Tuning parameters" table is generated
by :func:`markdown_table` (pinned by a test, like the CLI-flag table).

Stdlib only — the analyzer imports this under ``JAX_PLATFORMS=cpu``
with no device.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class TuningParameter:
    """One declared knob.

    ``kind`` separates *performance* parameters (bounded, unit-carrying,
    the autotune plane's search space) from *infra* plumbing
    (correlation ids, directories the supervisor wires through the
    environment) — both resolve through the registry, only the former
    belong in a tuning sweep.
    """

    name: str                 # canonical snake_case registry key
    type: str                 # "int" | "float" | "str" | "choice"
    default: object           # effective default (post env/auto logic)
    doc: str
    bounds: Optional[Tuple[Optional[float], Optional[float]]] = None
    choices: Optional[Tuple[str, ...]] = None
    unit: str = ""
    flag: Optional[str] = None   # the config.py CLI binding
    env: Optional[str] = None    # the TPU_COOC_* env binding
    kind: str = "perf"           # "perf" | "infra"

    def parse(self, raw: str) -> object:
        """Typed parse of a flag/env string (used by tooling; the
        owning call sites keep their own nuanced parsing)."""
        if self.type == "int":
            return int(raw)
        if self.type == "float":
            return float(raw)
        return raw

    def validate(self, value: object) -> None:
        """Bounds/choices check; raises ``ValueError`` with the knob's
        name so autotune rejections are self-describing."""
        if self.type == "choice" and self.choices is not None:
            if value not in self.choices:
                raise ValueError(
                    f"{self.name}: {value!r} not in {self.choices}")
            return
        if self.bounds is not None and isinstance(value, (int, float)):
            lo, hi = self.bounds
            if lo is not None and value < lo:
                raise ValueError(
                    f"{self.name}: {value} below minimum {lo}")
            if hi is not None and value > hi:
                raise ValueError(
                    f"{self.name}: {value} above maximum {hi}")


#: name -> parameter. Declaration order is the README table order.
REGISTRY: Dict[str, TuningParameter] = {}


def _register(p: TuningParameter) -> TuningParameter:
    if p.name in REGISTRY:
        raise ValueError(f"duplicate tuning parameter {p.name!r}")
    REGISTRY[p.name] = p
    return p


def get(name: str) -> TuningParameter:
    return REGISTRY[name]


def default(name: str):
    """The registered effective default — ``config.py`` field defaults
    and helper fallbacks read through here."""
    return REGISTRY[name].default


def bounds(name: str) -> Tuple[Optional[float], Optional[float]]:
    b = REGISTRY[name].bounds
    return b if b is not None else (None, None)


def by_env() -> Dict[str, TuningParameter]:
    return {p.env: p for p in REGISTRY.values() if p.env}


def by_flag() -> Dict[str, TuningParameter]:
    return {p.flag: p for p in REGISTRY.values() if p.flag}


def env_read(env_name: str, fallback: Optional[str] = None,
             environ=None) -> Optional[str]:
    """The sanctioned ``TPU_COOC_*`` read: exactly
    ``os.environ.get(env_name, fallback)``, but the variable must be a
    registered binding — an unregistered knob fails here at runtime and
    in cooclint at commit time."""
    if env_name not in by_env():
        raise KeyError(
            f"{env_name} is not a registered TuningParameter env "
            f"binding (declare it in tpu_cooccurrence/tuning.py)")
    return (environ if environ is not None else os.environ).get(
        env_name, fallback)


# -- the declared knobs -------------------------------------------------
# Performance parameters (the autotune search space).

_register(TuningParameter(
    name="pipeline_depth", type="int", default=0, bounds=(0, 2),
    unit="windows", flag="--pipeline-depth",
    doc="Sampled-but-unscored windows in flight: 0 = serial, 1 "
        "overlaps host sampling with device scoring, 2 double-buffers "
        "against per-window jitter. Bit-identical at every depth."))
_register(TuningParameter(
    name="checkpoint_compact_ratio", type="float", default=0.5,
    bounds=(0.0, None), unit="fraction",
    flag="--checkpoint-compact-ratio",
    doc="Delta-chain bytes over base bytes that trigger rewriting a "
        "fresh full base (bounds restore replay length)."))
_register(TuningParameter(
    name="spill_threshold_windows", type="int", default=0,
    bounds=(0, None), unit="windows", flag="--spill-threshold-windows",
    doc="Windows a slab row must sit cold before it may spill to the "
        "host tier; 0 disables tiered state."))
_register(TuningParameter(
    name="spill_target_hbm_frac", type="float", default=0.5,
    bounds=(0.0, 1.0), unit="fraction", flag="--spill-target-hbm-frac",
    doc="Device-slab occupancy the spiller drives toward; spilling "
        "engages only above it."))
_register(TuningParameter(
    name="wire_format", type="choice", default="auto",
    choices=("auto", "raw", "packed"), flag="--wire-format",
    doc="Sparse per-window uplink encoding: packed bit-packs the COO "
        "stream (fewer uplink bytes, decode in the program prologue), "
        "raw ships int32/int64 columns; auto picks by backend."))
_register(TuningParameter(
    name="cell_dtype", type="choice", default="auto",
    choices=("auto", "int32", "int16", "int8"), flag="--cell-dtype",
    doc="Sparse slab count-cell dtype; narrow cells stay exact via "
        "overflow promotion and halve/quarter slab HBM."))
_register(TuningParameter(
    name="count_dtype", type="choice", default="int32",
    choices=("int32", "int16"), flag="--count-dtype",
    doc="Dense C cell dtype; int16 halves HBM and doubles the "
        "dense/sharded vocab ceiling (reference-style wraparound)."))
_register(TuningParameter(
    name="score_ladder", type="int", default=4, bounds=(2, None),
    unit="x per bucket", flag="--score-ladder",
    env="TPU_COOC_SCORE_LADDER",
    doc="Sparse score-bucket ladder base (power of two >= 2): coarser "
        "ladders mean fewer compiled rectangle shapes but more "
        "padding per dispatch."))
_register(TuningParameter(
    name="fixed_score", type="choice", default="auto",
    choices=("auto", "on", "off"), flag="--fixed-score",
    env="TPU_COOC_FIXED_SCORE",
    doc="Sparse fixed-shape scoring (constant per-bucket rectangles); "
        "auto = on for real TPUs when results are deferred."))
_register(TuningParameter(
    name="upload_chunks", type="int", default=1, bounds=(1, None),
    unit="chunks", env="TPU_COOC_UPLOAD_CHUNKS",
    doc="Fixed K-way split of per-window device uploads (transfer-"
        "cliff lever); 1 = monolithic until the on-chip A/B proves the "
        "split."))
_register(TuningParameter(
    name="upload_chunk_kb", type="float", default=0.0, bounds=(0.0, None),
    unit="KiB", env="TPU_COOC_UPLOAD_CHUNK_KB",
    doc="Adaptive upload chunking: smallest pow2 K bringing each piece "
        "under this size; 0 = off. A set upload_chunks pins K first."))
_register(TuningParameter(
    name="row_index", type="choice", default="bitmap",
    choices=("bitmap", "dense"), env="TPU_COOC_ROW_INDEX",
    doc="Sparse row-registry layout: bitmap+rank directory "
        "(production) or dense reference arrays (A/B baseline)."))
_register(TuningParameter(
    name="donate", type="choice", default="auto",
    choices=("auto", "on", "off"), env="TPU_COOC_DONATE",
    doc="Donate state buffers to the jitted window dispatch (halves "
        "peak HBM); auto = on for non-CPU backends (TFRT CPU "
        "use-after-donate gating)."))
_register(TuningParameter(
    name="pow2_pad_min", type="int", default=256, bounds=(1, None),
    unit="rows",
    doc="Floor of the pow2 pad ladder for dispatch-shape planning "
        "(ops.device_scorer.pad_pow2): the bucket-plan high-water "
        "minimum under which every shape rounds up."))
_register(TuningParameter(
    name="rect_min_rows", type="int", default=256, bounds=(128, None),
    unit="rows",
    doc="Narrowest score-bucket rectangle routed to the fused Pallas "
        "kernel; narrower buckets stay on the XLA path (they don't "
        "tile the 128-lane VPU cleanly and are cheap for XLA anyway)."))
_register(TuningParameter(
    name="autoscale_trip_windows", type="int", default=3,
    bounds=(1, None), unit="windows", flag="--autoscale-trip-windows",
    doc="Consecutive gang-overloaded windows before ScalePolicy may "
        "scale out (hysteresis: trip)."))
_register(TuningParameter(
    name="autoscale_clear_windows", type="int", default=8,
    bounds=(1, None), unit="windows", flag="--autoscale-clear-windows",
    doc="Consecutive gang-idle windows before ScalePolicy may scale "
        "in (hysteresis: clear)."))
_register(TuningParameter(
    name="autoscale_cooldown_windows", type="int", default=8,
    bounds=(0, None), unit="windows",
    flag="--autoscale-cooldown-windows",
    doc="Observed windows ignored after a rescale while the new gang "
        "warms (hysteresis: cooldown)."))
_register(TuningParameter(
    name="collective_timeout_s", type="float", default=0.0,
    bounds=(0.0, None), unit="seconds", flag="--collective-timeout-s",
    env="TPU_COOC_COLLECTIVE_TIMEOUT_S",
    doc="Collective-entry watchdog: a guarded collective blocked this "
        "long exits 75 instead of hanging the gang; 0 = off."))

# Infra plumbing: resolves through the registry (closed TPU_COOC_*
# surface) but is not a tuning dimension.

_register(TuningParameter(
    name="run_id", type="str", default=None, kind="infra",
    flag="--run-id", env="TPU_COOC_RUN_ID",
    doc="Correlation id stamped on journal/trace records; inherited "
        "from a supervising parent, else minted fresh."))
_register(TuningParameter(
    name="attempt", type="int", default=0, kind="infra",
    env="TPU_COOC_ATTEMPT",
    doc="Supervisor restart ordinal stamped on journal records."))
_register(TuningParameter(
    name="gang_dir", type="str", default=None, kind="infra",
    env="TPU_COOC_GANG_DIR",
    doc="Gang heartbeat directory the supervisor shares with its "
        "workers."))
_register(TuningParameter(
    name="supervisor_state", type="str", default=None, kind="infra",
    env="TPU_COOC_SUPERVISOR_STATE",
    doc="Path of the supervisor's crash-loop state file (restart "
        "budget accounting across respawns)."))
_register(TuningParameter(
    name="smoke_events", type="int", default=None, kind="infra",
    env="TPU_COOC_SMOKE_EVENTS",
    doc="CPU-only bench shrink: cap measured events for smoke runs "
        "(ignored with a warning on accelerator backends)."))


def markdown_table(kind: str = "perf") -> str:
    """The README "Tuning parameters" table, generated — the docs
    cannot drift from the registry because a test diffs them."""
    rows = [p for p in REGISTRY.values() if p.kind == kind]
    out = ["| parameter | flag / env | type | default | bounds | unit "
           "| what it tunes |",
           "|---|---|---|---|---|---|---|"]
    for p in rows:
        binding = " / ".join(x for x in (
            f"`{p.flag}`" if p.flag else "",
            f"`{p.env}`" if p.env else "") if x) or "—"
        if p.choices:
            bound = "{" + ", ".join(p.choices) + "}"
        elif p.bounds:
            lo, hi = p.bounds
            bound = f"[{lo if lo is not None else '-inf'}, " \
                    f"{hi if hi is not None else 'inf'}]"
        else:
            bound = "—"
        out.append(
            f"| `{p.name}` | {binding} | {p.type} | "
            f"{p.default if p.default is not None else '—'} | {bound} "
            f"| {p.unit or '—'} | {p.doc} |")
    return "\n".join(out)
