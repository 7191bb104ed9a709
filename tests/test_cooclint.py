"""cooclint (tpu_cooccurrence.analysis): the tier-1 enforcement run plus
fixture-driven proof that each rule pack catches its seeded violation.

The enforcement test runs the analyzer over the whole checkout and
expects zero non-baseline findings — this is the commit-time gate the
analyzer exists for. The fixture tests feed bad-code snippets through
``analyze_source`` impersonating the file each rule watches, including
a regression fixture reproducing the PR-2 ``TransferLedger`` race
pattern (the unlocked ``+=`` on the ledger's byte totals from a worker
module) that motivated the lock-discipline pack.

This file's raw text necessarily quotes the bad fault-site patterns the
text-scanning rules hunt (the deleted PR-3 test excluded itself for the
same reason), so it opts out of that one rule file-wide:
# cooclint: disable-file=fault-site
"""

import json
import os
import subprocess
import sys

import pytest

from tpu_cooccurrence.analysis import (
    Analyzer,
    Finding,
    RULES,
    analyze_source,
    load_baseline,
)
from tpu_cooccurrence.analysis.core import save_baseline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Tier-1 runtime budget for the whole-repo pass (ISSUE 4 satellite:
#: the analyzer must stay under this or fail loudly here, in review).
RUNTIME_BUDGET_S = 10.0


def _rules(f):
    return sorted({x.rule for x in f})


# -- the tier-1 gate ---------------------------------------------------


def test_repo_is_clean_under_budget():
    """Whole-repo pass: no new findings, runtime within the tier-1
    budget (recorded in the run summary and asserted here)."""
    result = Analyzer(REPO, baseline=load_baseline()).run()
    assert not result.findings, "\n".join(map(str, result.findings))
    assert not result.stale_baseline, (
        f"stale baseline entries (run --prune-baseline): "
        f"{result.stale_baseline}")
    assert result.files_scanned > 50  # sanity: the walker saw the repo
    print(f"cooclint runtime: {result.elapsed_seconds:.2f}s "
          f"over {result.files_scanned} files")
    assert result.elapsed_seconds < RUNTIME_BUDGET_S


def test_runner_json_schema_and_exit_code():
    """``python -m tpu_cooccurrence.analysis --format json`` under
    JAX_PLATFORMS=cpu (the tier-1 environment): exit 0 on the clean
    repo, schema round-trips through Finding.from_dict, runtime is in
    the summary."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.analysis",
         "--root", REPO, "--format", "json"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["schema"] == "cooclint-findings/2"
    assert payload["exit_code"] == 0
    assert payload["files_scanned"] > 50
    assert payload["elapsed_seconds"] < RUNTIME_BUDGET_S
    # Round-trip: every finding dict reconstructs losslessly.
    for d in payload["findings"]:
        assert Finding.from_dict(d).to_dict() == d


# -- rule pack 1: lock discipline --------------------------------------

PR2_RACE_FIXTURE = '''
class PipelineWorker:
    def record_upload(self, ledger, arrays):
        n = sum(int(a.nbytes) for a in arrays)
        ledger.h2d_bytes += n
        ledger.h2d_calls += 1
'''


def test_lock_discipline_catches_pr2_ledger_race():
    """The PR-2 regression shape: an unlocked read-modify-write on the
    TransferLedger byte totals from a worker module."""
    findings = analyze_source(
        PR2_RACE_FIXTURE, path="tpu_cooccurrence/pipeline.py",
        rules=["lock-discipline"])
    assert len(findings) == 2
    assert {f.line for f in findings} == {5, 6}
    assert all(f.rule == "lock-discipline" for f in findings)


def test_lock_discipline_allows_locked_and_owner_access():
    locked = '''
class PipelineWorker:
    def record_upload(self, ledger, n):
        with ledger._lock:
            ledger.h2d_bytes += n
'''
    owner = '''
class TransferLedger:
    def up(self, n):
        with self._lock:
            self.h2d_bytes += n
'''
    assert analyze_source(locked, rules=["lock-discipline"]) == []
    assert analyze_source(owner, rules=["lock-discipline"]) == []


def test_lock_discipline_counters_and_results_state():
    bad = '''
def merge_fast(counters, other):
    for k, v in other._counters.items():
        counters._counters[k] += v
'''
    findings = analyze_source(bad, rules=["lock-discipline"])
    # one access per line: the iteration read and the augmented write
    assert {f.line for f in findings} == {3, 4}
    bad_results = "def poke(latest):\n    return latest._ptr_batch[0]\n"
    assert _rules(analyze_source(
        bad_results, rules=["lock-discipline"])) == ["lock-discipline"]


def test_lock_annotation_required_in_worker_modules():
    bad = "import threading\nLOCK = threading.Lock()\n"
    findings = analyze_source(
        bad, path="tpu_cooccurrence/pipeline.py",
        rules=["lock-annotation"])
    assert _rules(findings) == ["lock-annotation"]
    good = ("import threading\n"
            "# lock-ordering: leaf lock, never held across registry "
            "locks\n"
            "LOCK = threading.Lock()\n")
    assert analyze_source(good, path="tpu_cooccurrence/pipeline.py",
                          rules=["lock-annotation"]) == []
    # Outside the two-thread worker modules a bare lock is fine.
    assert analyze_source(bad, path="tpu_cooccurrence/io/source.py",
                          rules=["lock-annotation"]) == []


def test_lock_discipline_is_object_sensitive_inside_owner():
    """The PR-2 Counters.merge race, reintroduced INSIDE the owning
    class: self's lock over *other*'s dict must still be a finding —
    the owner exemption covers `self` only."""
    bad = '''
class Counters:
    def merge(self, other):
        with self._lock:
            for k, v in other._counters.items():
                self._counters[k] += v
'''
    findings = analyze_source(bad, rules=["lock-discipline"])
    assert len(findings) == 1
    assert "other" in findings[0].message and findings[0].line == 5


def test_lock_discipline_wrong_objects_lock_does_not_cover():
    bad = '''
def record(a, b, n):
    with a._lock:
        b.h2d_bytes += n
'''
    findings = analyze_source(bad, rules=["lock-discipline"])
    assert _rules(findings) == ["lock-discipline"]
    good = bad.replace("with a._lock:", "with b._lock:")
    assert analyze_source(good, rules=["lock-discipline"]) == []


# -- rule pack 2: jit / device hygiene ---------------------------------


def test_jit_purity_flags_host_syncs():
    bad = '''
import jax
import numpy as np

@jax.jit
def score(c, x):
    y = np.asarray(x)
    print("debug", y)
    return float(x)
'''
    findings = analyze_source(bad, rules=["jit-purity"])
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 3
    assert "np.asarray" in msgs and "print" in msgs and "float(x)" in msgs


def test_jit_purity_static_args_and_plain_functions_exempt():
    src = '''
import functools
import jax
import numpy as np

@functools.partial(jax.jit, static_argnames=("k",))
def topk(vals, k):
    return int(k) + vals.sum()

def host_helper(x):
    return float(np.asarray(x).sum())
'''
    assert analyze_source(src, rules=["jit-purity"]) == []


def test_jit_purity_block_until_ready_and_rng():
    bad = '''
import jax
import numpy as np

@jax.jit
def noisy(x):
    x.sum().block_until_ready()
    return x + np.random.rand()
'''
    findings = analyze_source(bad, rules=["jit-purity"])
    msgs = " | ".join(f.message for f in findings)
    assert "block_until_ready" in msgs and "host RNG" in msgs


def test_jit_purity_transitive_closure_any_module():
    """A helper reached from a jitted entry is hot-path in *every*
    module — the old rule special-cased one hop inside ops/ and missed
    everything else."""
    src = '''
import jax
import numpy as np

def helper(x):
    return np.asarray(x)

@jax.jit
def entry(x):
    return helper(x)
'''
    findings = analyze_source(src, path="tpu_cooccurrence/ops/llr.py",
                              rules=["jit-purity"])
    assert _rules(findings) == ["jit-purity"]
    # Same bug outside ops/ — the graph pass does not care which module
    # the trace walks through.
    job = analyze_source(src, path="tpu_cooccurrence/job.py",
                         rules=["jit-purity"])
    assert _rules(job) == ["jit-purity"]
    assert "traced from `entry`" in job[0].message


def test_jit_purity_two_hops_below_entry():
    """Host RNG two calls below the jit entry — provably invisible to
    the old one-hop rule, caught by call-graph reachability."""
    src = '''
import jax
import numpy as np

def noise(shape):
    return np.random.standard_normal(shape)

def helper(x):
    return x + noise(x.shape)

@jax.jit
def entry(x):
    return helper(x)
'''
    findings = analyze_source(src, path="tpu_cooccurrence/job.py",
                              rules=["jit-purity"])
    assert _rules(findings) == ["jit-purity"]
    f = findings[0]
    assert "host RNG" in f.message
    assert "entry -> helper -> noise" in f.message


def test_jit_purity_uncalled_helper_not_flagged():
    """Reachability, not co-location: a host-sync helper in the same
    file that no jitted code calls stays silent."""
    src = '''
import jax
import numpy as np

def orchestrate(x):
    return np.asarray(x)

@jax.jit
def entry(x):
    return x * 2
'''
    assert analyze_source(src, path="tpu_cooccurrence/job.py",
                          rules=["jit-purity"]) == []


DONATION_FIXTURE = '''
import functools
import jax
from ..ops.donation import donate_argnums

@functools.partial(jax.jit, donate_argnums=donate_argnums(0))
def update(c, d):
    return c + d

class Scorer:
    def step(self, d):
        out = update(self.cnt, d)
        return self.cnt.sum()
'''


def test_donation_reuse_flags_use_after_donate():
    findings = analyze_source(DONATION_FIXTURE, rules=["donation-reuse"])
    assert _rules(findings) == ["donation-reuse"]
    assert "self.cnt" in findings[0].message


def test_donation_reuse_allows_same_statement_rebind():
    good = DONATION_FIXTURE.replace(
        "        out = update(self.cnt, d)\n        return self.cnt.sum()",
        "        self.cnt = update(self.cnt, d)\n        return self.cnt.sum()")
    assert analyze_source(good, rules=["donation-reuse"]) == []


# -- rule pack 3: registry drift ---------------------------------------


def test_metric_name_rule():
    bad = ('from .registry import REGISTRY\n'
           'g = REGISTRY.gauge("cooc_bogus_thing", help="x")\n')
    findings = analyze_source(bad, rules=["metric-name"])
    assert _rules(findings) == ["metric-name"]
    assert "cooc_bogus_thing" in findings[0].message
    good = bad.replace("cooc_bogus_thing", "cooc_windows_fired")
    assert analyze_source(good, rules=["metric-name"]) == []


@pytest.mark.parametrize("pragma,flagged", [
    ("", True),
    ("<!-- # cooclint: disable-file=metric-name -->\n", False),
    ("<!-- # cooclint: disable-file=fault-site -->\n", True)])
def test_metric_name_doc_check_honours_a_file_pragma(pragma, flagged):
    """A document that quotes retired metric names by design (a change
    log) opts out of the metric-name check with a file pragma in an HTML
    comment; a pragma for another rule leaves the check on."""
    md = pragma + "Watch `cooc_bogus_thing` on /metrics.\n"
    findings = analyze_source(md, path="CHANGES.md", rules=["metric-name"])
    assert _rules(findings) == (["metric-name"] if flagged else [])


def test_metric_name_rule_counter_literals():
    bad = ('class J:\n'
           '    def f(self):\n'
           '        self.counters.add("TotallyMadeUpCounter", 1)\n')
    findings = analyze_source(bad, rules=["metric-name"])
    assert _rules(findings) == ["metric-name"]
    good = bad.replace("TotallyMadeUpCounter",
                       "ItemInteractionCounterLateElements")
    assert analyze_source(good, rules=["metric-name"]) == []


def test_fault_site_rule_fire_and_spec_strings():
    bad = ('def f(plan):\n'
           '    plan.fire("not_a_site", seq=1)\n'
           '    spec = "not_a_site:3:crash"\n')
    findings = analyze_source(bad, rules=["fault-site"])
    # The AST and raw-text scans overlap deliberately (each covers
    # shapes the other cannot); both anchor the same two lines.
    assert {f.line for f in findings} == {2, 3}
    good = bad.replace("not_a_site", "window_fire")
    assert analyze_source(good, rules=["fault-site"]) == []


def test_fault_site_rule_argv_pairs_without_kind():
    """CLI-test argv shape: the site rides a separate literal with no
    kind suffix — the text scan must still validate it (coverage the
    deleted PR-3 test had)."""
    bad = 'cmd = ["--inject-fault", "windw_fire:3"]\n'  # cooclint: disable=fault-site
    findings = analyze_source(bad, rules=["fault-site"])
    assert _rules(findings) == ["fault-site"]
    assert "windw_fire" in findings[0].message
    good = 'cmd = ["--inject-fault", "window_fire:3"]\n'
    assert analyze_source(good, rules=["fault-site"]) == []


def test_metric_name_reverse_check_flags_dead_canonical_entries(
        tmp_path):
    """A CANONICAL_METRICS entry nothing in the package emits is a dead
    registry row (mirrors the fault-site dead-entry check)."""
    from tpu_cooccurrence.observability.registry import CANONICAL_METRICS

    pkg = tmp_path / "tpu_cooccurrence" / "observability"
    pkg.mkdir(parents=True)
    (pkg / "registry.py").write_text(
        'G = REGISTRY.gauge("cooc_windows_fired")\n')
    result = Analyzer(str(tmp_path), rules=[RULES["metric-name"]]).run()
    dead = {f.message.split("'")[1] for f in result.findings}
    assert dead == CANONICAL_METRICS - {"cooc_windows_fired"}


def test_fault_site_rule_midstring_and_bare_fire():
    """Coverage parity with the deleted PR-3 scan: a quoted spec
    embedded mid-docstring and a bare imported fire() call must both
    be validated."""
    doc = ('def f():\n'
           '    """Example: pass "typo_site:3:crash" to the CLI."""\n')
    findings = analyze_source(doc, rules=["fault-site"])
    assert _rules(findings) == ["fault-site"]
    assert "typo_site" in findings[0].message
    bare = ('from tpu_cooccurrence.robustness.faults import fire\n'
            'fire("typo_site", seq=1)\n')
    findings = analyze_source(bare, rules=["fault-site"])
    assert _rules(findings) == ["fault-site"]
    # Quoted spec in a doc line (no --inject-fault token on the line).
    md = 'pass "typo_site:2:torn_write" to the child\n'
    findings = analyze_source(md, path="docs/RUNBOOK.md",
                              rules=["fault-site"])
    assert _rules(findings) == ["fault-site"]


def test_metric_name_reverse_check_ignores_definition_literals(
        tmp_path):
    """The CANONICAL_METRICS assignment itself is not an emission: a
    dead entry must be flagged even though it textually appears at its
    own definition site."""
    from tpu_cooccurrence.observability.registry import CANONICAL_METRICS

    pkg = tmp_path / "tpu_cooccurrence" / "observability"
    pkg.mkdir(parents=True)
    names = ",\n    ".join(f'"{n}"' for n in sorted(CANONICAL_METRICS))
    (pkg / "registry.py").write_text(
        "CANONICAL_METRICS = frozenset({\n    " + names + ",\n})\n"
        'G = REGISTRY.gauge("cooc_windows_fired")\n')
    result = Analyzer(str(tmp_path), rules=[RULES["metric-name"]]).run()
    dead = {f.message.split("'")[1] for f in result.findings}
    assert dead == CANONICAL_METRICS - {"cooc_windows_fired"}


def test_cli_flag_rule_on_a_mini_repo(tmp_path):
    pkg = tmp_path / "tpu_cooccurrence"
    pkg.mkdir()
    (pkg / "config.py").write_text(
        "import argparse\n"
        "import dataclasses\n\n\n"
        "@dataclasses.dataclass\n"
        "class Config:\n"
        "    top_k: int = 10\n\n\n"
        "def from_args():\n"
        "    p = argparse.ArgumentParser()\n"
        '    p.add_argument("--top-k", type=int, dest="top_k")\n'
        '    p.add_argument("--mystery-flag", type=int, dest="mystery")\n'
        "    return p\n")
    (tmp_path / "README.md").write_text("Flags: `--top-k`.\n")
    result = Analyzer(str(tmp_path), rules=[RULES["cli-flag"]]).run()
    msgs = " | ".join(f.message for f in result.findings)
    assert len(result.findings) == 2  # undocumented + orphaned dest
    assert "--mystery-flag" in msgs and "mystery" in msgs
    assert "--top-k" not in msgs


# -- rule pack 4: native / fold dtype ----------------------------------


def test_native_dtype_rule():
    bad = ('import numpy as np\n'
           'def call(x):\n'
           '    lib.kernel(_ptr64(x), 3)\n')
    findings = analyze_source(
        bad, path="tpu_cooccurrence/native/__init__.py",
        rules=["native-dtype"])
    assert _rules(findings) == ["native-dtype"]
    good_contig = ('import numpy as np\n'
                   'def call(x):\n'
                   '    x = np.ascontiguousarray(x, dtype=np.int64)\n'
                   '    lib.kernel(_ptr64(x), 3)\n')
    good_assert = ('import numpy as np\n'
                   'def call(scratch):\n'
                   '    assert scratch.buf.dtype == np.int32\n'
                   '    lib.kernel(_ptr32(scratch.buf), 1)\n')
    for good in (good_contig, good_assert):
        assert analyze_source(
            good, path="tpu_cooccurrence/native/__init__.py",
            rules=["native-dtype"]) == []


def test_fold_dtype_guard_rule():
    bad = ('import numpy as np\n'
           'def aggregate_window_coo(src, dst, delta, return_key=False):\n'
           '    return src, dst, delta\n')
    findings = analyze_source(
        bad, path="tpu_cooccurrence/ops/aggregate.py",
        rules=["fold-dtype-guard"])
    assert _rules(findings) == ["fold-dtype-guard"]
    good = ('import numpy as np\n'
            'def aggregate_window_coo(src, dst, delta, return_key=False):\n'
            '    if not np.issubdtype(delta.dtype, np.integer):\n'
            '        raise TypeError("delta dtype")\n'
            '    return src, dst, delta\n')
    assert analyze_source(
        good, path="tpu_cooccurrence/ops/aggregate.py",
        rules=["fold-dtype-guard"]) == []


# -- suppressions ------------------------------------------------------


def test_suppression_exact_line_named_rule():
    src = PR2_RACE_FIXTURE.replace(
        "ledger.h2d_bytes += n",
        "ledger.h2d_bytes += n  # cooclint: disable=lock-discipline")
    findings = analyze_source(src, path="tpu_cooccurrence/pipeline.py",
                              rules=["lock-discipline"])
    assert {f.line for f in findings} == {6}  # only the unsuppressed line


def test_suppression_bare_disables_all_rules_on_line():
    src = PR2_RACE_FIXTURE.replace(
        "ledger.h2d_calls += 1",
        "ledger.h2d_calls += 1  # cooclint: disable")
    findings = analyze_source(src, path="tpu_cooccurrence/pipeline.py",
                              rules=["lock-discipline"])
    assert {f.line for f in findings} == {5}


def test_suppression_file_level_named_rule():
    """`# cooclint: disable-file=rule` opts the whole file out of one
    rule (the fixture-holder escape hatch) without touching others."""
    src = ('# cooclint: disable-file=fault-site\n'
           'def f(plan, ledger, n):\n'
           '    plan.fire("typo_site")\n'
           '    ledger.h2d_bytes += n\n')
    assert analyze_source(src, rules=["fault-site"]) == []
    # Other rules still fire in the same file.
    assert _rules(analyze_source(
        src, rules=["lock-discipline"])) == ["lock-discipline"]


def test_suppression_wrong_rule_name_does_not_silence():
    src = PR2_RACE_FIXTURE.replace(
        "ledger.h2d_bytes += n",
        "ledger.h2d_bytes += n  # cooclint: disable=metric-name")
    findings = analyze_source(src, path="tpu_cooccurrence/pipeline.py",
                              rules=["lock-discipline"])
    assert {f.line for f in findings} == {5, 6}


# -- baseline ----------------------------------------------------------


def _mini_repo_with_race(tmp_path):
    pkg = tmp_path / "tpu_cooccurrence"
    pkg.mkdir()
    (pkg / "pipeline.py").write_text(PR2_RACE_FIXTURE)
    return tmp_path


def test_baseline_grandfathers_and_reports_stale(tmp_path):
    root = _mini_repo_with_race(tmp_path)
    baseline = [
        {"rule": "lock-discipline", "file": "tpu_cooccurrence/pipeline.py",
         "line": 5, "justification": "grandfathered for the test"},
        {"rule": "lock-discipline", "file": "tpu_cooccurrence/gone.py",
         "line": 1, "justification": "stale entry"},
    ]
    result = Analyzer(str(root), rules=[RULES["lock-discipline"]],
                      baseline=baseline).run()
    assert {f.line for f in result.findings} == {6}  # line 5 baselined
    assert len(result.baselined) == 1
    assert [e["file"] for e in result.stale_baseline] == [
        "tpu_cooccurrence/gone.py"]


def test_prune_baseline_rewrites_file(tmp_path):
    from tpu_cooccurrence.analysis.__main__ import main

    root = _mini_repo_with_race(tmp_path)
    bl_path = str(tmp_path / "baseline.json")
    save_baseline([
        {"rule": "lock-discipline", "file": "tpu_cooccurrence/pipeline.py",
         "line": 5, "justification": "kept"},
        {"rule": "lock-discipline", "file": "tpu_cooccurrence/pipeline.py",
         "line": 6, "justification": "kept"},
        {"rule": "lock-discipline", "file": "tpu_cooccurrence/gone.py",
         "line": 1, "justification": "stale"},
    ], bl_path)
    rc = main(["--root", str(root), "--baseline", bl_path,
               "--prune-baseline"])
    assert rc == 0  # everything real is baselined, stale was pruned
    kept = load_baseline(bl_path)
    assert len(kept) == 2
    assert all(e["file"] == "tpu_cooccurrence/pipeline.py" for e in kept)
    # A second run sees no stale entries.
    result = Analyzer(str(root), rules=[RULES["lock-discipline"]],
                      baseline=kept).run()
    assert not result.findings and not result.stale_baseline


def test_explicit_missing_baseline_path_is_usage_error(tmp_path):
    """A typo'd --baseline must not silently run with an empty baseline
    (full re-report); it is exit 2. The DEFAULT path staying optional
    is separate (a clean repo has an empty baseline file anyway)."""
    from tpu_cooccurrence.analysis.__main__ import main

    root = _mini_repo_with_race(tmp_path)
    rc = main(["--root", str(root),
               "--baseline", str(tmp_path / "nope.json")])
    assert rc == 2


def test_malformed_baseline_rejected(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text('{"findings": [{"rule": "x"}]}')
    with pytest.raises(ValueError, match="malformed baseline entry"):
        load_baseline(str(p))


def test_finding_json_round_trip():
    f = Finding(rule="lock-discipline", file="a/b.py", line=7,
                message="msg")
    assert Finding.from_dict(json.loads(json.dumps(f.to_dict()))) == f


# ---------------------------------------------------------------------------
# degrade-registry rule (ISSUE 5)

_DEGRADE_OK = '''
import enum

class DegradationLevel(enum.IntEnum):
    NORMAL = 0
    SHED_SAMPLING = 1

TRANSITION_RULES = {
    "NORMAL": "healthy",
    "SHED_SAMPLING": "overloaded",
}
LEVEL_EVENTS = {
    "NORMAL": "degrade/enter_normal",
    "SHED_SAMPLING": "degrade/enter_shed_sampling",
}
'''


def test_degrade_registry_clean_fixture_passes():
    assert analyze_source(
        _DEGRADE_OK, path="tpu_cooccurrence/robustness/degrade.py",
        rules=["degrade-registry"]) == []


def test_degrade_registry_flags_member_missing_from_tables():
    bad = _DEGRADE_OK.replace('    "SHED_SAMPLING": "overloaded",\n', "")
    findings = analyze_source(
        bad, path="tpu_cooccurrence/robustness/degrade.py",
        rules=["degrade-registry"])
    assert _rules(findings) == ["degrade-registry"]
    assert "TRANSITION_RULES" in findings[0].message
    assert "SHED_SAMPLING" in findings[0].message


def test_degrade_registry_flags_dead_table_row():
    # A key naming no member must be flagged. (Scope note: the rule
    # reads dict-LITERAL keys only — a row added later via subscript
    # assignment is outside its reach, like every registry rule here.)
    bad = _DEGRADE_OK.replace(
        '    "SHED_SAMPLING": "degrade/enter_shed_sampling",\n',
        '    "SHED_SAMPLING": "degrade/enter_shed_sampling",\n'
        '    "GONE": "degrade/enter_gone",\n')
    findings = analyze_source(
        bad, path="tpu_cooccurrence/robustness/degrade.py",
        rules=["degrade-registry"])
    assert _rules(findings) == ["degrade-registry"]
    assert "dead registry row" in findings[0].message


def test_degrade_registry_flags_removed_table():
    bad = _DEGRADE_OK.replace("TRANSITION_RULES", "RENAMED_TABLE")
    findings = analyze_source(
        bad, path="tpu_cooccurrence/robustness/degrade.py",
        rules=["degrade-registry"])
    assert any("TRANSITION_RULES dict literal not found" in f.message
               for f in findings)


def test_degrade_registry_requires_architecture_mention(tmp_path):
    """With docs/ARCHITECTURE.md present but missing a level name, the
    rule flags it — the level table is part of the registry."""
    root = tmp_path / "repo"
    pkg = root / "tpu_cooccurrence" / "robustness"
    pkg.mkdir(parents=True)
    (root / "docs").mkdir()
    (pkg / "degrade.py").write_text(_DEGRADE_OK)
    (root / "docs" / "ARCHITECTURE.md").write_text(
        "# arch\n\nonly NORMAL is documented here\n")
    result = Analyzer(str(root), rules=[RULES["degrade-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["degrade-registry"]
    assert "SHED_SAMPLING" in result.findings[0].message
    assert "ARCHITECTURE" in result.findings[0].message


# -- rule pack 6: pallas kernel registry --------------------------------


def _mini_pallas_repo(tmp_path, *, test_body, arch_body):
    """A minimal repo for the pallas-kernel-registry rule: one kernel
    core issuing pallas_call plus a public wrapper calling it."""
    root = tmp_path / "repo"
    ops = root / "tpu_cooccurrence" / "ops"
    ops.mkdir(parents=True)
    (ops / "pallas_score.py").write_text(
        "from jax.experimental import pallas as pl\n\n\n"
        "def _my_kernel_core(x):\n"
        "    return pl.pallas_call(None)(x)\n\n\n"
        "def my_kernel_wrapper(x):\n"
        "    return _my_kernel_core(x)\n")
    (root / "tests").mkdir()
    (root / "tests" / "test_parity_fixture.py").write_text(test_body)
    (root / "docs").mkdir()
    (root / "docs" / "ARCHITECTURE.md").write_text(arch_body)
    return root


def test_pallas_kernel_registry_wrapper_coverage_passes(tmp_path):
    """A parity test referencing the public WRAPPER covers the private
    kernel core (one call hop — the surface tests actually drive)."""
    root = _mini_pallas_repo(
        tmp_path,
        test_body="def test_parity():\n    assert my_kernel_wrapper\n",
        arch_body="| `_my_kernel_core` | streaming thing |\n")
    result = Analyzer(str(root), rules=[RULES["pallas-kernel-registry"]],
                      baseline=[]).run()
    assert result.findings == []


def test_pallas_kernel_registry_flags_untested_kernel(tmp_path):
    root = _mini_pallas_repo(
        tmp_path,
        test_body="def test_nothing():\n    pass\n",
        arch_body="| `_my_kernel_core` | streaming thing |\n")
    result = Analyzer(str(root), rules=[RULES["pallas-kernel-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["pallas-kernel-registry"]
    assert "no registered parity test" in result.findings[0].message
    assert "_my_kernel_core" in result.findings[0].message


def test_pallas_kernel_registry_flags_missing_arch_row(tmp_path):
    root = _mini_pallas_repo(
        tmp_path,
        test_body="def test_parity():\n    assert my_kernel_wrapper\n",
        arch_body="# arch\n\nno kernel table here\n")
    result = Analyzer(str(root), rules=[RULES["pallas-kernel-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["pallas-kernel-registry"]
    assert "Pallas kernel table" in result.findings[0].message


def test_pallas_kernel_registry_flags_empty_registry(tmp_path):
    """ops/pallas_score.py with every pallas_call gone = the registry
    this rule guards no longer exists; that is a finding, not silence."""
    root = _mini_pallas_repo(
        tmp_path,
        test_body="def test_parity():\n    assert my_kernel_wrapper\n",
        arch_body="| `_my_kernel_core` |\n")
    (root / "tpu_cooccurrence" / "ops" / "pallas_score.py").write_text(
        "def plain(x):\n    return x\n")
    result = Analyzer(str(root), rules=[RULES["pallas-kernel-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["pallas-kernel-registry"]
    assert "no pallas_call entry points" in result.findings[0].message


def test_pallas_kernel_registry_scans_beyond_pallas_score(tmp_path):
    """The rule's scope is the whole package: a fused-sparse kernel that
    grew inside state/ (not ops/pallas_score.py) needs the same parity
    surface + ARCHITECTURE row — uncovered, it is two findings anchored
    at ITS file."""
    root = _mini_pallas_repo(
        tmp_path,
        test_body="def test_parity():\n    assert my_kernel_wrapper\n",
        arch_body="| `_my_kernel_core` | streaming thing |\n")
    state = root / "tpu_cooccurrence" / "state"
    state.mkdir()
    (state / "fused_sparse.py").write_text(
        "from jax.experimental import pallas as pl\n\n\n"
        "def _slab_decode_kernel(x):\n"
        "    return pl.pallas_call(None)(x)\n")
    result = Analyzer(str(root), rules=[RULES["pallas-kernel-registry"]],
                      baseline=[]).run()
    assert sorted(f.message.split("'")[1] for f in result.findings) == \
        ["_slab_decode_kernel", "_slab_decode_kernel"]
    assert all(f.file.endswith("state/fused_sparse.py")
               for f in result.findings)


def test_pallas_kernel_registry_survives_missing_anchor_file(tmp_path):
    """A vanished ops/pallas_score.py must not silently waive the rule:
    kernels elsewhere in the package are still checked, and a repo with
    no kernels at all yields the registry-gone finding."""
    root = _mini_pallas_repo(
        tmp_path,
        test_body="def test_nothing():\n    pass\n",
        arch_body="# arch\n")
    (root / "tpu_cooccurrence" / "ops" / "pallas_score.py").unlink()
    state = root / "tpu_cooccurrence" / "state"
    state.mkdir()
    (state / "fused_sparse.py").write_text(
        "from jax.experimental import pallas as pl\n\n\n"
        "def _slab_decode_kernel(x):\n"
        "    return pl.pallas_call(None)(x)\n")
    result = Analyzer(str(root), rules=[RULES["pallas-kernel-registry"]],
                      baseline=[]).run()
    assert len(result.findings) == 2  # untested + un-documented
    assert all("_slab_decode_kernel" in f.message for f in result.findings)
    # With that kernel gone too there is nothing to guard — and no
    # anchor file, so fixture repos for OTHER rules stay silent here
    # (the registry-gone finding needs ops/pallas_score.py to exist).
    (state / "fused_sparse.py").write_text("def plain(x):\n    return x\n")
    result = Analyzer(str(root), rules=[RULES["pallas-kernel-registry"]],
                      baseline=[]).run()
    assert result.findings == []


def test_pallas_kernel_registry_covers_out_of_tree_kernel_via_wrapper(
        tmp_path):
    """Same out-of-ops kernel, but with a same-module wrapper referenced
    from tests/ and an ARCHITECTURE row: clean — the one-hop wrapper
    contract applies uniformly across the package."""
    root = _mini_pallas_repo(
        tmp_path,
        test_body="def test_parity():\n    assert my_kernel_wrapper\n"
                  "def test_slab():\n    assert slab_decode\n",
        arch_body="| `_my_kernel_core` | x |\n| `_slab_decode_kernel` |\n")
    state = root / "tpu_cooccurrence" / "state"
    state.mkdir()
    (state / "fused_sparse.py").write_text(
        "from jax.experimental import pallas as pl\n\n\n"
        "def _slab_decode_kernel(x):\n"
        "    return pl.pallas_call(None)(x)\n\n\n"
        "def slab_decode(x):\n"
        "    return _slab_decode_kernel(x)\n")
    result = Analyzer(str(root), rules=[RULES["pallas-kernel-registry"]],
                      baseline=[]).run()
    assert result.findings == []


# -- rule pack 6b: fused fallback-reason registry -----------------------


def _mini_fallback_repo(tmp_path, *, scorer_body, arch_body, test_body):
    """A minimal repo for the fused-fallback-registry rule: the sharded
    scorer with _fallback_chained call sites, the ARCHITECTURE fallback
    table, and a test asserting the reason literals."""
    root = tmp_path / "repo"
    par = root / "tpu_cooccurrence" / "parallel"
    par.mkdir(parents=True)
    (par / "sharded_sparse.py").write_text(scorer_body)
    (root / "docs").mkdir()
    (root / "docs" / "ARCHITECTURE.md").write_text(arch_body)
    (root / "tests").mkdir()
    (root / "tests" / "test_fallback_fixture.py").write_text(test_body)
    return root


_FALLBACK_SCORER = (
    "class S:\n"
    "    def _fallback_chained(self, reason):\n"
    "        self.last_fallback_reason = reason\n\n"
    "    def window(self, cold):\n"
    "        if cold:\n"
    "            self._fallback_chained('plan-rebuild')\n")


def test_fused_fallback_registry_documented_and_tested_passes(tmp_path):
    root = _mini_fallback_repo(
        tmp_path,
        scorer_body=_FALLBACK_SCORER,
        arch_body="| `plan-rebuild` | cold plans |\n",
        test_body="def test_cold():\n"
                  "    assert reason == 'plan-rebuild'\n")
    result = Analyzer(str(root), rules=[RULES["fused-fallback-registry"]],
                      baseline=[]).run()
    assert result.findings == []


def test_fused_fallback_registry_flags_undocumented_reason(tmp_path):
    """A reason absent from the ARCHITECTURE fallback table is a
    finding; prose mentioning the bare word does not count — the table
    quotes reasons backticked."""
    root = _mini_fallback_repo(
        tmp_path,
        scorer_body=_FALLBACK_SCORER,
        arch_body="plans rebuild after a plan-rebuild window\n",  # prose
        test_body="def test_cold():\n"
                  "    assert reason == 'plan-rebuild'\n")
    result = Analyzer(str(root), rules=[RULES["fused-fallback-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["fused-fallback-registry"]
    assert "fallback table" in result.findings[0].message
    assert "plan-rebuild" in result.findings[0].message


def test_fused_fallback_registry_flags_untested_reason(tmp_path):
    root = _mini_fallback_repo(
        tmp_path,
        scorer_body=_FALLBACK_SCORER,
        arch_body="| `plan-rebuild` | cold plans |\n",
        test_body="def test_nothing():\n    pass\n")
    result = Analyzer(str(root), rules=[RULES["fused-fallback-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["fused-fallback-registry"]
    assert "never asserted under tests/" in result.findings[0].message


def test_fused_fallback_registry_flags_dynamic_reason(tmp_path):
    """A non-literal reason defeats static registry checking and is a
    finding at the call site."""
    root = _mini_fallback_repo(
        tmp_path,
        scorer_body=("class S:\n"
                     "    def window(self, why):\n"
                     "        self._fallback_chained(why)\n"),
        arch_body="| `plan-rebuild` |\n",
        test_body="def test_nothing():\n    pass\n")
    result = Analyzer(str(root), rules=[RULES["fused-fallback-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["fused-fallback-registry"]
    assert "not a string literal" in result.findings[0].message


def test_fused_fallback_registry_flags_gone_registry(tmp_path):
    """The sharded scorer defining _fallback_chained with zero call
    sites = the fallback taxonomy this rule guards is gone; other
    fixture repos (no sharded_sparse.py) stay silent."""
    root = _mini_fallback_repo(
        tmp_path,
        scorer_body=("class S:\n"
                     "    def _fallback_chained(self, reason):\n"
                     "        pass\n"),
        arch_body="| `plan-rebuild` |\n",
        test_body="def test_nothing():\n    pass\n")
    result = Analyzer(str(root), rules=[RULES["fused-fallback-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["fused-fallback-registry"]
    assert "registry this rule guards is gone" in result.findings[0].message
    # No _fallback_chained anywhere at all -> silence (fixture repos for
    # other rules are not fallback registries).
    (root / "tpu_cooccurrence" / "parallel" / "sharded_sparse.py"
     ).write_text("def plain(x):\n    return x\n")
    result = Analyzer(str(root), rules=[RULES["fused-fallback-registry"]],
                      baseline=[]).run()
    assert result.findings == []


def test_fused_fallback_registry_flags_missing_arch(tmp_path):
    """A vanished ARCHITECTURE.md is a finding, not a silent waiver of
    the doc half of the registry."""
    root = _mini_fallback_repo(
        tmp_path,
        scorer_body=_FALLBACK_SCORER,
        arch_body="| `plan-rebuild` |\n",
        test_body="def test_cold():\n"
                  "    assert reason == 'plan-rebuild'\n")
    (root / "docs" / "ARCHITECTURE.md").unlink()
    result = Analyzer(str(root), rules=[RULES["fused-fallback-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["fused-fallback-registry"]
    assert "not found" in result.findings[0].message


# -- rule pack 8: serving route registry --------------------------------


def _mini_serving_repo(tmp_path, *, http_body, readme_body, test_body):
    """A minimal repo for the serving-route rule: the http module with a
    ROUTE_METRICS table plus README and tests/ to reference routes."""
    root = tmp_path / "repo"
    obs = root / "tpu_cooccurrence" / "observability"
    obs.mkdir(parents=True)
    (obs / "http.py").write_text(http_body)
    (root / "README.md").write_text(readme_body)
    (root / "tests").mkdir()
    (root / "tests" / "test_routes_fixture.py").write_text(test_body)
    return root


_GOOD_HTTP = (
    'ROUTE_METRICS = {\n'
    '    "/metrics": "cooc_scrape_seconds",\n'
    '    "/healthz": "cooc_healthz_seconds",\n'
    '    "/recommend": "cooc_query_seconds",\n'
    '}\n')


def test_serving_route_clean_repo_passes(tmp_path):
    root = _mini_serving_repo(
        tmp_path, http_body=_GOOD_HTTP,
        readme_body="curl /metrics /healthz /recommend\n",
        test_body='ROUTES = ["/metrics", "/healthz", "/recommend"]\n')
    result = Analyzer(str(root), rules=[RULES["serving-route"]],
                      baseline=[]).run()
    assert result.findings == []


def test_serving_route_flags_unregistered_metric_and_missing_refs(tmp_path):
    http = (
        'ROUTE_METRICS = {\n'
        '    "/newroute": "cooc_bogus_seconds",\n'
        '}\n')
    root = _mini_serving_repo(
        tmp_path, http_body=http,
        readme_body="nothing here\n",
        test_body="def test_nothing():\n    pass\n")
    result = Analyzer(str(root), rules=[RULES["serving-route"]],
                      baseline=[]).run()
    msgs = [f.message for f in result.findings]
    assert any("cooc_bogus_seconds" in m and "CANONICAL_METRICS" in m
               for m in msgs)
    assert any("README" in m for m in msgs)
    assert any("tests/ reference" in m for m in msgs)


def test_serving_route_flags_unlisted_route_literal(tmp_path):
    http = _GOOD_HTTP + (
        '\n\ndef do_GET(path):\n'
        '    if path == "/secret":\n'
        '        return "ok"\n')
    root = _mini_serving_repo(
        tmp_path, http_body=http,
        readme_body="/metrics /healthz /recommend\n",
        test_body='R = ["/metrics", "/healthz", "/recommend"]\n')
    result = Analyzer(str(root), rules=[RULES["serving-route"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["serving-route"]
    assert "/secret" in result.findings[0].message


def test_serving_route_flags_vanished_table(tmp_path):
    root = _mini_serving_repo(
        tmp_path, http_body="def handler():\n    return 404\n",
        readme_body="x\n", test_body="y = 1\n")
    result = Analyzer(str(root), rules=[RULES["serving-route"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["serving-route"]
    assert "ROUTE_METRICS" in result.findings[0].message


# -- rule pack 9: state-store registry ----------------------------------


def _mini_store_repo(tmp_path, *, test_body, arch_body):
    """A minimal repo for the state-store-registry rule: the base class
    plus one direct subclass and one transitive subclass."""
    root = tmp_path / "repo"
    state = root / "tpu_cooccurrence" / "state"
    state.mkdir(parents=True)
    (state / "store.py").write_text(
        "class StateStore:\n"
        "    def checkpoint_state(self):\n"
        "        raise NotImplementedError\n\n\n"
        "class MyDirectStore(StateStore):\n"
        "    pass\n\n\n"
        "class MyTieredStore(MyDirectStore):\n"
        "    pass\n")
    (root / "tests").mkdir()
    (root / "tests" / "test_store_fixture.py").write_text(test_body)
    (root / "docs").mkdir()
    (root / "docs" / "ARCHITECTURE.md").write_text(arch_body)
    return root


def test_state_store_registry_clean_fixture_passes(tmp_path):
    """Both stores referenced from tests/ (the transitive subclass
    counts as an implementation too) and in the ARCHITECTURE table."""
    root = _mini_store_repo(
        tmp_path,
        test_body=("def test_round_trip():\n"
                   "    assert MyDirectStore and MyTieredStore\n"),
        arch_body=("| `MyDirectStore` | direct |\n"
                   "| `MyTieredStore` | tiered |\n"))
    result = Analyzer(str(root), rules=[RULES["state-store-registry"]],
                      baseline=[]).run()
    assert result.findings == []


def test_state_store_registry_flags_untested_store(tmp_path):
    root = _mini_store_repo(
        tmp_path,
        test_body="def test_round_trip():\n    assert MyDirectStore\n",
        arch_body=("| `MyDirectStore` | direct |\n"
                   "| `MyTieredStore` | tiered |\n"))
    result = Analyzer(str(root), rules=[RULES["state-store-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["state-store-registry"]
    assert "MyTieredStore" in result.findings[0].message
    assert "round-trip" in result.findings[0].message


def test_state_store_registry_flags_missing_arch_row(tmp_path):
    root = _mini_store_repo(
        tmp_path,
        test_body=("def test_round_trip():\n"
                   "    assert MyDirectStore and MyTieredStore\n"),
        arch_body="# arch\n\nno state-store table here\n")
    result = Analyzer(str(root), rules=[RULES["state-store-registry"]],
                      baseline=[]).run()
    assert sorted(f.rule for f in result.findings) == [
        "state-store-registry", "state-store-registry"]
    assert all("state-store table" in f.message for f in result.findings)


def test_state_store_registry_flags_vanished_arch_doc(tmp_path):
    """A missing docs/ARCHITECTURE.md is a finding in its own right,
    not a silent waiver of the doc requirement for every store (same
    posture as the serving rule's vanished ROUTE_METRICS table)."""
    root = _mini_store_repo(
        tmp_path,
        test_body=("def test_round_trip():\n"
                   "    assert MyDirectStore and MyTieredStore\n"),
        arch_body="x\n")
    os.remove(root / "docs" / "ARCHITECTURE.md")
    result = Analyzer(str(root), rules=[RULES["state-store-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["state-store-registry"]
    assert "ARCHITECTURE.md not found" in result.findings[0].message


def test_state_store_registry_flags_empty_registry(tmp_path):
    """state/store.py with every implementation gone = the registry this
    rule guards no longer exists; that is a finding, not silence."""
    root = _mini_store_repo(
        tmp_path, test_body="x = 1\n", arch_body="# arch\n")
    (root / "tpu_cooccurrence" / "state" / "store.py").write_text(
        "class StateStore:\n    pass\n")
    result = Analyzer(str(root), rules=[RULES["state-store-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["state-store-registry"]
    assert "registry" in result.findings[0].message


# -- rule pack 10: checkpoint-format round trip -------------------------


_OK_DELTA_BODY = ("def encode():\n"
                  "    header = {\"gen\": 1}\n\n\n"
                  "def decode(header):\n"
                  "    return header[\"gen\"]\n")


def _mini_ckpt_repo(tmp_path, *, ckpt_body, delta_body=_OK_DELTA_BODY,
                    test_body="x = 1\n"):
    root = tmp_path / "repo"
    state = root / "tpu_cooccurrence" / "state"
    state.mkdir(parents=True)
    (state / "checkpoint.py").write_text(ckpt_body)
    (state / "delta.py").write_text(delta_body)
    (root / "tests").mkdir()
    (root / "tests" / "test_fmt_fixture.py").write_text(test_body)
    return root


def test_ckpt_format_clean_fixture_passes(tmp_path):
    root = _mini_ckpt_repo(
        tmp_path,
        ckpt_body=("def save():\n"
                   "    meta = {\"windows\": 1}\n"
                   "    meta[\"extra\"] = 2\n\n\n"
                   "def restore(meta):\n"
                   "    return meta[\"windows\"], meta.get(\"extra\")\n"),
        delta_body=("def encode():\n"
                    "    header = {\"gen\": 1}\n\n\n"
                    "def decode(header):\n"
                    "    return header[\"gen\"]\n"),
        test_body=("KEYS = {\"windows\", \"extra\", \"gen\"}\n"))
    result = Analyzer(str(root), rules=[RULES["ckpt-format-roundtrip"]],
                      baseline=[]).run()
    assert result.findings == []


def test_ckpt_format_flags_writer_only_field(tmp_path):
    """A meta key with no restore-side read is silent format drift."""
    root = _mini_ckpt_repo(
        tmp_path,
        ckpt_body=("def save():\n"
                   "    meta = {\"windows\": 1, \"orphan\": 2}\n\n\n"
                   "def restore(meta):\n"
                   "    return meta[\"windows\"]\n"),
        test_body="KEYS = {\"windows\", \"orphan\", \"gen\"}\n")
    result = Analyzer(str(root), rules=[RULES["ckpt-format-roundtrip"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["ckpt-format-roundtrip"]
    assert "'orphan'" in result.findings[0].message
    assert "never read back" in result.findings[0].message


def test_ckpt_format_flags_untested_field(tmp_path):
    root = _mini_ckpt_repo(
        tmp_path,
        ckpt_body=("def save():\n"
                   "    meta = {\"windows\": 1}\n\n\n"
                   "def restore(meta):\n"
                   "    return meta[\"windows\"]\n"),
        test_body="KEYS = {\"gen\"}\n")
    result = Analyzer(str(root), rules=[RULES["ckpt-format-roundtrip"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["ckpt-format-roundtrip"]
    assert "round-trip reference" in result.findings[0].message


def test_ckpt_format_flags_vanished_module(tmp_path):
    """A format module going missing is a finding in its own right, not
    a silent waiver (same posture as the other registry rules)."""
    root = _mini_ckpt_repo(
        tmp_path,
        ckpt_body=("def save():\n"
                   "    meta = {\"windows\": 1}\n\n\n"
                   "def restore(meta):\n"
                   "    return meta[\"windows\"]\n"),
        test_body="KEYS = {\"windows\"}\n")
    os.remove(root / "tpu_cooccurrence" / "state" / "delta.py")
    result = Analyzer(str(root), rules=[RULES["ckpt-format-roundtrip"]],
                      baseline=[]).run()
    msgs = [f.message for f in result.findings
            if f.rule == "ckpt-format-roundtrip"]
    assert any("missing" in m for m in msgs)


def test_ckpt_format_flags_empty_key_registry(tmp_path):
    """A checkpoint.py that no longer builds a meta dict means the
    registry this rule guards moved — finding, not silence."""
    root = _mini_ckpt_repo(
        tmp_path, ckpt_body="def save():\n    pass\n",
        delta_body=("def encode():\n"
                    "    header = {\"gen\": 1}\n\n\n"
                    "def decode(header):\n"
                    "    return header[\"gen\"]\n"),
        test_body="KEYS = {\"gen\"}\n")
    result = Analyzer(str(root), rules=[RULES["ckpt-format-roundtrip"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["ckpt-format-roundtrip"]
    assert "no format keys" in result.findings[0].message


def test_ckpt_format_rule_clean_on_repo():
    """The real repo is clean under the rule (baseline-free contract)."""
    result = Analyzer(REPO, rules=[RULES["ckpt-format-roundtrip"]],
                      baseline=[]).run()
    assert result.findings == []


# -- collective-watchdog / gang-fault-sites (rules_gang) ----------------


def test_collective_watchdog_flags_raw_collectives():
    bad = '''
from jax.experimental import multihost_utils

def exchange(vec):
    lens = multihost_utils.process_allgather(vec)
    multihost_utils.sync_global_devices("x")
    return lens
'''
    findings = analyze_source(
        bad, path="tpu_cooccurrence/sampling/multihost.py",
        rules=["collective-watchdog"])
    assert _rules(findings) == ["collective-watchdog"]
    assert {f.line for f in findings} == {5, 6}


def test_collective_watchdog_flags_bare_imported_call():
    bad = ('from jax.experimental.multihost_utils import '
           'process_allgather\n'
           'def f(v):\n'
           '    return process_allgather(v)\n')
    findings = analyze_source(
        bad, path="tpu_cooccurrence/parallel/sharded.py",
        rules=["collective-watchdog"])
    assert _rules(findings) == ["collective-watchdog"]


def test_collective_watchdog_allows_wrappers_and_wrapper_module():
    good = '''
from tpu_cooccurrence.parallel.distributed import (
    gang_barrier, guarded_allgather)

def exchange(vec):
    gang_barrier("x")
    return guarded_allgather(vec)
'''
    assert analyze_source(
        good, path="tpu_cooccurrence/sampling/multihost.py",
        rules=["collective-watchdog"]) == []
    # The wrapper module itself is the one allowed caller.
    raw = ('from jax.experimental import multihost_utils\n'
           'def g(a):\n'
           '    return multihost_utils.process_allgather(a)\n')
    assert analyze_source(
        raw, path="tpu_cooccurrence/parallel/distributed.py",
        rules=["collective-watchdog"]) == []


def test_gang_fault_sites_rule_clean_on_repo():
    result = Analyzer(REPO, rules=[RULES["gang-fault-sites"]],
                      baseline=[]).run()
    assert result.findings == []


def test_gang_fault_sites_flags_unfired_site(tmp_path):
    """A faults.py present but no package code firing a GANG_SITES
    member = a finding (the chaos specs can no longer trigger)."""
    root = tmp_path / "repo"
    pkg = root / "tpu_cooccurrence" / "robustness"
    pkg.mkdir(parents=True)
    (pkg / "faults.py").write_text("SITES = {}\n")
    result = Analyzer(str(root), rules=[RULES["gang-fault-sites"]],
                      baseline=[]).run()
    # Every gang site is unplugged in this mini-repo.
    from tpu_cooccurrence.robustness.gang import GANG_SITES

    assert len(result.findings) == len(GANG_SITES)
    assert all(f.rule == "gang-fault-sites" for f in result.findings)


# -- rule pack: serving fleet (replica routes + generation tag) --------


def _mini_fleet_repo(tmp_path, replica_body, http_body=None):
    """Mini repo with a registered route table, its docs/tests
    obligations satisfied, and a replica module under test."""
    obs = tmp_path / "tpu_cooccurrence" / "observability"
    obs.mkdir(parents=True)
    (obs / "http.py").write_text(
        http_body if http_body is not None else
        'ROUTE_METRICS = {"/metrics": "cooc_scrape_seconds"}\n\n\n'
        "class MetricsServer:\n"
        "    def recommend(self, query):\n"
        '        return 200, {"generation": 1}\n')
    serving = tmp_path / "tpu_cooccurrence" / "serving"
    serving.mkdir()
    (serving / "replica.py").write_text(replica_body)
    (tmp_path / "README.md").write_text("Routes: /metrics\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_routes.py").write_text('URL = "/metrics"\n')
    return tmp_path


def test_serving_route_rule_flags_replica_only_route(tmp_path):
    """A route-shaped literal the replica module quotes that is not in
    observability/http.py ROUTE_METRICS is an unmeasured endpoint."""
    root = _mini_fleet_repo(
        tmp_path,
        "from ..observability.http import MetricsServer\n\n\n"
        "class ReplicaServer(MetricsServer):\n"
        "    pass\n\n\n"
        "def sneaky(handler):\n"
        '    handler.route("/sneaky")\n')
    result = Analyzer(str(root), rules=[RULES["serving-route"]],
                      baseline=[]).run()
    assert len(result.findings) == 1
    f = result.findings[0]
    assert f.file.endswith("serving/replica.py")
    assert "/sneaky" in f.message and "ROUTE_METRICS" in f.message
    # Registered routes quoted by the replica are fine.
    root2 = _mini_fleet_repo(
        tmp_path / "clean",
        "from ..observability.http import MetricsServer\n\n\n"
        "class ReplicaServer(MetricsServer):\n"
        "    pass\n\n\n"
        'PROBE = "/metrics"\n')
    result = Analyzer(str(root2), rules=[RULES["serving-route"]],
                      baseline=[]).run()
    assert result.findings == []


def test_replica_generation_tag_inherited_body_is_clean():
    src = ("from ..observability.http import MetricsServer\n\n\n"
           "class ReplicaServer(MetricsServer):\n"
           "    pass\n")
    assert analyze_source(
        src, path="tpu_cooccurrence/serving/replica.py",
        rules=["replica-generation-tag"]) == []


def test_replica_generation_tag_flags_untagged_override():
    src = ("from ..observability.http import MetricsServer\n\n\n"
           "class ReplicaServer(MetricsServer):\n"
           "    def recommend(self, query):\n"
           '        return 200, {"items": []}\n')
    found = analyze_source(
        src, path="tpu_cooccurrence/serving/replica.py",
        rules=["replica-generation-tag"])
    assert len(found) == 1
    assert "generation" in found[0].message
    # The same override carrying the tag is clean.
    src_ok = src.replace('{"items": []}',
                         '{"items": [], "generation": 1}')
    assert analyze_source(
        src_ok, path="tpu_cooccurrence/serving/replica.py",
        rules=["replica-generation-tag"]) == []


def test_replica_generation_tag_requires_metricsserver_subclass():
    src = ("class LoneServer:\n"
           "    def recommend(self, query):\n"
           '        return 200, {"generation": 1}\n')
    found = analyze_source(
        src, path="tpu_cooccurrence/serving/replica.py",
        rules=["replica-generation-tag"])
    assert len(found) == 1
    assert "MetricsServer subclass" in found[0].message


def test_replica_generation_tag_flags_untagged_inherited_body(tmp_path):
    """No override: the obligation lands on the inherited
    observability/http.py recommend body."""
    root = _mini_fleet_repo(
        tmp_path,
        "from ..observability.http import MetricsServer\n\n\n"
        "class ReplicaServer(MetricsServer):\n"
        "    pass\n",
        http_body=(
            'ROUTE_METRICS = {"/metrics": "cooc_scrape_seconds"}\n\n\n'
            "class MetricsServer:\n"
            "    def recommend(self, query):\n"
            '        return 200, {"items": []}\n'))
    result = Analyzer(str(root), rules=[RULES["replica-generation-tag"]],
                      baseline=[]).run()
    assert len(result.findings) == 1
    assert result.findings[0].file.endswith("observability/http.py")
    assert "generation" in result.findings[0].message


def test_replica_generation_tag_silent_without_replica_module():
    """Fixture repos for other rules (no serving/replica.py) must not
    trip this rule."""
    assert analyze_source(
        "X = 1\n", path="tpu_cooccurrence/other.py",
        rules=["replica-generation-tag"]) == []


# -- rule pack 12: scale-policy registry ---------------------------------


def _mini_policy_repo(tmp_path, *, test_body, arch_body):
    """A minimal repo for the scale-policy-registry rule: the base
    class plus one direct subclass and one transitive subclass."""
    root = tmp_path / "repo"
    rob = root / "tpu_cooccurrence" / "robustness"
    rob.mkdir(parents=True)
    (rob / "autoscale.py").write_text(
        "class ScalePolicy:\n"
        "    def decide(self, *a):\n"
        "        raise NotImplementedError\n\n\n"
        "class MyLadderPolicy(ScalePolicy):\n"
        "    pass\n\n\n"
        "class MySteppedPolicy(MyLadderPolicy):\n"
        "    pass\n")
    (root / "tests").mkdir()
    (root / "tests" / "test_policy_fixture.py").write_text(test_body)
    (root / "docs").mkdir()
    (root / "docs" / "ARCHITECTURE.md").write_text(arch_body)
    return root


def test_scale_policy_registry_clean_fixture_passes(tmp_path):
    root = _mini_policy_repo(
        tmp_path,
        test_body=("def test_hysteresis():\n"
                   "    assert MyLadderPolicy and MySteppedPolicy\n"),
        arch_body=("| `MyLadderPolicy` | ladder |\n"
                   "| `MySteppedPolicy` | stepped |\n"))
    result = Analyzer(str(root), rules=[RULES["scale-policy-registry"]],
                      baseline=[]).run()
    assert result.findings == []


def test_scale_policy_registry_flags_untested_policy(tmp_path):
    root = _mini_policy_repo(
        tmp_path,
        test_body="def test_hysteresis():\n    assert MyLadderPolicy\n",
        arch_body=("| `MyLadderPolicy` | ladder |\n"
                   "| `MySteppedPolicy` | stepped |\n"))
    result = Analyzer(str(root), rules=[RULES["scale-policy-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["scale-policy-registry"]
    assert "MySteppedPolicy" in result.findings[0].message
    assert "hysteresis" in result.findings[0].message


def test_scale_policy_registry_flags_missing_arch_row(tmp_path):
    root = _mini_policy_repo(
        tmp_path,
        test_body=("def test_hysteresis():\n"
                   "    assert MyLadderPolicy and MySteppedPolicy\n"),
        arch_body="# arch\n\nno scale-policy table here\n")
    result = Analyzer(str(root), rules=[RULES["scale-policy-registry"]],
                      baseline=[]).run()
    assert sorted(f.rule for f in result.findings) == [
        "scale-policy-registry", "scale-policy-registry"]
    assert all("scale-policy table" in f.message
               for f in result.findings)


def test_scale_policy_registry_flags_vanished_arch_doc(tmp_path):
    root = _mini_policy_repo(
        tmp_path,
        test_body=("def test_hysteresis():\n"
                   "    assert MyLadderPolicy and MySteppedPolicy\n"),
        arch_body="x\n")
    os.remove(root / "docs" / "ARCHITECTURE.md")
    result = Analyzer(str(root), rules=[RULES["scale-policy-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["scale-policy-registry"]
    assert "ARCHITECTURE.md not found" in result.findings[0].message


def test_scale_policy_registry_flags_empty_registry(tmp_path):
    root = _mini_policy_repo(
        tmp_path, test_body="x = 1\n", arch_body="x\n")
    (root / "tpu_cooccurrence" / "robustness" / "autoscale.py"
     ).write_text("class ScalePolicy:\n    pass\n")
    result = Analyzer(str(root), rules=[RULES["scale-policy-registry"]],
                      baseline=[]).run()
    assert [f.rule for f in result.findings] == ["scale-policy-registry"]
    assert "registry this rule guards is gone" in result.findings[0].message


def test_scale_policy_registry_silent_without_autoscale_module():
    """Fixture repos for other rules must not trip this rule."""
    assert analyze_source(
        "X = 1\n", path="tpu_cooccurrence/other.py",
        rules=["scale-policy-registry"]) == []


# ---------------------------------------------------------------------------
# journal-schema-registry (ISSUE 17): every journal-emitted key must be
# in the schema tables, the ARCHITECTURE journal table, and tests/


def test_journal_registry_flags_unregistered_key():
    src = (
        "class J:\n"
        "    def emit(self):\n"
        "        self.journal.record({'v': 1, 'seq': 1,\n"
        "                             'warp_factor': 9})\n"
    )
    findings = analyze_source(src, path="tpu_cooccurrence/fixmod.py",
                              rules=["journal-schema-registry"])
    assert [f.rule for f in findings] == ["journal-schema-registry"]
    assert "warp_factor" in findings[0].message
    assert "*_SCHEMA" in findings[0].message


def test_journal_registry_sees_through_stamp_and_name_args():
    """The writers pass dict literals through a stamping wrapper or
    build the record incrementally (``rec = {...}; rec["k"] = ...``) —
    the collector must see every shape."""
    wrapped = (
        "class J:\n"
        "    def emit(self):\n"
        "        self.journal.record(self._stamp({'v': 1,\n"
        "                                         'bogus_a': 1}))\n"
    )
    findings = analyze_source(wrapped, path="tpu_cooccurrence/fm.py",
                              rules=["journal-schema-registry"])
    assert ["bogus_a" in f.message for f in findings] == [True]
    built = (
        "class J:\n"
        "    def emit(self):\n"
        "        rec = {'v': 1, 'seq': 1}\n"
        "        rec['bogus_b'] = 2\n"
        "        self.journal.record(self._stamp(rec))\n"
    )
    findings = analyze_source(built, path="tpu_cooccurrence/fm.py",
                              rules=["journal-schema-registry"])
    assert ["bogus_b" in f.message for f in findings] == [True]


def test_journal_registry_docs_and_tests_legs(tmp_path):
    """With docs/ and tests/ trees present, a registered-but-
    undocumented / untested key is flagged on those legs too."""
    root = tmp_path / "repo"
    (root / "tpu_cooccurrence").mkdir(parents=True)
    (root / "docs").mkdir()
    (root / "tests").mkdir()
    (root / "tpu_cooccurrence" / "writer.py").write_text(
        "class J:\n"
        "    def emit(self):\n"
        "        self.journal.record({'v': 1, 'seq': 1})\n")
    # `v` documented + tested; `seq` neither.
    (root / "docs" / "ARCHITECTURE.md").write_text(
        "| `v` | version |\n")
    (root / "tests" / "test_x.py").write_text("K = 'v'\n")
    result = Analyzer(str(root),
                      rules=[RULES["journal-schema-registry"]],
                      baseline=[]).run()
    msgs = sorted(f.message for f in result.findings)
    assert len(msgs) == 2
    assert all("'seq'" in m for m in msgs)
    assert any("undocumented" in m for m in msgs)
    assert any("no tests/ reference" in m for m in msgs)


def test_journal_registry_silent_without_writers():
    """Fixture repos for other rules must not trip this rule."""
    assert analyze_source(
        "X = 1\n", path="tpu_cooccurrence/other.py",
        rules=["journal-schema-registry"]) == []


def test_journal_registry_clean_on_repo():
    """The real writers, schema tables, ARCHITECTURE journal table and
    tests/ registry are in sync right now."""
    result = Analyzer(REPO, rules=[RULES["journal-schema-registry"]],
                      baseline=[]).run()
    assert result.findings == []


# -- rule pack: ingest offset-codec registry (ISSUE 18) -----------------


_OK_FILES_SRC = ("def offsets_state(self):\n"
                 "    in_flight = {\"path\": self.p}\n"
                 "    offsets = {\"v\": 1, \"in_flight\": in_flight}\n"
                 "    return offsets\n\n\n"
                 "def restore_offsets(self, state):\n"
                 "    self.v = state.get(\"v\")\n"
                 "    guard = state.get(\"in_flight\")\n"
                 "    self.p = guard[\"path\"]\n")

_OK_PART_SRC = ("def offsets_state(self):\n"
                "    partitions = {}\n"
                "    partitions[name] = {\"byte_offset\": 0}\n"
                "    offsets = {\"v\": 1, \"partitions\": partitions}\n"
                "    return offsets\n\n\n"
                "def restore_offsets(self, state):\n"
                "    self.v = state.get(\"v\")\n"
                "    for e in state[\"partitions\"].values():\n"
                "        self.b = e[\"byte_offset\"]\n")


def _mini_ingest_repo(tmp_path, *, files_src=_OK_FILES_SRC,
                      part_src=_OK_PART_SRC, test_body="x = 1\n"):
    root = tmp_path / "repo"
    io_dir = root / "tpu_cooccurrence" / "io"
    io_dir.mkdir(parents=True)
    (io_dir / "source.py").write_text(files_src)
    (io_dir / "partitioned.py").write_text(part_src)
    (root / "tests").mkdir()
    (root / "tests" / "test_ingest_fixture.py").write_text(test_body)
    return root


def test_ingest_registry_clean_fixture_passes(tmp_path):
    root = _mini_ingest_repo(
        tmp_path,
        test_body=("KEYS = {\"v\", \"in_flight\", \"path\", "
                   "\"partitions\", \"byte_offset\"}\n"))
    result = Analyzer(str(root), rules=[RULES["ingest-offset-registry"]],
                      baseline=[]).run()
    assert result.findings == []


def test_ingest_registry_flags_writer_only_key(tmp_path):
    """An offset field with no restore-side reader silently stops
    steering where the wire resumes — the drift this rule exists for."""
    root = _mini_ingest_repo(
        tmp_path,
        files_src=("def offsets_state(self):\n"
                   "    offsets = {\"v\": 1, \"orphan\": 2}\n"
                   "    return offsets\n\n\n"
                   "def restore_offsets(self, state):\n"
                   "    self.v = state.get(\"v\")\n"),
        test_body="KEYS = {\"v\", \"orphan\", \"partitions\", "
                  "\"byte_offset\"}\n")
    msgs = [f.message for f in Analyzer(
        str(root), rules=[RULES["ingest-offset-registry"]],
        baseline=[]).run().findings]
    assert any("'orphan'" in m and "never read back" in m for m in msgs)
    # The healthy partitioned module contributed no findings.
    assert not any("byte_offset" in m for m in msgs)


def test_ingest_registry_flags_untested_key(tmp_path):
    root = _mini_ingest_repo(
        tmp_path,
        test_body="KEYS = {\"v\", \"in_flight\", \"path\", "
                  "\"partitions\"}\n")  # byte_offset missing
    msgs = [f.message for f in Analyzer(
        str(root), rules=[RULES["ingest-offset-registry"]],
        baseline=[]).run().findings]
    assert len(msgs) == 1
    assert "'byte_offset'" in msgs[0]
    assert "round-trip reference" in msgs[0]
    assert "test_ingest_offsets.py" in msgs[0]


def test_ingest_registry_flags_vanished_module(tmp_path):
    """One end of the codec going missing is a finding (the other
    module is still present, so the scope guard does not waive it)."""
    root = _mini_ingest_repo(
        tmp_path,
        test_body=("KEYS = {\"v\", \"in_flight\", \"path\", "
                   "\"partitions\", \"byte_offset\"}\n"))
    os.remove(root / "tpu_cooccurrence" / "io" / "partitioned.py")
    msgs = [f.message for f in Analyzer(
        str(root), rules=[RULES["ingest-offset-registry"]],
        baseline=[]).run().findings]
    assert any("missing" in m for m in msgs)


def test_ingest_registry_silent_without_ingest_modules():
    """Fixture repos for other rules must not trip this rule."""
    assert analyze_source(
        "offsets = {\"v\": 1}\n", path="tpu_cooccurrence/other.py",
        rules=["ingest-offset-registry"]) == []


def test_ingest_registry_clean_on_repo():
    """The real sources, their restore paths and the
    tests/test_ingest_offsets.py registry are in sync right now."""
    result = Analyzer(REPO, rules=[RULES["ingest-offset-registry"]],
                      baseline=[]).run()
    assert result.findings == []


# ---------------------------------------------------------------------------
# thread-ownership rule (whole-program graph, PR 19)

PR2_THREAD_RACE = '''
import threading

class TransferLedger:
    def __init__(self):
        self.h2d_bytes = 0
        self.h2d_calls = 0

    def add(self, n):
        self.h2d_bytes += n
        self.h2d_calls += 1

def scorer_worker(ledger):
    ledger.h2d_bytes += 4

def main():
    ledger = TransferLedger()
    threading.Thread(target=scorer_worker, name="scorer").start()
    ledger.add(3)
'''


def test_thread_ownership_rediscovers_pr2_ledger_race():
    """The pre-fix PR-2 shape, no class list involved: the spawned
    scorer worker and the main thread both write the ledger's byte
    totals with no lock — derived purely from the call graph's thread
    roots."""
    findings = analyze_source(PR2_THREAD_RACE,
                              rules=["thread-ownership"])
    assert len(findings) == 1
    f = findings[0]
    assert "TransferLedger.h2d_bytes" in f.message
    assert "scorer" in f.message and "main" in f.message
    # Anchored on the spawned-writer side (the actionable site).
    assert f.line == 14


PR2_COUNTERS_RACE = '''
import threading

class Counters:
    def __init__(self):
        self._counts = {}

    def increment(self, key):
        self._counts[key] = self._counts.get(key, 0) + 1

    def merge(self, other):
        for k, v in other._counts.items():
            self._counts[k] = self._counts.get(k, 0) + v

def scorer_worker(counters):
    counters.increment("windows_scored")

def main():
    counters = Counters()
    threading.Thread(target=scorer_worker).start()
    counters.merge(Counters())
'''


def test_thread_ownership_rediscovers_pr2_counters_race():
    """The second PR-2 race: the worker folds counts into the shared
    Counters while the main thread's merge rewrites the same dict."""
    findings = analyze_source(PR2_COUNTERS_RACE,
                              rules=["thread-ownership"])
    assert len(findings) == 1
    assert "Counters._counts" in findings[0].message


def test_thread_ownership_lock_and_annotation_exempt():
    locked = PR2_THREAD_RACE.replace(
        "    ledger.h2d_bytes += 4",
        "    with ledger._lock:\n        ledger.h2d_bytes += 4").replace(
        "        self.h2d_bytes += n\n        self.h2d_calls += 1",
        "        with self._lock:\n"
        "            self.h2d_bytes += n\n"
        "            self.h2d_calls += 1")
    assert analyze_source(locked, rules=["thread-ownership"]) == []
    annotated = PR2_THREAD_RACE.replace(
        "    ledger.h2d_bytes += 4",
        "    # thread-owner: handoff precedes the scorer's first write\n"
        "    ledger.h2d_bytes += 4")
    assert analyze_source(annotated, rules=["thread-ownership"]) == []


def test_thread_ownership_mode_dependent_sharing_is_clean():
    """job.py's shape: one write site reachable from main (serial mode)
    AND the pipeline worker (pipelined mode). The root sets are equal,
    not mutually exclusive — no single run has two threads in that
    write, so it must not flag."""
    src = '''
import threading

class Ledger:
    def __init__(self):
        self.h2d_bytes = 0

def step(ledger):
    ledger.h2d_bytes += 1

def worker():
    step(Ledger())

def main():
    threading.Thread(target=worker).start()
    step(Ledger())
'''
    assert analyze_source(src, rules=["thread-ownership"]) == []


def test_thread_ownership_flags_self_concurrent_handler():
    """An HTTP handler runs one thread per request: a single unlocked
    write inside do_* races with itself, no second site needed."""
    src = '''
import http.server

class MetricsHandler(http.server.BaseHTTPRequestHandler):
    def do_GET(self):
        self.hits = getattr(self, "hits", 0) + 1
'''
    findings = analyze_source(src, rules=["thread-ownership"])
    assert len(findings) == 1
    assert "self-concurrent" in findings[0].message
    assert "MetricsHandler.hits" in findings[0].message


def test_thread_ownership_clean_on_repo():
    result = Analyzer(REPO, rules=[RULES["thread-ownership"]],
                      baseline=[]).run()
    assert result.findings == []


# ---------------------------------------------------------------------------
# tuning registry (PR 19 tentpole: tpu_cooccurrence/tuning.py + rules)

def test_tuning_registry_flags_unregistered_knob():
    src = ('import os\n'
           'budget = os.environ.get("TPU_COOC_NOT_A_KNOB", "0")\n')
    findings = analyze_source(src, rules=["tuning-registry"])
    msgs = [f.message for f in findings]
    assert any("not a registered" in m for m in msgs)
    assert any("tuning.env_read" in m for m in msgs)


def test_tuning_registry_flags_direct_read_of_registered_knob():
    """Even a registered knob must be read via tuning.env_read (the
    registry has to see the live read surface)."""
    for src in (
            'import os\nrid = os.environ.get("TPU_COOC_RUN_ID")\n',
            'import os\nrid = os.getenv("TPU_COOC_RUN_ID")\n',
            'import os\nrid = os.environ["TPU_COOC_RUN_ID"]\n',
            # an aliased module-level constant is seen through
            'import os\nK = "TPU_COOC_RUN_ID"\nrid = os.environ.get(K)\n'):
        findings = analyze_source(src, rules=["tuning-registry"])
        assert len(findings) == 1, src
        assert "tuning.env_read" in findings[0].message


def test_tuning_registry_env_read_is_clean():
    src = ('from tpu_cooccurrence import tuning\n'
           'rid = tuning.env_read("TPU_COOC_RUN_ID")\n')
    assert analyze_source(src, rules=["tuning-registry"]) == []


def test_tuning_env_read_rejects_unregistered_at_runtime():
    from tpu_cooccurrence import tuning
    with pytest.raises(KeyError, match="TPU_COOC_BOGUS"):
        tuning.env_read("TPU_COOC_BOGUS")
    assert tuning.env_read("TPU_COOC_RUN_ID",
                           environ={"TPU_COOC_RUN_ID": "r7"}) == "r7"


def test_tuning_parameter_validate_bounds_and_choices():
    from tpu_cooccurrence import tuning
    tuning.get("pipeline_depth").validate(2)
    with pytest.raises(ValueError, match="pipeline_depth"):
        tuning.get("pipeline_depth").validate(3)
    with pytest.raises(ValueError, match="wire_format"):
        tuning.get("wire_format").validate("gzip")
    assert tuning.bounds("score_ladder") == (2, None)


def test_tuning_magic_number_flags_inlined_default():
    src = ('def plan(rows):\n'
           '    if rows < 256:\n'
           '        return None\n'
           '    return rows\n')
    findings = analyze_source(src, path="tpu_cooccurrence/ops/plan.py",
                              rules=["tuning-magic-number"])
    assert len(findings) == 1
    assert findings[0].severity == "warning"
    assert "256" in findings[0].message
    # Outside the hot-path prefixes the same literal is style, not perf.
    assert analyze_source(src, path="tpu_cooccurrence/config.py",
                          rules=["tuning-magic-number"]) == []


def test_every_env_knob_in_package_is_registered():
    """Acceptance: every TPU_COOC_* token in package source resolves
    through the registry (grep-level, independent of the analyzer)."""
    import re
    from tpu_cooccurrence import tuning
    registered = set(tuning.by_env())
    pkg = os.path.join(REPO, "tpu_cooccurrence")
    offenders = []
    for dirpath, dirnames, files in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in files:
            if not fname.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fname),
                      encoding="utf-8") as fh:
                for tok in set(re.findall(r"TPU_COOC_[A-Z0-9_]+",
                                          fh.read())):
                    if tok not in registered:
                        offenders.append((fname, tok))
    assert not offenders


def test_readme_tuning_table_is_generated_and_pinned():
    """The README "Tuning parameters" table is the literal output of
    tuning.markdown_table() — docs cannot drift from the registry."""
    from tpu_cooccurrence import tuning
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as fh:
        readme = fh.read()
    assert tuning.markdown_table("perf") in readme
    assert tuning.markdown_table("infra") in readme


def test_config_reads_defaults_from_registry():
    """config.py field defaults come from tuning.default(...) — the
    registry is the single source of truth for knob defaults."""
    from tpu_cooccurrence import config as cfg
    from tpu_cooccurrence import tuning
    c = cfg.Config()
    assert c.pipeline_depth == tuning.default("pipeline_depth")
    assert c.checkpoint_compact_ratio == tuning.default(
        "checkpoint_compact_ratio")


# ---------------------------------------------------------------------------
# fingerprints + --changed (PR 19 satellites)

def test_findings_carry_symbol_severity_and_rule_doc():
    findings = analyze_source(
        PR2_RACE_FIXTURE, path="tpu_cooccurrence/pipeline.py",
        rules=["lock-discipline"])
    f = findings[0]
    assert f.symbol == "PipelineWorker.record_upload"
    assert f.severity == "error"
    assert f.rule_doc == RULES["lock-discipline"].description
    d = f.to_dict()
    assert d["symbol"] and d["severity"] and d["rule_doc"]


def test_baseline_symbol_fingerprint_survives_line_drift(tmp_path):
    """A {rule, file, symbol} baseline entry keeps matching after lines
    above the finding shift (the legacy line form would go stale)."""
    root = _mini_repo_with_race(tmp_path)
    baseline = [{"rule": "lock-discipline",
                 "file": "tpu_cooccurrence/pipeline.py",
                 "symbol": "PipelineWorker.record_upload",
                 "justification": "fingerprint form"}]
    result = Analyzer(str(root), rules=[RULES["lock-discipline"]],
                      baseline=baseline).run()
    assert not result.findings and not result.stale_baseline
    assert len(result.baselined) == 2
    # Same entry still matches with ten blank lines pushed above it.
    (root / "tpu_cooccurrence" / "pipeline.py").write_text(
        "\n" * 10 + PR2_RACE_FIXTURE)
    result = Analyzer(str(root), rules=[RULES["lock-discipline"]],
                      baseline=baseline).run()
    assert not result.findings and not result.stale_baseline


def test_prune_baseline_upgrades_legacy_entries_to_fingerprints(tmp_path):
    """--prune-baseline rewrites matched legacy {rule, file, line}
    entries into the stable {rule, file, symbol} form."""
    from tpu_cooccurrence.analysis.__main__ import main

    root = _mini_repo_with_race(tmp_path)
    bl_path = str(tmp_path / "baseline.json")
    save_baseline([
        {"rule": "lock-discipline",
         "file": "tpu_cooccurrence/pipeline.py", "line": 5,
         "justification": "kept"},
        {"rule": "lock-discipline",
         "file": "tpu_cooccurrence/pipeline.py", "line": 6,
         "justification": "kept"},
    ], bl_path)
    rc = main(["--root", str(root), "--baseline", bl_path,
               "--prune-baseline"])
    assert rc == 0
    kept = load_baseline(bl_path)
    assert all(e.get("symbol") == "PipelineWorker.record_upload"
               and "line" not in e for e in kept)
    assert all(e["justification"] == "kept" for e in kept)


def test_changed_mode_falls_back_to_full_run_without_git(tmp_path):
    from tpu_cooccurrence.analysis.__main__ import main

    root = _mini_repo_with_race(tmp_path)
    rc = main(["--root", str(root), "--changed"])
    assert rc == 1  # no git: full-run fallback still sees the race


def test_changed_mode_scopes_and_caches_on_real_repo():
    """--changed on the checkout: exits 0 (clean repo), reports its
    scope, and persists the sha-keyed pass-1 cache for the next run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_cooccurrence.analysis",
         "--root", REPO, "--changed"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    if "changed)" in proc.stdout:  # git + main ref available
        cache = os.path.join(REPO, ".cooclint-cache.json")
        assert os.path.exists(cache)
        with open(cache, encoding="utf-8") as fh:
            data = json.load(fh)
        assert data["schema"] == "cooclint-pass1/1"
        assert data["modules"]
