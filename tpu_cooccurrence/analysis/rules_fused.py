"""Pallas-kernel registry drift.

Every Pallas program entry point in ``ops/pallas_score.py`` — a
module-level function whose body issues a ``pl.pallas_call`` — is a
compiled device artifact whose correctness rests entirely on a parity
test (kernel output vs the XLA/oracle formulation; TPU behavior cannot
be unit-tested any other way on this CPU-only CI) and whose existence
is operator-facing contract: the ARCHITECTURE "Pallas kernel table"
names each one with its role and routing rule. A kernel added without
both is exactly how the fused-window plane would rot — a Mosaic
miscompile class (see the float32-id workaround in
``_score_topk_kernel``) that nothing ever compares against a reference
implementation, documented nowhere an operator looks.

Coverage is one call hop wide: a kernel core (e.g. ``dense_topk``)
counts as parity-tested when a module-level
wrapper that calls it is referenced from ``tests/`` — the wrappers are
the public surface the tests drive. AST-checked (nothing imported) and
baseline-free by construction, mirroring the ``degrade-registry`` rule.

Scope (extended for the fused-sparse plane): EVERY module under
``tpu_cooccurrence/`` is scanned for ``pallas_call`` entry points, not
just ``ops/pallas_score.py`` — a fused-sparse program that grew its own
kernel in ``state/`` must register a parity surface and an ARCHITECTURE
kernel-table row exactly like the ops-layer kernels (wrapper coverage
stays one hop wide *within the defining module*). The sharded scorer in
``parallel/sharded_sparse.py`` is covered by the same sweep — its fused
program bodies call the shared kernels through module-level wrappers.

A second registry rides the same module (``fused-fallback-registry``):
every *chained-fallback reason* the sharded fused window can take — the
string literal at a ``_fallback_chained("<reason>")`` call site — is an
operator-facing contract twice over: the ARCHITECTURE fallback table
names it (an operator reading ``last_fallback_reason`` in the journal
must find it documented), and a test exercises it (a fallback branch
nothing ever drives is exactly the untested-escape-hatch class the
fused plane's bit-identity claim cannot survive). Baseline-free,
AST-only, fixture-tested in ``tests/test_cooclint.py``.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Set, Tuple

from .core import (
    FileContext,
    Finding,
    RepoContext,
    Rule,
    register,
)

_PALLAS_PATH = "tpu_cooccurrence/ops/pallas_score.py"
_PKG_PREFIX = "tpu_cooccurrence/"
_ARCH_PATH = "docs/ARCHITECTURE.md"


def _module_functions(tree: ast.Module) -> Dict[str, ast.FunctionDef]:
    return {node.name: node for node in tree.body
            if isinstance(node, ast.FunctionDef)}


def _called_names(fn: ast.FunctionDef) -> Set[str]:
    """Last segments of every callee in ``fn``'s body (``pl.pallas_call``
    -> ``pallas_call``; ``foo(...)`` -> ``foo``)."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute):
            out.add(f.attr)
        elif isinstance(f, ast.Name):
            out.add(f.id)
    return out


def _kernel_entry_points(tree: ast.Module) -> Dict[str, ast.FunctionDef]:
    """Module-level functions that issue a ``pallas_call`` directly."""
    return {name: fn for name, fn in _module_functions(tree).items()
            if "pallas_call" in _called_names(fn)}


def _test_referenced_names(repo: RepoContext) -> Set[str]:
    """Every identifier the test suite mentions (names, attributes,
    imported aliases) — the "registered parity test" evidence."""
    return repo.test_referenced_names()


@register
class FusedKernelRegistryRule(Rule):
    name = "pallas-kernel-registry"
    description = ("every Pallas kernel entry point under "
                   "tpu_cooccurrence/ needs a registered parity test "
                   "(referenced from tests/, directly or via a calling "
                   "wrapper in the same module) and a row in the "
                   "ARCHITECTURE Pallas kernel table")

    def finalize(self, repo: RepoContext) -> Iterable[Finding]:
        # No anchor-file gate: a vanished/unparseable ops/pallas_score.py
        # must not silently waive the rule for kernels elsewhere in the
        # package (the state-store-registry rule's vanished-ARCHITECTURE
        # precedent) — the package-wide scan below is the whole gate.
        sources = [c for c in repo.python_files()
                   if c.path.startswith(_PKG_PREFIX)
                   and "pallas_call" in c.source  # cheap pre-filter
                   and c.tree is not None]
        per_file = [(ctx, _kernel_entry_points(ctx.tree))
                    for ctx in sources]
        if not any(kernels for _ctx, kernels in per_file):
            # The registry-gone finding is anchored on the kernel home
            # module existing at all — fixture repos for OTHER rules
            # carry no ops/pallas_score.py and are not kernel registries.
            if any(c.path == _PALLAS_PATH for c in repo.files):
                yield Finding(
                    rule=self.name, file=_PALLAS_PATH, line=1,
                    message="no pallas_call entry points found (the "
                            "kernel registry this rule guards is gone)")
            return
        refs = _test_referenced_names(repo)
        arch = next((c for c in repo.files if c.path == _ARCH_PATH), None)
        for ctx, kernels in per_file:
            if not kernels:
                continue
            functions = _module_functions(ctx.tree)
            # Wrappers: module-level functions that call a kernel entry
            # point (one hop within the defining module — the public
            # surface parity tests drive).
            callers: Dict[str, Set[str]] = {k: set() for k in kernels}
            for name, fn in functions.items():
                for callee in _called_names(fn) & set(kernels):
                    if name != callee:
                        callers[callee].add(name)
            for kernel, fn in sorted(kernels.items()):
                covered = kernel in refs or bool(callers[kernel] & refs)
                if not covered:
                    yield Finding(
                        rule=self.name, file=ctx.path, line=fn.lineno,
                        message=(f"Pallas kernel entry point {kernel!r} "
                                 f"has no registered parity test: nothing "
                                 f"under tests/ references it (or a "
                                 f"wrapper that calls it) — a kernel "
                                 f"nothing compares against a reference "
                                 f"is a silent-miscompile risk"))
                if arch is not None and kernel not in arch.source:
                    yield Finding(
                        rule=self.name, file=ctx.path, line=fn.lineno,
                        message=(f"Pallas kernel entry point {kernel!r} "
                                 f"is not in {_ARCH_PATH} — add it to "
                                 f"the Pallas kernel table"))


_SHARDED_PATH = "tpu_cooccurrence/parallel/sharded_sparse.py"


def _fallback_sites(
        tree: ast.Module) -> Tuple[List[Tuple[int, str]], List[int]]:
    """``_fallback_chained("<reason>")`` call sites: (line, reason) for
    literal reasons, plus lines whose reason is NOT a string literal
    (those defeat static registry checking and are findings
    themselves)."""
    literal: List[Tuple[int, str]] = []
    dynamic: List[int] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "_fallback_chained"):
            continue
        if (node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            literal.append((node.lineno, node.args[0].value))
        else:
            dynamic.append(node.lineno)
    return literal, dynamic


@register
class FusedFallbackRegistryRule(Rule):
    name = "fused-fallback-registry"
    description = ("every chained-fallback reason literal at a "
                   "_fallback_chained(...) call site must be quoted in "
                   "the ARCHITECTURE fallback table and asserted by a "
                   "test under tests/")

    def finalize(self, repo: RepoContext) -> Iterable[Finding]:
        sites: List[Tuple[FileContext, int, str]] = []
        any_call_sites = False
        for ctx in repo.package_files():
            if "_fallback_chained" not in ctx.source or ctx.tree is None:
                continue
            literal, dynamic = _fallback_sites(ctx.tree)
            any_call_sites = any_call_sites or bool(literal or dynamic)
            for lineno in dynamic:
                yield Finding(
                    rule=self.name, file=ctx.path, line=lineno,
                    message=("_fallback_chained reason is not a string "
                             "literal — the fallback-reason registry is "
                             "only checkable when every call site names "
                             "its reason inline"))
            for lineno, reason in literal:
                sites.append((ctx, lineno, reason))
        if not any_call_sites:
            # Anchor: the sharded scorer defining _fallback_chained with
            # zero call sites means the fallback taxonomy this rule
            # guards is gone (every fused gate must route through it).
            src = next((c for c in repo.files
                        if c.path == _SHARDED_PATH), None)
            if (src is not None and src.tree is not None
                    and "_fallback_chained" in src.source):
                yield Finding(
                    rule=self.name, file=_SHARDED_PATH, line=1,
                    message=("_fallback_chained is defined but never "
                             "called with a reason literal (the "
                             "fallback-reason registry this rule guards "
                             "is gone)"))
            return
        if not sites:
            return
        arch = next((c for c in repo.files if c.path == _ARCH_PATH), None)
        if arch is None:
            yield Finding(
                rule=self.name, file=sites[0][0].path, line=1,
                message=(f"{_ARCH_PATH} not found — the fused fallback "
                         f"table this rule checks reasons against is "
                         f"gone"))
        test_literals: Set[str] = repo.test_string_constants()
        seen: Set[str] = set()
        for ctx, lineno, reason in sites:
            if reason in seen:
                continue
            seen.add(reason)
            # The table quotes reasons backticked — plain prose mention
            # of a generic word like "promotion" is not registry
            # evidence.
            if arch is not None and f"`{reason}`" not in arch.source:
                yield Finding(
                    rule=self.name, file=ctx.path, line=lineno,
                    message=(f"fallback reason {reason!r} is not in the "
                             f"{_ARCH_PATH} fused fallback table — an "
                             f"operator reading last_fallback_reason "
                             f"must find it documented"))
            if reason not in test_literals:
                yield Finding(
                    rule=self.name, file=ctx.path, line=lineno,
                    message=(f"fallback reason {reason!r} is never "
                             f"asserted under tests/ — a fallback "
                             f"branch nothing drives is an untested "
                             f"escape hatch in the fused plane's "
                             f"bit-identity contract"))
