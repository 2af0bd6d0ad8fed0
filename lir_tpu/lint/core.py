"""Finding/reporting core shared by every lint pass.

Design constraints (ISSUE 8):

- stdlib only (``ast``, ``re``, ``json``) — the linter must run in a
  bare CI container and in the pre-push hook without importing jax or
  any engine module;
- deterministic output — findings sort by (path, line, pass) and their
  MESSAGES carry no line numbers, so the baseline survives unrelated
  edits shifting code around;
- baseline diffing — the gate is "zero findings outside
  tools/lint_baseline.json", counted per fingerprint (pass, path,
  scope, message) so two identical violations in one function need two
  baseline entries;
- suppression — a ``# lint: allow(<pass>[, <pass>...])`` comment on the
  finding's line waives exactly those passes there; ``# lint:
  skip-file`` waives a whole module. Passes may add their own richer
  conventions (``@host_readout``, ``# guarded-by:``) on top.

Each pass is a small class with ``name`` and ``run(project)``; new
fleet-era passes (ROADMAP items 3/5) slot into :data:`ALL_PASSES`.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import re
from collections import Counter
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

ALLOW_RE = re.compile(r"#\s*lint:\s*allow\(([a-z0-9_\-, ]+)\)")
SKIP_FILE_RE = re.compile(r"#\s*lint:\s*skip-file")

Fingerprint = Tuple[str, str, str, str]  # (pass, path, scope, message)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violation. ``scope`` is the enclosing function/class qualname
    (or "<module>"); ``line`` is for humans and clickable editors only —
    the baseline fingerprint deliberately excludes it so re-indenting a
    file does not churn the baseline."""

    pass_name: str
    path: str            # repo-relative posix path
    line: int
    scope: str
    message: str

    @property
    def fingerprint(self) -> Fingerprint:
        return (self.pass_name, self.path, self.scope, self.message)

    def render(self) -> str:
        return (f"{self.path}:{self.line}: [{self.pass_name}] "
                f"{self.scope}: {self.message}")


class Module:
    """One parsed source file + its suppression comments."""

    def __init__(self, path: Path, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=rel)
        self.skip = bool(SKIP_FILE_RE.search(source[:2048]))
        self.allow: Dict[int, Set[str]] = {}
        for i, text in enumerate(self.lines, start=1):
            m = ALLOW_RE.search(text)
            if m:
                names = {t.strip() for t in m.group(1).split(",") if t.strip()}
                self.allow[i] = names

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def allowed(self, pass_name: str, lineno: int) -> bool:
        if self.skip:
            return True
        names = self.allow.get(lineno, ())
        return pass_name in names or "*" in names


class Project:
    """The analyzed tree: every parsed module under ``lir_tpu/`` (or the
    whole root for fixture mini-projects) plus root-level text files the
    config-drift pass reads (DEPLOY.md)."""

    def __init__(self, root: Path, modules: Sequence[Module]):
        self.root = root
        self.modules = list(modules)
        self._by_rel = {m.rel: m for m in self.modules}

    def module(self, rel: str) -> Optional[Module]:
        return self._by_rel.get(rel)

    def text(self, rel: str) -> Optional[str]:
        p = self.root / rel
        try:
            return p.read_text(encoding="utf-8")
        except OSError:
            return None


def load_project(root: Path) -> Project:
    """Parse the tree. Scans ``root/lir_tpu`` when present (the real
    repo — tests and tools are out of scope: fixtures SEED violations
    and tools are one-off host scripts), else every .py under ``root``
    (fixture mini-projects)."""
    root = Path(root).resolve()
    base = root / "lir_tpu" if (root / "lir_tpu").is_dir() else root
    modules: List[Module] = []
    for path in sorted(base.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        rel = path.relative_to(root).as_posix()
        modules.append(Module(path, rel, path.read_text(encoding="utf-8")))
    return Project(root, modules)


# ---------------------------------------------------------------------------
# AST helpers shared by the passes
# ---------------------------------------------------------------------------

def dotted(node: ast.AST) -> Optional[str]:
    """'a', 'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def terminal_name(func: ast.AST) -> Optional[str]:
    """The rightmost component of a call target: ``f`` for both ``f(...)``
    and ``mod.sub.f(...)`` — cross-module matching by convention (this
    codebase never reuses an exported callable name for something with
    different donation/trace semantics)."""
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def parent_map(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    parents: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def iter_functions(module: Module):
    """(qualname, FunctionDef) for every def in the module, with
    Class.method / outer.inner qualnames. Walked once a module and kept
    on it: the passes ask thousands of times a run."""
    found = getattr(module, "_functions", None)
    if found is not None:
        return found

    def walk(node: ast.AST, prefix: str):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}{child.name}"
                yield q, child
                yield from walk(child, f"{q}.")
            elif isinstance(child, ast.ClassDef):
                yield from walk(child, f"{prefix}{child.name}.")
            else:
                yield from walk(child, prefix)

    module._functions = found = list(walk(module.tree, ""))
    return found


def const_str_tuple(node: ast.AST) -> Tuple[str, ...]:
    """String constants out of a 'x' / ('x', 'y') / ['x'] node."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, str))
    return ()


def const_int_tuple(node: ast.AST) -> Tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, int))
    return ()


def arg_names(fn: ast.FunctionDef) -> List[str]:
    a = fn.args
    return ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
            + [p.arg for p in a.kwonlyargs])


# ---------------------------------------------------------------------------
# Pass registry + runner
# ---------------------------------------------------------------------------

class LintPass:
    """Base class: subclasses set ``name`` and implement ``run``."""

    name = "abstract"

    def run(self, project: Project) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError


def all_passes() -> List[LintPass]:
    # Imported lazily so ``from lir_tpu.lint import core`` never cycles.
    from . import (configdrift, donation, hostsync, locks, metricsdrift,
                   trace)

    return [donation.DonationPass(), trace.TraceHazardPass(),
            hostsync.HostSyncPass(), locks.LockDisciplinePass(),
            configdrift.ConfigDriftPass(),
            metricsdrift.MetricsDriftPass()]


ALL_PASSES = tuple(p.name for p in all_passes())


def run_passes(project: Project,
               only: Optional[Iterable[str]] = None) -> List[Finding]:
    """Run every (selected) pass, drop suppressed findings, sort."""
    selected = set(only) if only else None
    findings: List[Finding] = []
    for p in all_passes():
        if selected is not None and p.name not in selected:
            continue
        for f in p.run(project):
            mod = project.module(f.path)
            if mod is not None and mod.allowed(p.name, f.line):
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.pass_name, f.message))
    return findings


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------

BASELINE_VERSION = 1


def load_baseline(path: Path) -> Counter:
    """Fingerprint -> allowed count. Missing file = empty baseline."""
    try:
        data = json.loads(Path(path).read_text(encoding="utf-8"))
    except OSError:
        return Counter()
    allowed: Counter = Counter()
    for rec in data.get("findings", ()):
        fp: Fingerprint = (rec["pass"], rec["path"], rec["scope"],
                           rec["message"])
        allowed[fp] += int(rec.get("count", 1))
    return allowed


def save_baseline(path: Path, findings: Sequence[Finding]) -> None:
    counts: Counter = Counter(f.fingerprint for f in findings)
    recs = [{"pass": fp[0], "path": fp[1], "scope": fp[2], "message": fp[3],
             "count": n}
            for fp, n in sorted(counts.items())]
    Path(path).write_text(json.dumps(
        {"version": BASELINE_VERSION,
         "comment": "graft-lint baseline: pre-existing findings being "
                    "burned down. Never ADD entries to ship a new "
                    "violation — fix it or justify a # lint: allow "
                    "(DEPLOY.md §1i).",
         "findings": recs}, indent=2) + "\n", encoding="utf-8")


def diff_baseline(findings: Sequence[Finding], allowed: Counter
                  ) -> Tuple[List[Finding], int]:
    """(new findings, stale baseline entries). A fingerprint's findings
    beyond its baselined count are new; baseline entries with no live
    finding left are stale (burned down — prune with --write-baseline)."""
    remaining = Counter(allowed)
    new: List[Finding] = []
    for f in findings:
        if remaining[f.fingerprint] > 0:
            remaining[f.fingerprint] -= 1
        else:
            new.append(f)
    stale = sum(n for n in remaining.values() if n > 0)
    return new, stale
