"""host-sync: no implicit device→host transfer on the hot path.

JAX dispatch is asynchronous: the sweep/serve loops stay ahead of the
device only while nothing host-side touches a live device value. An
innocent ``np.asarray(x)``, ``float(x)``, ``x.tolist()``, ``if x:`` or
per-element iteration BLOCKS the dispatching thread until the device
catches up — the exact stall class VERDICT r2 measured as the sweep
running at 49% of the isolated scoring rate before the writer thread
split. The sanctioned pattern is one EXPLICIT ``jax.device_get`` at a
readout boundary (off the dispatch thread where possible), then pure
host work on the result.

Scope: the hot-path modules only — ``lir_tpu/engine/``, ``lir_tpu/ops/``
and ``lir_tpu/serve/batcher.py``. Statistics, report, survey and CLI
code sync freely.

Taint: a value is "device" when it flows from a ``jnp.``/``jax.lax.``/
``jax.nn.``/``jax.random.`` call, from a function this project jits
(shared registry with the donation pass), or from one of the engine's
dispatch entry points (:data:`DEVICE_FNS`). ``jax.device_get(...)``
(and ``np.asarray`` itself — flagged once) launder the result back to
host. Taint follows assignments, tuple unpacking, attribute/subscript
access, and same-module calls (a helper called with a device row is
analyzed with that parameter tainted — that is how the reference's
"decode one row at a time straight off the device" bugs get caught at
the helper's ``np.asarray``).

Allowlist for legitimate boundaries: decorate the function with
``@host_readout`` (``lir_tpu/utils/annotations.py``) or put ``# lint:
allow(host-sync)`` on the line; both carry an implicit "this is a
deliberate sync point" claim reviewers can see.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import (Finding, LintPass, Module, Project, arg_names, dotted,
                   iter_functions, parent_map, terminal_name)

HOT_DIRS = ("lir_tpu/engine/", "lir_tpu/ops/")
HOT_FILES = ("lir_tpu/serve/batcher.py",)

DEVICE_PREFIXES = ("jnp.", "jax.lax.", "jax.nn.", "jax.random.", "lax.")
# Engine entry points that return live device values (codebase-specific
# table — the passes are allowed to know this repo).
DEVICE_FNS = {
    "decode_fused", "decode_fused_shared", "decode_fused_grouped",
    "decode_fused_shared_piggy", "piggy_drain", "prefill",
    "readout_from_fused", "readout_from_step_logits", "sample_decode",
    "greedy_decode_dispatch",
    "gather_slots", "scatter_pages", "flash_attention", "flash_decode",
    # Streaming-statistics sink (engine/stream_stats.py): the fold
    # update returns the live device accumulator; touching it host-side
    # anywhere but an explicit snapshot() readout is the per-row sync
    # the sink exists to eliminate. (Redundant with the jitted-def
    # registry while fold_update keeps its jax.jit decorator — pinned
    # here so renaming the decorator can't silently drop coverage.)
    "fold_update",
}
LAUNDER_FNS = {"device_get", "block_until_ready"}
NP_TRANSFER = {"asarray", "array", "ascontiguousarray"}
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval",
                "weak_type", "nbytes"}
CONCRETIZING_METHODS = {"item", "tolist"}
COERCIONS = {"int", "bool", "float"}
READOUT_DECORATOR = "host_readout"
MAX_ROUNDS = 6


def _jitted_def_names(project: Project) -> Set[str]:
    from .donation import JIT_NAMES  # same decorator grammar

    names: Set[str] = set()
    for mod in project.modules:
        for q, fn in iter_functions(mod):
            for deco in fn.decorator_list:
                call = deco if isinstance(deco, ast.Call) else None
                t = terminal_name(call.func if call else deco)
                if t == "partial" and call and call.args:
                    if terminal_name(call.args[0]) in JIT_NAMES:
                        names.add(fn.name)
                elif t in JIT_NAMES:
                    names.add(fn.name)
    return names


def _is_hot(rel: str) -> bool:
    return rel.startswith(HOT_DIRS) or rel in HOT_FILES


def _has_readout_decorator(fn: ast.FunctionDef) -> bool:
    for deco in fn.decorator_list:
        if terminal_name(deco if not isinstance(deco, ast.Call)
                         else deco.func) == READOUT_DECORATOR:
            return True
    return False


class _Scan:
    def __init__(self, pass_name: str, mod: Module, qual: str,
                 fn: ast.FunctionDef, tainted: Set[str],
                 device_calls: Set[str]):
        self.pass_name = pass_name
        self.mod = mod
        self.qual = qual
        self.fn = fn
        self.tainted = set(tainted)
        self.device_calls = device_calls
        self.parents = parent_map(fn)
        self.findings: List[Finding] = []
        self.flagged_lines: Set[int] = set()
        self.propagations: List[Tuple[str, Dict[int, bool],
                                      Dict[str, bool]]] = []

    def _is_device_call(self, call: ast.Call) -> bool:
        path = dotted(call.func)
        if path and path.startswith(DEVICE_PREFIXES):
            return True
        name = terminal_name(call.func)
        return name in self.device_calls

    def _is_launder_call(self, call: ast.Call) -> bool:
        return terminal_name(call.func) in LAUNDER_FNS

    def _is_static_use(self, node: ast.AST) -> bool:
        parent = self.parents.get(node)
        cur = node
        while parent is not None and not isinstance(parent, ast.stmt):
            if isinstance(parent, ast.Attribute) and parent.value is cur \
                    and parent.attr in STATIC_ATTRS:
                return True
            if isinstance(parent, ast.Compare) \
                    and all(isinstance(op, (ast.Is, ast.IsNot))
                            for op in parent.ops):
                return True
            if isinstance(parent, ast.Call) \
                    and terminal_name(parent.func) in {"len", "isinstance",
                                                       "id", "type", "repr"}:
                return True
            cur, parent = parent, self.parents.get(parent)
        return False

    def _tainted_names(self, expr: ast.AST) -> List[ast.Name]:
        # Names nested inside OTHER calls don't count: ``f(x)`` on a
        # device value usually returns host data (metadata probes,
        # decode helpers) — if ``f`` itself produces device values it is
        # in the device-call table and ``_expr_device`` covers it. A
        # laundering call likewise cleans its own subtree.
        shielded: Set[int] = set()
        for n in ast.walk(expr):
            if not isinstance(n, ast.Call):
                continue
            if self._is_launder_call(n) or not self._is_device_call(n):
                shielded.update(id(x) for x in ast.walk(n))
                shielded.discard(id(n))    # the call node itself may
                #                            still be judged by
                #                            _expr_device
        return [n for n in ast.walk(expr)
                if isinstance(n, ast.Name) and n.id in self.tainted
                and isinstance(n.ctx, ast.Load)
                and id(n) not in shielded
                and not self._is_static_use(n)]

    def _expr_device(self, expr: ast.AST) -> bool:
        """Expression yields a device value: tainted name, or a direct
        device-producing call."""
        if isinstance(expr, ast.Call):
            # A call either produces device values (table/prefix match)
            # or it doesn't — device args to an unknown host function do
            # NOT make its RESULT device (decode helpers, metadata
            # probes return host data; the sync, if any, is inside the
            # callee, which the cross-function propagation analyzes with
            # the tainted parameter).
            return (self._is_device_call(expr)
                    and not self._is_launder_call(expr))
        if isinstance(expr, (ast.Attribute, ast.Subscript)):
            return self._expr_device(expr.value)
        if isinstance(expr, (ast.Tuple, ast.List)):
            return any(self._expr_device(e) for e in expr.elts)
        if isinstance(expr, ast.BinOp):
            return (self._expr_device(expr.left)
                    or self._expr_device(expr.right))
        if isinstance(expr, ast.Name):
            return (expr.id in self.tainted
                    and not self._is_static_use(expr))
        return bool(self._tainted_names(expr))

    def _flag(self, line: int, message: str) -> None:
        if line in self.flagged_lines:
            return
        self.flagged_lines.add(line)
        self.findings.append(Finding(self.pass_name, self.mod.rel, line,
                                     self.qual, message))

    def scan(self, module_defs: Dict[str, ast.FunctionDef]) -> None:
        nested: Set[int] = set()
        for child in ast.walk(self.fn):
            if child is not self.fn and isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nested.update(id(n) for n in ast.walk(child))
        nodes = [n for n in ast.walk(self.fn) if id(n) not in nested]
        nodes.sort(key=lambda n: (getattr(n, "lineno", 0),
                                  getattr(n, "col_offset", 0)))
        for node in nodes:
            if isinstance(node, ast.Assign):
                value_device = self._expr_device(node.value)
                laundered = (isinstance(node.value, ast.Call)
                             and self._is_launder_call(node.value))
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            if value_device and not laundered:
                                self.tainted.add(n.id)
                            else:
                                self.tainted.discard(n.id)
            elif isinstance(node, (ast.If, ast.While)):
                hits = self._tainted_names(node.test)
                if hits:
                    self._flag(hits[0].lineno,
                               f"truthiness of device value "
                               f"'{hits[0].id}' blocks on the device — "
                               f"jax.device_get at an explicit readout "
                               f"boundary first")
            elif isinstance(node, ast.For):
                hits = self._tainted_names(node.iter)
                if hits:
                    self._flag(node.lineno,
                               f"python iteration over device value "
                               f"'{hits[0].id}' synchronizes per element "
                               f"— device_get the whole array once")
            elif isinstance(node, ast.Call):
                self._check_call(node, module_defs)

    def _check_call(self, call: ast.Call,
                    module_defs: Dict[str, ast.FunctionDef]) -> None:
        func = call.func
        name = terminal_name(func)
        path = dotted(func) or ""
        if path.startswith(("np.", "numpy.")) and name in NP_TRANSFER:
            for arg in call.args[:1]:
                hits = self._tainted_names(arg)
                if hits or self._expr_device(arg):
                    label = hits[0].id if hits else (dotted(arg) or "<expr>")
                    self._flag(call.lineno,
                               f"np.{name}() on device value '{label}' is "
                               f"an implicit device→host transfer — use "
                               f"jax.device_get at an explicit readout "
                               f"boundary")
                    return
        if isinstance(func, ast.Name) and name in COERCIONS:
            for arg in call.args:
                hits = self._tainted_names(arg)
                if hits or self._expr_device(arg):
                    label = hits[0].id if hits else (dotted(arg) or "<expr>")
                    self._flag(call.lineno,
                               f"{name}() on device value '{label}' "
                               f"synchronizes the dispatch thread — "
                               f"device_get first")
                    return
        if isinstance(func, ast.Attribute) \
                and func.attr in CONCRETIZING_METHODS:
            base_hits = self._tainted_names(func.value)
            if base_hits or self._expr_device(func.value):
                label = (base_hits[0].id if base_hits
                         else (dotted(func.value) or "<expr>"))
                self._flag(call.lineno,
                           f".{func.attr}() on device value '{label}' is "
                           f"an implicit device→host transfer — "
                           f"device_get first")
                return
        if isinstance(func, ast.Name) and name in module_defs:
            by_pos = {i: True for i, a in enumerate(call.args)
                      if self._tainted_names(a) or self._expr_device(a)}
            by_kw = {kw.arg: True for kw in call.keywords
                     if kw.arg and (self._tainted_names(kw.value)
                                    or self._expr_device(kw.value))}
            if by_pos or by_kw:
                self.propagations.append((name, by_pos, by_kw))


class HostSyncPass(LintPass):
    name = "host-sync"

    def run(self, project: Project) -> List[Finding]:
        device_calls = set(DEVICE_FNS) | _jitted_def_names(project)
        findings: List[Finding] = []
        for mod in project.modules:
            if not _is_hot(mod.rel):
                continue
            findings.extend(self._run_module(mod, device_calls))
        return findings

    def _run_module(self, mod: Module, device_calls: Set[str]
                    ) -> List[Finding]:
        defs: Dict[str, ast.FunctionDef] = {}
        quals: Dict[str, str] = {}
        skip: Set[str] = set()
        for q, fn in iter_functions(mod):
            defs.setdefault(fn.name, fn)
            quals.setdefault(fn.name, q)
            if _has_readout_decorator(fn):
                skip.add(fn.name)
        tainted: Dict[str, Set[str]] = {name: set() for name in defs}
        findings: List[Finding] = []
        seen: Dict[str, frozenset] = {}
        for _ in range(MAX_ROUNDS):
            frontier = {n: p for n, p in tainted.items()
                        if seen.get(n) != frozenset(p)}
            if not frontier:
                break
            round_findings: List[Finding] = []
            grew: Dict[str, Set[str]] = {}
            for name, params in sorted(frontier.items()):
                seen[name] = frozenset(params)
                if name in skip:
                    continue
                scan = _Scan(self.name, mod, quals[name], defs[name],
                             params, device_calls)
                scan.scan(defs)
                round_findings.extend(scan.findings)
                for callee, by_pos, by_kw in scan.propagations:
                    target = defs.get(callee)
                    if target is None:
                        continue
                    names = arg_names(target)
                    marked = grew.setdefault(
                        callee, set(tainted.get(callee, set())))
                    for i in by_pos:
                        if i < len(names):
                            marked.add(names[i])
                    for kw in by_kw:
                        if kw in names:
                            marked.add(kw)
            findings = [f for f in findings
                        if f.scope not in {quals[n] for n in frontier}]
            findings.extend(round_findings)
            for name, params in grew.items():
                tainted[name] = set(tainted.get(name, set())) | params
        return findings
