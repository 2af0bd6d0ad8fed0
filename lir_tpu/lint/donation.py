"""donation-safety: no read of a binding after it was donated.

``jax.jit(..., donate_argnums=...)`` / ``donate_argnames=...`` hands the
argument's device buffer to XLA for reuse as an output buffer: the
moment the donating call dispatches, the caller's binding points at a
DELETED buffer, and touching it raises (best case) or — under the
engine's async dispatch chains — silently reads freed memory on a
runtime that doesn't check. The motivating surfaces are the
``CacheHandoff`` donation chain threaded through ``engine/runner.py``
and the page pool's donated ``scatter_pages`` (``models/paged.py``): a
refactor that innocently logs or re-dispatches a cache after handing it
off is exactly the class of bug the PR-5 guard layer only sees as a
runtime crash on device.

Mechanics (two phases, whole-project):

1. **Registry**: every ``FunctionDef`` whose decorators include
   ``jit``/``pjit`` (directly or via ``functools.partial``) with
   ``donate_argnames``/``donate_argnums`` is recorded with its donated
   parameter names/positions; ``name = jax.jit(fn, donate_argnums=...)``
   module-level assignments register under the ASSIGNED name too.
2. **Call-site scan**: in every function body, a call to a registered
   donor with a plain name (or dotted attribute) in a donated slot marks
   that binding dead from the call's line on; any later load of the same
   binding in the same function — without an intervening rebind — is a
   finding. ``x = f(x)`` rebinding on the donating statement itself is
   the sanctioned chain idiom and clears the binding.

The line-order approximation (source order stands in for control flow)
is deliberate: it is exact for the straight-line dispatch code this
engine writes, and a branch-heavy false positive is a ``# lint:
allow(donation-safety)`` with a justification — cheap next to a
use-after-donate on a pod.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from .core import (Finding, LintPass, Module, Project, arg_names,
                   const_int_tuple, const_str_tuple, dotted, iter_functions,
                   parent_map, terminal_name)

JIT_NAMES = {"jit", "pjit"}

# Donors the registry scan can't see syntactically: compile_plan.
# registry_call feeds its ``scratch_cache`` argument to a dispatch
# program (an AOT-compiled executable, or the lazy jit behind the same
# argument list) that donates it — the caller's scratch binding is dead
# afterwards.
EXTRA_DONORS = {
    "registry_call": ("run", "params", "args", "scratch_cache"),
}
EXTRA_DONATED = {"registry_call": {"scratch_cache"}}


@dataclasses.dataclass
class DonorSig:
    """A callable that donates some of its arguments."""

    name: str
    params: List[str]              # positional order, '' when unknown
    donated_names: Set[str]
    donated_positions: Set[int]

    def donated_param(self, index: int, keyword: Optional[str]
                      ) -> Optional[str]:
        """The donated parameter a call-site argument lands in, else
        None. ``index`` for positional args, ``keyword`` for keywords
        (``**kwargs`` splats pass keyword=None and never match — the
        dict binding itself is not the donated buffer)."""
        if keyword is not None:
            if keyword in self.donated_names:
                return keyword
            if self.params and keyword in self.params:
                if self.params.index(keyword) in self.donated_positions:
                    return keyword
            return None
        if index < 0:
            return None
        if index in self.donated_positions:
            return (self.params[index] if index < len(self.params)
                    else f"arg{index}")
        if self.params and index < len(self.params) \
                and self.params[index] in self.donated_names:
            return self.params[index]
        return None


def _donation_kwargs(call: ast.Call) -> Tuple[Set[str], Set[int]]:
    names: Set[str] = set()
    nums: Set[int] = set()
    for kw in call.keywords:
        if kw.arg == "donate_argnames":
            names |= set(const_str_tuple(kw.value))
        elif kw.arg == "donate_argnums":
            nums |= set(const_int_tuple(kw.value))
    return names, nums


def _jit_call_with_donation(node: ast.AST) -> Optional[Tuple[Set[str],
                                                             Set[int]]]:
    """``node`` is a Call of jit/pjit or partial(jit/pjit, ...) carrying
    donation kwargs -> (donated names, donated positions)."""
    if not isinstance(node, ast.Call):
        return None
    t = terminal_name(node.func)
    if t == "partial" and node.args:
        inner = terminal_name(node.args[0])
        if inner not in JIT_NAMES:
            return None
    elif t not in JIT_NAMES:
        return None
    names, nums = _donation_kwargs(node)
    if not names and not nums:
        return None
    return names, nums


def build_registry(project: Project) -> Dict[str, DonorSig]:
    """Donating callables by terminal name, across every module."""
    registry: Dict[str, DonorSig] = {}
    for mod in project.modules:
        defs = {q.rsplit(".", 1)[-1]: fn for q, fn in iter_functions(mod)}
        for q, fn in iter_functions(mod):
            for deco in fn.decorator_list:
                don = _jit_call_with_donation(deco)
                if don is not None:
                    names, nums = don
                    registry[fn.name] = DonorSig(
                        fn.name, arg_names(fn), set(names), set(nums))
        # name = jax.jit(fn, donate_argnums=...) assignments
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            target = node.targets[0]
            if not isinstance(target, ast.Name):
                continue
            don = _jit_call_with_donation(node.value)
            if don is None:
                continue
            names, nums = don
            wrapped = node.value
            params: List[str] = []
            if isinstance(wrapped, ast.Call) and wrapped.args:
                base = wrapped.args[0]
                if terminal_name(wrapped.func) == "partial" \
                        and len(wrapped.args) > 1:
                    base = wrapped.args[1]
                base_name = terminal_name(base)
                if base_name in defs:
                    params = arg_names(defs[base_name])
            registry[target.id] = DonorSig(target.id, params, set(names),
                                           set(nums))
    for name, params in EXTRA_DONORS.items():
        registry.setdefault(name, DonorSig(
            name, list(params), set(EXTRA_DONATED[name]), set()))
    return registry


class DonationPass(LintPass):
    name = "donation-safety"

    def run(self, project: Project) -> List[Finding]:
        registry = build_registry(project)
        if not registry:
            return []
        findings: List[Finding] = []
        for mod in project.modules:
            for qual, fn in iter_functions(mod):
                findings.extend(self._check_function(mod, qual, fn,
                                                     registry))
        return findings

    def _check_function(self, mod: Module, qual: str, fn: ast.FunctionDef,
                        registry: Dict[str, DonorSig]) -> List[Finding]:
        # Gather loads/stores of dotted bindings and donation events, all
        # keyed by line (source order approximates control flow; see
        # module docstring). Nested defs are checked separately — skip
        # their bodies here.
        findings: List[Finding] = []
        nested: Set[int] = set()
        for child in ast.walk(fn):
            if child is fn:
                continue
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nested.update(id(n) for n in ast.walk(child))
        parents = parent_map(fn)
        loads: List[Tuple[str, int, ast.AST]] = []
        stores: List[Tuple[str, int, ast.AST]] = []
        events: List[Tuple[str, str, str, int, int, ast.AST]] = []
        for node in ast.walk(fn):
            if id(node) in nested and node is not fn:
                continue
            if isinstance(node, (ast.Name, ast.Attribute)):
                path = dotted(node)
                if path is None:
                    continue
                ctx = getattr(node, "ctx", None)
                if isinstance(ctx, ast.Store):
                    stores.append((path, node.lineno, node))
                elif isinstance(ctx, ast.Load):
                    loads.append((path, node.lineno, node))
                continue
            if isinstance(node, ast.Call):
                callee = terminal_name(node.func)
                sig = registry.get(callee or "")
                if sig is None:
                    continue
                end = getattr(node, "end_lineno", node.lineno)
                for i, arg in enumerate(node.args):
                    param = sig.donated_param(i, None)
                    path = dotted(arg)
                    if param and path:
                        events.append((path, param, callee, node.lineno,
                                       end, node))
                for kw in node.keywords:
                    if kw.arg is None:       # **splat: not a donated slot
                        continue
                    param = sig.donated_param(-1, kw.arg)
                    path = dotted(kw.value)
                    if param and path:
                        events.append((path, param, callee, node.lineno,
                                       end, node))
        for path, param, callee, line, end, call_node in events:
            # A rebind on/after the donating statement revives the name
            # (the x = f(x) chain idiom assigns AFTER the call returns).
            rebinds = sorted(
                l for p, l, n in stores
                if p == path and l >= line
                and not _exclusive_branches(call_node, n, parents))
            for lpath, lline, lnode in sorted(loads, key=lambda t: t[1]):
                if lpath != path or lline <= end:
                    continue
                if rebinds and rebinds[0] <= lline:
                    break
                if _exclusive_branches(call_node, lnode, parents):
                    continue      # read sits in the sibling if/else arm
                if _identity_use(lnode, parents):
                    continue      # `x is None` touches the ref, not the
                    #               dead buffer
                findings.append(Finding(
                    self.name, mod.rel, lline, qual,
                    f"'{path}' is read after being donated to "
                    f"{callee}() (parameter '{param}') — the buffer is "
                    f"dead once the donating call dispatches; rebind the "
                    f"name from the call's result or drop the read"))
                break          # one finding per donation event
        return findings


def _branch_chain(node: ast.AST, parents: Dict[ast.AST, ast.AST]
                  ) -> Dict[int, str]:
    """{id(if_stmt): arm} for every enclosing If — 'body' or 'orelse'."""
    chain: Dict[int, str] = {}
    cur = node
    parent = parents.get(cur)
    while parent is not None:
        if isinstance(parent, ast.If):
            in_body = any(cur is s or any(cur is w for w in ast.walk(s))
                          for s in parent.body)
            chain[id(parent)] = "body" if in_body else "orelse"
        cur, parent = parent, parents.get(parent)
    return chain


def _exclusive_branches(a: ast.AST, b: ast.AST,
                        parents: Dict[ast.AST, ast.AST]) -> bool:
    """True when ``a`` and ``b`` sit in different arms of a shared If —
    line order lies about reachability there."""
    ca, cb = _branch_chain(a, parents), _branch_chain(b, parents)
    return any(ca[k] != cb[k] for k in ca.keys() & cb.keys())


def _identity_use(node: ast.AST, parents: Dict[ast.AST, ast.AST]) -> bool:
    """The load only feeds an ``is``/``is not`` test: identity checks
    touch the python reference, never the (dead) device buffer."""
    parent = parents.get(node)
    cur = node
    while parent is not None and not isinstance(parent, ast.stmt):
        if isinstance(parent, ast.Compare) \
                and all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in parent.ops):
            return True
        cur, parent = parent, parents.get(parent)
    return False
