"""DT2xx — recompile hazards.

A jitted function that closes over mutable state, branches in Python on a
traced value, or is rebuilt per iteration silently retraces; on TPU that is
seconds of XLA compile in the middle of serving.  These rules target the
trap shapes this repo has actually hit (MULTICHIP logs, start-up probes).
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, Set

from .core import Finding, ModuleContext, Rule

_MUTABLE_CALLS = {"dict", "list", "set", "bytearray",
                  "collections.defaultdict", "collections.deque",
                  "collections.OrderedDict", "collections.Counter"}
_SAFE_ATTRS = {"shape", "ndim", "dtype", "size", "sharding"}
_SAFE_CALLS = {"len", "isinstance", "hasattr", "getattr", "type"}


def _module_mutables(ctx: ModuleContext) -> Set[str]:
    """Module-level names bound to mutable containers."""
    out: Set[str] = set()
    for stmt in ctx.tree.body:
        targets = []
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        else:
            continue
        mutable = isinstance(value, (ast.List, ast.Dict, ast.Set,
                                     ast.ListComp, ast.DictComp, ast.SetComp))
        if isinstance(value, ast.Call):
            mutable = (ctx.call_name(value) or "") in _MUTABLE_CALLS
        if not mutable:
            continue
        for t in targets:
            if isinstance(t, ast.Name):
                out.add(t.id)
    return out


def _local_bindings(func: ast.AST) -> Set[str]:
    """Names bound inside ``func`` (params, assignments, nested defs)."""
    args = func.args
    names = {a.arg for a in (args.posonlyargs + args.args + args.kwonlyargs)}
    if args.vararg:
        names.add(args.vararg.arg)
    if args.kwarg:
        names.add(args.kwarg.arg)
    for node in ast.walk(func):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            names.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)) and node is not func:
            names.add(node.name)
    return names


class JitMutableClosure(Rule):
    code = "DT201"
    name = "jit-mutable-closure"
    rationale = ("a jitted function reading mutable module state bakes the "
                 "traced snapshot in — later mutations are silently ignored "
                 "or force retraces")

    def visit_module(self, ctx: ModuleContext) -> Iterator[Finding]:
        mutables = _module_mutables(ctx)
        for func in ctx.jit_targets:
            local = _local_bindings(func)
            seen: Set[str] = set()
            for node in ast.walk(func):
                if isinstance(node, ast.Global):
                    yield ctx.finding(
                        self.code, node,
                        "`global` inside a jitted function: writes happen "
                        "at trace time, not per call")
                elif (isinstance(node, ast.Name)
                      and isinstance(node.ctx, ast.Load)
                      and node.id in mutables
                      and node.id not in local
                      and node.id not in seen):
                    seen.add(node.id)
                    yield ctx.finding(
                        self.code, node,
                        f"jitted function reads mutable module global "
                        f"`{node.id}`; its value is frozen at trace time — "
                        "pass it as an argument or make it immutable")


class TracerBranch(Rule):
    code = "DT202"
    name = "tracer-branch"
    rationale = ("Python `if`/`while` on a traced argument either crashes at "
                 "trace time or forks one compilation per value")

    def _offending_names(self, ctx: ModuleContext, test: ast.AST,
                         traced: Set[str]) -> Set[str]:
        bad: Set[str] = set()
        for node in ast.walk(test):
            if not (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in traced):
                continue
            parent = ctx.parents.get(node)
            # x.shape / x.ndim / x.dtype are static under tracing
            if isinstance(parent, ast.Attribute) and \
                    parent.attr in _SAFE_ATTRS:
                continue
            # len(x), isinstance(x, T), type(x) are host-side
            if isinstance(parent, ast.Call) and \
                    (ctx.call_name(parent) or "") in _SAFE_CALLS:
                continue
            # `x is None` / `x is not None` never touches the tracer value
            comp = parent
            while comp is not None and not isinstance(comp, ast.Compare):
                if isinstance(comp, (ast.Lambda, ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    comp = None
                    break
                comp = ctx.parents.get(comp)
            if isinstance(comp, ast.Compare) and \
                    all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in comp.ops):
                continue
            bad.add(node.id)
        return bad

    def visit_module(self, ctx: ModuleContext) -> Iterator[Finding]:
        for func in ctx.jit_targets:
            traced = ctx.traced_params(func)
            if not traced:
                continue
            for node in ast.walk(func):
                if not isinstance(node, (ast.If, ast.While, ast.IfExp)):
                    continue
                bad = self._offending_names(ctx, node.test, traced)
                if bad:
                    names = ", ".join(f"`{n}`" for n in sorted(bad))
                    yield ctx.finding(
                        self.code, node,
                        f"Python branch on traced argument(s) {names} inside "
                        "a jitted function; use jnp.where/lax.cond or mark "
                        "the argument static")


class JitInLoop(Rule):
    code = "DT203"
    name = "jit-in-loop"
    rationale = ("`jax.jit(...)` constructed inside a loop makes a fresh "
                 "cache per iteration — every call recompiles")

    def visit_module(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and ctx.call_name(node) in ("jax.jit", "jax.pjit")):
                continue
            cur = ctx.parents.get(node)
            while cur is not None and not isinstance(
                    cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if isinstance(cur, (ast.For, ast.While, ast.AsyncFor)):
                    yield ctx.finding(
                        self.code, node,
                        "`jax.jit` built inside a loop: each wrapper has an "
                        "empty compile cache — hoist it out of the loop")
                    break
                cur = ctx.parents.get(cur)


RULES = [JitMutableClosure(), TracerBranch(), JitInLoop()]
