"""dynalint framework: module model, suppression parsing, rule runner.

Everything here is plain ``ast`` — no imports of the analyzed code, so the
linter runs in milliseconds, needs no devices, and can never be broken by
an import-time side effect in the code under analysis.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

# ----------------------------------------------------------------------------
# configuration


@dataclass(frozen=True)
class AnalysisConfig:
    """Scoping knobs for the rule pipeline.

    ``hot_modules`` are repo-relative posix prefixes whose *function bodies*
    are hot-path scope for the DT1xx rules even without ``@hot_path``; the
    decorator extends scope anywhere else.  ``layout_modules`` are the only
    places DT5xx lets mesh axis names / ``Mesh`` construction live.
    """

    root: Path = Path(".")
    # the serving hot loop: kernels, the JAX engine, the scheduler.  Cold
    # engine modules (weights loading, the kernel's parity gate, config)
    # stay out so a checkpoint load is not "a host sync in the decode loop".
    hot_modules: Tuple[str, ...] = (
        "dynamo_tpu/ops/",
        "dynamo_tpu/engine/engine.py",
        "dynamo_tpu/engine/model.py",
        "dynamo_tpu/engine/scheduler.py",
        "dynamo_tpu/spec/",
    )
    layout_modules: Tuple[str, ...] = ("dynamo_tpu/parallel/layout.py",)
    # canonical mesh axis vocabulary DT501 polices (SNIPPETS.md [3] layout)
    axis_names: Tuple[str, ...] = ("dp", "tp", "fsdp", "sp", "ep", "data")


# ----------------------------------------------------------------------------
# findings


@dataclass
class Finding:
    code: str          # e.g. "DT102"
    path: str          # repo-relative posix path
    line: int
    col: int
    message: str
    symbol: str = "<module>"   # enclosing function qualname
    snippet: str = ""          # stripped source line (baseline fingerprint)

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.code} "
                f"[{self.symbol}] {self.message}")


# ----------------------------------------------------------------------------
# suppressions

_SUPPRESS_RE = re.compile(
    r"#\s*dynalint:\s*(disable|disable-next-line)\s*=\s*"
    r"(all|DT[0-9]{3}(?:\s*,\s*DT[0-9]{3})*)"
)


def parse_suppressions(source_lines: Sequence[str]) -> Dict[int, Set[str]]:
    """Map 1-based line number -> set of suppressed codes ("all" wildcard)."""
    out: Dict[int, Set[str]] = {}
    for i, text in enumerate(source_lines, start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        target = i + 1 if m.group(1) == "disable-next-line" else i
        codes = {c.strip() for c in m.group(2).split(",")}
        out.setdefault(target, set()).update(codes)
    return out


# ----------------------------------------------------------------------------
# module context

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)


@dataclass
class JitInfo:
    """How a function reaches ``jax.jit`` and which params escape tracing."""

    static_names: Set[str] = field(default_factory=set)
    static_nums: Set[int] = field(default_factory=set)
    n_bound: int = 0  # leading params pre-bound by functools.partial (consts)
    # donated buffers (jit-callable indices — add n_bound to map to params)
    donate_names: Set[str] = field(default_factory=set)
    donate_nums: Set[int] = field(default_factory=set)
    # node findings anchor to: the jit call site, or the def for decorators
    site: Optional[ast.AST] = None


class ModuleContext:
    """One parsed module plus the derived maps every rule needs."""

    def __init__(self, path: str, source: str, config: AnalysisConfig):
        self.path = path  # repo-relative posix
        self.config = config
        self.source_lines = source.splitlines()
        self.tree = ast.parse(source)
        self.suppressions = parse_suppressions(self.source_lines)
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        self.aliases = self._collect_aliases()
        self.jit_targets: Dict[ast.AST, JitInfo] = {}
        self._collect_jit_targets()
        self._module_is_hot = any(
            path.startswith(prefix) or path == prefix.rstrip("/")
            for prefix in config.hot_modules
        )
        self.is_layout_module = path in config.layout_modules

    # ------------------------------- names ------------------------------

    def _collect_aliases(self) -> Dict[str, str]:
        aliases: Dict[str, str] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    if a.name == "*":
                        continue
                    aliases[a.asname or a.name] = f"{node.module}.{a.name}"
        return aliases

    def dotted(self, node: ast.AST) -> Optional[str]:
        """Resolve a Name/Attribute chain to a canonical dotted path."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        head = self.aliases.get(node.id, node.id)
        parts.append(head)
        return ".".join(reversed(parts))

    def call_name(self, call: ast.Call) -> Optional[str]:
        return self.dotted(call.func)

    # ------------------------------ scoping -----------------------------

    def enclosing_functions(self, node: ast.AST) -> List[ast.AST]:
        """Innermost-first chain of enclosing function defs."""
        out = []
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, _FUNC_NODES):
                out.append(cur)
            cur = self.parents.get(cur)
        return out

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        chain = self.enclosing_functions(node)
        return chain[0] if chain else None

    def qualname(self, node: ast.AST) -> str:
        names = []
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, _FUNC_NODES + (ast.ClassDef,)):
                names.append(cur.name)
            cur = self.parents.get(cur)
        return ".".join(reversed(names)) or "<module>"

    def _decorated_hot(self, func: ast.AST) -> bool:
        for dec in getattr(func, "decorator_list", []):
            target = dec.func if isinstance(dec, ast.Call) else dec
            name = self.dotted(target) or ""
            if name == "hot_path" or name.endswith(".hot_path"):
                return True
        return False

    def hot_scope(self, node: ast.AST) -> bool:
        """True when ``node`` sits inside a function on the hot path."""
        chain = self.enclosing_functions(node)
        if isinstance(node, _FUNC_NODES):
            chain = [node] + chain
        if not chain:
            return False  # module level runs at import time — cold
        if self._module_is_hot:
            return True
        return any(self._decorated_hot(f) for f in chain)

    def in_async(self, node: ast.AST) -> bool:
        """True when ``node``'s innermost enclosing function is a coroutine."""
        fn = self.enclosing_function(node)
        return isinstance(fn, ast.AsyncFunctionDef)

    # ---------------------------- jit targets ---------------------------

    def _jit_statics(self, call: ast.Call) -> JitInfo:
        info = JitInfo()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and isinstance(c.value, str):
                        info.static_names.add(c.value)
            elif kw.arg == "static_argnums":
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and isinstance(c.value, int):
                        info.static_nums.add(c.value)
            elif kw.arg == "donate_argnames":
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and isinstance(c.value, str):
                        info.donate_names.add(c.value)
            elif kw.arg == "donate_argnums":
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and isinstance(c.value, int):
                        info.donate_nums.add(c.value)
        return info

    def _is_jit_name(self, node: ast.AST) -> bool:
        name = self.dotted(node)
        return name in ("jax.jit", "jit", "jax.pjit", "pjit")

    def _collect_jit_targets(self) -> None:
        # local function name -> def node (module and class level)
        defs: Dict[str, ast.AST] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, _FUNC_NODES):
                defs.setdefault(node.name, node)

        for node in ast.walk(self.tree):
            # @jax.jit / @functools.partial(jax.jit, static_argnames=...)
            if isinstance(node, _FUNC_NODES):
                for dec in node.decorator_list:
                    if self._is_jit_name(dec):
                        self.jit_targets[node] = JitInfo(site=node)
                    elif isinstance(dec, ast.Call):
                        if self._is_jit_name(dec.func):
                            self.jit_targets[node] = self._jit_statics(dec)
                            self.jit_targets[node].site = node
                        elif (self.dotted(dec.func) == "functools.partial"
                              and dec.args
                              and self._is_jit_name(dec.args[0])):
                            self.jit_targets[node] = self._jit_statics(dec)
                            self.jit_targets[node].site = node
            # jax.jit(f, ...) / jax.jit(functools.partial(f, cfg), ...)
            elif isinstance(node, ast.Call) and self._is_jit_name(node.func):
                if not node.args:
                    continue
                info = self._jit_statics(node)
                info.site = node
                target = node.args[0]
                if (isinstance(target, ast.Call)
                        and self.dotted(target.func) == "functools.partial"
                        and target.args
                        and isinstance(target.args[0], ast.Name)):
                    # partial-bound leading args are Python constants, not
                    # tracers — branching on them never retraces
                    info.n_bound = len(target.args) - 1
                    target = target.args[0]
                elif (isinstance(target, ast.Call)
                      and isinstance(target.func, ast.Name)
                      and target.func.id in defs):
                    # jax.jit(raw_X(...)): the factory-call idiom — the
                    # jitted callable is the inner def the factory returns,
                    # and donate/static indices address ITS signature
                    inner = self._factory_inner(defs[target.func.id])
                    if inner is not None:
                        self.jit_targets.setdefault(inner, info)
                    continue
                if isinstance(target, ast.Name) and target.id in defs:
                    self.jit_targets.setdefault(defs[target.id], info)

    def _factory_inner(self, factory: ast.AST) -> Optional[ast.AST]:
        """The inner def a factory returns (``def make(): def f(..) ...;
        return f``), or None when the return is anything more clever."""
        inner = {n.name: n for n in factory.body
                 if isinstance(n, _FUNC_NODES)}
        for stmt in factory.body:
            if (isinstance(stmt, ast.Return)
                    and isinstance(stmt.value, ast.Name)
                    and stmt.value.id in inner):
                return inner[stmt.value.id]
        return None

    def traced_params(self, func: ast.AST) -> Set[str]:
        """Parameter names of a jit target that are traced (non-static)."""
        info = self.jit_targets.get(func)
        if info is None:
            return set()
        args = func.args
        names = [a.arg for a in args.posonlyargs + args.args]
        traced = set()
        for i, name in enumerate(names):
            if i < info.n_bound or i in info.static_nums:
                continue
            if name in info.static_names:
                continue
            traced.add(name)
        traced.update(a.arg for a in args.kwonlyargs
                      if a.arg not in info.static_names)
        return traced

    # ---------------------------- reporting -----------------------------

    def finding(self, code: str, node: ast.AST, message: str) -> Finding:
        line = getattr(node, "lineno", 1)
        snippet = ""
        if 1 <= line <= len(self.source_lines):
            snippet = self.source_lines[line - 1].strip()
        return Finding(
            code=code, path=self.path, line=line,
            col=getattr(node, "col_offset", 0), message=message,
            symbol=self.qualname(node), snippet=snippet,
        )

    def suppressed(self, f: Finding) -> bool:
        codes = self.suppressions.get(f.line)
        return bool(codes) and ("all" in codes or f.code in codes)


# ----------------------------------------------------------------------------
# rules + runner


class Rule:
    """One lint rule: a code, a one-line rationale, and a module visitor."""

    code: str = "DT000"
    name: str = "abstract"
    rationale: str = ""

    def visit_module(self, ctx: ModuleContext) -> Iterator[Finding]:
        raise NotImplementedError


def iter_python_files(paths: Iterable[Path]) -> Iterator[Path]:
    for p in paths:
        p = Path(p)
        if p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if "__pycache__" not in f.parts:
                    yield f
        elif p.suffix == ".py":
            yield p


def analyze_source(
    source: str,
    path: str,
    rules: Sequence[Rule],
    config: Optional[AnalysisConfig] = None,
    respect_suppressions: bool = True,
) -> List[Finding]:
    """Run ``rules`` over one module's source; the test-fixture entry point."""
    config = config or AnalysisConfig()
    try:
        ctx = ModuleContext(path, source, config)
    except SyntaxError as e:
        return [Finding("DT001", path, e.lineno or 1, e.offset or 0,
                        f"syntax error: {e.msg}")]
    findings: List[Finding] = []
    for rule in rules:
        for f in rule.visit_module(ctx):
            if respect_suppressions and ctx.suppressed(f):
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.code))
    return findings


def run_paths(
    paths: Iterable[Path],
    rules: Sequence[Rule],
    config: Optional[AnalysisConfig] = None,
) -> List[Finding]:
    config = config or AnalysisConfig()
    root = Path(config.root).resolve()
    findings: List[Finding] = []
    for file in iter_python_files(paths):
        try:
            rel = file.resolve().relative_to(root).as_posix()
        except ValueError:
            rel = file.as_posix()
        findings.extend(analyze_source(
            file.read_text(encoding="utf-8"), rel, rules, config))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.code))
    return findings
