"""Visitor-based static lint over ``src/repro/**`` — jax/pallas rules.

The rule engine parses each file once, computes the module's JIT REGIONS
(functions that end up traced: passed to ``jax.jit`` / ``jax.lax.scan`` /
``jax.vmap`` / friends, possibly wrapped in ``functools.partial`` or a
local adapter, or decorated with ``@jax.jit``; plus every function nested
inside one), runs a lightweight name-taint pass over each region (region
parameters and anything assigned from them are "traced"; ``.shape`` /
``.dtype`` / ``.ndim`` / ``.size`` accesses and ``is (not) None`` checks
are pruned as trace-time static), and then applies the rules below.

Rules (suppress a line with ``# analysis: disable=ID`` or ``=all``; a
``# analysis: disable-file=ID`` directive in the first 10 lines suppresses
the whole file):

====== ===================================================================
XH101  tracer leak: ``int()``/``float()``/``bool()`` on a traced value
       inside a jit region — concretizes the tracer (works only at trace
       time, silently bakes in a constant) or raises under jit.
XH102  tracer leak: ``.item()`` / ``.tolist()`` on a traced value inside a
       jit region — forces a host sync / concretization.
XH103  tracer leak: Python ``if``/``while``/conditional expression on a
       traced value inside a jit region — control flow must be
       ``jnp.where`` / ``lax.cond`` / ``lax.select``; a Python branch on a
       tracer either retraces per value or raises.
XH201  dtype drift: ``jnp.zeros``/``ones``/``arange``/``full``/``empty``
       without an explicit dtype in kernels/ or serve/ — the default dtype
       follows the x64 flag and platform, so numerics (and trace cache
       keys) can drift between hosts. Scoped to the paths where bitwise
       identity contracts live.
XH301  host sync inside a jit region: ``np.asarray``/``np.array`` on a
       traced value, ``jax.device_get``/``device_put``,
       ``.block_until_ready()`` — either a tracer leak or a hidden
       per-step synchronization.
XH401  XAIF bypass: ``repro.kernels.*`` imported from models/ or serve/ —
       model and engine code must dispatch through ``xaif.call`` so tuned
       policies, supports() fallbacks and the circuit breaker apply
       (the shared shape utils in ``_tiling`` are exempt).
XH501  missing donation: ``jax.jit`` of a function that takes AND returns
       a cache/state pytree without ``donate_argnums`` — the update
       allocates a second copy of the cache every call.
====== ===================================================================

The engine is deliberately conservative: unresolvable callables (method
references, cross-module names) are skipped, closure variables of a
``make_*`` factory are treated as static (they are baked into the trace),
and taint never crosses function boundaries. False negatives are
acceptable; false positives on HEAD are not — the CI gate requires a
clean tree without blanket suppressions.
"""
from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

RULES: Dict[str, Tuple[str, str]] = {
    # id -> (summary, fix-it)
    "XH101": ("int()/float()/bool() on a traced value inside a jit region",
              "use jnp ops (astype, jnp.where) or hoist the cast out of "
              "the jitted region; static dims come from x.shape, which is "
              "exempt"),
    "XH102": (".item()/.tolist() on a traced value inside a jit region",
              "return the array and fetch it on the host after the jitted "
              "call (one transfer per chunk, never per step)"),
    "XH103": ("Python control flow on a traced value inside a jit region",
              "replace the branch with jnp.where / jax.lax.cond / "
              "jax.lax.select; branch on static config only"),
    "XH201": ("array constructor without an explicit dtype in a "
              "kernels/serve path",
              "pass dtype= explicitly (e.g. jnp.int32/jnp.float32) so "
              "numerics don't follow the host's default-dtype flags"),
    "XH301": ("host synchronization inside a jit region",
              "keep device values on device; fetch once per chunk outside "
              "the jitted function (jax.device_get at the call site)"),
    "XH401": ("direct repro.kernels import bypasses xaif.call dispatch",
              "route the call through xaif.call(op, policy, ...) so tuned "
              "policies, supports() fallback and the circuit breaker "
              "apply"),
    "XH501": ("jax.jit of a cache/state-updating function without "
              "donate_argnums",
              "add donate_argnums for the cache/state arguments so the "
              "update reuses the input buffers instead of allocating a "
              "copy"),
}

_DISABLE_RE = re.compile(r"#\s*analysis:\s*disable=([A-Za-z0-9_,\s]+)")
_DISABLE_FILE_RE = re.compile(
    r"#\s*analysis:\s*disable-file=([A-Za-z0-9_,\s]+)")

# transforms whose callable arguments get traced
_JIT_WRAPPERS = {"jit"}
_TRACE_TRANSFORMS = {
    "jit", "scan", "cond", "while_loop", "fori_loop", "switch", "map",
    "vmap", "pmap", "grad", "value_and_grad", "checkpoint", "remat",
    "shard_map", "associative_scan",
}
# params of a jit region that are NOT traced values
_STATIC_PARAMS = {"self", "_", "__"}
# donation rule: parameter names that mark a cache/state pytree
_DONATABLE = {"cache", "dcache", "slot_cache", "st", "state", "carry",
              "opt_state", "train_state"}
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size"}
_DTYPE_CTORS = {"zeros": 1, "ones": 1, "empty": 1, "full": 2, "arange": 3}
_ALLOWED_KERNEL_UTILS = {"repro.kernels._tiling"}


@dataclass
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str
    fixit: str = ""

    def to_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message,
                "fixit": self.fixit}

    def __str__(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"{self.message}\n    fix: {self.fixit}")


def _attr_chain(node: ast.AST) -> str:
    """Dotted name of a Name/Attribute chain ('' if not a plain chain)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _callable_names(node: ast.AST, depth: int = 0) -> List[str]:
    """Names of plain-function candidates inside a callable argument,
    unwrapping adapters: ``partial(f, ...)`` -> f, ``wrap(f)`` -> f."""
    if depth > 4:
        return []
    if isinstance(node, ast.Name):
        return [node.id]
    if isinstance(node, ast.Call):
        out: List[str] = []
        for a in node.args:
            out.extend(_callable_names(a, depth + 1))
        return out
    return []


Scope = Tuple[Tuple[str, int], ...]       # (('c'|'f', node id), ...)


class _RegionCollector(ast.NodeVisitor):
    """First pass: every FunctionDef with its lexical scope + every name
    handed to a tracing transform (with the scope of the call site).

    Name resolution follows Python's lexical rules so a local jitted
    closure does not alias a same-named method elsewhere in the module:
    a def declared in a function scope is visible in that scope and its
    nested scopes; a def declared directly in a class body is visible
    only in the class body itself (methods see it via ``self.``, which
    we never resolve); module-level defs are visible everywhere."""

    def __init__(self):
        # name -> [(defining scope, FunctionDef)]
        self.defs: Dict[str, List[Tuple[Scope, ast.FunctionDef]]] = {}
        # names handed to a tracing transform, with the call-site scope
        self.jit_refs: List[Tuple[str, Scope]] = []
        # jax.jit(...) call nodes with their scopes (for the donate rule)
        self.jit_calls: List[Tuple[ast.Call, Scope]] = []
        self._stack: List[Tuple[str, int]] = []

    def _scope(self) -> Scope:
        return tuple(self._stack)

    def visit_FunctionDef(self, node: ast.FunctionDef):
        scope = self._scope()
        self.defs.setdefault(node.name, []).append((scope, node))
        for dec in node.decorator_list:
            chain = _attr_chain(dec)
            if chain.split(".")[-1] in _JIT_WRAPPERS:
                self.jit_refs.append((node.name, scope))
            if isinstance(dec, ast.Call):
                fn = _attr_chain(dec.func)
                if fn.split(".")[-1] in ("partial",) and dec.args:
                    inner = _attr_chain(dec.args[0])
                    if inner.split(".")[-1] in _JIT_WRAPPERS:
                        self.jit_refs.append((node.name, scope))
                elif fn.split(".")[-1] in _JIT_WRAPPERS:
                    self.jit_refs.append((node.name, scope))
        self._stack.append(("f", id(node)))
        self.generic_visit(node)
        self._stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node: ast.ClassDef):
        self._stack.append(("c", id(node)))
        self.generic_visit(node)
        self._stack.pop()

    def visit_Call(self, node: ast.Call):
        chain = _attr_chain(node.func)
        leaf = chain.split(".")[-1] if chain else ""
        if leaf in _TRACE_TRANSFORMS:
            scope = self._scope()
            for arg in node.args:
                for name in _callable_names(arg):
                    self.jit_refs.append((name, scope))
            if leaf in _JIT_WRAPPERS:
                self.jit_calls.append((node, scope))
        self.generic_visit(node)

    def resolve(self, name: str, scope: Scope) -> List[ast.FunctionDef]:
        """Defs ``name`` could refer to at ``scope``, innermost first."""
        visible: List[Tuple[Scope, ast.FunctionDef]] = []
        for def_scope, fn in self.defs.get(name, ()):
            if def_scope and def_scope[-1][0] == "c":
                if def_scope == scope:           # class-body name
                    visible.append((def_scope, fn))
            elif scope[:len(def_scope)] == def_scope:
                visible.append((def_scope, fn))
        if not visible:
            return []
        best = max(len(s) for s, _ in visible)
        return [fn for s, fn in visible if len(s) == best]


class _TaintVisitor(ast.NodeVisitor):
    """Per-region rule pass with a sequential name-taint over statements."""

    def __init__(self, linter: "_FileLinter", region: ast.FunctionDef):
        self.linter = linter
        self.tainted: Set[str] = set()
        args = region.args
        for a in (args.posonlyargs + args.args + args.kwonlyargs
                  + ([args.vararg] if args.vararg else [])
                  + ([args.kwarg] if args.kwarg else [])):
            if a.arg not in _STATIC_PARAMS:
                self.tainted.add(a.arg)

    # -- taint of an expression --------------------------------------------

    def _is_tainted(self, node: ast.AST) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False             # x.shape / x.dtype are static
            return self._is_tainted(node.value)
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` is a trace-time identity check
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            return (self._is_tainted(node.left)
                    or any(self._is_tainted(c) for c in node.comparators))
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain in ("len", "isinstance", "hasattr", "getattr", "type"):
                return False             # static structure checks
            return (any(self._is_tainted(a) for a in node.args)
                    or any(self._is_tainted(k.value) for k in node.keywords)
                    or self._is_tainted(node.func))
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.expr, ast.keyword)):
                if self._is_tainted(child):
                    return True
        return False

    # -- taint propagation --------------------------------------------------

    def _bind_targets(self, target: ast.AST, tainted: bool):
        if isinstance(target, ast.Name):
            if tainted:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind_targets(elt, tainted)
        elif isinstance(target, ast.Starred):
            self._bind_targets(target.value, tainted)

    def visit_Assign(self, node: ast.Assign):
        self.generic_visit(node)
        t = self._is_tainted(node.value)
        for target in node.targets:
            self._bind_targets(target, t)

    def visit_AugAssign(self, node: ast.AugAssign):
        self.generic_visit(node)
        if self._is_tainted(node.value):
            self._bind_targets(node.target, True)

    def visit_AnnAssign(self, node: ast.AnnAssign):
        self.generic_visit(node)
        if node.value is not None:
            self._bind_targets(node.target, self._is_tainted(node.value))

    def visit_For(self, node: ast.For):
        if self._is_tainted(node.iter):
            self._bind_targets(node.target, True)
        self.generic_visit(node)

    # -- rules --------------------------------------------------------------

    def visit_Call(self, node: ast.Call):
        chain = _attr_chain(node.func)
        leaf = chain.split(".")[-1] if chain else ""
        # XH101: int()/float()/bool() on a traced value
        if (isinstance(node.func, ast.Name)
                and node.func.id in ("int", "float", "bool")
                and len(node.args) == 1
                and self._is_tainted(node.args[0])):
            self.linter.report("XH101", node,
                               f"{node.func.id}() concretizes a traced "
                               f"value inside a jitted region")
        # XH102: .item()/.tolist() on a traced value
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in ("item", "tolist")
                and self._is_tainted(node.func.value)):
            self.linter.report("XH102", node,
                               f".{node.func.attr}() forces a host sync "
                               f"inside a jitted region")
        # XH301: host syncs
        if chain in ("np.asarray", "np.array", "numpy.asarray",
                     "numpy.array", "onp.asarray", "onp.array"):
            if any(self._is_tainted(a) for a in node.args):
                self.linter.report("XH301", node,
                                   f"{chain}() on a traced value pulls it "
                                   f"to host inside a jitted region")
        elif chain in ("jax.device_get", "jax.device_put") or \
                leaf == "block_until_ready":
            self.linter.report("XH301", node,
                               f"{chain or leaf}() inside a jitted region")
        self.generic_visit(node)

    def _flag_branch(self, node: ast.AST, kind: str):
        test = getattr(node, "test", None)
        if test is not None and self._is_tainted(test):
            self.linter.report("XH103", node,
                               f"{kind} on a traced value — trace-time "
                               f"Python control flow")

    def visit_If(self, node: ast.If):
        self._flag_branch(node, "if")
        self.generic_visit(node)

    def visit_While(self, node: ast.While):
        self._flag_branch(node, "while")
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp):
        self._flag_branch(node, "conditional expression")
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert):
        # assert on a traced value is also trace-time control flow, but
        # shape asserts (static) dominate; only flag plainly-traced tests
        if self._is_tainted(node.test):
            self.linter.report("XH103", node,
                               "assert on a traced value — trace-time "
                               "Python control flow")
        self.generic_visit(node)


class _FileLinter:
    def __init__(self, path: str, src: str, relpath: Optional[str] = None):
        self.path = relpath or path
        self.src = src
        self.lines = src.splitlines()
        self.findings: List[Finding] = []
        self.file_disabled: Set[str] = set()
        for line in self.lines[:10]:
            m = _DISABLE_FILE_RE.search(line)
            if m:
                self.file_disabled |= {
                    s.strip() for s in m.group(1).split(",")}

    # -- reporting with suppression ----------------------------------------

    def _suppressed(self, rule: str, line: int) -> bool:
        if "all" in self.file_disabled or rule in self.file_disabled:
            return True
        if 1 <= line <= len(self.lines):
            m = _DISABLE_RE.search(self.lines[line - 1])
            if m:
                ids = {s.strip() for s in m.group(1).split(",")}
                return "all" in ids or rule in ids
        return False

    def report(self, rule: str, node: ast.AST, message: str):
        line = getattr(node, "lineno", 1)
        if self._suppressed(rule, line):
            return
        summary, fixit = RULES[rule]
        self.findings.append(Finding(
            rule=rule, path=self.path, line=line,
            col=getattr(node, "col_offset", 0) + 1,
            message=f"{message} [{summary}]", fixit=fixit))

    # -- the passes ---------------------------------------------------------

    def run(self) -> List[Finding]:
        try:
            tree = ast.parse(self.src)
        except SyntaxError as e:
            self.findings.append(Finding(
                rule="XH000", path=self.path, line=e.lineno or 1, col=1,
                message=f"syntax error: {e.msg}",
                fixit="fix the syntax error"))
            return self.findings

        collector = _RegionCollector()
        collector.visit(tree)

        # jit regions: every def a transform ref resolves to (by lexical
        # scope — a local closure never aliases a same-named method),
        # plus defs nested inside one
        regions: List[ast.FunctionDef] = []
        seen: Set[int] = set()

        def add_region(fn: ast.FunctionDef):
            if id(fn) in seen:
                return
            seen.add(id(fn))
            regions.append(fn)
            for sub in ast.walk(fn):
                if isinstance(sub, ast.FunctionDef) and id(sub) not in seen:
                    seen.add(id(sub))
                    regions.append(sub)

        for name, scope in collector.jit_refs:
            for fn in collector.resolve(name, scope):
                add_region(fn)

        for fn in regions:
            visitor = _TaintVisitor(self, fn)
            for stmt in fn.body:
                visitor.visit(stmt)

        self._check_dtypes(tree)
        self._check_bypass(tree)
        self._check_donation(collector)
        return self.findings

    # -- XH201: dtype drift -------------------------------------------------

    def _in_scope_for_dtype(self) -> bool:
        p = self.path.replace(os.sep, "/")
        return "/kernels/" in p or "/serve/" in p

    @staticmethod
    def _has_dtype(node: ast.Call, ctor: str) -> bool:
        if any(k.arg == "dtype" for k in node.keywords):
            return True
        return len(node.args) > _DTYPE_CTORS[ctor]

    def _check_dtypes(self, tree: ast.AST):
        if not self._in_scope_for_dtype():
            return
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if "." not in chain:
                continue
            base, leaf = chain.rsplit(".", 1)
            if base in ("jnp", "jax.numpy") and leaf in _DTYPE_CTORS:
                if not self._has_dtype(node, leaf):
                    self.report("XH201", node,
                                f"jnp.{leaf}() without an explicit dtype")

    # -- XH401: xaif bypass -------------------------------------------------

    def _in_scope_for_bypass(self) -> bool:
        p = self.path.replace(os.sep, "/")
        return "/models/" in p or "/serve/" in p

    def _check_bypass(self, tree: ast.AST):
        if not self._in_scope_for_bypass():
            return
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                mod = node.module
                if (mod.startswith("repro.kernels")
                        and mod not in _ALLOWED_KERNEL_UTILS):
                    self.report("XH401", node,
                                f"import from {mod} in a model/serve path")
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if (alias.name.startswith("repro.kernels")
                            and alias.name not in _ALLOWED_KERNEL_UTILS):
                        self.report("XH401", node,
                                    f"import of {alias.name} in a "
                                    f"model/serve path")

    # -- XH501: missing donation -------------------------------------------

    def _check_donation(self, collector: _RegionCollector):
        for call, scope in collector.jit_calls:
            if any(k.arg == "donate_argnums" for k in call.keywords):
                continue
            if not call.args:
                continue
            for name in _callable_names(call.args[0]):
                for fn in collector.resolve(name, scope):
                    params = [a.arg for a in (fn.args.posonlyargs
                                              + fn.args.args)]
                    donatable = [p for p in params if p in _DONATABLE]
                    if not donatable:
                        continue
                    if self._returns_donatable(fn, set(donatable)):
                        self.report(
                            "XH501", call,
                            f"jax.jit({name}) updates "
                            f"{'/'.join(donatable)} but declares no "
                            f"donate_argnums")
                        break
                else:
                    continue
                break

    @staticmethod
    def _returns_donatable(fn: ast.FunctionDef, names: Set[str]) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Return) and node.value is not None:
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Name) and sub.id in names:
                        return True
        return False


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def lint_file(path: str, src: Optional[str] = None,
              relpath: Optional[str] = None) -> List[Finding]:
    if src is None:
        with open(path, "r", encoding="utf-8") as f:
            src = f.read()
    return _FileLinter(path, src, relpath=relpath).run()


def lint_paths(paths: Iterable[str],
               root: Optional[str] = None) -> List[Finding]:
    findings: List[Finding] = []
    for p in paths:
        rel = os.path.relpath(p, root) if root else p
        findings.extend(lint_file(p, relpath=rel))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def lint_tree(root: str) -> List[Finding]:
    """Lint every ``.py`` under ``root`` (the ``src/repro`` tree)."""
    paths: List[str] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in sorted(filenames):
            if name.endswith(".py"):
                paths.append(os.path.join(dirpath, name))
    return lint_paths(paths, root=os.path.dirname(os.path.abspath(root)))
