"""graphlint — AST linter enforcing TPU-graph hygiene on graph-scope code.

Why a linter: the whole design premise of this reproduction is that the
hot path is ONE XLA program with static shapes (the reference's per-step
host bounces — NumPy ``Proposal``, dynamic ``nonzero`` shapes — are the
sin being fixed).  Nothing in the type system enforces that: a single
careless ``.item()``, boolean-mask index, or per-call ``jax.jit(partial)``
silently reintroduces host syncs or per-step recompiles, and the only
symptom is a bench regression rounds later.  graphlint catches these bug
classes at lint time; ``tests/test_recompile_guard.py`` is the runtime
twin (jit cache-miss budget + tracer-leak checks).

Rule families (full catalogue with bad/good examples: docs/ANALYSIS.md):

* GL1xx — host-sync discipline: no host numpy / ``.item()`` / scalar
  coercions / ``print`` on traced values inside jitted scopes.
* GL2xx — static-shape discipline: no ``jnp.nonzero`` / one-arg
  ``jnp.where`` / boolean-mask indexing / Python control flow on tracers.
* GL3xx — jit-cache hygiene: no per-call jit of fresh lambdas/partials,
  no jit construction inside loops, no mutable defaults on static args.
* GL4xx — dtype/constant hygiene: no float64-promoting literals, no
  module-level jnp constants (they initialize the backend at import —
  see the comments in ``ops/nms.py`` / ``ops/targets.py``), no bare
  list/tuple operands in traced arithmetic.

Scope inference: a function is **jit-scoped** when it is (a) decorated
with ``jax.jit`` / ``functools.partial(jax.jit, ...)`` / a custom-VJP
builder, (b) passed (directly, as a lambda, or through a local
``functools.partial`` alias) to a tracing transform (``jit``, ``vmap``,
``grad``, ``lax.scan``/``cond``/``while_loop``, ``shard_map``,
``pallas_call``, ``defvjp``, ...), (c) a method of a ``flax.linen.Module``
subclass, (d) lexically nested in a jit-scoped function, (e) marked
``# graphlint: jit`` (for functions that are traced through indirection
the AST cannot follow, e.g. a closure returned by a factory and jitted by
the caller), or (f) **called** from a jit-scoped function — a transitive
closure over the module-local + cross-module call graph, so hygiene rules
follow the trace into helpers like ``ops/boxes.py`` without annotations.

False-positive suppression: inside a jit-scoped function, expressions are
classified **static** (Python values fixed at trace time — safe to
coerce, branch on, or hand to host numpy) by local dataflow: literals,
parameters named in ``static_argnames``/``static_argnums``/
``nondiff_argnums``, parameters annotated with scalar/config/host-numpy
types, ``.shape``/``.size``/``.ndim``/``.dtype`` reads, ``self`` fields
of flax modules, arithmetic/comparisons/whitelisted builtins over those,
and calls whose return annotation is a static type.  Everything else is
presumed traced.

Waivers: append ``# graphlint: disable=GL101 <reason>`` to the offending
line (or put the comment on its own line directly above).  A waiver MUST
carry a reason — a bare waiver is itself a finding (GL001) — so every
intentional exception is documented in place.

CLI::

    python -m mx_rcnn_tpu.analysis.graphlint [paths...] [--json]
        [--show-waived] [--list-rules]

Exit status 0 iff no unwaived findings.
"""

from __future__ import annotations

import argparse
import ast
import io
import json
import os
import re
import sys
import tokenize
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from mx_rcnn_tpu.analysis.common import (Finding, apply_waivers,
                                         canonical, check_paths_exist,
                                         collect_import_aliases, dotted,
                                         iter_py_files, waiver_re)

RULES: Dict[str, str] = {
    "GL001": "waiver without a reason (every waiver must say why)",
    "GL002": "waiver names an unknown rule code",
    "GL101": "host numpy call on traced values in jit scope",
    "GL102": "host materialization (.item()/.tolist()/device_get) in jit scope",
    "GL103": "float()/int()/bool() coercion of a traced value in jit scope",
    "GL104": "host print() in jit scope (use jax.debug.print)",
    "GL105": "host clock / obs span in jit scope (measures tracing, not "
             "compute)",
    "GL201": "dynamic-shape op (nonzero/argwhere/one-arg where) in jit scope",
    "GL202": "boolean-mask indexing in jit scope (dynamic result shape)",
    "GL203": "Python if/while on a traced value in jit scope",
    "GL301": "jax.jit of a fresh lambda/partial (new jit cache per call)",
    "GL302": "jax.jit built inside a loop or jitted-and-called in one expression",
    "GL303": "static jit argument with a mutable default",
    "GL401": "float64-promoting dtype in graph scope",
    "GL402": "module-level jnp constant (initializes the backend at import)",
    "GL403": "traced arithmetic with a bare list/tuple literal operand",
}

# transforms whose callable arguments are traced
_TRANSFORMS = {
    "jit", "vmap", "pmap", "grad", "value_and_grad", "checkpoint", "remat",
    "custom_vjp", "custom_jvp", "pallas_call", "scan", "while_loop", "cond",
    "switch", "fori_loop", "map", "shard_map",
    "defvjp", "defjvp", "associative_scan", "named_call",
}

# annotations whose values are host/static at trace time
_STATIC_ANN = re.compile(
    r"^(?:Tuple|tuple|Sequence|List|list|Optional|int|float|bool|str|"
    r"Config|np\.ndarray|numpy\.ndarray|\[|\]|,|\.\.\.|\s|\||None)+$"
)

_STATIC_BUILTINS = {
    "len", "int", "float", "bool", "str", "min", "max", "round", "abs",
    "sum", "tuple", "list", "range", "sorted", "isinstance", "getattr",
    "hasattr", "divmod", "repr",
}

# host clocks (GL105): inside a jitted function these run at TRACE time —
# the measured interval is tracing/compilation, not the compute the
# author meant to time.  Same for the obs/trace.py span() context manager
# (host-side instrumentation belongs AROUND the jitted call, never in it).
_HOST_CLOCKS = {
    "time.time", "time.time_ns", "time.perf_counter",
    "time.perf_counter_ns", "time.monotonic", "time.monotonic_ns",
    "time.process_time", "time.process_time_ns",
}

# dynamic-output-shape ops (GL201); one-arg `where` is handled separately
_DYNAMIC_SHAPE_OPS = {"nonzero", "flatnonzero", "argwhere", "unique",
                      "extract", "compress"}

# waiver/Finding machinery shared with threadlint/configlint
# (analysis/common.py); the pragmas below are graphlint-specific
_WAIVER_RE = waiver_re("graphlint")
_PRAGMA_JIT_RE = re.compile(r"graphlint:\s*jit\b")
_PRAGMA_HOST_RE = re.compile(r"graphlint:\s*host\b")


@dataclass
class FuncInfo:
    node: ast.AST                      # FunctionDef / AsyncFunctionDef / Lambda
    qualname: str
    module: "ModuleInfo"
    jit: bool = False
    jit_reason: str = ""
    host_pragma: bool = False
    static_params: Set[str] = field(default_factory=set)
    parent: Optional["FuncInfo"] = None
    callees: Set[Tuple[str, str]] = field(default_factory=set)  # (mod, name)


@dataclass
class ModuleInfo:
    path: str
    name: str                          # dotted module name if under a package
    tree: ast.Module
    lines: List[str]
    graph_scope: bool
    aliases: Dict[str, str] = field(default_factory=dict)  # local -> canonical
    funcs: Dict[str, FuncInfo] = field(default_factory=dict)  # by qualname
    by_name: Dict[str, FuncInfo] = field(default_factory=dict)  # top-level defs
    waivers: Dict[int, Tuple[Set[str], str]] = field(default_factory=dict)
    jit_pragmas: Set[int] = field(default_factory=set)
    host_pragmas: Set[int] = field(default_factory=set)


# --------------------------------------------------------------------------
# name resolution helpers
# --------------------------------------------------------------------------

_dotted = dotted


def _canonical(mod: ModuleInfo, node: ast.AST) -> Optional[str]:
    """Resolve a Name/Attribute chain through the module's import aliases:
    ``jnp.where`` -> ``jax.numpy.where``, ``pl.pallas_call`` ->
    ``jax.experimental.pallas.pallas_call``."""
    return canonical(mod.aliases, node)


def _is_np(canon: Optional[str]) -> bool:
    return canon is not None and (canon == "numpy"
                                  or canon.startswith("numpy."))


def _is_jnp(canon: Optional[str]) -> bool:
    return canon is not None and (canon.startswith("jax.numpy.")
                                  or canon == "jax.numpy")


def _ann_is_static(ann: Optional[ast.AST]) -> bool:
    if ann is None:
        return False
    try:
        text = ast.unparse(ann)
    except Exception:
        return False
    return bool(_STATIC_ANN.match(text.strip().strip('"\'')))


# --------------------------------------------------------------------------
# pass 1: per-module collection
# --------------------------------------------------------------------------

def _collect_comments(source: str, mod: ModuleInfo) -> None:
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            line = tok.start[0]
            m = _WAIVER_RE.search(tok.string)
            if m:
                codes = {c.strip().upper() for c in m.group(1).split(",")
                         if c.strip()}
                mod.waivers[line] = (codes, m.group(2).strip())
            if _PRAGMA_JIT_RE.search(tok.string):
                mod.jit_pragmas.add(line)
            if _PRAGMA_HOST_RE.search(tok.string):
                mod.host_pragmas.add(line)
    except tokenize.TokenError:
        pass


def _collect_imports(mod: ModuleInfo) -> None:
    mod.aliases.update(collect_import_aliases(mod.tree))


def _static_params_of(mod: ModuleInfo, node: ast.AST) -> Set[str]:
    """Parameters fixed at trace time: static_argnames/static_argnums of a
    jit decorator, nondiff_argnums of custom_vjp, scalar-annotated args."""
    static: Set[str] = set()
    if isinstance(node, ast.Lambda):
        return static
    args = node.args
    allargs = args.posonlyargs + args.args + args.kwonlyargs
    for a in allargs:
        if _ann_is_static(a.annotation):
            static.add(a.arg)
    positions: List[int] = []
    names: List[str] = []
    for dec in node.decorator_list:
        for call in [n for n in ast.walk(dec) if isinstance(n, ast.Call)]:
            for kw in call.keywords:
                if kw.arg in ("static_argnames",):
                    for c in ast.walk(kw.value):
                        if isinstance(c, ast.Constant) and isinstance(c.value, str):
                            names.append(c.value)
                if kw.arg in ("static_argnums", "nondiff_argnums"):
                    for c in ast.walk(kw.value):
                        if isinstance(c, ast.Constant) and isinstance(c.value, int):
                            positions.append(c.value)
    static.update(names)
    ordered = [a.arg for a in args.posonlyargs + args.args]
    for i in positions:
        if 0 <= i < len(ordered):
            static.add(ordered[i])
    return static


def _jit_decorated(mod: ModuleInfo, node: ast.AST) -> Optional[str]:
    """Non-None (a human-readable reason) when a decorator makes the
    function traced: jax.jit, partial(jax.jit, ...), jax.checkpoint,
    jax.custom_vjp/custom_jvp (possibly partial-wrapped)."""
    if isinstance(node, ast.Lambda):
        return None
    for dec in node.decorator_list:
        targets = [dec]
        if isinstance(dec, ast.Call):
            targets.append(dec.func)
            targets.extend(dec.args)  # functools.partial(jax.jit, ...)
            for t in list(targets):
                if isinstance(t, ast.Call):
                    targets.append(t.func)
                    targets.extend(t.args)
        for t in targets:
            canon = _canonical(mod, t)
            if canon and canon.startswith("jax") and \
                    canon.rsplit(".", 1)[-1] in _TRANSFORMS:
                return f"@{canon.rsplit('.', 1)[-1]}"
    return None


class _ModuleScanner(ast.NodeVisitor):
    """Collects FuncInfos, flax-module classes, and jit roots."""

    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.stack: List[FuncInfo] = []
        self.class_stack: List[Tuple[str, bool]] = []  # (name, is_flax)

    def _qual(self, name: str) -> str:
        prefix = ""
        if self.stack:
            prefix = self.stack[-1].qualname + "."
        elif self.class_stack:
            prefix = ".".join(c for c, _ in self.class_stack) + "."
        return prefix + name

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        is_flax = any(
            (_canonical(self.mod, b) or "").endswith("Module")
            for b in node.bases)
        self.class_stack.append((node.name, is_flax))
        self.generic_visit(node)
        self.class_stack.pop()

    def _add_func(self, node, name: str) -> FuncInfo:
        info = FuncInfo(node=node, qualname=self._qual(name), module=self.mod,
                        parent=self.stack[-1] if self.stack else None)
        info.static_params = _static_params_of(self.mod, node)
        reason = _jit_decorated(self.mod, node)
        in_flax_class = bool(self.class_stack and self.class_stack[-1][1]
                             and not self.stack)
        if any(l in self.mod.host_pragmas for l in
               (node.lineno, node.lineno - 1)):
            info.host_pragma = True
        elif reason:
            info.jit, info.jit_reason = True, reason
        elif in_flax_class:
            info.jit, info.jit_reason = True, "flax module method"
            info.static_params.add("self")
        elif any(l in self.mod.jit_pragmas for l in
                 (node.lineno, node.lineno - 1)):
            info.jit, info.jit_reason = True, "# graphlint: jit"
        self.mod.funcs[info.qualname] = info
        if not self.stack and not self.class_stack:
            self.mod.by_name[name] = info
        return info

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        info = self._add_func(node, node.name)
        self.stack.append(info)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda) -> None:
        info = self._add_func(node, f"<lambda:{node.lineno}>")
        self.stack.append(info)
        self.generic_visit(node)
        self.stack.pop()


def _mark_transform_roots(mod: ModuleInfo) -> None:
    """Mark functions passed to tracing transforms as jit roots.  Handles
    direct names, lambdas, inline ``functools.partial(f, ...)``, local
    aliases ``g = functools.partial(f, ...)``, and ``obj.defvjp(fwd, bwd)``.
    """
    partial_alias: Dict[str, str] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            canon = _canonical(mod, node.value.func) or ""
            if canon.endswith("partial") and node.value.args:
                inner = _dotted(node.value.args[0])
                if inner and len(node.targets) == 1:
                    tgt = _dotted(node.targets[0])
                    if tgt:
                        partial_alias[tgt] = inner

    def mark(name: Optional[str]) -> None:
        if not name:
            return
        name = partial_alias.get(name, name)
        info = mod.by_name.get(name) or mod.funcs.get(name)
        if info is None:  # nested def referenced by bare name
            for q, fi in mod.funcs.items():
                if q.split(".")[-1] == name:
                    info = fi
                    break
        if info is not None and not info.host_pragma and not info.jit:
            info.jit, info.jit_reason = True, "passed to transform"

    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        canon = _canonical(mod, node.func) or ""
        leaf = canon.rsplit(".", 1)[-1]
        is_defvjp = (isinstance(node.func, ast.Attribute)
                     and node.func.attr in ("defvjp", "defjvp"))
        if not is_defvjp:
            if leaf not in _TRANSFORMS:
                continue
            if not (canon.startswith("jax") or "pallas" in canon
                    or "shard_map" in canon):
                continue
            if ".tree" in canon or "tree_util" in canon:
                continue  # jax.tree.map is a pytree map, not a transform
            if leaf == "partial" or canon.endswith("functools.partial"):
                continue
        cands = list(node.args) + [kw.value for kw in node.keywords
                                   if kw.arg in ("f", "fun", "body_fun",
                                                 "cond_fun", "kernel")]
        for arg in cands:
            if isinstance(arg, ast.Lambda):
                info = mod.funcs.get(f"<lambda:{arg.lineno}>")
                for fi in mod.funcs.values():
                    if fi.node is arg:
                        info = fi
                if info is not None and not info.host_pragma:
                    info.jit, info.jit_reason = True, "lambda under transform"
            elif isinstance(arg, ast.Call):
                inner_canon = _canonical(mod, arg.func) or ""
                if inner_canon.endswith("partial") and arg.args:
                    mark(_dotted(arg.args[0]))
            else:
                mark(_dotted(arg))


def load_module(path: str, pkg_root: str) -> Optional[ModuleInfo]:
    try:
        with open(path, "r", encoding="utf-8") as f:
            source = f.read()
        tree = ast.parse(source, filename=path)
    except (OSError, SyntaxError) as e:
        print(f"graphlint: cannot parse {path}: {e}", file=sys.stderr)
        return None
    rel = os.path.relpath(path, pkg_root) if pkg_root else path
    dotted = rel[:-3].replace(os.sep, ".") if rel.endswith(".py") else rel
    # graph scope keys off the path RELATIVE to the linted root (plus the
    # immediate parent for single-file invocations, e.g. the test
    # fixture) — absolute components would misclassify a checkout that
    # happens to live under a directory named ops/core/models/parallel
    graph_dirs = ("ops", "core", "models", "parallel")
    parent = os.path.basename(os.path.dirname(os.path.abspath(path)))
    graph_scope = (any(p in graph_dirs for p in rel.split(os.sep))
                   or parent in graph_dirs)
    mod = ModuleInfo(path=path, name=dotted, tree=tree,
                     lines=source.splitlines(), graph_scope=graph_scope)
    _collect_comments(source, mod)
    _collect_imports(mod)
    _ModuleScanner(mod).visit(tree)
    _mark_transform_roots(mod)
    return mod


# --------------------------------------------------------------------------
# pass 2: cross-module jit closure
# --------------------------------------------------------------------------

def _collect_callees(mod: ModuleInfo) -> None:
    """Record, per function, calls that resolve to module-local defs or to
    names imported from sibling package modules."""
    class V(ast.NodeVisitor):
        def __init__(self):
            self.stack: List[FuncInfo] = []

        def _enter(self, node):
            for fi in mod.funcs.values():
                if fi.node is node:
                    self.stack.append(fi)
                    return fi
            return None

        def visit_FunctionDef(self, node):
            fi = self._enter(node)
            self.generic_visit(node)
            if fi:
                self.stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Lambda(self, node):
            fi = self._enter(node)
            self.generic_visit(node)
            if fi:
                self.stack.pop()

        def visit_Call(self, node):
            if self.stack:
                names: List[Optional[str]] = [_dotted(node.func)]
                canon = _canonical(mod, node.func) or ""
                # functools.partial(f, ...): the wrapped f is the callee
                if canon.endswith("partial") and node.args:
                    names.append(_dotted(node.args[0]))
                for name in names:
                    if not name:
                        continue
                    if name in mod.by_name:
                        self.stack[-1].callees.add((mod.name, name))
                    else:
                        full = mod.aliases.get(name)
                        if full and "." in full:
                            m, _, f = full.rpartition(".")
                            self.stack[-1].callees.add((m, f))
            self.generic_visit(node)

    V().visit(mod.tree)


def _propagate_jit(mods: List[ModuleInfo]) -> None:
    by_modname: Dict[str, ModuleInfo] = {}
    for m in mods:
        by_modname[m.name] = m
        # also index by the tail of the dotted name so absolute imports
        # (mx_rcnn_tpu.ops.boxes) match modules loaded from a subtree path
        by_modname.setdefault(m.name.rsplit(".", 1)[-1], m)
        _collect_callees(m)

    def resolve(ref: Tuple[str, str]) -> Optional[FuncInfo]:
        modname, fname = ref
        m = by_modname.get(modname) or by_modname.get(
            modname.rsplit(".", 1)[-1])
        if m is None:
            return None
        return m.by_name.get(fname)

    changed = True
    while changed:
        changed = False
        for m in mods:
            for fi in m.funcs.values():
                jit = fi.jit
                if not jit and fi.parent is not None and fi.parent.jit:
                    jit = True
                    fi.jit_reason = f"nested in {fi.parent.qualname}"
                if not jit or fi.host_pragma:
                    continue
                if not fi.jit:
                    fi.jit = True
                    changed = True
                for ref in fi.callees:
                    callee = resolve(ref)
                    if callee is not None and not callee.jit \
                            and not callee.host_pragma:
                        callee.jit = True
                        callee.jit_reason = f"called from {fi.qualname}"
                        changed = True


# --------------------------------------------------------------------------
# static-expression classification (local dataflow)
# --------------------------------------------------------------------------

class _StaticEnv:
    def __init__(self, mod: ModuleInfo, statics: Set[str]):
        self.mod = mod
        self.statics = set(statics)
        self.mask_names: Set[str] = set()   # names holding boolean masks

    def is_static(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Name):
            return node.id in self.statics
        if isinstance(node, ast.Attribute):
            if node.attr in ("shape", "size", "ndim", "dtype"):
                return True
            canon = _canonical(self.mod, node)
            if canon and (canon.startswith("jax") or _is_np(canon)):
                # module attributes (jnp.float32, np.pi) are trace-time
                # constants; module CALLS are handled under ast.Call
                return True
            return self.is_static(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_static(node.value) and self.is_static(node.slice)
        if isinstance(node, (ast.Tuple, ast.List)):
            return all(self.is_static(e) for e in node.elts)
        if isinstance(node, ast.BinOp):
            return self.is_static(node.left) and self.is_static(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.is_static(node.operand)
        if isinstance(node, ast.BoolOp):
            return all(self.is_static(v) for v in node.values)
        if isinstance(node, ast.Compare):
            return (self.is_static(node.left)
                    and all(self.is_static(c) for c in node.comparators))
        if isinstance(node, ast.IfExp):
            return (self.is_static(node.test) and self.is_static(node.body)
                    and self.is_static(node.orelse))
        if isinstance(node, ast.Slice):
            return all(p is None or self.is_static(p)
                       for p in (node.lower, node.upper, node.step))
        if isinstance(node, ast.Starred):
            return self.is_static(node.value)
        if isinstance(node, ast.JoinedStr):
            return True
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            return self._comp_static(node, [node.elt])
        if isinstance(node, ast.DictComp):
            return self._comp_static(node, [node.key, node.value])
        if isinstance(node, ast.Call):
            return self._call_static(node)
        return False

    def _comp_static(self, node, elts) -> bool:
        """A comprehension over static iterables of static elements is
        static (the loop targets are bound static while judging ``elt``)."""
        if not all(self.is_static(g.iter) for g in node.generators):
            return False
        added: Set[str] = set()
        for g in node.generators:
            for t in ast.walk(g.target):
                if isinstance(t, ast.Name) and t.id not in self.statics:
                    added.add(t.id)
        self.statics |= added
        try:
            return all(self.is_static(e) for e in elts) and all(
                self.is_static(cond)
                for g in node.generators for cond in g.ifs)
        finally:
            self.statics -= added

    def _call_static(self, node: ast.Call) -> bool:
        args_static = (all(self.is_static(a) for a in node.args)
                       and all(self.is_static(k.value)
                               for k in node.keywords))
        if isinstance(node.func, ast.Name):
            if node.func.id in _STATIC_BUILTINS:
                return args_static
            # locally-defined helper returning a static type
            # (e.g. ``_pick_blocks(...) -> Tuple[int, int]``)
            fi = self.mod.by_name.get(node.func.id)
            if fi is not None and not isinstance(fi.node, ast.Lambda) \
                    and _ann_is_static(fi.node.returns):
                return args_static
        canon = _canonical(self.mod, node.func)
        if _is_np(canon):
            # host numpy over static values is trace-time constant folding
            return args_static
        if isinstance(node.func, ast.Attribute):
            # str/tuple methods on a static receiver (x.replace, x.split)
            return self.is_static(node.func.value) and args_static
        return False

    def _is_masky(self, value: ast.AST) -> bool:
        """Comparison-shaped values (potential boolean masks)."""
        if isinstance(value, ast.Compare):
            return True
        if isinstance(value, ast.BoolOp):
            return any(self._is_masky(v) for v in value.values)
        if isinstance(value, ast.Name):
            return value.id in self.mask_names
        if isinstance(value, ast.BinOp) and isinstance(
                value.op, (ast.BitAnd, ast.BitOr)):
            return (self._is_masky(value.left)
                    or self._is_masky(value.right))
        return False

    def bind(self, target: ast.AST, value: ast.AST) -> None:
        """Record one assignment for the static/mask name sets."""
        static = self.is_static(value)
        masky = self._is_masky(value) and not static
        if isinstance(target, ast.Name):
            if static:
                self.statics.add(target.id)
            else:
                self.statics.discard(target.id)
            if masky:
                self.mask_names.add(target.id)
            else:
                self.mask_names.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            if static:
                for e in target.elts:
                    if isinstance(e, ast.Name):
                        self.statics.add(e.id)
            else:
                vals = (value.elts if isinstance(value, (ast.Tuple, ast.List))
                        and len(value.elts) == len(target.elts) else None)
                for i, e in enumerate(target.elts):
                    if not isinstance(e, ast.Name):
                        continue
                    if vals is not None:
                        self.bind(e, vals[i])
                    elif isinstance(value, ast.Attribute) \
                            and value.attr == "shape":
                        self.statics.add(e.id)  # ``a, b = x.shape``
                    else:
                        self.statics.discard(e.id)


# --------------------------------------------------------------------------
# rule checks
# --------------------------------------------------------------------------

class _Checker:
    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.findings: List[Finding] = []

    def report(self, node: ast.AST, code: str, message: str,
               func: str = "") -> None:
        self.findings.append(Finding(
            path=self.mod.path, line=node.lineno, col=node.col_offset,
            code=code, message=message, func=func))

    # ---- module-level rules ------------------------------------------------

    def check_module_level(self) -> None:
        if not self.mod.graph_scope:
            return
        for stmt in self.mod.tree.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef, ast.Import, ast.ImportFrom)):
                continue
            for node in ast.walk(stmt):
                if isinstance(node, ast.Call):
                    canon = _canonical(self.mod, node.func)
                    if _is_jnp(canon):
                        self.report(node, "GL402",
                                    f"module-level '{_dotted(node.func)}' "
                                    "call bakes a device constant at import")
        for node in ast.walk(self.mod.tree):
            if isinstance(node, ast.Attribute) and node.attr == "float64":
                canon = _canonical(self.mod, node)
                if canon in ("numpy.float64", "jax.numpy.float64"):
                    self.report(node, "GL401",
                                f"'{_dotted(node)}' promotes to float64")
            if isinstance(node, ast.keyword) and node.arg == "dtype" \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id == "float":
                self.report(node.value, "GL401",
                            "dtype=float resolves to float64 on the host")
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "astype" and node.args \
                    and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id == "float":
                self.report(node, "GL401",
                            ".astype(float) promotes to float64")

    def check_jit_construction(self) -> None:
        """GL301/GL302/GL303 — apply to graph-scope modules everywhere
        (jit objects are built in host code)."""
        if not self.mod.graph_scope:
            return
        loops: List[ast.AST] = [n for n in ast.walk(self.mod.tree)
                                if isinstance(n, (ast.For, ast.While))]

        def inside_loop(node: ast.AST) -> bool:
            return any(loop.lineno <= node.lineno
                       <= getattr(loop, "end_lineno", loop.lineno)
                       for loop in loops)

        in_func_lines: List[Tuple[int, int]] = [
            (fi.node.lineno, getattr(fi.node, "end_lineno", fi.node.lineno))
            for fi in self.mod.funcs.values()
            if not isinstance(fi.node, ast.Lambda)]

        def inside_func(node: ast.AST) -> bool:
            return any(a <= node.lineno <= b for a, b in in_func_lines)

        for node in ast.walk(self.mod.tree):
            if not isinstance(node, ast.Call):
                continue
            canon = _canonical(self.mod, node.func) or ""
            if canon.rsplit(".", 1)[-1] == "jit" and canon.startswith("jax"):
                if inside_loop(node):
                    self.report(node, "GL302",
                                "jax.jit built inside a loop retraces "
                                "every iteration")
                if node.args and inside_func(node):
                    a = node.args[0]
                    is_partial = (isinstance(a, ast.Call) and
                                  (_canonical(self.mod, a.func) or ""
                                   ).endswith("partial"))
                    if isinstance(a, ast.Lambda) or is_partial:
                        self.report(node, "GL301",
                                    "jax.jit of a fresh lambda/partial — a "
                                    "new callable (and jit cache) per call")
            # immediate invocation: jax.jit(f)(x)
            if isinstance(node.func, ast.Call):
                inner = _canonical(self.mod, node.func.func) or ""
                if inner.rsplit(".", 1)[-1] == "jit" \
                        and inner.startswith("jax"):
                    self.report(node, "GL302",
                                "jax.jit(f)(...) discards the jit cache "
                                "after one call")
        # GL303: mutable defaults on static params of jit-decorated defs
        for fi in self.mod.funcs.values():
            if isinstance(fi.node, ast.Lambda) or not fi.jit:
                continue
            if not _jit_decorated(self.mod, fi.node):
                continue
            args = fi.node.args
            pos = args.posonlyargs + args.args
            defaults = args.defaults
            off = len(pos) - len(defaults)
            pairs = [(a, d) for a, d in zip(pos[off:], defaults)]
            pairs += [(a, d) for a, d in zip(args.kwonlyargs,
                                             args.kw_defaults) if d]
            for a, d in pairs:
                if a.arg in fi.static_params and isinstance(
                        d, (ast.List, ast.Dict, ast.Set)):
                    self.report(d, "GL303",
                                f"static arg '{a.arg}' has a mutable "
                                "(unhashable) default", fi.qualname)

    # ---- jit-scope rules ---------------------------------------------------

    def check_jit_scopes(self) -> None:
        for fi in self.mod.funcs.values():
            if fi.jit and not fi.host_pragma:
                self._check_one(fi)

    def _body_nodes(self, fi: FuncInfo):
        """Statements of this function, excluding nested function bodies
        (nested defs are checked as their own FuncInfos)."""
        own: List[ast.AST] = []
        nested = [f.node for f in self.mod.funcs.values()
                  if f.parent is fi]

        def walk(node):
            for child in ast.iter_child_nodes(node):
                if child in nested:
                    continue
                own.append(child)
                walk(child)
        body = fi.node.body if not isinstance(fi.node, ast.Lambda) \
            else [fi.node.body]
        for stmt in body:
            if isinstance(stmt, ast.AST):
                own.append(stmt)
                walk(stmt)
        return own

    def _inherited_statics(self, fi: FuncInfo) -> Set[str]:
        statics = set(fi.static_params)
        p = fi.parent
        while p is not None:
            statics |= p.static_params
            p = p.parent
        return statics

    def _check_one(self, fi: FuncInfo) -> None:
        env = _StaticEnv(self.mod, self._inherited_statics(fi))
        q = fi.qualname
        for node in self._body_nodes(fi):
            # dataflow first so later statements see earlier bindings
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    env.bind(t, node.value)
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                env.bind(node.target, node.value)
            elif isinstance(node, ast.AugAssign):
                if isinstance(node.target, ast.Name) \
                        and not env.is_static(node.value):
                    env.statics.discard(node.target.id)

            if isinstance(node, ast.Call):
                self._check_call(node, env, q)
            elif isinstance(node, (ast.If, ast.While)):
                self._check_branch(node, env, q)
            elif isinstance(node, ast.Subscript):
                self._check_subscript(node, env, q)
            elif isinstance(node, ast.BinOp):
                self._check_binop(node, env, q)

    def _check_call(self, node: ast.Call, env: _StaticEnv, q: str) -> None:
        canon = _canonical(self.mod, node.func)
        name = _dotted(node.func) or "<call>"
        if _is_np(canon) and not env._call_static(node):
            self.report(node, "GL101",
                        f"host numpy call '{name}' on traced values forces "
                        "a device sync", q)
            return
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("item", "tolist") \
                and not env.is_static(node.func.value):
            self.report(node, "GL102",
                        f"'.{node.func.attr}()' materializes a traced value "
                        "on the host", q)
        if canon in ("jax.device_get",):
            self.report(node, "GL102",
                        "jax.device_get inside a traced scope", q)
        if isinstance(node.func, ast.Name) \
                and node.func.id in ("float", "int", "bool") and node.args:
            if not all(env.is_static(a) for a in node.args):
                self.report(node, "GL103",
                            f"{node.func.id}() on a traced value is a "
                            "blocking host sync (and a retrace trap)", q)
        if isinstance(node.func, ast.Name) and node.func.id == "print":
            self.report(node, "GL104",
                        "host print() in jit scope runs at TRACE time only "
                        "— use jax.debug.print", q)
        if canon in _HOST_CLOCKS:
            self.report(node, "GL105",
                        f"host clock '{name}' in jit scope fires at TRACE "
                        "time — it measures tracing, not compute; time "
                        "around the jitted call instead", q)
        if canon is not None and canon.rsplit(".", 1)[-1] == "span" \
                and ("obs.trace" in canon or canon.endswith("obs.span")):
            self.report(node, "GL105",
                        "obs span in jit scope wraps TRACING, not device "
                        "compute — put the span around the jitted call "
                        "(device time comes from the profiler)", q)
        if canon is not None and canon.startswith("jax"):
            leaf = canon.rsplit(".", 1)[-1]
            if leaf in _DYNAMIC_SHAPE_OPS:
                self.report(node, "GL201",
                            f"'{name}' has a data-dependent output shape",
                            q)
            if leaf == "where" and len(node.args) == 1 and not node.keywords:
                self.report(node, "GL201",
                            "one-arg jnp.where is nonzero() in disguise "
                            "(dynamic output shape)", q)

    def _check_branch(self, node, env: _StaticEnv, q: str) -> None:
        test = node.test
        if env.is_static(test):
            return
        # only flag tests that visibly involve array computation — a bare
        # unresolved Name is more often a host flag than a tracer, and
        # the runtime leak/concretization checks catch those
        involves_array = False
        for sub in ast.walk(test):
            if isinstance(sub, ast.Call):
                canon = _canonical(self.mod, sub.func)
                if canon is not None and (canon.startswith("jax")):
                    involves_array = True
            if isinstance(sub, ast.Name) and sub.id in env.mask_names:
                involves_array = True
        if involves_array:
            kw = "while" if isinstance(node, ast.While) else "if"
            self.report(node, "GL203",
                        f"Python '{kw}' on a traced value — use jnp.where/"
                        "lax.cond (this either crashes under jit or burns "
                        "a recompile per value)", q)

    def _check_subscript(self, node: ast.Subscript, env: _StaticEnv,
                         q: str) -> None:
        sl = node.slice
        parts = sl.elts if isinstance(sl, ast.Tuple) else [sl]
        for p in parts:
            booly = isinstance(p, (ast.Compare, ast.BoolOp)) \
                and not env.is_static(p)
            if isinstance(p, ast.UnaryOp) and isinstance(p.op, ast.Invert):
                booly = booly or not env.is_static(p.operand)
            if isinstance(p, ast.Name) and p.id in env.mask_names:
                booly = True
            if isinstance(p, ast.Call):
                canon = _canonical(self.mod, p.func) or ""
                if canon.rsplit(".", 1)[-1].startswith("logical_"):
                    booly = True
            if booly:
                self.report(node, "GL202",
                            "boolean-mask indexing has a data-dependent "
                            "shape — use jnp.where or fixed-size top_k", q)
                return

    def _check_binop(self, node: ast.BinOp, env: _StaticEnv, q: str) -> None:
        if not isinstance(node.op, (ast.Add, ast.Sub, ast.Mult, ast.Div)):
            return
        for lit, other in ((node.left, node.right),
                           (node.right, node.left)):
            # List literals only: ``tup + tup`` is shape concatenation, the
            # idiomatic static-shape arithmetic this repo is full of
            if isinstance(lit, ast.List) and lit.elts \
                    and env.is_static(lit) and not env.is_static(other):
                self.report(node, "GL403",
                            "bare list literal in traced arithmetic "
                            "— wrap in jnp.asarray(..., dtype) to pin "
                            "dtype and rank", q)
                return


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

_iter_py_files = iter_py_files


def _apply_waivers(mod: ModuleInfo, findings: List[Finding]) -> List[Finding]:
    # the waivers themselves are linted: no reason -> GL001; bad code -> GL002
    return apply_waivers(mod.path, mod.waivers, findings, RULES,
                         prefix="GL", tool="graphlint")


def lint_paths(paths: Sequence[str],
               pkg_root: Optional[str] = None) -> List[Finding]:
    """Lint all .py files under ``paths``; returns findings (waived ones
    carry their waiver reason).  ``pkg_root`` anchors dotted module names
    for the cross-module closure (default: common parent of ``paths``)."""
    files = _iter_py_files(paths)
    if pkg_root is None:
        pkg_root = os.path.commonpath([os.path.abspath(p) for p in paths]) \
            if paths else "."
        if os.path.isfile(pkg_root):
            pkg_root = os.path.dirname(pkg_root)
    mods = [m for m in (load_module(f, pkg_root) for f in files)
            if m is not None]
    _propagate_jit(mods)
    findings: List[Finding] = []
    for mod in mods:
        c = _Checker(mod)
        c.check_module_level()
        c.check_jit_construction()
        c.check_jit_scopes()
        findings.extend(_apply_waivers(mod, c.findings))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.code))
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="graphlint",
        description="TPU-graph hygiene linter (rules: docs/ANALYSIS.md)")
    p.add_argument("paths", nargs="*", default=["mx_rcnn_tpu"],
                   help="files or directories to lint")
    p.add_argument("--json", action="store_true",
                   help="emit findings as JSON records")
    p.add_argument("--show-waived", action="store_true",
                   help="also print waived findings")
    p.add_argument("--list-rules", action="store_true")
    args = p.parse_args(argv)
    if args.list_rules:
        for code, desc in sorted(RULES.items()):
            print(f"{code}  {desc}")
        return 0
    # a typo'd path (or a package rename) must FAIL the gate, not lint
    # zero files and pass vacuously
    rc = check_paths_exist("graphlint", args.paths)
    if rc is not None:
        return rc
    findings = lint_paths(args.paths)
    active = [f for f in findings if f.waived is None]
    waived = [f for f in findings if f.waived is not None]
    shown = findings if args.show_waived else active
    if args.json:
        for f in shown:
            print(json.dumps({"path": f.path, "line": f.line,
                              "col": f.col + 1, "code": f.code,
                              "message": f.message, "func": f.func,
                              "waived": f.waived}))
    else:
        for f in shown:
            print(f.render())
    print(f"graphlint: {len(active)} finding(s), {len(waived)} waived",
          file=sys.stderr)
    return 1 if active else 0


if __name__ == "__main__":
    raise SystemExit(main())
