"""jit-purity: host-side impurities inside traced (jit / shard_map) code.

Check ids:
  jit-py-branch   — Python ``if``/``while``/``for`` driven by a value
                    derived from traced arguments (concretization error at
                    trace time, or a silent retrace-per-value if the value
                    is a static arg in disguise)
  jit-np-call     — ``np.*`` applied to a traced value (numpy calls
                    concretize tracers; the jnp twin stays on device)
  jit-host-sync   — ``.item()`` / ``.tolist()`` / ``float()`` / ``int()``
                    / ``bool()`` on a traced value inside traced code
  jit-static-arg  — hazardous static_argnums/static_argnames declarations:
                    an index past the positional params, a static param
                    with an unhashable default, or a static param the body
                    treats as an array (jnp/np math on it)

Traced functions are found by declaration: ``@jax.jit`` (directly or via
``functools.partial``), ``jax.jit(f)`` / ``shard_map(f)`` / ``pjit(f)``
on a locally-defined function or lambda, and ``jax.lax`` control-flow
callbacks (scan/cond/while_loop/fori_loop/switch) whose body functions
are local. Nested defs inside a traced function inherit its taint
environment (closures over tracers).

Taint is flow-insensitive within a function (a name assigned from a
traced expression anywhere is traced everywhere) but attribute-aware:
``x.shape``, ``x.ndim``, ``x.dtype`` and ``len(x)`` / ``isinstance(x,…)``
/ ``x is None`` are static under tracing and never propagate taint —
that's what keeps the common "pad to the bucket" host logic clean.
"""

from __future__ import annotations

import ast

from euler_tpu.analysis.core import Checker, Finding, Module, register
from euler_tpu.analysis.symbols import assigned_names, dotted, func_param_names

CHECKER = "jit-purity"

_JIT_WRAPPERS = {
    "jax.jit",
    "jax.pjit",
    "jax.experimental.pjit.pjit",
    "jax.sharding.shard_map",
    "jax.shard_map",
}
_LAX_CALLBACK = {
    "jax.lax.scan",
    "jax.lax.cond",
    "jax.lax.while_loop",
    "jax.lax.fori_loop",
    "jax.lax.switch",
    "jax.lax.map",
    "jax.lax.associative_scan",
}
# attribute reads that are static at trace time
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "itemsize"}
# builtins/functions whose result is static regardless of arg taint
_STATIC_CALLS = {
    "len",
    "isinstance",
    "type",
    "hasattr",
    "getattr",
    "callable",
    "id",
    "repr",
}
_HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_HOST_SYNC_BUILTINS = {"float", "int", "bool", "complex"}


def _canon_jit(mod, node) -> str | None:
    """Canonical name if `node` spells a jit-like wrapper, unwrapping
    functools.partial(jax.jit, ...)."""
    if isinstance(node, ast.Call):
        canon = mod.symbols.canonical_of(node.func)
        if canon in ("functools.partial", "partial") and node.args:
            return _canon_jit(mod, node.args[0])
        return canon if canon in _JIT_WRAPPERS else None
    canon = mod.symbols.canonical_of(node)
    return canon if canon in _JIT_WRAPPERS else None


def _static_params(mod, deco_call: ast.Call | None, fn: ast.FunctionDef):
    """Names of params marked static on a jit call/decorator, plus any
    declaration-level findings about the marking itself."""
    statics: set[str] = set()
    findings: list[Finding] = []
    if deco_call is None:
        return statics, findings
    params = [
        p.arg for p in fn.args.posonlyargs + fn.args.args
    ]
    for kw in deco_call.keywords:
        if kw.arg == "static_argnums":
            idxs = []
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    idxs.append(e.value)
            for i in idxs:
                if i >= len(params) or i < -len(params):
                    findings.append(
                        Finding(
                            "jit-static-arg",
                            CHECKER,
                            mod.relpath,
                            kw.value.lineno,
                            mod.qualname_of(fn) or fn.name,
                            f"static_argnums index {i} is out of range for "
                            f"{fn.name}({', '.join(params)})",
                        )
                    )
                else:
                    statics.add(params[i])
        elif kw.arg == "static_argnames":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    if e.value not in func_param_names(fn):
                        findings.append(
                            Finding(
                                "jit-static-arg",
                                CHECKER,
                                mod.relpath,
                                e.lineno,
                                mod.qualname_of(fn) or fn.name,
                                f"static_argnames {e.value!r} is not a "
                                f"parameter of {fn.name}",
                            )
                        )
                    else:
                        statics.add(e.value)
    # unhashable defaults on static params retrace-or-throw at call time
    defaults = fn.args.defaults
    if defaults:
        for p, d in zip(params[-len(defaults):], defaults):
            if p in statics and isinstance(
                d, (ast.List, ast.Dict, ast.Set)
            ):
                findings.append(
                    Finding(
                        "jit-static-arg",
                        CHECKER,
                        mod.relpath,
                        d.lineno,
                        mod.qualname_of(fn) or fn.name,
                        f"static param {p!r} has an unhashable "
                        f"{type(d).__name__.lower()} default — jit statics "
                        "must be hashable",
                    )
                )
    return statics, findings


def _collect_traced(mod: Module):
    """(fn node, static param names, declaration findings) for every
    locally-declared traced function."""
    local_defs: dict[int, ast.FunctionDef] = {}
    by_name_stack: list[dict[str, ast.FunctionDef]] = []

    traced: dict[int, tuple[ast.FunctionDef, set[str]]] = {}
    findings: list[Finding] = []

    # index every def by enclosing scope so Name references resolve
    class Indexer(ast.NodeVisitor):
        def __init__(self):
            self.scopes = [{}]  # name -> def node

        def visit_FunctionDef(self, node):
            self.scopes[-1][node.name] = node
            local_defs[id(node)] = node
            self.scopes.append({})
            self.generic_visit(node)
            self.scopes.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_ClassDef(self, node):
            self.scopes.append({})
            self.generic_visit(node)
            self.scopes.pop()

    # pass 1: decorators
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for deco in node.decorator_list:
            canon = _canon_jit(mod, deco)
            if canon:
                call = deco if isinstance(deco, ast.Call) else None
                # functools.partial(jax.jit, static_argnums=...) carries
                # the statics on the partial call itself
                statics, dfind = _static_params(mod, call, node)
                traced[id(node)] = (node, statics)
                findings.extend(dfind)

    # pass 2: jit(f) / shard_map(f) / lax callbacks on local names+lambdas
    name_index: dict[str, ast.FunctionDef] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            name_index.setdefault(node.name, node)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        canon = _canon_jit(mod, node.func)
        is_lax = (
            mod.symbols.canonical_of(node.func) in _LAX_CALLBACK
        )
        if not canon and not is_lax:
            continue
        cand = node.args[0] if node.args else None
        targets: list[ast.AST] = [cand] if cand is not None else []
        if is_lax:
            # cond/switch take several branch callables
            targets = list(node.args)
        for t in targets:
            fn = None
            if isinstance(t, ast.Lambda):
                fn = t
            elif isinstance(t, ast.Name) and t.id in name_index:
                fn = name_index[t.id]
            if fn is None or id(fn) in traced:
                continue
            if isinstance(fn, ast.Lambda):
                traced[id(fn)] = (fn, set())
            else:
                statics, dfind = _static_params(
                    mod, node if canon else None, fn
                )
                traced[id(fn)] = (fn, statics)
                findings.extend(dfind)
    return list(traced.values()), findings


class _TaintChecker:
    def __init__(self, mod: Module, fn, statics: set[str]):
        self.mod = mod
        self.fn = fn
        self.statics = statics
        params = (
            func_param_names(fn)
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
            else [a.arg for a in fn.args.args]
        )
        self.tainted = {
            p for p in params if p not in statics and p not in ("self", "cls")
        }
        self.qual = (
            mod.qualname_of(fn)
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
            else f"{mod.qualname_of(fn)}.<lambda>"
        ) or getattr(fn, "name", "<lambda>")

    # -- expression taint -------------------------------------------------

    def taints(self, node: ast.AST) -> bool:
        """Does evaluating `node` read a traced value in a way that makes
        the RESULT traced (static accessors break the chain)?"""
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self.taints(node.value)
        if isinstance(node, ast.Call):
            fname = dotted(node.func) or ""
            tail = fname.rpartition(".")[2]
            if tail in _STATIC_CALLS:
                return False
            if tail in ("range", "enumerate", "zip") or fname == "range":
                return any(self.taints(a) for a in node.args)
            return (
                any(self.taints(a) for a in node.args)
                or any(self.taints(k.value) for k in node.keywords)
                or self.taints(node.func)
            )
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` is a static trace-time fact
            if (
                len(node.ops) == 1
                and isinstance(node.ops[0], (ast.Is, ast.IsNot))
                and isinstance(node.comparators[0], ast.Constant)
                and node.comparators[0].value is None
            ):
                return False
            return self.taints(node.left) or any(
                self.taints(c) for c in node.comparators
            )
        if isinstance(node, (ast.BoolOp,)):
            return any(self.taints(v) for v in node.values)
        if isinstance(node, ast.BinOp):
            return self.taints(node.left) or self.taints(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.taints(node.operand)
        if isinstance(node, ast.Subscript):
            return self.taints(node.value) or self.taints(node.slice)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.taints(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(
                self.taints(v)
                for v in list(node.keys) + list(node.values)
                if v is not None
            )
        if isinstance(node, ast.IfExp):
            return (
                self.taints(node.test)
                or self.taints(node.body)
                or self.taints(node.orelse)
            )
        if isinstance(node, ast.Starred):
            return self.taints(node.value)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            return any(
                self.taints(g.iter) for g in node.generators
            ) or self.taints(node.elt)
        if isinstance(node, ast.Slice):
            return any(
                self.taints(x)
                for x in (node.lower, node.upper, node.step)
                if x is not None
            )
        return False

    # -- propagation ------------------------------------------------------

    def propagate(self):
        body = self.fn.body
        stmts = body if isinstance(body, list) else [ast.Return(value=body)]
        for _ in range(5):
            before = len(self.tainted)
            for node in ast.walk(self.fn):
                if isinstance(node, ast.Assign):
                    if self.taints(node.value):
                        for t in node.targets:
                            self.tainted.update(assigned_names(t))
                elif isinstance(node, ast.AugAssign):
                    if self.taints(node.value) or self.taints(node.target):
                        self.tainted.update(assigned_names(node.target))
                elif isinstance(node, ast.AnnAssign) and node.value:
                    if self.taints(node.value):
                        self.tainted.update(assigned_names(node.target))
                elif isinstance(node, ast.For):
                    if self.taints(node.iter):
                        self.tainted.update(assigned_names(node.target))
                elif isinstance(node, ast.withitem):
                    if node.optional_vars is not None and self.taints(
                        node.context_expr
                    ):
                        self.tainted.update(
                            assigned_names(node.optional_vars)
                        )
            if len(self.tainted) == before:
                break
        return stmts

    # -- findings ---------------------------------------------------------

    def check(self) -> list[Finding]:
        self.propagate()
        out: list[Finding] = []

        def f(check, line, msg):
            out.append(
                Finding(check, CHECKER, self.mod.relpath, line, self.qual, msg)
            )

        for node in ast.walk(self.fn):
            if isinstance(node, (ast.If, ast.While)):
                if self.taints(node.test):
                    kind = "if" if isinstance(node, ast.If) else "while"
                    f(
                        "jit-py-branch",
                        node.lineno,
                        f"Python `{kind}` on a value derived from traced "
                        "args — concretizes the tracer (use jnp.where / "
                        "lax.cond, or mark the arg static)",
                    )
            elif isinstance(node, ast.For):
                if self.taints(node.iter) and not self._static_iter(node.iter):
                    f(
                        "jit-py-branch",
                        node.lineno,
                        "Python `for` over a traced value — iteration "
                        "count becomes data-dependent (use lax.scan / "
                        "lax.fori_loop)",
                    )
            elif isinstance(node, ast.Assert):
                if self.taints(node.test):
                    f(
                        "jit-py-branch",
                        node.lineno,
                        "assert on a traced value — concretizes the tracer "
                        "(use checkify or drop the assert)",
                    )
            elif isinstance(node, ast.Call):
                out.extend(self._check_call(node))
        # static params the body does math on → array-valued static arg
        for node in ast.walk(self.fn):
            if isinstance(node, ast.BinOp):
                for side in (node.left, node.right):
                    if (
                        isinstance(side, ast.Name)
                        and side.id in self.statics
                    ):
                        f(
                            "jit-static-arg",
                            node.lineno,
                            f"static param {side.id!r} used in arithmetic — "
                            "an array-valued static arg retraces per call "
                            "(and np arrays are unhashable)",
                        )
        return out

    def _static_iter(self, it: ast.AST) -> bool:
        """range(x.shape[0]) etc. — taints() already returns False for
        pure-static args, so anything reaching here is genuinely traced."""
        return False

    def _check_call(self, node: ast.Call) -> list[Finding]:
        out: list[Finding] = []
        canon = self.mod.symbols.canonical_of(node.func) or ""
        fname = dotted(node.func) or ""
        tail = fname.rpartition(".")[2]
        args_tainted = any(self.taints(a) for a in node.args) or any(
            self.taints(k.value) for k in node.keywords
        )

        def f(check, msg):
            out.append(
                Finding(
                    check, CHECKER, self.mod.relpath, node.lineno,
                    self.qual, msg,
                )
            )

        if (
            canon.startswith("numpy.")
            and not canon.startswith("numpy.random.SeedSequence")
            and args_tainted
        ):
            f(
                "jit-np-call",
                f"{fname}(...) applied to a traced value — numpy "
                "concretizes tracers; use the jax.numpy twin",
            )
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _HOST_SYNC_METHODS
        ):
            if self.taints(node.func.value):
                f(
                    "jit-host-sync",
                    f".{tail}() on a traced value inside traced code — "
                    "host sync / concretization error",
                )
        elif (
            isinstance(node.func, ast.Name)
            and node.func.id in _HOST_SYNC_BUILTINS
            and len(node.args) == 1
            and self.taints(node.args[0])
        ):
            f(
                "jit-host-sync",
                f"{node.func.id}() on a traced value inside traced code — "
                "concretization error at trace time",
            )
        return out


@register
class JitPurityChecker(Checker):
    name = CHECKER

    def check(self, project) -> list[Finding]:
        out: list[Finding] = []
        for mod in project.modules:
            traced, decl_findings = _collect_traced(mod)
            out.extend(decl_findings)
            for fn, statics in traced:
                out.extend(_TaintChecker(mod, fn, statics).check())
        return out
