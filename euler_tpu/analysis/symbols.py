"""Scoped symbol table + name canonicalization for the checkers.

Two services:

  dotted(node)          — an expression's dotted-name spelling
                          ("np.random.default_rng", "self._lock"), or None
                          for anything that isn't a plain name chain.
  ModuleSymbols         — per-module import-alias map and scope tree, so
                          checkers resolve "np.x" -> "numpy.x" and ask
                          "what is `self._lock` bound to in this class?"

Scope tracking is deliberately shallow: checkers here need to classify
bindings (lock / threading.local / set / function / class), not run full
type inference. Every classification is by the canonical dotted name of
the constructor call, so aliased imports (``import threading as t``)
resolve the same way.
"""

from __future__ import annotations

import ast

# constructors whose results the checkers treat specially
LOCK_TYPES = {
    "threading.Lock",
    "threading.RLock",
    "threading.Condition",
    "threading.Semaphore",
    "threading.BoundedSemaphore",
    "multiprocessing.Lock",
    "multiprocessing.RLock",
}
THREAD_LOCAL_TYPES = {"threading.local"}


def dotted(node: ast.AST) -> str | None:
    """Name / attribute chain as a dotted string, else None."""
    parts: list[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    return None


class ModuleSymbols:
    """Import aliases + per-class/module bindings for one module."""

    def __init__(self, tree: ast.Module):
        self.tree = tree
        # alias -> canonical module path ("np" -> "numpy",
        # "pjit" -> "jax.experimental.pjit.pjit")
        self.aliases: dict[str, str] = {}
        self._scan_imports(tree)
        # module-level name -> canonical constructor dotted name (for
        # Assign targets whose value is a Call), e.g. _LOCK -> threading.RLock
        self.global_ctors: dict[str, str] = {}
        # module-level functions and classes
        self.functions: dict[str, ast.FunctionDef] = {}
        self.classes: dict[str, ast.ClassDef] = {}
        for stmt in tree.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[stmt.name] = stmt
            elif isinstance(stmt, ast.ClassDef):
                self.classes[stmt.name] = stmt
            elif isinstance(stmt, ast.Assign) and isinstance(
                stmt.value, ast.Call
            ):
                ctor = self.canonical_of(stmt.value.func)
                if ctor:
                    for t in stmt.targets:
                        if isinstance(t, ast.Name):
                            self.global_ctors[t.id] = ctor

    def _scan_imports(self, tree: ast.Module):
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.aliases[a.asname or a.name] = (
                        f"{node.module}.{a.name}"
                    )

    def canonical(self, name: str | None) -> str | None:
        """Dotted name with its leading alias resolved: np.random.x ->
        numpy.random.x; jnp.sum -> jax.numpy.sum."""
        if not name:
            return None
        head, _, rest = name.partition(".")
        base = self.aliases.get(head, head)
        return f"{base}.{rest}" if rest else base

    def canonical_of(self, node: ast.AST) -> str | None:
        return self.canonical(dotted(node))

    # -- classification helpers -----------------------------------------

    def is_lock_ctor(self, call: ast.AST) -> bool:
        return (
            isinstance(call, ast.Call)
            and self.canonical_of(call.func) in LOCK_TYPES
        )

    def class_self_ctors(self, cls: ast.ClassDef) -> dict[str, str]:
        """self.<attr> -> canonical ctor name, for assignments anywhere in
        the class body (locks are usually bound in __init__ but lazily
        rebound elsewhere; scan all methods)."""
        out: dict[str, str] = {}
        for node in ast.walk(cls):
            if not isinstance(node, ast.Assign):
                continue
            if not isinstance(node.value, ast.Call):
                continue
            ctor = self.canonical_of(node.value.func)
            if not ctor:
                continue
            for t in node.targets:
                d = dotted(t)
                if d and d.startswith("self.") and d.count(".") == 1:
                    out[d[len("self."):]] = ctor
        return out

    def thread_local_names(self) -> set[str]:
        """Module-level and self.* names bound to threading.local() — the
        lock checkers must treat their attributes as thread-confined."""
        out = {
            name
            for name, ctor in self.global_ctors.items()
            if ctor in THREAD_LOCAL_TYPES
        }
        for cls in self.classes.values():
            for attr, ctor in self.class_self_ctors(cls).items():
                if ctor in THREAD_LOCAL_TYPES:
                    out.add(f"self.{attr}")
        return out


def func_param_names(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


def assigned_names(target: ast.AST) -> list[str]:
    """Plain names bound by an assignment target (tuples unpacked,
    attributes/subscripts skipped — those are mutations, not bindings)."""
    out: list[str] = []
    if isinstance(target, ast.Name):
        out.append(target.id)
    elif isinstance(target, (ast.Tuple, ast.List)):
        for elt in target.elts:
            out.extend(assigned_names(elt))
    elif isinstance(target, ast.Starred):
        out.extend(assigned_names(target.value))
    return out


def terminates(stmts: list[ast.stmt]) -> bool:
    """True when a statement block always leaves the enclosing block
    (return/raise/continue/break as the last effective statement) — used
    for path-sensitive analyses (key reuse, branch merging)."""
    if not stmts:
        return False
    last = stmts[-1]
    if isinstance(last, (ast.Return, ast.Raise, ast.Continue, ast.Break)):
        return True
    if isinstance(last, ast.If):
        return (
            bool(last.orelse)
            and terminates(last.body)
            and terminates(last.orelse)
        )
    return False
