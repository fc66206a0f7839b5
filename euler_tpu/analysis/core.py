"""graftlint framework: modules, findings, suppressions, baseline, runner.

The moving parts every checker shares:

  Module   — one parsed source file: AST, raw lines, per-line suppression
             map, and the lazily-built scoped symbol table.
  Project  — the set of Modules one lint run covers (checkers that
             cross-reference files, like wire-protocol, see all of them).
  Checker  — registry entry: ``check(project) -> [Finding]``.
  Report   — findings split into new / suppressed / baselined, plus
             stale baseline entries (entries that matched nothing — they
             rot unless surfaced).

Suppression comment (same line, or on a comment-only line the suppression
applies to the next code line):

    x = risky()  # graftlint: disable=det-unseeded-rng -- why it is fine

Baseline entries match on (check, path, symbol) — NOT line numbers, so
unrelated edits above a baselined finding don't invalidate it. `symbol`
is the enclosing function/class qualname (or the flagged name for
module-level findings), which is exactly the granularity a reviewer
reasons about.
"""

from __future__ import annotations

import ast
import json
import os
import re
import time
from dataclasses import dataclass, field


@dataclass(frozen=True)
class Finding:
    check: str  # specific id, e.g. "jit-np-call"
    checker: str  # owning checker group, e.g. "jit-purity"
    path: str  # repo-relative path
    line: int
    symbol: str  # enclosing qualname (baseline match key)
    message: str

    def key(self) -> tuple:
        return (self.check, self.path, self.symbol)

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.check}] {self.symbol}: {self.message}"


_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable=([\w\-,]+)\s*(?:--\s*(.*))?"
)


class Module:
    """One parsed source file plus its suppression map."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.suppressions = self._scan_suppressions()
        self._symbols = None  # lazy (symbols.ModuleSymbols)
        self._parents: dict | None = None

    # -- suppressions ----------------------------------------------------

    def _scan_suppressions(self) -> dict[int, set[str]]:
        """line number -> set of disabled check ids. A suppression on a
        comment-only line applies to the next non-blank code line."""
        out: dict[int, set[str]] = {}
        pending: set[str] | None = None
        for i, raw in enumerate(self.lines, start=1):
            stripped = raw.strip()
            m = _SUPPRESS_RE.search(raw)
            ids = (
                {c.strip() for c in m.group(1).split(",") if c.strip()}
                if m
                else None
            )
            if stripped.startswith("#"):
                if ids:
                    pending = (pending or set()) | ids
                continue
            if not stripped:
                continue
            here = set()
            if pending:
                here |= pending
                pending = None
            if ids:
                here |= ids
            if here:
                out[i] = out.get(i, set()) | here
        return out

    def suppressed(self, finding: Finding) -> bool:
        ids = self.suppressions.get(finding.line)
        if not ids:
            return False
        return bool(ids & {finding.check, finding.checker, "all"})

    # -- helpers checkers lean on ----------------------------------------

    @property
    def symbols(self):
        if self._symbols is None:
            from euler_tpu.analysis.symbols import ModuleSymbols

            self._symbols = ModuleSymbols(self.tree)
        return self._symbols

    def qualname_of(self, node: ast.AST) -> str:
        """Dotted name of the innermost function/class enclosing `node`
        (module-level nodes get "<module>")."""
        if self._parents is None:
            self._parents = {}
            for parent in ast.walk(self.tree):
                for child in ast.iter_child_nodes(parent):
                    self._parents[child] = parent
        parts: list[str] = []
        cur = self._parents.get(node)
        while cur is not None:
            if isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                parts.append(cur.name)
            cur = self._parents.get(cur)
        return ".".join(reversed(parts)) or "<module>"


class Project:
    def __init__(self, modules: list[Module], root: str):
        self.modules = modules
        self.root = root
        self.by_relpath = {m.relpath: m for m in modules}
        self._callgraph = None  # lazy (callgraph.ProjectCallGraph)

    def module(self, relpath: str) -> Module | None:
        return self.by_relpath.get(relpath)

    @property
    def callgraph(self):
        """The repo-wide import-resolved call graph (built once per run;
        every interprocedural checker shares it)."""
        if self._callgraph is None:
            from euler_tpu.analysis.callgraph import ProjectCallGraph

            self._callgraph = ProjectCallGraph(self)
        return self._callgraph


# -- registry ---------------------------------------------------------------


CHECKERS: dict[str, "Checker"] = {}


class Checker:
    """Base: subclasses set `name` and implement check(project)."""

    name: str = ""

    def check(self, project: Project) -> list[Finding]:
        raise NotImplementedError


def register(cls):
    inst = cls()
    if not inst.name:
        raise ValueError(f"checker {cls.__name__} has no name")
    CHECKERS[inst.name] = inst
    return cls


# -- project loading --------------------------------------------------------

_DEFAULT_EXCLUDE = ("__pycache__", ".git", "tests", "artifacts")


def repo_root() -> str:
    # euler_tpu/analysis/core.py -> repo root is two levels above the pkg
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def iter_py_files(paths: list[str], exclude=_DEFAULT_EXCLUDE):
    for p in paths:
        if os.path.isfile(p):
            yield p
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(
                d for d in dirnames if d not in exclude
            )
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def load_project(
    paths: list[str] | None = None,
    root: str | None = None,
    exclude=_DEFAULT_EXCLUDE,
) -> Project:
    """Default target: the euler_tpu package — the code the tier-1 gate
    guards."""
    root = root or repo_root()
    if paths is None:
        paths = [os.path.join(root, "euler_tpu")]
    modules = []
    for path in iter_py_files(paths, exclude):
        rel = os.path.relpath(path, root)
        with open(path, encoding="utf-8") as f:
            src = f.read()
        try:
            modules.append(Module(path, rel, src))
        except SyntaxError as e:  # surface, don't die mid-walk
            raise SyntaxError(f"{rel}: {e}") from e
    return Project(modules, root)


# -- baseline ---------------------------------------------------------------


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(__file__), "baseline.json")


def load_baseline(path: str | None = None) -> list[dict]:
    path = path or default_baseline_path()
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    entries = data.get("entries", data if isinstance(data, list) else [])
    for e in entries:
        missing = {"check", "path", "symbol", "reason"} - set(e)
        if missing:
            raise ValueError(f"baseline entry {e} missing {sorted(missing)}")
    return entries


def save_baseline(entries: list[dict], path: str | None = None):
    path = path or default_baseline_path()
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"entries": entries}, f, indent=2, sort_keys=True)
        f.write("\n")


# -- runner -----------------------------------------------------------------


@dataclass
class Report:
    findings: list[Finding] = field(default_factory=list)  # actionable
    suppressed: list[Finding] = field(default_factory=list)
    baselined: list[Finding] = field(default_factory=list)
    stale_baseline: list[dict] = field(default_factory=list)
    files: int = 0
    wall_s: float = 0.0  # full-run wall time (load + all checkers)

    @property
    def ok(self) -> bool:
        return not self.findings

    def counts(self) -> dict[str, int]:
        """Actionable finding count per checker group (the lane metric)."""
        out: dict[str, int] = {c: 0 for c in sorted(CHECKERS)}
        for f in self.findings:
            out[f.checker] = out.get(f.checker, 0) + 1
        return out

    def to_json(self) -> dict:
        return {
            "ok": self.ok,
            "files": self.files,
            "wall_s": round(self.wall_s, 4),
            "counts": self.counts(),
            "total": len(self.findings),
            "suppressed": len(self.suppressed),
            "baselined": len(self.baselined),
            "stale_baseline": self.stale_baseline,
            "findings": [
                {
                    "check": f.check,
                    "checker": f.checker,
                    "path": f.path,
                    "line": f.line,
                    "symbol": f.symbol,
                    "message": f.message,
                }
                for f in self.findings
            ],
        }


def run(
    project: Project,
    checks: list[str] | None = None,
    baseline: list[dict] | None = None,
) -> Report:
    t0 = time.monotonic()
    report = Report(files=len(project.modules))
    baseline = baseline or []
    matched_entries: set[int] = set()
    names = checks if checks is not None else sorted(CHECKERS)
    for name in names:
        if name not in CHECKERS:
            raise ValueError(
                f"unknown checker {name!r} (have: {sorted(CHECKERS)})"
            )
        for f in sorted(
            CHECKERS[name].check(project), key=lambda f: (f.path, f.line)
        ):
            mod = project.module(f.path)
            if mod is not None and mod.suppressed(f):
                report.suppressed.append(f)
                continue
            hit = None
            for i, e in enumerate(baseline):
                if (e["check"], e["path"], e["symbol"]) == f.key():
                    hit = i
                    break
            if hit is not None:
                matched_entries.add(hit)
                report.baselined.append(f)
            else:
                report.findings.append(f)
    report.stale_baseline = [
        e for i, e in enumerate(baseline) if i not in matched_entries
    ]
    report.wall_s = time.monotonic() - t0
    return report
