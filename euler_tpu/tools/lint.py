"""graftlint CLI — the standalone lint lane.

    python -m euler_tpu.tools.lint                # human-readable findings
    python -m euler_tpu.tools.lint --json         # one JSON line (lane
                                                  # contract: counts per
                                                  # checker + findings)
    python -m euler_tpu.tools.lint --baseline P   # alternate baseline file
    python -m euler_tpu.tools.lint --write-baseline  # absorb current
                                                  # findings (each entry
                                                  # needs a reason edited in)
    python -m euler_tpu.tools.lint path/a.py dir/ # explicit targets
    python -m euler_tpu.tools.lint --changed-only # full analysis, but only
                                                  # report findings in files
                                                  # changed vs git HEAD

Exit codes: 0 = clean (no non-baselined findings), 1 = findings,
2 = usage/internal error. Stale baseline entries (matching nothing) are
reported but do not fail the run — they fail the tier-1 gate instead
(tests/test_lint.py), where a human is already looking.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def changed_files(root: str) -> set[str]:
    """Repo-relative paths changed vs HEAD: tracked modifications
    (staged or not) plus untracked files. Raises OSError when git is
    unavailable or `root` is not a work tree — --changed-only is a
    git-backed mode, silently linting nothing would read as "clean"."""
    out: set[str] = set()
    diff = subprocess.run(
        ["git", "-C", root, "diff", "--name-only", "HEAD"],
        capture_output=True,
        text=True,
    )
    if diff.returncode != 0:
        raise OSError(
            f"git diff failed under {root}: {diff.stderr.strip()}"
        )
    out.update(ln.strip() for ln in diff.stdout.splitlines() if ln.strip())
    status = subprocess.run(
        ["git", "-C", root, "status", "--porcelain"],
        capture_output=True,
        text=True,
    )
    if status.returncode != 0:
        raise OSError(
            f"git status failed under {root}: {status.stderr.strip()}"
        )
    for ln in status.stdout.splitlines():
        if len(ln) < 4:
            continue
        path = ln[3:].strip()
        # renames print "old -> new"; the new path is the one on disk
        if " -> " in path:
            path = path.split(" -> ", 1)[1]
        out.add(path.strip('"'))
    return {os.path.normpath(p) for p in out}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m euler_tpu.tools.lint", description=__doc__
    )
    ap.add_argument(
        "paths",
        nargs="*",
        help="files/dirs to lint (default: euler_tpu/)",
    )
    ap.add_argument("--json", action="store_true", help="machine output")
    ap.add_argument(
        "--baseline",
        default=None,
        help="baseline file (default: euler_tpu/analysis/baseline.json)",
    )
    ap.add_argument(
        "--no-baseline",
        action="store_true",
        help="ignore the baseline (show everything)",
    )
    ap.add_argument(
        "--checks",
        default=None,
        help="comma-separated checker names (default: all)",
    )
    ap.add_argument(
        "--changed-only",
        action="store_true",
        help="analyze the whole repo (cross-module facts need every file)"
        " but report only findings in files changed vs git HEAD",
    )
    ap.add_argument(
        "--write-baseline",
        action="store_true",
        help="write current findings into the baseline file (reasons are"
        " stamped TODO — edit them before committing)",
    )
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from euler_tpu import analysis

    try:
        project = analysis.load_project(args.paths or None)
        baseline = (
            []
            if args.no_baseline
            else analysis.load_baseline(args.baseline)
        )
        checks = (
            [c.strip() for c in args.checks.split(",") if c.strip()]
            if args.checks
            else None
        )
        report = analysis.run(project, checks=checks, baseline=baseline)
        if args.changed_only:
            # The project is always loaded and analyzed WHOLE — the
            # interprocedural facts (call graph, executor ownership,
            # swap-name sets) are wrong on a partial view. Scoping is a
            # reporting filter only: exit code reflects changed files.
            changed = changed_files(project.root)
            report.findings = [
                f for f in report.findings
                if os.path.normpath(f.path) in changed
            ]
    except (ValueError, SyntaxError, OSError) as e:
        print(f"graftlint: error: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        from euler_tpu.analysis.core import save_baseline

        entries = list(baseline)
        known = {(e["check"], e["path"], e["symbol"]) for e in entries}
        for f in report.findings:
            if f.key() not in known:
                known.add(f.key())
                entries.append(
                    {
                        "check": f.check,
                        "path": f.path,
                        "symbol": f.symbol,
                        "reason": f"TODO: justify — {f.message[:80]}",
                    }
                )
        entries.sort(key=lambda e: (e["path"], e["check"], e["symbol"]))
        save_baseline(entries, args.baseline)
        print(f"baseline: {len(entries)} entries written")
        return 0

    if args.json:
        print(json.dumps(report.to_json(), sort_keys=True))
        return 0 if report.ok else 1

    for f in report.findings:
        print(f.render())
    if report.stale_baseline:
        print(
            f"warning: {len(report.stale_baseline)} stale baseline entries"
            " match no current finding:",
            file=sys.stderr,
        )
        for e in report.stale_baseline:
            print(
                f"  {e['path']} [{e['check']}] {e['symbol']}",
                file=sys.stderr,
            )
    counts = report.counts()
    summary = ", ".join(f"{k}={v}" for k, v in counts.items())
    print(
        f"graftlint: {len(report.findings)} finding(s) over {report.files}"
        f" files ({summary}; {len(report.baselined)} baselined,"
        f" {len(report.suppressed)} suppressed)"
    )
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
