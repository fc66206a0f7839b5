"""The repo's root entry points, run as a user runs them: `chip_smoke.py`
refuses a host without a TPU and ends on one result line of fixed keys,
and the lint lane's JSON line counts every checker."""

import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_device_entry_points_refuse_cpu(script):
    """No CPU fallback: on a host without a TPU chip_smoke.py exits
    non-zero in seconds, names the platform it found, and prints no
    result line."""
    r = subprocess.run(
        [sys.executable, script],
        cwd=_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "tpu" in r.stderr, r.stderr[-500:]
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]


def test_chip_smoke_result_line_has_the_contract_keys_only():
    """chip_smoke.py's last stdout line holds ok / device{platform, kind,
    count} and no other key, for a reader that refuses any other; the
    per-phase report goes on the `report:` line before it."""
    import jax

    sys.path.insert(0, _ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(_ROOT)
    devices = jax.devices()
    row = json.loads(chip_smoke.result_line(devices))
    assert row == {
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }


def test_lint_json_lane_per_checker_counts():
    """The lint lane's JSON line (graftlint v2): every registered checker
    must publish a count key — a checker silently dropping out of the
    counts dict means the lane stopped measuring it — and the full-run
    wall time rides along so regressions in analysis cost are visible."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "euler_tpu.tools.lint", "--json"],
        cwd=_ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row["ok"] is True, row
    expected = {
        "blocking-under-lock",
        "borrowed-buffer-escape",
        "determinism",
        "durable-write",
        "executor-deadlock",
        "hot-swap-reread",
        "jit-purity",
        "lock-discipline",
        "typed-error-retry",
        "unbounded-cache",
        "wire-protocol",
    }
    assert set(row["counts"]) == expected, row["counts"]
    assert all(v == 0 for v in row["counts"].values()), row["counts"]
    assert row["files"] > 100, row
    assert isinstance(row["wall_s"], float) and row["wall_s"] > 0, row
