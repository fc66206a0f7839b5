"""Without a TPU the benchmark prints no result and exits non-zero; so
it does in a directory that holds only the benchmark's own files."""

import os
import shutil
import subprocess
import sys

import run as harness

CELL = "sage-products-id.train-device"


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600,
    )


def test_refuses_without_a_tpu():
    out = _run(harness.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        harness.HERE, tmp_path / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".cache"),
    )
    out = _run(tmp_path, "--rehearse")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
