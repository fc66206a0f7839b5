"""The persistent compile cache can be placed from outside, and when it is
not, it sits at one fixed path inside the checkout (the path is part of the
cache key, so a directory that moves never hits)."""

import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "import json, jax;"
    "from euler_tpu.utils.compile_cache import configure_compile_cache;"
    "p = configure_compile_cache();"
    "print(json.dumps([p, jax.config.jax_compilation_cache_dir]))"
)


def _probe(env, cwd):
    r = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.splitlines()[-1])


def test_placed_cache_sets_nothing_in_code(monkeypatch, tmp_path):
    import jax

    from euler_tpu.utils.compile_cache import configure_compile_cache

    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)

    def refuse(*a, **kw):
        raise AssertionError(f"jax.config.update called: {a}")

    monkeypatch.setattr(jax.config, "update", refuse)
    assert configure_compile_cache() == placed


def test_unplaced_cache_is_one_path_inside_the_checkout(tmp_path):
    env = {
        k: v for k, v in os.environ.items()
        if k != "JAX_COMPILATION_CACHE_DIR"
    }
    want = os.path.join(_ROOT, ".jax_cache")
    # two processes, started from different directories: same path
    first = _probe(env, _ROOT)
    second = _probe(dict(env, PYTHONPATH=_ROOT), str(tmp_path))
    assert first == second == [want, want]
