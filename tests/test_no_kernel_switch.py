"""One aggregation path and one paged-read path: nothing the user can
set selects another, and nothing on the import path loads a kernel
library. Each test here fails on a tree that still reads the variable."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu.dataflow.base import fanout_block
from euler_tpu.layers import get_conv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GONE = "EULER_TPU_PALLAS"  # the variable the deleted switch read


def _run(code: str, **env) -> str:
    done = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO,
        env={**os.environ, "PYTHONPATH": REPO, **env},
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return done.stdout


def test_importing_the_package_loads_no_kernel_library_and_no_switch():
    out = _run(
        "import sys\n"
        "import euler_tpu, euler_tpu.ops, euler_tpu.layers\n"
        "import euler_tpu.dataflow.device, euler_tpu.retrieval.topk\n"
        "print(sorted(m for m in sys.modules"
        " if m.startswith('jax.experimental.pallas')))\n"
        "print(sorted(n for n in ('set_pallas', 'pallas_mode',"
        " 'gather_weighted_sum') if hasattr(euler_tpu.ops, n)))\n",
        **{GONE: "pallas"},
    )
    assert out.split("\n")[:2] == ["[]", "[]"], out


def lowered_text(conv: str) -> str:
    """StableHLO of one conv, forward and backward, on a grid block."""
    rng = np.random.default_rng(0)
    n_dst, k, f = 16, 4, 32
    mask = rng.random((n_dst, k)) > 0.3
    block = fanout_block(n_dst, k, np.ones((n_dst, k), np.float32), mask)
    x_dst = jnp.asarray(rng.normal(size=(n_dst, f)), jnp.float32)
    x_src = jnp.asarray(rng.normal(size=(n_dst * k, f)), jnp.float32)
    layer = get_conv(conv)(out_dim=f)
    params = layer.init(jax.random.PRNGKey(0), x_dst, x_src, block)

    def loss(params, x_dst, x_src):
        return layer.apply(params, x_dst, x_src, block).sum()

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        params, x_dst, x_src
    ).as_text()


@pytest.mark.parametrize("conv", ["sage", "gat"])
def test_the_old_variable_changes_nothing_a_conv_lowers_to(conv):
    """At the parent `auto` sent a grid block down a `take` + `einsum`
    branch on the CPU; now the text is the same with it set."""
    with_it_set = _run(
        "import sys; sys.path.insert(0, 'tests')\n"
        "from test_no_kernel_switch import lowered_text\n"
        f"sys.stdout.write(lowered_text({conv!r}))\n",
        **{GONE: "auto"},
    )
    text = lowered_text(conv)
    assert "reduce_window" in text
    assert with_it_set == text
