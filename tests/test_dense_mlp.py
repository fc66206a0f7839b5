"""`DenseMLP`'s SwiGLU, whose backward is written by hand
(`layers/moe.py:swiglu`), on the CPU: its gradients are `jax.vjp`'s of
the plain form, bare and under `jax.checkpoint`, at a width that is a
multiple of 128 and one that is not; each `DenseMLP` traced counts
itself `mlp_grads_by_hand`; and the shared expert of a `SparseMoE`,
which calls the plain form too, still lowers to the plain form's
program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu.layers import moe
from euler_tpu.utils import trace


def _plain(x, w_gate, w_up, w_down):
    return moe._glu("silu", x, w_gate, w_up, w_down, jnp.matmul)


def _inputs(tokens, hidden, dim):
    k = jax.random.split(jax.random.PRNGKey(dim), 4)
    x = jax.random.normal(k[0], (tokens, hidden))
    return (
        x,
        0.3 * jax.random.normal(k[1], (hidden, dim)),
        0.3 * jax.random.normal(k[2], (hidden, dim)),
        0.3 * jax.random.normal(k[3], (dim, hidden)),
    )


@pytest.mark.parametrize("remat", [False, True], ids=["bare", "checkpoint"])
@pytest.mark.parametrize("dim", [256, 200])
def test_the_backward_by_hand_is_the_plain_forms_vjp(dim, remat):
    """Value equal to the last bit; the cotangents of x and the three
    weights equal to float32 summation order (the products' operands
    are float32 off the TPU)."""
    inputs = _inputs(48, 64, dim)
    wrap = jax.checkpoint if remat else (lambda f: f)

    def value_and_grads(fn):
        weigh = lambda *a: jnp.sum(jnp.sin(wrap(fn)(*a)))  # noqa: E731
        return jax.jit(jax.value_and_grad(weigh, argnums=(0, 1, 2, 3)))(*inputs)

    (got, got_grads), (want, want_grads) = value_and_grads(moe.swiglu), value_and_grads(_plain)
    assert float(got) == float(want)
    for g, w in zip(got_grads, want_grads):
        assert g.dtype == w.dtype == jnp.float32 and g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6 * float(jnp.max(jnp.abs(w))))


def test_each_dense_mlp_traced_counts_itself():
    """Two `DenseMLP`s in one traced step move `mlp_grads_by_hand` by
    two; an expert layer with a shared expert moves it by none."""
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    first, second = moe.DenseMLP(24), moe.DenseMLP(40)
    experts = moe.SparseMoE(num_experts=4, top_k=2, expert_dim=8, shared_dim=8)
    params = [m.init(jax.random.PRNGKey(i), x) for i, m in enumerate((first, second, experts))]

    def moved(fn):
        before = trace.counts().get("mlp_grads_by_hand", 0)
        jax.jit(jax.grad(fn)).lower(params, x)
        return trace.counts().get("mlp_grads_by_hand", 0) - before

    def two(p, x):
        return jnp.sum(second.apply(p[1], first.apply(p[0], x)[0])[0])

    assert moved(two) == 2
    assert moved(lambda p, x: jnp.sum(experts.apply(p[2], x)[0])) == 0


def test_the_shared_expert_keeps_the_plain_forms_program(monkeypatch):
    """A `SparseMoE`'s value and gradient lower to the same text as the
    same layer with its shared expert's `_glu(..., jnp.matmul)` written
    out inline, and to another where the shared expert takes `swiglu`:
    the backward by hand reaches the dense layer alone."""
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    for gated in (True, False):
        layer = moe.SparseMoE(
            num_experts=8, top_k=2, expert_dim=8, shared_dim=8, held=(0, 4), shared_gated=gated
        )
        params = layer.init(jax.random.PRNGKey(1), x)
        glu = moe._glu

        def lowered():  # a function of its own each time: JAX caches a trace by it
            step = jax.value_and_grad(lambda p, x: jnp.sum(layer.apply(p, x)[0]), (0, 1))
            return jax.jit(step).lower(params, x).as_text()

        def text(shared):
            def once(act, x, g, u, d, matmul):  # the shared expert's call, and no other
                if matmul is not jnp.matmul:
                    return glu(act, x, g, u, d, matmul)
                monkeypatch.setattr(moe, "_glu", glu)
                return shared(x, g, u, d)

            monkeypatch.setattr(moe, "_glu", once)
            try:
                return lowered()
            finally:
                monkeypatch.setattr(moe, "_glu", glu)

        inline = text(
            lambda x, g, u, d: jnp.matmul(jax.nn.silu(jnp.matmul(x, g)) * jnp.matmul(x, u), d)
        )
        assert lowered() == inline
        assert text(moe.swiglu) != inline
