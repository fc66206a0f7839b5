"""The plain reference's training loop: loss, gradient, Adam, by hand.

Imports nothing of the program. `first_steps` follows the program's
first three steps from the same seed and returns the numbers that
`compare` holds the program to. `dtype` below float32 is the control:
the same mathematics with weights, activations and optimizer state held
in that type.
"""

from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp

B1, B2, EPS = 0.9, 0.999, 1e-8
STEPS = 3


def step_key(seed: int, step: int):
    """The sampling key of global step `step`, as the program's
    Estimator derives it for a device flow."""
    return jax.random.fold_in(jax.random.PRNGKey(seed + 2), step)


@functools.partial(
    jax.jit, static_argnames=("loss_fn", "lr", "dtype", "fault"),
    donate_argnums=(0, 1, 2),
)
def _step(params, m, v, tables, key, count, *, loss_fn, lr, dtype, fault):
    with jax.default_matmul_precision(
        "highest" if dtype == jnp.float32 else "default"
    ):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tables, key, dtype, fault
        )
    count = count + 1
    t = count.astype(jnp.float32)
    new_p, new_m, new_v = {}, {}, {}
    for k, g in grads.items():
        g32 = g.astype(jnp.float32)
        mk = (B1 * m[k].astype(jnp.float32) + (1 - B1) * g32).astype(dtype)
        vk = (B2 * v[k].astype(jnp.float32) + (1 - B2) * g32 * g32).astype(
            dtype
        )
        mhat = mk.astype(jnp.float32) / (1 - B1**t)
        vhat = vk.astype(jnp.float32) / (1 - B2**t)
        new_p[k] = (
            params[k].astype(jnp.float32) - lr * mhat / (jnp.sqrt(vhat) + EPS)
        ).astype(dtype)
        new_m[k], new_v[k] = mk, vk
    gnorm = {
        k: jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
        for k, g in grads.items()
    }
    return new_p, new_m, new_v, count, loss.astype(jnp.float32), gnorm


def first_steps(
    loss_fn, tables, spec: list, seed: int, lr: float,
    dtype=jnp.float32, fault: str = "", weights_seed: int | None = None,
) -> dict:
    """Weights from `spec` and `seed`, three steps on the batches of
    `seed`: the seed `weights.run_seed` gave the program's side
    (`proof.py` may make the weights from a `weights_seed` of their own,
    as it does the program's). Returns the three losses, the first
    gradient's norm per leaf, and the norm of each leaf's change after
    the three."""
    from weights import change_norms, key_seed, make_params

    if weights_seed is None:
        weights_seed = seed
    seed = key_seed(seed)
    params = {
        k: v.astype(dtype) for k, v in make_params(spec, weights_seed).items()
    }
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    v = {k: jnp.zeros_like(p) for k, p in params.items()}
    count = jnp.zeros((), jnp.int32)
    losses, first_grad = [], None
    for step in range(STEPS):
        params, m, v, count, loss, gnorm = _step(
            params, m, v, tables, step_key(seed, step), count,
            loss_fn=loss_fn, lr=lr, dtype=dtype, fault=fault,
        )
        losses.append(loss)
        if step == 0:
            first_grad = gnorm
    change = change_norms(params, make_params(spec, weights_seed))
    out = {
        "loss": [float(x) for x in losses],
        "grad_norm": {k: float(x) for k, x in first_grad.items()},
        "change_norm": {k: float(x) for k, x in change.items()},
    }
    del params, m, v
    return out


def worst_leaf_gap(got: dict, want: dict, leaves) -> float:
    """The widest gap between the two sides' norms of one leaf, against
    the reference's norm of that leaf or of the median leaf, whichever
    is larger."""
    floor = statistics.median(want[k] for k in leaves)
    return max(
        abs(got[k] - want[k]) / max(want[k], floor, 1e-30) for k in leaves
    )


def compare(got: dict, want: dict) -> dict:
    """The numbers `correct` is decided on, each a relative gap. Leaves
    whose reference gradient is under a thousandth of the median leaf's
    move by round-off alone under Adam: they stay in the gradient's gap
    and leave the change's."""
    leaves = sorted(want["grad_norm"])
    median = statistics.median(want["grad_norm"].values())
    moving = [k for k in leaves if want["grad_norm"][k] >= 1e-3 * median]
    out = {
        f"loss_step{i + 1}": abs(g - w) / abs(w)
        for i, (g, w) in enumerate(zip(got["loss"], want["loss"]))
    }
    out["grad_norm_gap"] = worst_leaf_gap(
        got["grad_norm"], want["grad_norm"], leaves
    )
    out["change_norm_gap"] = worst_leaf_gap(
        got["change_norm"], want["change_norm"], moving
    )
    return out
