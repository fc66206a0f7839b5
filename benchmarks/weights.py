"""Weights from the configuration's `model.run_seed`, else from
`--seed`, made by the benchmark and handed to both sides; `run_seed`
says which of the two a run is made from.

A family lists its leaves as (path, shape, init, scale). The program gets
them nested as its `init_params`; the plain reference reads the same
flat dict made again from the same seed. One jitted call, on the device,
in float32 — the type the program trains in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def key_seed(seed: int) -> int:
    """`--seed` may pass 2**31; a PRNG seed here is 32 bits."""
    return int(seed) % (2**32 - 4)


def run_seed(config: dict, seed: int) -> int:
    """The seed a run's weights, its token or root batches and every
    sampling key (the Estimator's seed, the reference's `step_key`) are
    made from: the configuration's `model.run_seed` where it fixes one,
    beside `graph.graph_seed`, else `--seed`. The one place that reads
    the key, for the program's side and the reference's."""
    return int(config["model"].get("run_seed", seed))


def make_params(spec: list, seed: int) -> dict:
    """Flat {path: f32 array}. `init` is "normal" (scale = stddev) or
    "zeros"."""

    def init(key):
        out = {}
        for i, (path, shape, kind, scale) in enumerate(spec):
            if kind == "zeros":
                out[path] = jnp.zeros(shape, jnp.float32)
            else:
                out[path] = scale * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32
                )
        return out

    return jax.jit(init)(jax.random.PRNGKey(key_seed(seed)))


def nest(flat: dict) -> dict:
    """{"a/b/c": x} -> {"a": {"b": {"c": x}}}, the program's tree."""
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for name in parents:
            node = node.setdefault(name, {})
        node[leaf] = value
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    flat = {}
    for name, value in tree.items():
        path = f"{prefix}/{name}" if prefix else name
        if isinstance(value, dict):
            flat.update(flatten(value, path))
        else:
            flat[path] = value
    return flat


@jax.jit
def leaf_norms(flat: dict) -> dict:
    return {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for k, v in flat.items()
    }


@jax.jit
def change_norms(after: dict, before: dict) -> dict:
    return {
        k: jnp.sqrt(
            jnp.sum(
                jnp.square(
                    after[k].astype(jnp.float32) - before[k].astype(jnp.float32)
                )
            )
        )
        for k in after
    }
