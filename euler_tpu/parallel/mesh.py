"""Device mesh + sharding helpers.

The TPU-native replacement for the reference's distribution strategies
(SURVEY.md §2.3): synchronous data parallelism over a ('data',) mesh axis
replaces TF between-graph replication with parameter servers
(scripts/dist_tf_euler.sh:28-43); embedding-table model parallelism over the
('model',) axis replaces PS-partitioned embedding variables
(layers.py:119-171). Gradients all-reduce over ICI inside the jitted step —
XLA inserts the collectives from the shardings; there is no hand-written
NCCL/MPI equivalent.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"

shard_map = jax.shard_map


def make_mesh(
    n_devices: int | None = None, model: int = 1, devices=None
) -> Mesh:
    """(data, model) mesh over the first n_devices devices."""
    devs = list(devices if devices is not None else jax.devices())
    n = n_devices if n_devices is not None else len(devs)
    if n % model:
        raise ValueError(f"n_devices={n} not divisible by model={model}")
    grid = mesh_utils.create_device_mesh((n // model, model), devs[:n])
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch-major) axis across the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(batch, mesh: Mesh, batch_axis: int = 0):
    """device_put a MiniBatch/pytree: batch-dim sharding where divisible.

    Arrays whose `batch_axis` dim divides the data-axis size are split
    across it; everything else (scalars, ragged leftovers) is replicated.
    batch_axis=1 serves steps_per_call>1 training, where arrays are stacked
    [K_steps, batch, ...] and the scan axis K must stay unsharded.
    """
    ndata = mesh.shape[DATA_AXIS]
    ds = NamedSharding(
        mesh, P(*([None] * batch_axis), DATA_AXIS)
    )
    rep = replicated(mesh)

    def put(x):
        x = np.asarray(x) if not isinstance(x, jax.Array) else x
        if (
            getattr(x, "ndim", 0) >= batch_axis + 1
            and x.shape[batch_axis] % ndata == 0
        ):
            return jax.device_put(x, ds)
        return jax.device_put(x, rep)

    return jax.tree.map(put, batch)


def param_shardings(mesh: Mesh, params):
    """NamedShardings for a flax param tree (call BEFORE unboxing).

    Leaves declared with `nn.with_partitioning` (flax `Partitioned` boxes)
    get their spec (e.g. embedding tables over 'model'); plain leaves are
    replicated. The returned tree matches the *unboxed* params structure.
    """
    import flax.linen as nn

    specs = nn.get_partition_spec(params)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s if isinstance(s, P) else P()),
        specs,
        is_leaf=lambda s: isinstance(s, P) or s is None,
    )


def unbox_and_shard(mesh: Mesh, params):
    """Boxed flax params → (sharded plain params, shardings tree)."""
    import flax.linen as nn

    shardings = param_shardings(mesh, params)
    plain = nn.meta.unbox(params)
    return (
        jax.tree.map(lambda x, s: jax.device_put(x, s), plain, shardings),
        shardings,
    )


def shard_params(mesh: Mesh, params):
    return unbox_and_shard(mesh, params)[0]
