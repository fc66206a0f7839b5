"""Staged device tables as arguments of jitted programs.

`DeviceGraphTables` and `DeviceFeatureCache` stage gigabytes into HBM
once. A jitted program that reads `self.adj` bakes the array into its
executable as a literal: compiled, hashed, cached and uploaded with it,
and never re-read after a `refresh_rows`. So the Estimator's programs
take the tables as an argument instead: `tables()` is the argument,
read from the owner at every dispatch, and `bind(tables)` is the owner
as the traced code sees it, so `sample`, `gather` and every `_draw_*`
stay written against `self.<table>`.

What counts as a table is every `jax.Array` attribute, whatever a
subclass calls it: there is no list of names to keep in step with
`__init__`.
"""

from __future__ import annotations

import jax


class StagedTables:
    def tables(self) -> dict:
        """Every staged device array of this object, by attribute name."""
        return {
            name: value
            for name, value in vars(self).items()
            if isinstance(value, jax.Array)
        }

    def bind(self, tables: dict):
        """A view of this object whose tables are `tables` (tracers,
        under `jit`); everything else is shared with the original."""
        view = object.__new__(type(self))
        view.__dict__.update(vars(self))
        view.__dict__.update(tables)
        return view

    def replicate(self, mesh) -> None:
        """Under a mesh every device reads the tables: lay out, once,
        each table that is not on the mesh's devices yet as a replicated
        array, in place. A table its owner sharded over the mesh stays as
        it is. (Left on one device, a table would be moved to the others
        by every dispatch of a mesh-sharded program.)"""
        from jax.sharding import NamedSharding, PartitionSpec

        replicated = NamedSharding(mesh, PartitionSpec())
        for name, table in self.tables().items():
            if table.sharding.device_set != replicated.device_set:
                setattr(self, name, jax.device_put(table, replicated))
