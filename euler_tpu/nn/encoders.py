"""Feature encoders (tf_euler/python/utils/encoders.py:32-171 parity).

`ShallowEncoder` combines an id-embedding lookup, a dense-feature projection,
and sparse-feature embeddings — the input stage of DeepWalk/LINE/TransX and
the GNN example models. The id table is declared with
`nn.with_partitioning` over the "model" mesh axis, so under a
`jax.sharding.Mesh` the table rows shard across devices and XLA inserts the
gather collectives (the TPU-native version of the reference's
parameter-server-partitioned embedding variables, layers.py:119-171).
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp

from euler_tpu.ops import gather
from euler_tpu.utils import trace


class Embedding(nn.Module):
    """Sharded id-embedding table: rows partitioned over the 'model' axis.

    row_init overrides the default normal(0.02) row initializer — KG
    models use this to start relation projections at identity/zero so
    TransR/D begin as TransE (the published training recipe). (Named
    row_init, not init: flax reserves Module.init.)"""

    vocab: int
    dim: int
    partitioned: bool = True
    row_init: object = None

    @nn.compact
    def __call__(self, ids: jnp.ndarray) -> jnp.ndarray:
        init = self.row_init or nn.initializers.normal(stddev=0.02)
        if self.partitioned:
            init = nn.with_partitioning(init, ("model", None))
        # rows padded to a 128 multiple: shardable by any practical model-axis
        # size and aligned to the TPU lane tile
        rows = -(-self.vocab // 128) * 128
        table = self.param("table", init, (rows, self.dim), jnp.float32)
        with trace.scope("embed"):
            return gather(jnp.asarray(table), jnp.clip(ids, 0, self.vocab - 1))


class SparseEmbedding(nn.Module):
    """Masked bag-of-ids embedding (layers.py SparseEmbedding parity).

    ids: int32[..., L] hashed into the table; mask: bool[..., L].
    combiner 'mean' | 'sum'.
    """

    vocab: int
    dim: int
    combiner: str = "mean"

    @nn.compact
    def __call__(self, ids, mask):
        emb = Embedding(self.vocab, self.dim, partitioned=True)(
            ids % self.vocab
        )
        m = mask.astype(jnp.float32)[..., None]
        total = jnp.sum(emb * m, axis=-2)
        if self.combiner == "sum":
            return total
        count = jnp.maximum(jnp.sum(m, axis=-2), 1.0)
        return total / count


class ShallowEncoder(nn.Module):
    """id-emb ⊕ dense-proj ⊕ sparse-emb combiner (encoders.py:32-171)."""

    dim: int
    max_id: int = 0  # 0 disables the id embedding
    sparse_vocabs: Sequence[int] = ()
    combiner: str = "add"  # add | concat
    use_feature_proj: bool = True

    @nn.compact
    def __call__(self, ids=None, dense=None, sparse=None):
        """ids: int32[...]; dense: f32[..., F]; sparse: [(ids, mask), ...]."""
        parts = []
        if self.max_id > 0 and ids is not None:
            parts.append(Embedding(self.max_id + 1, self.dim)(ids))
        if dense is not None and dense.shape[-1] > 0:
            parts.append(
                nn.Dense(self.dim)(dense) if self.use_feature_proj else dense
            )
        for vocab, (sids, smask) in zip(self.sparse_vocabs, sparse or ()):
            parts.append(SparseEmbedding(vocab, self.dim)(sids, smask))
        if not parts:
            raise ValueError("ShallowEncoder needs at least one input kind")
        if self.combiner == "concat":
            return jnp.concatenate(parts, axis=-1)
        out = parts[0]
        for p in parts[1:]:
            out = out + p
        return out
