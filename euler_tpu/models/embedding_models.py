"""Shallow embedding models: DeepWalk / node2vec / LINE
(examples/deepwalk, examples/line parity).

All are target/context embedding tables trained with sampled-softmax
negative sampling; tables are sharded over the 'model' mesh axis. The walk
and pair generation run host-side (euler_tpu.dataflow.walk); the device step
is pure embedding math — gathers + batched dot products on the MXU.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax

from euler_tpu.dataflow.walk import gen_pair
from euler_tpu.nn.encoders import Embedding
from euler_tpu.nn.metrics import mrr
from euler_tpu.utils import trace


class SkipGramModel(nn.Module):
    """Target/context tables + sampled softmax (DeepWalk & LINE-2nd).

    Batch: dict(src int32[B], pos int32[B], negs int32[B, N], mask bool[B]).
    """

    num_nodes: int
    dim: int = 128
    shared_context: bool = False  # True → LINE first-order (one table)

    def setup(self):
        self.target = Embedding(self.num_nodes + 1, self.dim)
        if not self.shared_context:
            self.ctx_table = Embedding(self.num_nodes + 1, self.dim)

    def embed(self, ids: jnp.ndarray) -> jnp.ndarray:
        return self.target(ids)

    def _ctx(self, ids):
        return self.target(ids) if self.shared_context else self.ctx_table(ids)

    def __call__(self, batch):
        src, pos, negs = batch["src"], batch["pos"], batch["negs"]
        mask = batch["mask"].astype(jnp.float32)
        e_src = self.target(src)  # [B, D]
        e_pos = self._ctx(pos)  # [B, D]
        e_neg = self._ctx(negs)  # [B, N, D]
        with trace.scope("loss"):
            pos_logit = jnp.sum(e_src * e_pos, axis=-1)
            neg_logit = jnp.einsum("bd,bnd->bn", e_src, e_neg)
            logits = jnp.concatenate([pos_logit[:, None], neg_logit], axis=1)
            labels = jnp.zeros(src.shape[0], dtype=jnp.int32)
            per = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            )
            loss = jnp.sum(per * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            return e_src, loss, "mrr", mrr(pos_logit, neg_logit)


def deepwalk_batches(
    graph,
    batch_size: int,
    walk_len: int = 5,
    window: int = 2,
    num_negs: int = 5,
    edge_types=None,
    p: float = 1.0,
    q: float = 1.0,
    node_type: int = -1,
    rng=None,
):
    """Walk → skipgram pairs → (src, pos, negs, mask) batch source.

    p/q ≠ 1 gives node2vec biased walks (random_walk_op.cc:27-90).
    """
    rng = rng if rng is not None else np.random.default_rng()

    def fn():
        roots = graph.sample_node(batch_size, node_type, rng=rng)
        walks = graph.random_walk(
            roots, edge_types, walk_len=walk_len, p=p, q=q, rng=rng
        )
        pairs, mask = gen_pair(walks, window, window)
        negs = graph.sample_node(len(pairs) * num_negs, node_type, rng=rng)
        return (
            {
                "src": pairs[:, 0].astype(np.int64).astype(np.int32),
                "pos": pairs[:, 1].astype(np.int64).astype(np.int32),
                "negs": negs.astype(np.int64)
                .astype(np.int32)
                .reshape(len(pairs), num_negs),
                "mask": mask,
            },
        )

    return fn


def line_batches(
    graph,
    batch_size: int,
    num_negs: int = 5,
    edge_type: int = -1,
    rng=None,
):
    """Edge-sampling batch source for LINE (examples/line)."""
    rng = rng if rng is not None else np.random.default_rng()

    def fn():
        edges = graph.sample_edge(batch_size, edge_type, rng=rng)
        negs = graph.sample_node(batch_size * num_negs, -1, rng=rng)
        return (
            {
                "src": edges[:, 0].astype(np.int64).astype(np.int32),
                "pos": edges[:, 1].astype(np.int64).astype(np.int32),
                "negs": negs.astype(np.int64)
                .astype(np.int32)
                .reshape(batch_size, num_negs),
                "mask": np.ones(batch_size, dtype=bool),
            },
        )

    return fn
