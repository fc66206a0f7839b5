"""Task heads: supervised and unsupervised (negative-sampling) models.

Mirrors the reference's model contract (tf_euler/python/mp_utils/base.py:24-95):
a model call returns (embedding, loss, metric_name, metric). `SuperviseModel`
is sigmoid cross-entropy + micro-F1 (base.py:24-49); `UnsuperviseModel` embeds
(src, pos, negs) with a shared GNN and optimizes sampled-softmax
cross-entropy, reporting MRR (base.py:52-95).
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp
import optax

from euler_tpu.dataflow.base import MiniBatch
from euler_tpu.nn.base_gnn import GNNNet
from euler_tpu.nn.metrics import micro_f1, mrr
from euler_tpu.utils import trace


class SuperviseModel(nn.Module):
    conv: str
    dims: Sequence[int]
    label_dim: int
    conv_kwargs: dict | None = None
    remat: bool = False  # rematerialize conv layers (GNNNet.remat)

    def setup(self):
        self.gnn = GNNNet(
            conv=self.conv, dims=self.dims, conv_kwargs=self.conv_kwargs,
            remat=self.remat,
        )
        self.out = nn.Dense(self.label_dim)

    def embed(self, batch: MiniBatch) -> jnp.ndarray:
        return self.gnn(batch)

    def __call__(self, batch: MiniBatch):
        emb = self.embed(batch)
        if batch.target_idx is not None:
            # whole-graph flows: only the target rows carry loss/metric
            emb = emb[batch.target_idx]
        with trace.scope("loss"):
            logits = self.out(emb)
            labels = batch.labels
            loss = optax.sigmoid_binary_cross_entropy(logits, labels)
            loss = jnp.mean(jnp.sum(loss, axis=-1))
            return emb, loss, "f1", micro_f1(labels, logits)


class UnsuperviseModel(nn.Module):
    """src/pos/neg contrastive head over a shared GNN encoder."""

    conv: str
    dims: Sequence[int]
    conv_kwargs: dict | None = None
    temperature: float = 1.0
    remat: bool = False  # rematerialize conv layers (GNNNet.remat)

    def setup(self):
        self.gnn = GNNNet(
            conv=self.conv, dims=self.dims, conv_kwargs=self.conv_kwargs,
            remat=self.remat,
        )

    def embed(self, batch: MiniBatch) -> jnp.ndarray:
        return self.gnn(batch)

    def __call__(self, src: MiniBatch, pos: MiniBatch, negs: MiniBatch):
        """negs hold B*N roots (N negatives per source)."""
        e_src = self.embed(src)  # [B, D]
        e_pos = self.embed(pos)  # [B, D]
        e_neg = self.embed(negs)  # [B*N, D]
        with trace.scope("loss"):
            b, d = e_src.shape
            e_neg = e_neg.reshape(b, -1, d)
            pos_logit = jnp.sum(e_src * e_pos, axis=-1) / self.temperature
            neg_logit = (
                jnp.einsum("bd,bnd->bn", e_src, e_neg) / self.temperature
            )  # [B], [B, N]
            logits = jnp.concatenate([pos_logit[:, None], neg_logit], axis=1)
            labels = jnp.zeros(b, dtype=jnp.int32)  # positive is column 0
            loss = jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(logits, labels)
            )
            return e_src, loss, "mrr", mrr(pos_logit, neg_logit)
