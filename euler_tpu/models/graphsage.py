"""GraphSAGE — the flagship model family (examples/graphsage parity).

Supervised and unsupervised variants over sampled-fanout dataflows, with an
optional ShallowEncoder input stage (id embedding sharded over the 'model'
mesh axis + dense-feature projection), matching the reference's
GraphSageEncoder composition (examples/graphsage/graphsage.py +
utils/encoders.py SageEncoder).
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp
import optax

from euler_tpu.dataflow.base import MiniBatch
from euler_tpu.nn.base_gnn import GNNNet
from euler_tpu.nn.encoders import ShallowEncoder
from euler_tpu.nn.metrics import micro_f1, mrr
from euler_tpu.utils import trace


class _EncodedGNN(nn.Module):
    """ShallowEncoder applied per hop, then the conv stack."""

    conv: str
    dims: Sequence[int]
    encoder_dim: int = 0  # 0 → raw features
    max_id: int = 0
    conv_kwargs: dict | None = None
    remat: bool = False  # rematerialize conv layers (GNNNet.remat)

    def setup(self):
        if self.encoder_dim:
            self.encoder = ShallowEncoder(
                dim=self.encoder_dim, max_id=self.max_id
            )
        self.gnn = GNNNet(
            conv=self.conv, dims=self.dims, conv_kwargs=self.conv_kwargs,
            remat=self.remat,
        )

    def __call__(self, batch: MiniBatch) -> jnp.ndarray:
        if not self.encoder_dim:
            return self.gnn(batch)
        ids = batch.hop_ids or (None,) * len(batch.feats)
        feats = tuple(
            self.encoder(
                ids=i if self.max_id else None, dense=f
            )
            for i, f in zip(ids, batch.feats)
        )
        return self.gnn(batch.replace(feats=feats))


class GraphSAGESupervised(nn.Module):
    dims: Sequence[int]
    label_dim: int
    encoder_dim: int = 0
    max_id: int = 0
    conv: str = "sage"
    conv_kwargs: dict | None = None
    remat: bool = False

    def setup(self):
        self.net = _EncodedGNN(
            conv=self.conv,
            dims=self.dims,
            encoder_dim=self.encoder_dim,
            max_id=self.max_id,
            conv_kwargs=self.conv_kwargs,
            remat=self.remat,
        )
        self.out = nn.Dense(self.label_dim)

    def embed(self, batch: MiniBatch) -> jnp.ndarray:
        return self.net(batch)

    def __call__(self, batch: MiniBatch):
        emb = self.embed(batch)
        with trace.scope("loss"):
            logits = self.out(emb)
            loss = optax.sigmoid_binary_cross_entropy(logits, batch.labels)
            loss = jnp.mean(jnp.sum(loss, axis=-1))
            return emb, loss, "f1", micro_f1(batch.labels, logits)


class GraphSAGEUnsupervised(nn.Module):
    dims: Sequence[int]
    encoder_dim: int = 0
    max_id: int = 0
    conv: str = "sage"
    conv_kwargs: dict | None = None
    remat: bool = False

    def setup(self):
        self.net = _EncodedGNN(
            conv=self.conv,
            dims=self.dims,
            encoder_dim=self.encoder_dim,
            max_id=self.max_id,
            conv_kwargs=self.conv_kwargs,
            remat=self.remat,
        )

    def embed(self, batch: MiniBatch) -> jnp.ndarray:
        return self.net(batch)

    def __call__(self, src: MiniBatch, pos: MiniBatch, negs: MiniBatch):
        e_src = self.embed(src)
        e_pos = self.embed(pos)
        e_neg = self.embed(negs)
        with trace.scope("loss"):
            b, d = e_src.shape
            e_neg = e_neg.reshape(b, -1, d)
            pos_logit = jnp.sum(e_src * e_pos, axis=-1)
            neg_logit = jnp.einsum("bd,bnd->bn", e_src, e_neg)
            logits = jnp.concatenate([pos_logit[:, None], neg_logit], axis=1)
            labels = jnp.zeros(b, dtype=jnp.int32)
            loss = jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(logits, labels)
            )
            return e_src, loss, "mrr", mrr(pos_logit, neg_logit)
