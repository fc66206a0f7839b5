"""GNN networks over MiniBatch blocks.

`GNNNet` mirrors the reference's `BaseGNNNet.__call__` loop
(tf_euler/python/mp_utils/base_gnn.py:74-92): layer l transforms hops
[0, H-l) using one shared conv per layer, consuming one block per step, so
after H layers hop 0 carries the final root embeddings. `JKGNNNet` adds
jumping-knowledge concatenation (base_gnn.py:94-139).
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp

from euler_tpu.dataflow.base import MiniBatch
from euler_tpu.layers import get_conv
from euler_tpu.utils import trace


class GNNNet(nn.Module):
    """Stack of shared-per-layer convs over a fanout MiniBatch.

    conv: layer name from euler_tpu.layers.CONVS
    dims: output dim per layer; len(dims) must equal len(batch.blocks)
    remat: rematerialize each layer's forward on the backward pass
      (jax.checkpoint / nn.remat) — a fanout batch's activations scale as
      Σ_l B·Πk_i·F per layer, which dominates HBM for deep stacks or wide
      fanouts; remat trades one extra forward FLOP pass for dropping them,
      the standard TPU memory lever. Numerics are identical (asserted in
      tests/test_training.py).
    """

    conv: str
    dims: Sequence[int]
    activation: str = "relu"
    conv_kwargs: dict | None = None
    remat: bool = False

    def setup(self):
        cls = get_conv(self.conv)
        if self.remat:
            cls = nn.remat(cls, static_argnums=())
        kwargs = dict(self.conv_kwargs or {})
        self.convs = [cls(out_dim=d, **kwargs) for d in self.dims]

    def __call__(self, batch: MiniBatch) -> jnp.ndarray:
        num_hops = len(batch.blocks)
        assert len(self.dims) == num_hops, (
            f"dims {self.dims} must match hop count {num_hops}"
        )
        act = getattr(nn, self.activation)
        xs = list(batch.feats)
        with trace.scope("conv"):
            for layer in range(num_hops):
                conv = self.convs[layer]
                last = layer == num_hops - 1
                new_xs = []
                for hop in range(num_hops - layer):
                    h = conv(xs[hop], xs[hop + 1], batch.blocks[hop])
                    if not last:
                        h = act(h)
                    # zero out padded node slots so garbage never propagates
                    h = h * batch.masks[hop][: h.shape[0], None]
                    new_xs.append(h)
                xs = new_xs
        return xs[0]


class JKGNNNet(nn.Module):
    """Jumping-knowledge variant: concatenates every layer's hop-0 output
    (base_gnn.py:94-139) then projects."""

    conv: str
    dims: Sequence[int]
    out_dim: int
    activation: str = "relu"

    def setup(self):
        cls = get_conv(self.conv)
        self.convs = [cls(out_dim=d) for d in self.dims]
        self.proj = nn.Dense(self.out_dim)

    def __call__(self, batch: MiniBatch) -> jnp.ndarray:
        num_hops = len(batch.blocks)
        act = getattr(nn, self.activation)
        xs = list(batch.feats)
        collected = []
        with trace.scope("conv"):
            for layer in range(num_hops):
                conv = self.convs[layer]
                new_xs = []
                for hop in range(num_hops - layer):
                    h = conv(xs[hop], xs[hop + 1], batch.blocks[hop])
                    h = act(h)
                    h = h * batch.masks[hop][: h.shape[0], None]
                    new_xs.append(h)
                xs = new_xs
                collected.append(xs[0])
            return self.proj(jnp.concatenate(collected, axis=-1))
