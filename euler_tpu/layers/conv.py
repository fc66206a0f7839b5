"""Convolution layers over padded Blocks.

PyG-style conv contract from the reference (tf_euler/python/convolution/
conv.py:27-53): a conv consumes (x_dst, x_src, block) and produces new dst
embeddings. Shapes are static and aggregation is a masked sum over each dst
row's edges (euler_tpu.ops), in the cheapest form the block allows: a grid
block (fixed fanout) sums each run of `grid` consecutive message rows, and
where its sources are in order the messages are x_src itself — no gather,
no scatter; any other block (relation, COO) gathers by edge_src and
segment-sums by edge_dst.

Layers mirror tf_euler/python/convolution/: GCNConv (gcn_conv.py:32-54),
SAGEConv, GATConv, GINConv, GraphConv, APPNPConv, SGCNConv, TAGConv,
AGNNConv, DNAConv, ARMAConv, GatedGraphConv, RelationConv (rgcn).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from euler_tpu.dataflow.base import Block
from euler_tpu.ops import gather, grid_add, scatter_add, scatter_softmax
from euler_tpu.utils import trace


def edge_count(block: Block) -> jnp.ndarray:
    """f32[n_dst]: each dst row's valid edges, from the block mask."""
    ones = jnp.asarray(block.mask, jnp.float32)
    if block.grid:
        return grid_add(ones, block.grid)
    return scatter_add(ones, block.edge_dst, block.n_dst)


def degrees(block: Block, with_self: bool = True) -> jnp.ndarray:
    """In-batch degree of each dst row (+1 for the implicit self loop)."""
    deg_dst = edge_count(block)
    if with_self:
        deg_dst = deg_dst + 1.0
    return deg_dst


class Conv(nn.Module):
    """Base conv: subclasses implement __call__(x_dst, x_src, block).

    dtype is the flax compute dtype for the layer matmuls: params stay
    f32 while dtype=jnp.bfloat16 runs the MXU in bf16 (mixed precision).
    """

    out_dim: int = 0
    dtype: object = None

    def msg(self, x_src, block: Block):
        if block.src_in_order:
            return x_src
        return gather(x_src, block.edge_src)

    def agg_add(self, msgs, block: Block):
        """Masked sum of each dst row's messages. The form is chosen from
        what the block says of itself and tallied (`agg_grid` /
        `agg_scatter`; the program's `step.first_call` span carries both)."""
        if block.grid:
            trace.count("agg_grid")
            return grid_add(msgs, block.grid, mask=block.mask)
        trace.count("agg_scatter")
        return scatter_add(msgs, block.edge_dst, block.n_dst, mask=block.mask)


class GCNConv(Conv):
    """Symmetric-normalized GCN with implicit self-loops (gcn_conv.py:32-54).

    When the block carries true graph degrees (src_deg/dst_deg, attached by
    full-neighbor/whole-graph flows with gcn_norm=True) this is the exact
    Â = D̂^-1/2 (A+I) D̂^-1/2 propagation of the GCN paper; otherwise it
    falls back to the reference's in-batch degree approximation.
    """

    use_bias: bool = True

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        if block.dst_deg is not None and block.src_deg is not None:
            dd = block.dst_deg + 1.0  # +1: implicit self loop
            ds = block.src_deg + 1.0
            norm_e = jnp.power(
                gather(ds, block.edge_src) * gather(dd, block.edge_dst), -0.5
            )
            msgs = self.msg(x_src, block) * norm_e[:, None]
            h = self.agg_add(msgs, block) + x_dst / dd[:, None]
        else:
            deg_dst = degrees(block)  # [n_dst]
            # in sampled/padded flows each src slot feeds exactly one dst;
            # its in-batch degree is 1 (+1 self), matching the reference's
            # in-batch degree computation rather than global degrees
            norm_dst = jnp.power(deg_dst, -0.5)
            norm_src = jnp.power(2.0, -0.5)
            msgs = self.msg(x_src, block) * norm_src
            h = (self.agg_add(msgs, block) + x_dst) * norm_dst[:, None]
        return nn.Dense(dtype=self.dtype, features=self.out_dim, use_bias=self.use_bias)(h)


class SAGEConv(Conv):
    """GraphSAGE mean aggregator: W·[x_dst ‖ mean(x_src)] (sage_conv.py).

    The mean is the base class's masked sum over the block's valid-edge
    count, which on grid blocks is a reduce over each row's slots.
    """

    use_bias: bool = True

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        total = self.agg_add(self.msg(x_src, block), block)
        mean = total / jnp.maximum(edge_count(block), 1.0)[:, None]
        h = jnp.concatenate([x_dst, mean], axis=-1)
        return nn.Dense(dtype=self.dtype, features=self.out_dim, use_bias=self.use_bias)(h)


class GATConv(Conv):
    """Graph attention with masked segment softmax (gat_conv.py).

    improved=True adds the transformed dst embedding to the attention
    output (gat_conv.py apply_node `improved`). heads>1 runs multi-head
    attention; concat=True concatenates head outputs (out_dim must divide
    by heads), else heads are averaged — the reference builds the same
    thing from head_num parallel single-head convs (examples/gat/gat.py
    get_conv, head_num=4 concat improved=True for the published score).
    """

    negative_slope: float = 0.2
    improved: bool = False
    heads: int = 1
    concat: bool = True

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        if self.concat:
            if self.out_dim % self.heads:
                raise ValueError(
                    f"out_dim {self.out_dim} must divide heads {self.heads}"
                )
            per = self.out_dim // self.heads
        else:
            per = self.out_dim
        total = per * self.heads
        w = nn.Dense(dtype=self.dtype, features=total, use_bias=False)
        h_dst = w(x_dst)
        h_src = w(x_src)
        hd = h_dst.reshape(-1, self.heads, per)
        hs = h_src.reshape(-1, self.heads, per)
        # params live in f32 (flax convention); compute casts to dtype
        att_s = self.param(
            "att_src", nn.initializers.lecun_normal(), (self.heads, per)
        )
        att_d = self.param(
            "att_dst", nn.initializers.lecun_normal(), (self.heads, per)
        )
        a_src = jnp.einsum("nhp,hp->nh", hs, att_s.astype(hs.dtype))
        a_dst = jnp.einsum("nhp,hp->nh", hd, att_d.astype(hd.dtype))
        e = gather(a_src, block.edge_src) + gather(a_dst, block.edge_dst)
        e = nn.leaky_relu(e, self.negative_slope)  # [E, heads]
        alpha = scatter_softmax(
            e, block.edge_dst, block.n_dst, mask=block.mask
        )  # [E, heads]
        msgs = gather(hs, block.edge_src) * alpha[:, :, None]
        out = self.agg_add(
            msgs.reshape(-1, total), block
        ).reshape(-1, self.heads, per)
        out = (
            out.reshape(-1, total) if self.concat else out.mean(axis=1)
        )
        if not self.improved:
            return out
        skip = h_dst if self.concat else hd.mean(axis=1)
        return out + skip


class GINConv(Conv):
    """GIN: MLP((1+eps)·x_dst + Σ x_src) (gin_conv.py)."""

    eps_init: float = 0.0
    hidden_dim: int = 0

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        eps = self.param("eps", nn.initializers.constant(self.eps_init), ())
        agg = self.agg_add(self.msg(x_src, block), block)
        h = (1.0 + eps) * x_dst + agg
        hidden = self.hidden_dim or self.out_dim
        h = nn.Dense(dtype=self.dtype, features=hidden)(h)
        h = nn.relu(h)
        return nn.Dense(dtype=self.dtype, features=self.out_dim)(h)


class GraphConv(Conv):
    """W1·x_dst + W2·Σ x_src (graph_conv.py)."""

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        agg = self.agg_add(self.msg(x_src, block), block)
        return nn.Dense(dtype=self.dtype, features=self.out_dim)(x_dst) + nn.Dense(dtype=self.dtype, features=self.out_dim, use_bias=False
        )(agg)


class APPNPConv(Conv):
    """One APPNP propagation step: (1-α)·Â h + α·h0 (appnp_conv.py).

    The dense transform runs once outside (in the net); this layer only
    propagates, like the reference's conv.
    """

    alpha: float = 0.1

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block, x0_dst=None):
        deg_dst = degrees(block)
        norm_dst = jnp.power(deg_dst, -0.5)
        msgs = self.msg(x_src, block) * jnp.power(2.0, -0.5)
        agg = (self.agg_add(msgs, block) + x_dst) * norm_dst[:, None]
        x0 = x_dst if x0_dst is None else x0_dst
        return (1.0 - self.alpha) * agg + self.alpha * x0


class SGCNConv(Conv):
    """Simplified GCN: propagation only, no nonlinearity (sgcn_conv.py)."""

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        deg_dst = degrees(block)
        norm = jnp.power(deg_dst, -0.5)[:, None]
        msgs = self.msg(x_src, block) * jnp.power(2.0, -0.5)
        return (self.agg_add(msgs, block) + x_dst) * norm


class TAGConv(Conv):
    """Topology-adaptive GCN: W·[h0 ‖ Âh0] per hop step (tagcn_conv.py)."""

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        deg_dst = degrees(block)
        norm = jnp.power(deg_dst, -0.5)[:, None]
        prop = (self.agg_add(self.msg(x_src, block), block) + x_dst) * norm
        return nn.Dense(dtype=self.dtype, features=self.out_dim)(jnp.concatenate([x_dst, prop], axis=-1))


class AGNNConv(Conv):
    """Attention over cosine similarity with learned temperature (agnn_conv.py)."""

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        beta = self.param("beta", nn.initializers.ones, ())
        xn_dst = x_dst / (jnp.linalg.norm(x_dst, axis=-1, keepdims=True) + 1e-9)
        xn_src = x_src / (jnp.linalg.norm(x_src, axis=-1, keepdims=True) + 1e-9)
        cos = jnp.sum(
            gather(xn_src, block.edge_src) * gather(xn_dst, block.edge_dst),
            axis=-1,
        )
        alpha = scatter_softmax(
            beta * cos, block.edge_dst, block.n_dst, mask=block.mask
        )
        msgs = gather(x_src, block.edge_src) * alpha[:, None]
        return self.agg_add(msgs, block)


class ARMAConv(Conv):
    """ARMA_K filter, one GCS step per stack: σ(Â·x·W + x0·V), stacks
    averaged (arma_conv.py)."""

    stacks: int = 2

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        deg_dst = degrees(block)
        norm = jnp.power(deg_dst, -0.5)[:, None]
        prop = (self.agg_add(self.msg(x_src, block), block) + x_dst) * norm
        outs = []
        for _ in range(self.stacks):
            outs.append(
                nn.relu(
                    nn.Dense(dtype=self.dtype, features=self.out_dim, use_bias=False)(prop)
                    + nn.Dense(dtype=self.dtype, features=self.out_dim)(x_dst)
                )
            )
        return sum(outs) / self.stacks


class DNAConv(Conv):
    """Dot-product attention aggregation (dna_conv.py semantics adapted to
    hop blocks: query = dst, keys/values = src neighbors)."""

    heads: int = 1

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        d = self.out_dim
        q = nn.Dense(dtype=self.dtype, features=d, use_bias=False)(x_dst)
        kk = nn.Dense(dtype=self.dtype, features=d, use_bias=False)(x_src)
        v = nn.Dense(dtype=self.dtype, features=d, use_bias=False)(x_src)
        e = jnp.sum(
            gather(kk, block.edge_src) * gather(q, block.edge_dst), axis=-1
        ) / jnp.sqrt(jnp.asarray(d, jnp.float32))
        alpha = scatter_softmax(e, block.edge_dst, block.n_dst, mask=block.mask)
        msgs = gather(v, block.edge_src) * alpha[:, None]
        return self.agg_add(msgs, block) + q


class GatedGraphConv(Conv):
    """GRU state update from summed messages (gated_conv.py)."""

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        d = self.out_dim
        pad = d - x_dst.shape[-1]
        h = x_dst if pad == 0 else jnp.pad(x_dst, ((0, 0), (0, max(pad, 0))))
        h = h[:, :d]
        m = self.agg_add(
            nn.Dense(dtype=self.dtype, features=d, use_bias=False)(self.msg(x_src, block)), block
        )
        gru = nn.GRUCell(dtype=self.dtype, features=d)
        _, out = gru(h, m)
        return out


class RelationConv(Conv):
    """RGCN: W_0·x_dst + Σ_r mean_r(W_r·x_src) with optional basis
    decomposition (relation_conv.py). Call with per-relation blocks."""

    num_relations: int = 1
    num_bases: int = 0  # 0 → full per-relation weights

    @nn.compact
    def __call__(self, x_dst, x_src, rel_blocks):
        d_in = x_src.shape[-1]
        out = nn.Dense(dtype=self.dtype, features=self.out_dim)(x_dst)
        if self.num_bases:
            basis = self.param(
                "basis",
                nn.initializers.lecun_normal(),
                (self.num_bases, d_in, self.out_dim),
            )
            coef = self.param(
                "coef",
                nn.initializers.normal(0.1),
                (self.num_relations, self.num_bases),
            )
            weights = jnp.einsum("rb,bio->rio", coef, basis)
        else:
            weights = self.param(
                "rel_w",
                nn.initializers.lecun_normal(),
                (self.num_relations, d_in, self.out_dim),
            )
        for r, block in enumerate(rel_blocks):
            msgs = self.msg(x_src, block) @ weights[r]
            total = self.agg_add(msgs, block)
            out = out + total / jnp.maximum(edge_count(block), 1.0)[:, None]
        return out


class LGCNConv(Conv):
    """Learnable graph conv (LGCN, encoders.py:872-922 parity): per-channel
    top-k over each node's sampled neighbors, self feature prepended, then
    two 1-D convolutions over the length-(k+1) sequence; the dst embedding
    is the sequence's first position. Requires a grid block (fixed fanout),
    which is how the reference feeds it (sample_neighbor(nb_num))."""

    k: int = 3
    hidden_dim: int = 128

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        if not block.grid:
            raise ValueError("LGCNConv needs a grid (fixed-fanout) block")
        if block.grid < self.k:
            raise ValueError(
                f"LGCNConv k={self.k} needs fanout >= k, got {block.grid}"
            )
        d = block.grid
        feat = x_src[block.edge_src.reshape(-1, d)]  # [n_dst, d, F]
        # padded slots behave like default-feature (zero) neighbors, as the
        # reference's default-id feature fetch does
        feat = feat * block.mask.reshape(-1, d)[..., None].astype(feat.dtype)
        topk = jax.lax.top_k(jnp.swapaxes(feat, 1, 2), self.k)[0]
        topk = jnp.swapaxes(topk, 1, 2)  # [n_dst, k, F]
        seq = jnp.concatenate([x_dst[:, None, :], topk], axis=1)
        kernel = self.k // 2 + 1
        h = nn.Conv(dtype=self.dtype, features=self.hidden_dim, kernel_size=(kernel,), padding="VALID")(seq)
        h = nn.Conv(dtype=self.dtype, features=self.out_dim, kernel_size=(kernel,), padding="VALID")(h)
        return h[:, 0, :]


class GeniePathConv(Conv):
    """GeniePath lazy variant: GAT-style breadth attention + LSTM depth
    gate (GenieEncoder, encoders.py:238-291).

    The reference runs the depth LSTM over the stack of per-layer root
    representations; in a layer-stacked conv the equivalent recurrence is
    the LSTM state DERIVED FROM x_dst — the previous layer's output — so
    each layer gates the attention-aggregated breadth signal against the
    depth-so-far instead of a zero state (a zero carry would reduce this
    to a saturating one-step LSTM with no depth memory; measured 0.46 vs
    0.80 F1 on the cora-like quality probe)."""

    @nn.compact
    def __call__(self, x_dst, x_src, block: Block):
        d = self.out_dim
        w = nn.Dense(dtype=self.dtype, features=d, use_bias=False)
        h_src, h_dst = w(x_src), w(x_dst)
        a = nn.Dense(dtype=self.dtype, features=1, use_bias=False)
        e = nn.tanh(
            a(gather(h_src, block.edge_src) + gather(h_dst, block.edge_dst))
        )[:, 0]
        alpha = scatter_softmax(e, block.edge_dst, block.n_dst, mask=block.mask)
        breadth = self.agg_add(
            gather(h_src, block.edge_src) * alpha[:, None], block
        )
        lstm = nn.LSTMCell(dtype=self.dtype, features=d)
        carry = (
            nn.Dense(dtype=self.dtype, features=d, name="carry_c")(x_dst),
            nn.Dense(dtype=self.dtype, features=d, name="carry_h")(x_dst),
        )
        _, out = lstm(carry, breadth)
        return out
