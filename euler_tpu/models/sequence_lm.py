"""Decoder-only language models as `Estimator` models: one decoder,
`DecoderLM`, whose layers differ in their mixer and in whether their
feed-forward is dense, and the six architectures that plan their
layers on it.

Decoder layer l: `h += Mixer_l(norm(h))`, then `h += FFN(norm(h))` (each
added times `residual_multiplier`, 1 but in Granite); with
`sandwich_norms` each sublayer's output passes a norm of its own before
it is added (four norms a layer). The feed-forward is a `SparseMoE` that
holds `experts_here` of the routed experts, or in the first
`num_dense_layers` layers a `DenseMLP` (layers/moe.py). `Qwen3NextLM`
(HF `modeling_qwen3_next.py`) plans `GatedAttention` where `(l + 1) %
full_attention_interval == 0` and `GatedDeltaNet` elsewhere, with a
shared expert; `KeyeVL2LM` (Keye-VL-2.0's language model) plans
`IndexedSparseAttention` in every layer, no shared expert, and adds the
mean of the layers' indexer losses to the loss; `TrinityLM` (Arcee's
Trinity, HF `afmoe`) plans `GatedAttention` by `layer_types`, with a
window and rotary or with neither, behind a sigmoid-scored router;
`SmallThinkerLM` (PowerInfer's SmallThinker) plans it by two layouts,
window and rotary, with no output gate and no head norms, ReLU-gated
experts and a router that reads the layer's input ahead of the attention;
`Lfm2MoeLM` (LiquidAI's LFM2, HF `lfm2_moe`) plans `GatedShortConv` or
`GatedAttention` by `layer_types`, behind a sigmoid-scored router, and
ties its head to the embedding; `GraniteHybridLM` (IBM's Granite 4.0-H,
HF `granitemoehybrid`) plans `Mamba2Mixer` or position-free
`GatedAttention` by `layer_types`, a dense feed-forward in every layer,
Granite's embedding, residual and logit multipliers and a tied head
(layers/sequence.py). Embedding
(`nn/encoders.py:Embedding`,
so `euler.embed` and the table's scatter-add gradient are the ones every
embedding model here has) times `embed_scale`, the layers, a final norm,
a head — a leaf of its own, or with `tie_embeddings` the embedding table
transposed, whose gradient is then that scatter-add plus the head's
dense product in one leaf — and the mean next-token cross-entropy in
float32. Every layer is
rematerialised in the backward pass: what is kept of the forward is each
layer's input and, where the mixer names one, its attention core's output
(`_KEEP_CORE`).

The batch is what `DeviceSequenceFlow.sample` returns: int32 ids
[B, T + 1]; positions 0..T-1 are the inputs and 1..T the targets. The
vocabulary may be a slice (`vocab_size` rows of the published table): ids,
logits and loss are over the slice. `positions` [3, B, T] (time, height,
width) is what a flow with images would hand in; text has all three equal
to 0..T-1, which is what the model makes when it is given none.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from euler_tpu.layers.moe import DenseMLP, SparseMoE
from euler_tpu.layers.sequence import (
    CORE_OUTPUT,
    GatedAttention,
    GatedDeltaNet,
    GatedShortConv,
    IndexedSparseAttention,
    Mamba2Mixer,
    RMSNorm,
)
from euler_tpu.nn.encoders import Embedding
from euler_tpu.utils import trace

# What a rematerialised layer keeps of its forward besides its input: the
# values its mixer names `CORE_OUTPUT` (`layers/sequence.py:_keep_core`
# says why), so that the layer's second forward skips the mixer's loop of
# query blocks or of chunks, by far its longest part. The price is those
# values from forward to backward: [B, T, heads * head_dim] float32 a
# layer, 268 MB at 16,384 tokens of 32 x 128, and under a `GatedDeltaNet`
# its groups' start states as well (a [dk, dv] float32 a head and a group:
# 134 MB at 2 x 8,192 tokens in groups of 256). Every mixer but
# `GatedShortConv` names its core; that one is rematerialised whole.
_KEEP_CORE = jax.checkpoint_policies.save_only_these_names(CORE_OUTPUT)


class DecoderLayer(nn.Module):
    """-> (h, the assignments routed to held experts, the mixer's own
    loss or None). `mixer` is called as `(x, positions) -> (y, its own
    loss or None)`; the feed-forward, `mlp` where the layer has one and
    else `moe`, as `x [N, H] -> (y, assignments routed)`. With `sandwich`
    each sublayer's output is normalised before it is added. With
    `route_on_input` the experts' router is handed the stream as it
    entered the layer, before `input_norm` and the mixer (a router that
    stands ahead of the attention: nothing of its pick waits for the
    mixer), and the layer counts itself `router_on_input`. Each
    sublayer's output is added times `residual_multiplier`. Run under
    `nn.remat` (`DecoderLM._layer`): nothing in here outlives the forward
    but what `_KEEP_CORE` names."""

    mixer: nn.Module
    moe: nn.Module | None
    eps: float = 1e-6
    mlp: nn.Module | None = None  # a dense feed-forward in the experts' place
    sandwich: bool = False
    route_on_input: bool = False  # the experts' router reads the layer's input
    residual_multiplier: float = 1.0  # each sublayer's output is added times this

    @nn.compact
    def __call__(self, h, positions):
        entered = h
        mixed, aux = self.mixer(RMSNorm(self.eps, name="input_norm")(h), positions)
        if self.sandwich:
            mixed = RMSNorm(self.eps, name="mixer_out_norm")(mixed)
        if self.residual_multiplier != 1.0:
            mixed = mixed * self.residual_multiplier
        h = h + mixed
        x = RMSNorm(self.eps, name="post_norm")(h)
        x = x.reshape(-1, x.shape[-1])
        if self.mlp is not None:
            y, routed = self.mlp(x)
        elif self.route_on_input:
            trace.count("router_on_input")
            y, routed = self.moe(x, route_on=entered.reshape(x.shape))
        else:
            y, routed = self.moe(x)
        y = y.reshape(h.shape)
        if self.sandwich:
            y = RMSNorm(self.eps, name="ffn_out_norm")(y)
        if self.residual_multiplier != 1.0:
            y = y * self.residual_multiplier
        return h + y, routed, aux


class DecoderLM(nn.Module):
    """The decoder the six architectures share; a subclass plans
    `mixer(l)`. With `tie_embeddings` there is no `head` leaf: the logits
    are `x @ table[:vocab].T`, part by part as with a head of its own,
    and the model counts itself `head_tied`; the logits are divided by
    `logits_divisor`. Returns `(emb, loss, "routed_share", share)`: the
    final hidden states [B, T, H], the loss, and the share of the step's
    token-expert assignments that landed on experts held here
    (`experts_here[1] / num_experts` when the router is even); a model
    whose every layer is dense routes nothing and returns `(emb, loss,
    "loss", loss)`."""

    vocab_size: int
    hidden_size: int
    num_layers: int
    # softmax attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    rope_theta: float = 1e7
    rope_sections: tuple = ()  # pairs turned by each of several position axes
    attention_block: int = 512
    # experts
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512  # 0: no shared expert
    norm_topk_prob: bool = True
    router_score: str = "softmax"  # or "sigmoid" (layers/moe.py)
    router_norm_eps: float = 1e-20  # the sigmoid router's divisor is sum + this
    route_scale: float = 1.0
    shared_expert_gated: bool = True
    expert_activation: str = "silu"  # the experts' gate: or "relu"
    route_on_input: bool = False  # routers read their layer's input, not the experts'
    experts_here: tuple = (0, 0)  # (first, count); count 0 = all
    # the first layers' feed-forward is dense, of this width
    num_dense_layers: int = 0
    intermediate_size: int = 0
    sandwich_norms: bool = False
    embed_scale: float = 1.0
    residual_multiplier: float = 1.0  # sublayers' outputs are added times this
    logits_divisor: float = 1.0  # the head's logits are divided by this
    rms_norm_eps: float = 1e-6
    loss_chunks: int = 1  # the head and loss run over T in this many parts
    tie_embeddings: bool = False  # the head is the embedding table transposed

    def mixer(self, index: int) -> nn.Module:
        raise NotImplementedError

    def _layer(self, index: int):
        """Layer `index`, rematerialised in the backward pass under
        `_KEEP_CORE`."""
        moe = mlp = None
        if index < self.num_dense_layers:
            mlp = DenseMLP(self.intermediate_size, parent=None)
        else:
            moe = SparseMoE(
                num_experts=self.num_experts,
                top_k=self.num_experts_per_tok,
                expert_dim=self.moe_intermediate_size,
                shared_dim=self.shared_expert_intermediate_size,
                held=tuple(self.experts_here),
                norm_topk=self.norm_topk_prob,
                score=self.router_score,
                route_scale=self.route_scale,
                shared_gated=self.shared_expert_gated,
                activation=self.expert_activation,
                norm_eps=self.router_norm_eps,
                parent=None,
            )
        return nn.remat(DecoderLayer, policy=_KEEP_CORE)(
            self.mixer(index), moe, self.rms_norm_eps, mlp, self.sandwich_norms,
            self.route_on_input, self.residual_multiplier, name=f"layer_{index}",
        )

    @nn.compact
    def __call__(self, ids, positions=None):
        tokens, targets = ids[:, :-1], ids[:, 1:]
        if positions is None and self.rope_sections:  # text: every axis is time
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), (len(self.rope_sections),) + tokens.shape
            )
        embed = Embedding(self.vocab_size, self.hidden_size, name="embed")
        h = embed(tokens)
        if self.embed_scale != 1.0:
            with trace.scope("embed"):
                h = h * self.embed_scale
        routed, own = jnp.zeros((), jnp.int32), []
        for index in range(self.num_layers):
            h, here, aux = self._layer(index)(h, positions)
            routed = routed + here
            own += [] if aux is None else [aux]
        emb = RMSNorm(self.rms_norm_eps, name="final_norm")(h)
        if self.tie_embeddings:
            trace.count("head_tied")
            table = nn.meta.unbox(embed.get_variable("params", "table"))
            w_head = table[: self.vocab_size].T  # the rows past it are padding
        else:
            w_head = self.param(
                "head", nn.initializers.normal(stddev=0.02),
                (self.hidden_size, self.vocab_size), jnp.float32,
            )

        @jax.checkpoint
        def part_loss(x, y, w):
            with trace.scope("head"):
                logits = (x @ w).astype(jnp.float32)
                if self.logits_divisor != 1.0:
                    logits = logits / self.logits_divisor
            with trace.scope("loss"):
                return jnp.sum(
                    optax.softmax_cross_entropy_with_integer_labels(logits, y)
                )

        # the logits of all T positions at once would be the step's largest
        # tensor: each part's are made, used and made again in the backward
        total = sum(
            part_loss(x, y, w_head)
            for x, y in zip(
                jnp.split(emb, self.loss_chunks, axis=1),
                jnp.split(targets, self.loss_chunks, axis=1),
            )
        )
        with trace.scope("loss"):
            loss = total / targets.size
            if own:  # the mixers' own losses, coefficient 1
                loss = loss + sum(own) / len(own)
        expert_layers = max(self.num_layers - self.num_dense_layers, 0)
        if not expert_layers:  # nothing is routed: the loss is the metric
            return emb, loss, "loss", loss
        assignments = tokens.size * self.num_experts_per_tok * expert_layers
        share = routed.astype(jnp.float32) / assignments
        return emb, loss, "routed_share", share


class Qwen3NextLM(DecoderLM):
    """A period of `full_attention_interval - 1` `GatedDeltaNet` layers
    and one `GatedAttention` layer (rotary on `partial_rotary_factor` of
    the head), a shared expert beside the routed ones."""

    full_attention_interval: int = 4
    partial_rotary_factor: float = 0.25
    # gated DeltaNet
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    chunk: int = 64

    def mixer(self, index: int):
        if (index + 1) % self.full_attention_interval == 0:
            return GatedAttention(
                num_heads=self.num_heads,
                num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim,
                rope_theta=self.rope_theta,
                rotary_dim=int(self.head_dim * self.partial_rotary_factor),
                block=self.attention_block,
                eps=self.rms_norm_eps,
                parent=None,  # adopted by the layer, as its `mixer`
            )
        return GatedDeltaNet(
            num_k_heads=self.linear_num_key_heads,
            num_v_heads=self.linear_num_value_heads,
            head_k_dim=self.linear_key_head_dim,
            head_v_dim=self.linear_value_head_dim,
            conv_kernel=self.linear_conv_kernel_dim,
            chunk=self.chunk,
            eps=self.rms_norm_eps,
            parent=None,  # adopted by the layer, as its `mixer`
        )


class KeyeVL2LM(DecoderLM):
    """`IndexedSparseAttention` in every layer: grouped queries, rotary
    by three position axes over the whole head, the `topk` keys of the
    context an indexer of `index_heads` x `index_dim` picks; no shared
    expert. The loss is the cross-entropy plus the mean over the layers
    of the indexer's KL term. The vision tower is not here: `positions`
    is where its three axes would come in."""

    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_sections: tuple = (16, 24, 24)
    index_heads: int = 16
    index_dim: int = 64
    topk: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    shared_expert_intermediate_size: int = 0

    def mixer(self, index: int):
        return IndexedSparseAttention(
            num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim,
            index_heads=self.index_heads,
            index_dim=self.index_dim,
            topk=self.topk,
            rope_theta=self.rope_theta,
            sections=tuple(self.rope_sections),
            block=self.attention_block,
            eps=self.rms_norm_eps,
            parent=None,  # adopted by the layer, as its `mixer`
        )


class TrinityLM(DecoderLM):
    """`GatedAttention` in every layer, of the kind `layer_types` names:
    "sliding_attention" sees `sliding_window` keys and turns queries and
    keys by rotary over the whole head, "full_attention" sees every
    earlier key and has no rotary, so no position at all. The first
    `num_dense_layers` feed-forwards are dense; the others route by
    sigmoid scores, scale the kept weights by `route_scale` and add one
    shared expert as it is. Sandwich norms; the embedding enters times
    `embed_scale` (sqrt(hidden) under muP). The router's `expert_bias`
    stays where it starts: the balancing rule that moves it between
    steps belongs to a train step that carries a state no gradient
    updates."""

    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e4
    layer_types: tuple = ("sliding_attention",) * 3 + ("full_attention",)
    sliding_window: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    router_score: str = "sigmoid"
    route_scale: float = 2.826
    shared_expert_gated: bool = False
    num_dense_layers: int = 2
    intermediate_size: int = 6144
    sandwich_norms: bool = True
    rms_norm_eps: float = 1e-5

    def mixer(self, index: int):
        kind = self.layer_types[index]
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer {index} is of no known kind: {kind!r}")
        local = kind == "sliding_attention"
        return GatedAttention(
            num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim,
            rope_theta=self.rope_theta,
            rotary_dim=self.head_dim if local else 0,
            block=self.attention_block,
            eps=self.rms_norm_eps,
            window=self.sliding_window if local else None,
            parent=None,  # adopted by the layer, as its `mixer`
        )


class SmallThinkerLM(DecoderLM):
    """PowerInfer's SmallThinker: plain grouped-query attention in every
    layer — no output gate, no head norms —, a window of
    `sliding_window_size` keys where `sliding_window_layout[l]` is 1 and
    every earlier key elsewhere, rotary over the whole head where
    `rope_layout[l]` is 1 and no position at all elsewhere (the two
    layouts need not agree here; published, they do: a full layer that
    knows no position, then three window layers with rotary). Every
    feed-forward is routed, ReLU-gated experts (ReGLU) with no shared
    one, and its router reads the layer's input, ahead of `input_norm`
    and the attention (`route_on_input`): the softmax over the kept
    logits, which is `router_score` "softmax" with `norm_topk_prob`."""

    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1.5e6
    sliding_window_layout: tuple = (0, 1, 1, 1)
    rope_layout: tuple = (0, 1, 1, 1)
    sliding_window_size: int = 4096
    num_experts: int = 64
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 768
    shared_expert_intermediate_size: int = 0
    expert_activation: str = "relu"
    route_on_input: bool = True

    def mixer(self, index: int):
        return GatedAttention(
            num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim,
            rope_theta=self.rope_theta,
            rotary_dim=self.head_dim if self.rope_layout[index] else 0,
            block=self.attention_block,
            eps=self.rms_norm_eps,
            window=self.sliding_window_size if self.sliding_window_layout[index] else None,
            gated=False,
            head_norms=False,
            parent=None,  # adopted by the layer, as its `mixer`
        )


class Lfm2MoeLM(DecoderLM):
    """LiquidAI's LFM2 mixture of experts (HF `lfm2_moe`): the mixer of
    layer l is what `layer_types[l]` names — "conv", a `GatedShortConv`
    of `conv_L_cache` taps (published: three layers in four), or
    "full_attention", grouped-query attention at a head of hidden / heads
    (64) over every earlier key, RMSNorm on each query and key head,
    rotary over the whole head, no output gate —; anything else raises.
    The first `num_dense_layers` feed-forwards are dense SwiGLUs; the
    others route by sigmoid scores, pick on `s + expert_bias`, weigh by
    the `s` themselves over `sum + 1e-6` and have no shared expert. No
    sandwich norms; the head is the embedding table transposed. The
    router's `expert_bias` stays where it starts, as `TrinityLM`'s does."""

    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    layer_types: tuple = ("full_attention", "conv", "conv", "conv")
    conv_L_cache: int = 3
    num_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    shared_expert_intermediate_size: int = 0
    router_score: str = "sigmoid"
    router_norm_eps: float = 1e-6
    num_dense_layers: int = 2
    intermediate_size: int = 11776
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = True

    def mixer(self, index: int):
        kind = self.layer_types[index]
        if kind == "conv":
            return GatedShortConv(taps=self.conv_L_cache, parent=None)
        if kind != "full_attention":
            raise ValueError(f"layer {index} is of no known kind: {kind!r}")
        return GatedAttention(
            num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim,
            rope_theta=self.rope_theta,
            rotary_dim=self.head_dim,
            block=self.attention_block,
            eps=self.rms_norm_eps,
            gated=False,
            parent=None,  # adopted by the layer, as its `mixer`
        )


class GraniteHybridLM(DecoderLM):
    """IBM's Granite 4.0-H (HF `granitemoehybrid` with no experts): the
    mixer of layer l is what `layer_types[l]` names — "mamba", a
    `Mamba2Mixer` of `mamba_n_heads` heads of `mamba_d_head` over a state
    of `mamba_d_state` (published: nine layers in ten), or "attention",
    grouped-query attention over every earlier key with no position at
    all (`position_embedding_type` "nope"), no head norms, no output
    gate, the scores times `attention_multiplier` —; anything else
    raises. Every feed-forward is the dense SwiGLU "shared MLP" of
    `intermediate_size`. Granite's multipliers: the embedding enters
    times `embed_scale` (`embedding_multiplier`), each sublayer's output
    is added times `residual_multiplier`, the logits are divided by
    `logits_divisor` (`logits_scaling`); the head is the embedding table
    transposed. A model whose layers are all dense returns the loss as
    its metric (`DecoderLM`)."""

    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    layer_types: tuple = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    attention_multiplier: float = 0.015625
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    num_dense_layers: int = 1 << 30  # every layer's feed-forward is dense
    intermediate_size: int = 8192
    embed_scale: float = 12.0
    residual_multiplier: float = 0.22
    logits_divisor: float = 8.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = True

    def mixer(self, index: int):
        kind = self.layer_types[index]
        if kind == "mamba":
            return Mamba2Mixer(
                num_heads=self.mamba_n_heads,
                head_dim=self.mamba_d_head,
                state_size=self.mamba_d_state,
                conv_kernel=self.mamba_d_conv,
                chunk=self.mamba_chunk_size,
                eps=self.rms_norm_eps,
                parent=None,  # adopted by the layer, as its `mixer`
            )
        if kind != "attention":
            raise ValueError(f"layer {index} is of no known kind: {kind!r}")
        return GatedAttention(
            num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim,
            rotary_dim=0,
            block=self.attention_block,
            eps=self.rms_norm_eps,
            gated=False,
            head_norms=False,
            scale=self.attention_multiplier,
            parent=None,  # adopted by the layer, as its `mixer`
        )
