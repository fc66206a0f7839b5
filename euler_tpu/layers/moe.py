"""`SparseMoE`: a mixture-of-experts feed-forward layer that is told which
of the routed experts it holds; `DenseMLP`, the dense layer that stands
in its place in a model's leading layers.

Expert parallelism divides a layer's experts over chips; every chip
routes every token over ALL experts (router width and top-k as
published), and computes its own experts' part of the result for the
tokens routed to them. This layer is one chip's part: `held = (first,
count)` of `num_experts`. What the absent experts would have added is
left out and the partial result goes on; across a group the exchange
(all-to-all of rows, sum of partial results) belongs around this layer,
and on one chip there is none. The shares of all `num_experts / count`
chips, the shared expert counted once, add up to the whole layer
(tests/test_sequence_lm.py).

Dispatch and combine are the repo's message-passing primitives: the
token-expert assignments are sorted by expert, the tokens' rows gathered
in that order (`ops.gather`), multiplied group by group
(`seq_ops.grouped_matmul`), weighted, and summed back per token
(`ops.scatter_add`).

No assignment to a held expert is dropped, and no bound is guessed. A
grouped matmul needs a static number of rows and the number routed here
is the router's to decide, between none and the worst case (every token
routed to `min(top_k, count)` held experts). So the sorted assignments
are taken in tiles of as many rows as there are tokens — of a multiple
of that where an even router would fill such a tile (`tile_rows`) — and
only the tiles that hold a routed row are run: a loop whose trip count
the step's own routing sets, forward and backward (`held_experts`). An
even router fills at most four fifths of a tile; a router that has
learned to prefer the experts held here costs the tiles it fills and
nothing overflows.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from euler_tpu.ops import gather, scatter_add, seq_ops
from euler_tpu.utils import trace

_MATRIX = nn.initializers.normal(stddev=0.02)


# an expert's gate activation, by the model's name for it
_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _glu(activation, x, w_gate, w_up, w_down, matmul):
    """`W_down (act(W_gate x) * W_up x)`: SwiGLU under "silu", ReGLU
    under "relu"."""
    return matmul(_GATES[activation](matmul(x, w_gate)) * matmul(x, w_up), w_down)


def tile_rows(tokens: int, top_k: int, count: int, num_experts: int) -> int:
    """Sorted assignments a tile: `tokens` times the least divisor of
    `top_k` (so that whole tiles cover the `tokens * top_k` assignments)
    that leaves a quarter to spare over the rows an even router sends to
    `count` of `num_experts`. Where those rows all but fill a tile — 8
    picks a token, an eighth of the experts held — every router near even,
    as each is at the start, would run one tile at one step and two at the
    next, and the step's time would follow the draw."""
    even = tokens * top_k * count / num_experts
    return tokens * next(
        m for m in range(1, top_k + 1)
        if top_k % m == 0 and (m * tokens >= 1.25 * even or m == top_k)
    )


def _tile(top_k, step, activation, start, order, ends, x, weight, w_gate, w_up, w_down):
    """What the held experts add to every token from the sorted
    assignments `start .. start + step`: [N, H]."""
    tokens = x.shape[0]
    with trace.scope("moe.dispatch"):
        mine = jax.lax.dynamic_slice(order, (start,), (step,))
        token = mine // top_k
        rows = gather(x, token)
        # each expert's rows inside this tile; the rows past the last
        # routed one belong to no group, and the grouped matmul takes and
        # leaves them as zeros, forward and transposed
        sizes = jnp.diff(jnp.clip(ends, start, start + step), prepend=start)
    with trace.scope("moe.experts"):
        out = _glu(
            activation, rows, w_gate, w_up, w_down,
            lambda a, w: seq_ops.grouped_matmul(a, w, sizes),
        )
    with trace.scope("moe.combine"):
        return scatter_add(out * gather(weight, mine)[:, None], token, tokens)


def _tiles(step, ends):
    """How many tiles of `step` sorted assignments hold a routed row."""
    return (ends[-1] + step - 1) // step


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def held_experts(top_k, step, activation, order, ends, x, weight, w_gate, w_up, w_down):
    """sum over the assignments (token n, expert e held here) of
    `weight[n, e] E_e(x[n])`, per token: [N, H].

    `order` [N * top_k] lists the flat assignments sorted by held expert,
    those to absent experts last; `ends` [count] is where each held
    expert's run of them ends; `weight` [N * top_k] the routing weights.
    The tiles, of `step` assignments (`tile_rows`), are run by a loop of
    `_tiles` trips, which reverse-mode differentiation cannot pass
    through: the backward pass is the same loop over each tile's own vjp."""
    return jax.lax.fori_loop(
        0, _tiles(step, ends),
        lambda t, y: y + _tile(
            top_k, step, activation, t * step, order, ends, x, weight, w_gate, w_up, w_down
        ),
        jnp.zeros_like(x),
    )


def _held_experts_fwd(top_k, step, activation, order, ends, *inputs):
    return held_experts(top_k, step, activation, order, ends, *inputs), (order, ends, inputs)


def _held_experts_bwd(top_k, step, activation, kept, dy):
    order, ends, inputs = kept

    def tile_grads(t, grads):
        _, pull = jax.vjp(
            functools.partial(_tile, top_k, step, activation, t * step, order, ends), *inputs
        )
        return jax.tree_util.tree_map(jnp.add, grads, pull(dy))

    grads = jax.lax.fori_loop(
        0, _tiles(step, ends), tile_grads,
        jax.tree_util.tree_map(jnp.zeros_like, inputs),
    )
    return (None, None, *grads)


held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


class SparseMoE(nn.Module):
    """x [N, H], route_on [N, H] or None -> (y [N, H], the number of
    token-expert assignments that landed on held experts).

    `p = softmax(r W_r)` over all experts in float32, `r` the tensor the
    router reads: `route_on` where the caller hands one (a model whose
    router stands ahead of its attention hands the layer's input), else
    `x`, the experts' own input; the `top_k` largest are kept and, with
    `norm_topk`, divided by their sum — which is also the softmax over
    the `top_k` kept logits alone, so a model that states its router so
    (SmallThinker: `moe_primary_router_apply_softmax`) is this `score`
    and no third;
    `y = sum_{e in top_k, e held} p_e E_e(x) + sigmoid(x . w_s) E_shared(x)`,
    `E(x) = W_down (act(W_gate x) * W_up x)`, `act` the model's
    `activation`: "silu" (SwiGLU) or "relu" (ReGLU; a layer counts itself
    `experts_relu`). Every assignment to a held expert is computed,
    however many there are (`held_experts`).
    `shared_dim` 0 is a layer with no shared expert: no such term and no
    such parameters; `shared_gated` False adds the shared expert as it is,
    with no sigmoid mix and no `w_s`.

    `score` "sigmoid" is the router of the aux-loss-free balancing
    (DeepSeek-V3's): `p = sigmoid(x W_r)`, each expert scored by itself;
    the pick is made on `p + b`, `b` an `expert_bias` that no gradient
    reaches (whoever balances the load moves it; it starts at zero), and
    the kept weights are the `p` themselves, without it, divided by their
    sum + `norm_eps` (1e-20: a guard against 0 / 0 and no more; a model
    whose router states its own, LFM2's 1e-6, passes it); a layer that
    routes so counts itself `router_sigmoid`. Either router's kept
    weights are multiplied by `route_scale`.
    """

    num_experts: int
    top_k: int
    expert_dim: int
    shared_dim: int
    held: tuple = (0, 0)  # (first, count); count 0 = all of them
    norm_topk: bool = True
    score: str = "softmax"  # or "sigmoid"
    route_scale: float = 1.0
    shared_gated: bool = True
    activation: str = "silu"  # or "relu": the experts' gate
    norm_eps: float = 1e-20  # added to the sigmoid router's divisor

    @nn.compact
    def __call__(self, x, route_on=None):
        hidden = x.shape[1]
        first, count = self.held
        count = count or self.num_experts
        k = self.top_k
        w_router = self.param(
            "router", _MATRIX, (hidden, self.num_experts), jnp.float32
        )
        shape = (count, hidden, self.expert_dim)
        w_gate = self.param("experts_gate", _MATRIX, shape, jnp.float32)
        w_up = self.param("experts_up", _MATRIX, shape, jnp.float32)
        w_down = self.param(
            "experts_down", _MATRIX, (count, self.expert_dim, hidden), jnp.float32
        )
        with trace.scope("moe.route"):
            # float32 for real: a top-k pick that flips on a bf16-rounded
            # logit would send a token to other experts
            logits = jnp.matmul(
                (x if route_on is None else route_on).astype(jnp.float32), w_router,
                precision=jax.lax.Precision.HIGHEST,
            )
            if self.score == "sigmoid":
                trace.count("router_sigmoid")
                bias = self.param(
                    "expert_bias", nn.initializers.zeros, (self.num_experts,), jnp.float32
                )
                scores = jax.nn.sigmoid(logits)
                _, top_e = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
                top_p = jnp.take_along_axis(scores, top_e, axis=-1)
                if self.norm_topk:
                    top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + self.norm_eps)
            else:
                top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
                if self.norm_topk:
                    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
            if self.route_scale != 1.0:
                top_p = top_p * self.route_scale
        with trace.scope("moe.dispatch"):
            here = (top_e >= first) & (top_e < first + count)
            # assignments sorted by held expert, those to absent experts last
            slot = jnp.where(here, top_e - first, count).reshape(-1)
            order = jnp.argsort(slot, stable=True).astype(jnp.int32)
            ends = jnp.cumsum(jnp.bincount(slot, length=count + 1)[:count])
            ends = ends.astype(jnp.int32)
        step = tile_rows(x.shape[0], k, count, self.num_experts)
        if self.activation == "relu":
            trace.count("experts_relu")
        y = held_experts(
            k, step, self.activation, order, ends, x, top_p.reshape(-1), w_gate, w_up, w_down
        )
        if self.shared_dim:
            shape = (hidden, self.shared_dim)
            s_gate = self.param("shared_gate", _MATRIX, shape, jnp.float32)
            s_up = self.param("shared_up", _MATRIX, shape, jnp.float32)
            s_down = self.param("shared_down", _MATRIX, shape[::-1], jnp.float32)
            if self.shared_gated:
                s_mix = self.param("shared_mix", _MATRIX, (hidden, 1), jnp.float32)
            with trace.scope("moe.shared"):
                if self.shared_gated:
                    mix = jax.nn.sigmoid((x @ s_mix).astype(jnp.float32))
                    y = y + mix * _glu(self.activation, x, s_gate, s_up, s_down, jnp.matmul)
                else:
                    y = y + _glu(self.activation, x, s_gate, s_up, s_down, jnp.matmul)
        return y, ends[-1]


def _contract(a, b, axis):
    """`a` and `b` contracted over their `axis`, accumulated in float32."""
    return jax.lax.dot_general(
        a, b, (((axis,), (axis,)), ((), ())), preferred_element_type=jnp.float32
    )


@jax.custom_vjp
def swiglu(x, w_gate, w_up, w_down):
    """`_glu("silu", x, w_gate, w_up, w_down, jnp.matmul)`, whose backward
    is written by hand (`_swiglu_bwd`)."""
    return _glu("silu", x, w_gate, w_up, w_down, jnp.matmul)


def _swiglu_fwd(x, w_gate, w_up, w_down):
    # the products are made again in the backward: the decoder layer is
    # rematerialised whole, and keeping them would only add memory
    return swiglu(x, w_gate, w_up, w_down), (x, w_gate, w_up, w_down)


def _swiglu_grads(operand, kept, dy):
    """The cotangents of x and the three weights, each product's operands
    of `operand` type. Left to `jax.vjp`, XLA fuses the gate's derivative
    — float32 g, u and dh, a sigmoid — into the weight gradients'
    products as their operand, which a product reads again for every
    block of its output rows; here `dg`, `du` and `h` are made once, in
    one pass, x and dy cast once, and each weight gradient reads arrays."""
    x, w_gate, w_up, w_down = kept
    g, u = jnp.matmul(x, w_gate), jnp.matmul(x, w_up)
    dh = jnp.matmul(dy, w_down.T)
    s = jax.nn.sigmoid(g)
    made = (dh * u * s * (1 + g * (1 - s)), dh * g * s, g * s * u, x, dy)
    dg, du, h, x_, dy_ = jax.lax.optimization_barrier(tuple(a.astype(operand) for a in made))
    dx = _contract(dg, w_gate.astype(operand), 1) + _contract(du, w_up.astype(operand), 1)
    return dx, _contract(x_, dg, 0), _contract(x_, du, 0), _contract(h, dy_, 0)


def _swiglu_bwd(kept, dy):
    # the operands as XLA's default precision takes a float32 product's on
    # each platform: bfloat16 into the TPU's MXU; float32 elsewhere
    return jax.lax.platform_dependent(
        kept, dy,
        tpu=functools.partial(_swiglu_grads, jnp.bfloat16),
        default=functools.partial(_swiglu_grads, jnp.float32),
    )


swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


class DenseMLP(nn.Module):
    """The dense feed-forward a model's leading layers have in an expert
    layer's place: `W_down (SiLU(W_gate x) * W_up x)` at `dim`, under
    `euler.mlp`, its backward `swiglu`'s. Called as an expert layer is:
    x [N, H] -> (y [N, H], 0 assignments routed). Counts itself
    `dense_layers` and `mlp_grads_by_hand`."""

    dim: int

    @nn.compact
    def __call__(self, x):
        shape = (x.shape[1], self.dim)
        w_gate = self.param("gate", _MATRIX, shape, jnp.float32)
        w_up = self.param("up", _MATRIX, shape, jnp.float32)
        w_down = self.param("down", _MATRIX, shape[::-1], jnp.float32)
        trace.count("dense_layers")
        trace.count("mlp_grads_by_hand")
        with trace.scope("mlp"):
            return swiglu(x, w_gate, w_up, w_down), jnp.zeros((), jnp.int32)
