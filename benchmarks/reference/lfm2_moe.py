"""LFM2-24B-A2B (LiquidAI, HF `model_type` `lfm2_moe`) written out
plainly: the token walks, the doubly gated short convolution that is the
mixer of three layers in four, grouped-query attention at a head of 64 in
the fourth, a leading dense layer, the mixture of experts behind a
sigmoid-scored router as a weighted sum over the experts held here, the
head that is the embedding table transposed and the next-token
cross-entropy. Imports nothing of the program.

With `rms(u) = u / sqrt(mean(u^2) + eps)`, eps 1e-5, and norm weights
written `1 + w`, `w` from zeros (HF writes `w` from ones: the same
function). Hidden 2,048. The equations are HF `modeling_lfm2_moe.py` /
`modeling_lfm2.py` from memory (no network here); what the catalog's
`config` has no key for is marked ASSUMED, and the configuration file
lists each under `assumed` with its origin.

- embedding: `h = E[id]`.
- layer l: `h += Mixer_l((1 + w1) rms(h))` (`operator_norm`, here
  `input_norm`), `h += FFN_l((1 + w2) rms(h))` (`ffn_norm`, here
  `post_norm`).
- `conv` mixer (`Lfm2ShortConv`, `conv_L_cache` 3, `conv_bias` false):
  `[B | C | x~] = x W_in`, W_in [2,048, 6,144] with its columns in that
  order (ASSUMED); `u = B * x~`; `c_t = w[:, 0] u_{t-2} + w[:, 1] u_{t-1}
  + w[:, 2] u_t`, zeros before the sequence (depthwise, causal: three
  shifted products); `y = (C * c) W_out`. No activation, no norm.
- `full_attention` mixer: 32 query / 8 key-value heads of 64 (hidden /
  heads: ASSUMED, the row gives no `head_dim`), no bias, no output gate;
  `q_i = (1 + wq) rms(W_q x)_i`, `k_g = (1 + wk) rms(W_k x)_g` (RMSNorm
  over the head's 64: `q_layernorm`, `k_layernorm`), then both turned by
  rotary over the whole head (dimension j with j + 32), theta 1e6;
  `o_i[t] = sum_{s <= t} softmax_s(q_i[t] . k_g(i)[s] / sqrt(64))
  v_g(i)[s]`, `y = W_o o`.
- feed-forward: in the leading dense layer `W_down (SiLU(W_gate x) *
  W_up x)` at width 11,776 (`intermediate_size` as it is: ASSUMED, no
  `block_auto_adjust_ff_dim`). In every other layer `s = sigmoid(x W_r)`
  in float32 over all 64 experts; the 4 largest of `s + b` are picked
  (`use_expert_bias`; `b` zeros, which takes no gradient); `p_e = s_e /
  (sum of the 4 picked s + 1e-6)` (`norm_topk_prob`; the 1e-6 ASSUMED),
  times `routed_scaling_factor` 1; `f = sum_{e picked and held} p_e
  E_e(x)`, `E` the same SwiGLU at width 1,536; no shared expert. Experts
  not held add nothing.
- loss: mean over all positions of the cross-entropy of `(1 + wf) rms(h)
  E^T` (`embedding_norm`, here `final_norm`) against the next token: the
  head IS the embedding table (`tie_embedding`: ASSUMED, the family's
  convention), read a second time.
- LEFT OUT: the update of `b` between steps; `b` is in the pick, so the
  forward is whole.

The draws follow the program's documented stream
(`DeviceSequenceFlow.sample`), as `reference/qwen3_next.py` writes it out.

What makes it fit beside 7.5 GB of float32 state at the timed size, and
changes no number: every layer, and inside it the mixer and the
feed-forward, every block of `query_block` queries (its 32 heads' scores
against all T keys, the causal line a mask over the full row), every
expert, the dense layer's row blocks and every part of the loss is
rematerialised in the backward pass (`jax.checkpoint`); the blocks, the
experts and the loss's parts are taken one after another (`lax.map`,
`lax.scan`), so that what each adds to a gradient is summed as it comes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# conv_no_out_gate: `y = c W_out`, the second gate left out;
# conv_two_taps: the tap on u_{t-2} left out; router_softmax: softmax
# scores in the sigmoid's place; no_head_norms: q and k enter the rotary
# as projected
FAULTS = (
    "", "half_batch", "conv_no_out_gate", "conv_two_taps", "router_softmax",
    "no_head_norms",
)
CONV, FULL = "conv", "full_attention"
ROUTER_EPS = 1e-6  # ASSUMED: HF's divisor is `sum + 1e-6`


def param_spec(config: dict, graph: dict) -> list:
    """(path, shape, init, scale) of every leaf, in the program's tree."""
    m = config["model"]
    hidden = config["hidden_size"]
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = m["head_dim"]
    held, f = m["experts_here"][1], config["moe_intermediate_size"]
    scales = config["assumed"]["weight_scales"]
    mat = ("normal", scales["matrix"])
    rows = -(-config["vocab_size"] // 128) * 128
    spec = [("params/embed/table", (rows, hidden), "normal", scales["embedding"])]
    for i, kind in enumerate(layer_kinds(config)):
        layer = f"params/layer_{i}"
        spec += [
            (f"{layer}/input_norm/w", (hidden,), "zeros", 0.0),
            (f"{layer}/post_norm/w", (hidden,), "zeros", 0.0),
        ]
        if kind == CONV:
            spec += [
                (f"{layer}/mixer/in_proj", (hidden, 3 * hidden)) + mat,
                (f"{layer}/mixer/conv", (hidden, config["conv_L_cache"]), "normal", scales["conv"]),
                (f"{layer}/mixer/out_proj", (hidden, hidden)) + mat,
            ]
        else:
            spec += [
                (f"{layer}/mixer/q_proj", (hidden, nq * d)) + mat,
                (f"{layer}/mixer/k_proj", (hidden, nkv * d)) + mat,
                (f"{layer}/mixer/v_proj", (hidden, nkv * d)) + mat,
                (f"{layer}/mixer/o_proj", (nq * d, hidden)) + mat,
                (f"{layer}/mixer/q_norm/w", (d,), "zeros", 0.0),
                (f"{layer}/mixer/k_norm/w", (d,), "zeros", 0.0),
            ]
        if i < config["num_dense_layers"]:
            wide = config["intermediate_size"]
            spec += [
                (f"{layer}/mlp/gate", (hidden, wide)) + mat,
                (f"{layer}/mlp/up", (hidden, wide)) + mat,
                (f"{layer}/mlp/down", (wide, hidden)) + mat,
            ]
        else:
            moe = f"{layer}/moe"
            spec += [
                (f"{moe}/router", (hidden, m["router_experts"])) + mat,
                (f"{moe}/expert_bias", (m["router_experts"],), "zeros", 0.0),
                (f"{moe}/experts_gate", (held, hidden, f)) + mat,
                (f"{moe}/experts_up", (held, hidden, f)) + mat,
                (f"{moe}/experts_down", (held, f, hidden)) + mat,
            ]
    # no `head` leaf: the table is the head
    spec += [("params/final_norm/w", (hidden,), "zeros", 0.0)]
    return spec


def walks(tables, key, num_nodes: int, count: int, length: int):
    """`count` uniform walks: [count, length + 1] node indices."""
    kroot, kwalk = jax.random.split(key)
    cur = jax.random.randint(kroot, (count,), 1, num_nodes + 1) - 1

    def move(cur, sk):
        start = tables["indptr"][cur]
        deg = tables["indptr"][cur + 1] - start
        u = jax.random.uniform(sk, (count, 1))
        slot = (u * deg[:, None]).astype(jnp.int32)
        slot = jnp.minimum(slot, jnp.maximum(deg[:, None] - 1, 0))
        nxt = tables["dst"][start[:, None] + slot].reshape(-1)
        return nxt, nxt

    _, rest = jax.lax.scan(move, cur, jax.random.split(kwalk, length))
    return jnp.concatenate([cur[:, None], rest.T], axis=1)


def sequences(tables, key, num_nodes: int, batch: int, seq_len: int, doc_len: int):
    """[batch, seq_len + 1] token ids."""
    docs = seq_len // doc_len
    w = walks(tables, key, num_nodes, batch * docs, doc_len)
    w = w.reshape(batch, docs, doc_len + 1)
    packed = w[:, :, :doc_len].reshape(batch, seq_len)
    return jnp.concatenate([packed, w[:, -1, doc_len:]], axis=1)


def rms(u, eps):
    return u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)


def silu(u):
    return u * jax.nn.sigmoid(u)


def swiglu(x, w_gate, w_up, w_down):
    return (silu(x @ w_gate) * (x @ w_up)) @ w_down


def rotate(u, theta):
    """u [B, T, heads, d], turned over the whole of d by positions 0..T-1."""
    half = u.shape[-1] // 2
    inv_freq = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    angle = np.arange(u.shape[1], dtype=np.float32)[:, None] * inv_freq
    cos = jnp.asarray(np.cos(angle), u.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), u.dtype)[None, :, None, :]
    a, b = u[..., :half], u[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def short_conv(p, x, config, fault=""):
    """x [B, T, H] -> y [B, T, H]: the gate, the taps as shifted products,
    the gate."""
    hidden = x.shape[-1]
    gates = x @ p["in_proj"]
    b, c, fed = gates[..., :hidden], gates[..., hidden : 2 * hidden], gates[..., 2 * hidden :]
    u = b * fed
    taps = config["conv_L_cache"]
    w = p["conv"]  # [H, taps]: tap j weighs u_{t - (taps - 1) + j}
    mixed = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j
        if fault == "conv_two_taps" and back == taps - 1:
            continue  # DEPARTS from the published three taps: the farthest left out
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, : u.shape[1]]
        mixed = mixed + shifted * w[:, j]
    if fault != "conv_no_out_gate":  # DEPARTS when planted: `y = c W_out`
        mixed = c * mixed
    return mixed @ p["out_proj"]


def attention(p, x, config, eps, query_block, fault=""):
    """x [B, T, H] -> y [B, T, H]: every earlier key, full rows."""
    batch, length, _ = x.shape
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["model"]["head_dim"]
    q = (x @ p["q_proj"]).reshape(batch, length, nq, d)
    k = (x @ p["k_proj"]).reshape(batch, length, nkv, d)
    v = (x @ p["v_proj"]).reshape(batch, length, nkv, d)
    if fault != "no_head_norms":  # DEPARTS when planted: no q / k layernorm
        q = rms(q, eps) * (1.0 + p["q_norm/w"])
        k = rms(k, eps) * (1.0 + p["k_norm/w"])
    theta = config["rope_parameters"]["rope_theta"]
    q, k = rotate(q, theta), rotate(k, theta)
    # query head i reads key/value head i // (nq / nkv): [B, T, group, head in it, d]
    q = q.reshape(batch, length, nkv, nq // nkv, d)
    keys = jnp.arange(length)[None, :]

    @jax.checkpoint
    def rows(block):
        q_b, first = block
        at = first + jnp.arange(query_block)[:, None]
        scores = jnp.einsum("btgrd,bsgd->bgrts", q_b, k) * d**-0.5
        scores = jnp.where(keys <= at, scores.astype(jnp.float32), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bgrts,bsgd->btgrd", probs.astype(x.dtype), v)

    if length % query_block:
        raise ValueError(f"{length} positions are not whole blocks of {query_block}")
    q_blocks = jnp.moveaxis(
        q.reshape(batch, length // query_block, query_block, nkv, nq // nkv, d), 1, 0
    )
    firsts = jnp.arange(0, length, query_block)
    o = jnp.moveaxis(jax.lax.map(rows, (q_blocks, firsts)), 0, 1)
    return o.reshape(batch, length, nq * d) @ p["o_proj"]


def router_weights(p, x, config, fault=""):
    """[N, E] float32: the kept weight of each expert, 0 where not
    picked. The published form: sigmoid, pick on `s + b`, the `s` over
    their sum + 1e-6, times `routed_scaling_factor`."""
    top_k = config["num_experts_per_tok"]
    logits = (x @ p["router"]).astype(jnp.float32)
    if fault == "router_softmax":  # DEPARTS when planted
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    _, top_e = jax.lax.top_k(scores + jax.lax.stop_gradient(p["expert_bias"]), top_k)
    top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    if config["norm_topk_prob"]:
        top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + ROUTER_EPS)
    top_p = top_p * config["routed_scaling_factor"]
    return jnp.sum(
        jax.nn.one_hot(top_e, scores.shape[-1], dtype=jnp.float32) * top_p[..., None],
        axis=1,
    )


def mixture(p, x, config, fault="", held=None):
    """x [N, H]. Every token passes every expert held here (`held`:
    (first, count), the configuration's unless given); an expert the
    token was not routed to gets weight 0. No shared expert."""
    first, count = held or config["model"]["experts_here"]
    weight = router_weights(p, x, config, fault).astype(x.dtype)

    @jax.checkpoint
    def expert(e):
        out = swiglu(x, p["experts_gate"][e], p["experts_up"][e], p["experts_down"][e])
        return weight[:, first + e][:, None] * out

    y, _ = jax.lax.scan(
        lambda y, e: (y + expert(e), None), jnp.zeros_like(x), jnp.arange(count)
    )
    return y


def dense(p, x, row_block):
    """The leading layer's SwiGLU over x [N, H], `row_block` rows at a
    time: its [N, 11,776] intermediates are the widest tensor there is."""
    if x.shape[0] % row_block:
        raise ValueError(f"{x.shape[0]} rows are not whole blocks of {row_block}")
    part = jax.checkpoint(lambda x_b: swiglu(x_b, p["gate"], p["up"], p["down"]))
    return jax.lax.map(part, x.reshape(-1, row_block, x.shape[-1])).reshape(x.shape)


def sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix) + 1 :]: v for k, v in params.items() if k.startswith(prefix + "/")}


def layer_kinds(config: dict) -> list:
    """The kinds of the layers that are here: `model.layer_types_here`,
    the stretch of the published `layer_types` this stage holds."""
    kinds = config["model"]["layer_types_here"]
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError(f"{len(kinds)} layer kinds for {config['num_hidden_layers']} layers")
    if set(kinds) - {CONV, FULL}:
        raise ValueError(f"layer kinds {sorted(set(kinds) - {CONV, FULL})} are not known")
    return kinds


def forward_loss(params, ids, config, blocks, fault):
    """ids [B, T + 1] -> mean next-token cross-entropy."""
    eps = config["norm_eps"]
    tokens, targets = ids[:, :-1], ids[:, 1:]
    table = params["params/embed/table"]
    h = table[tokens]

    def norm(p, name, u):
        return rms(u, eps) * (1.0 + p[f"{name}/w"])

    for i, kind in enumerate(layer_kinds(config)):
        if kind == CONV:
            mixer = jax.checkpoint(lambda p, x: short_conv(p, x, config, fault))
        else:
            mixer = jax.checkpoint(
                lambda p, x: attention(p, x, config, eps, blocks["query_block"], fault)
            )
        if i < config["num_dense_layers"]:
            feed = jax.checkpoint(lambda p, x: dense(sub(p, "mlp"), x, blocks["dense_rows"]))
        else:
            feed = jax.checkpoint(lambda p, x: mixture(sub(p, "moe"), x, config, fault))

        @jax.checkpoint
        def layer(h, p, mixer=mixer, feed=feed):
            h = h + mixer(sub(p, "mixer"), norm(p, "input_norm", h))
            x = norm(p, "post_norm", h)
            return h + feed(p, x.reshape(-1, x.shape[-1])).reshape(h.shape)

        h = layer(h, sub(params, f"params/layer_{i}"))
    x = rms(h, eps) * (1.0 + params["params/final_norm/w"])
    keep = jnp.ones(targets.shape, jnp.float32)
    if fault == "half_batch":
        flat = jnp.arange(targets.size).reshape(targets.shape)
        keep = (flat < targets.size // 2).astype(jnp.float32)

    # the tied head: the table's first `vocab_size` rows, used a second time
    head = table[: config["vocab_size"]].T

    @jax.checkpoint
    def part(block):
        x_p, y_p, keep_p = block
        logits = (x_p @ head).astype(jnp.float32)
        per = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, y_p[..., None], axis=-1
        )[..., 0]
        return jnp.sum(per * keep_p)

    # one part after another (`lax.map`), so that the head's gradient is
    # summed as the parts come and not kept once a part
    def split(a):  # [B, T, ...] -> [parts, B, T / parts, ...]
        return jnp.stack(jnp.split(a, blocks["loss_parts"], axis=1))

    total = jnp.sum(jax.lax.map(part, (split(x), split(targets), split(keep))))
    return total / jnp.sum(keep)


def make(config: dict, mix: dict, graph: dict):
    m = config["model"]
    n = graph["num_nodes"]
    blocks = config["reference_blocks"]
    tables = {
        "indptr": jnp.asarray(graph["indptr"].astype(np.int32)),
        "dst": jnp.asarray(graph["dst"]),
    }

    def loss_fn(params, tables, key, dtype, fault):
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        ids = sequences(tables, key, n, m["batch_size"], m["seq_len"], m["doc_len"])
        return forward_loss(params, ids, config, blocks, fault).astype(dtype)

    return tables, loss_fn
