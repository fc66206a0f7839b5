"""SmallThinker-21BA3B-Instruct (PowerInfer) written out plainly: the
token walks, plain grouped-query attention of two kinds mixed 1:3 — every
earlier key with no position at all, a sliding window with rotary —, the
mixture of ReLU-gated experts as a weighted sum over the experts held
here, behind a router that reads the layer's INPUT, and the next-token
cross-entropy. Imports nothing of the program.

With `rms(u) = u / sqrt(mean(u^2) + eps)`, eps 1e-6, and norm weights
written `1 + w`, `w` from zeros (HF writes `w` from ones: the same
function). Hidden 2,560; 28 query / 4 key-value heads of 128 (7 query
heads a key head). What the catalog's `config` does not state is marked
ASSUMED; the configuration file lists each under `assumed` with its
origin.

- embedding: `h = E[id]`.
- router, layer l, on the stream AS IT ENTERS the layer (ASSUMED: before
  the input norm; `described_as`: "router placed before attention"):
  `r = h W_r` in float32 over all 64 experts; the 6 largest of `r` are
  kept and `p = softmax` over those 6 logits
  (`moe_primary_router_apply_softmax`, `norm_topk_prob`).
- attention, `a = (1 + w1) rms(h)`: `q_i = (W_q a)_i`, `k_g = (W_k a)_g`,
  `v_g = (W_v a)_g`: no head norm, no output gate, no bias (ASSUMED).
  **Window layers** (`sliding_window_layout[l] == 1`): query t sees the
  keys s with `t - 4096 < s <= t` (4,096 keys, itself among them).
  **Full layers** (0): every s <= t. Where `rope_layout[l] == 1` (the
  window layers, as published) q and k are turned by rotary over the whole
  head (dimension j with j + 64), theta 1.5e6, no scaling; where it is 0
  the layer knows no position.
  `o_i[t] = sum_s softmax_s(q_i[t] . k_g(i)[s] / sqrt(128)) v_g(i)[s]`,
  `h' = h + W_o o`.
- feed-forward, `x = (1 + w2) rms(h')`:
  `y = sum_{e kept and held} p_e E_e(x)`,
  `E(x) = W_down (ReLU(W_gate x) * W_up x)` at width 768 (ReGLU: `moe`
  "sparse ReGLU"); no shared expert, no dense layer. Experts not held add
  nothing. `h'' = h' + y`.
- loss: mean over all positions of the cross-entropy of
  `(1 + wf) rms(h) W_head` against the next token, untied head.

The draws follow the program's documented stream
(`DeviceSequenceFlow.sample`), as `reference/qwen3_next.py` writes it out.

What makes it fit beside 10.5 GB of float32 state at the timed size, and
changes no number: every layer, and inside it the attention and the
feed-forward, every block of `query_block` queries (its 28 heads' scores
against all T keys, the window a mask over the full row), every expert
and every part of the loss is rematerialised in the backward pass
(`jax.checkpoint`); the blocks, the experts and the loss's parts are
taken one after another (`lax.map`, `lax.scan`), so that what each adds
to a gradient is summed as it comes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# router_after_attention: the router reads the experts' input `x`, the
# usual place; silu_experts: SiLU in ReLU's place; no_window: the window
# layers see every earlier key; rotary_everywhere: the full layers are
# turned too
FAULTS = (
    "", "half_batch", "router_after_attention", "silu_experts", "no_window",
    "rotary_everywhere",
)


def param_spec(config: dict, graph: dict) -> list:
    """(path, shape, init, scale) of every leaf, in the program's tree."""
    m = config["model"]
    hidden = config["hidden_size"]
    nq, nkv, d = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    held, f = m["experts_here"][1], config["moe_ffn_hidden_size"]
    scales = config["assumed"]["weight_scales"]
    mat = ("normal", scales["matrix"])
    rows = -(-config["vocab_size"] // 128) * 128
    spec = [("params/embed/table", (rows, hidden), "normal", scales["embedding"])]
    for i in range(config["num_hidden_layers"]):
        layer = f"params/layer_{i}"
        spec += [
            (f"{layer}/input_norm/w", (hidden,), "zeros", 0.0),
            (f"{layer}/post_norm/w", (hidden,), "zeros", 0.0),
            (f"{layer}/mixer/q_proj", (hidden, nq * d)) + mat,
            (f"{layer}/mixer/k_proj", (hidden, nkv * d)) + mat,
            (f"{layer}/mixer/v_proj", (hidden, nkv * d)) + mat,
            (f"{layer}/mixer/o_proj", (nq * d, hidden)) + mat,
            (f"{layer}/moe/router", (hidden, m["router_experts"])) + mat,
            (f"{layer}/moe/experts_gate", (held, hidden, f)) + mat,
            (f"{layer}/moe/experts_up", (held, hidden, f)) + mat,
            (f"{layer}/moe/experts_down", (held, f, hidden)) + mat,
        ]
    spec += [
        ("params/final_norm/w", (hidden,), "zeros", 0.0),
        ("params/head", (hidden, config["vocab_size"])) + mat,
    ]
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


def relu(u):
    return jnp.where(u > 0, u, 0)


def silu(u):
    return u * jax.nn.sigmoid(u)


def rotate(u, theta):
    """u [B, T, heads, d], turned over the whole of d by positions 0..T-1."""
    half = u.shape[-1] // 2
    inv_freq = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    angle = np.arange(u.shape[1], dtype=np.float32)[:, None] * inv_freq
    cos = jnp.asarray(np.cos(angle), u.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), u.dtype)[None, :, None, :]
    a, b = u[..., :half], u[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, x, config, local, turned, query_block, fault=""):
    """x [B, T, H] -> y [B, T, H]; `local`: the layer has the window,
    `turned`: its queries and keys pass the rotary."""
    batch, length, _ = x.shape
    nq, nkv, d = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    q = (x @ p["q_proj"]).reshape(batch, length, nq, d)
    k = (x @ p["k_proj"]).reshape(batch, length, nkv, d)
    v = (x @ p["v_proj"]).reshape(batch, length, nkv, d)
    if turned or fault == "rotary_everywhere":
        q, k = rotate(q, config["rope_theta"]), rotate(k, config["rope_theta"])
    # query head i reads key/value head i // (nq / nkv): [B, T, group, head in it, d]
    q = q.reshape(batch, length, nkv, nq // nkv, d)
    window = config["sliding_window_size"] if local and fault != "no_window" else length
    keys = jnp.arange(length)[None, :]

    @jax.checkpoint
    def rows(block):
        q_b, first = block
        at = first + jnp.arange(query_block)[:, None]
        seen = (keys <= at) & (keys > at - window)  # [block, T]: the full row
        scores = jnp.einsum("btgrd,bsgd->bgrts", q_b, k) * d**-0.5
        scores = jnp.where(seen, scores.astype(jnp.float32), -jnp.inf)
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


def mixture(p, x, route_on, config, fault=""):
    """x, route_on [N, H]: the experts read `x`, the router `route_on`.
    Every token passes every expert held here; an expert the token was
    not routed to gets weight 0."""
    first, held = config["model"]["experts_here"]
    top_k = config["moe_num_active_primary_experts"]
    if fault == "router_after_attention":
        route_on = x
    logits = (route_on @ p["router"]).astype(jnp.float32)
    top_r, top_e = jax.lax.top_k(logits, top_k)
    top_p = jax.nn.softmax(top_r, axis=-1)  # over the kept logits alone
    weight = jnp.sum(
        jax.nn.one_hot(top_e, logits.shape[-1], dtype=jnp.float32)
        * top_p[..., None],
        axis=1,
    ).astype(x.dtype)  # [N, E]: the kept weight, 0 where not picked
    act = silu if fault == "silu_experts" else relu

    @jax.checkpoint
    def expert(e):
        out = (act(x @ p["experts_gate"][e]) * (x @ p["experts_up"][e])) @ p["experts_down"][e]
        return weight[:, first + e][:, None] * out

    y, _ = jax.lax.scan(
        lambda y, e: (y + expert(e), None), jnp.zeros_like(x), jnp.arange(held)
    )
    return y


def sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix) + 1 :]: v for k, v in params.items() if k.startswith(prefix + "/")}


def layouts(config: dict) -> list:
    """(has the window, passes the rotary) of each layer that is here:
    `model.layouts_here`, the stretch of the two published layouts this
    stage holds."""
    here = config["model"]["layouts_here"]
    kinds = list(zip(here["sliding_window_layout"], here["rope_layout"]))
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError(f"{len(kinds)} layer kinds for {config['num_hidden_layers']} layers")
    return [(bool(window), bool(rope)) for window, rope in kinds]


def forward_loss(params, ids, config, blocks, fault):
    """ids [B, T + 1] -> mean next-token cross-entropy."""
    eps = config["rms_norm_eps"]
    tokens, targets = ids[:, :-1], ids[:, 1:]
    h = params["params/embed/table"][tokens]

    def norm(p, name, u):
        return rms(u, eps) * (1.0 + p[f"{name}/w"])

    for i, (local, turned) in enumerate(layouts(config)):
        attend = jax.checkpoint(
            lambda p, x, local=local, turned=turned: attention(
                p, x, config, local, turned, blocks["query_block"], fault
            )
        )
        feed = jax.checkpoint(lambda p, x, r: mixture(p, x, r, config, fault))

        @jax.checkpoint
        def layer(h, p, attend=attend, feed=feed):
            entered = h.reshape(-1, h.shape[-1])  # what the router reads
            h = h + attend(sub(p, "mixer"), norm(p, "input_norm", h))
            x = norm(p, "post_norm", h)
            y = feed(sub(p, "moe"), x.reshape(entered.shape), entered)
            return h + y.reshape(h.shape)

        h = layer(h, sub(params, f"params/layer_{i}"))
    x = rms(h, eps) * (1.0 + params["params/final_norm/w"])
    keep = jnp.ones(targets.shape, jnp.float32)
    if fault == "half_batch":
        flat = jnp.arange(targets.size).reshape(targets.shape)
        keep = (flat < targets.size // 2).astype(jnp.float32)

    head = params["params/head"]

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
