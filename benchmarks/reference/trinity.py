"""Trinity-Mini (Arcee, HF `model_type` `afmoe`) written out plainly: the
token walks, gated grouped-query attention of two kinds mixed 3:1 — a
sliding window with rotary, every earlier key with none —, a leading
dense layer, the mixture of experts behind a sigmoid-scored router as a
weighted sum over the experts held here, sandwich norms and the
next-token cross-entropy. Imports nothing of the program.

With `rms(u) = u / sqrt(mean(u^2) + eps)`, eps 1e-5, and norm weights
written `1 + w`, `w` from zeros (HF writes `w` from ones: the same
function). Hidden 2,048; 32 query / 4 key-value heads of 128. What the
catalog's `config` does not state is marked ASSUMED; the configuration
file lists each under `assumed` with its origin.

- embedding: `h = sqrt(2048) * E[id]` (`mup_enabled`).
- attention, layer l, `x = (1 + w1) rms(h)`: `q_i = (1 + wq) rms(W_q x)_i`,
  `k_g = (1 + wk) rms(W_k x)_g` (RMSNorm over each head's 128: ASSUMED, HF
  `modeling_afmoe.py`), `v_g = (W_v x)_g`, gate `z = W_z x` in R^4096
  (ASSUMED, same origin). W_q and W_z are one matrix `q_proj` whose
  columns lie, head by head, [query | gate]: a permutation of HF's
  columns. **Window layers** (`layer_types[l] == "sliding_attention"`): q
  and k turned by rotary over the whole head (dimension j with j + 64),
  theta 10,000, no scaling; query t sees the keys s with
  `t - 2048 < s <= t` (2,048 keys, itself among them: HF's mask). **Full
  layers** (`"full_attention"`, every 4th): no rotary (ASSUMED: HF turns
  only where the layer is local), every s <= t.
  `o_i[t] = sum_s softmax_s(q_i[t] . k_g(i)[s] / sqrt(128)) v_g(i)[s]`,
  `y = W_o (o * sigmoid(z))`, `h += (1 + w2) rms(y)`.
- feed-forward, `x = (1 + w3) rms(h)`: in the leading dense layer
  `f = W_down (SiLU(W_gate x) * W_up x)` at width 6,144. In every other
  layer `s = sigmoid(x W_r)` in float32 over all 128 experts; the 8
  largest of `s + b` are picked (`b` the expert bias, zeros, which takes no
  gradient; `n_group` = `topk_group` = 1: no group limit);
  `p_e = 2.826 s_e / (sum of the 8 picked s + 1e-20)` (`route_norm`,
  `route_scale`); `f = sum_{e picked and held} p_e E_e(x) + E_shared(x)`,
  `E(x) = W_down (SiLU(W_gate x) * W_up x)` at width 1,024, the one shared
  expert unweighted. Experts not held add nothing.
  `h += (1 + w4) rms(f)` (sandwich norms: ASSUMED, same origin).
- loss: mean over all positions of the cross-entropy of
  `(1 + wf) rms(h) W_head` against the next token, untied head.
- LEFT OUT: the aux-loss-free update of `b` between steps
  (`load_balance_coeff`); `b` is in the pick, so the forward is whole.

The draws follow the program's documented stream
(`DeviceSequenceFlow.sample`), as `reference/qwen3_next.py` writes it out.

What makes it fit beside 11.3 GB of float32 state at the timed size, and
changes no number: every layer, and inside it the attention and the
feed-forward, every block of `query_block` queries (its 32 heads' scores
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

# no_window: the window layers see every earlier key; rotary_everywhere:
# the full layers are turned too; softmax_router: softmax scores in the
# sigmoid's place and no `route_scale`
FAULTS = ("", "half_batch", "no_window", "rotary_everywhere", "softmax_router")
LOCAL = "sliding_attention"


def param_spec(config: dict, graph: dict) -> list:
    """(path, shape, init, scale) of every leaf, in the program's tree."""
    m = config["model"]
    hidden = config["hidden_size"]
    nq, nkv, d = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    held, f = m["experts_here"][1], config["moe_intermediate_size"]
    shared = f * config["num_shared_experts"]
    scales = config["assumed"]["weight_scales"]
    mat = ("normal", scales["matrix"])
    rows = -(-config["vocab_size"] // 128) * 128
    spec = [("params/embed/table", (rows, hidden), "normal", scales["embedding"])]
    for i in range(config["num_hidden_layers"]):
        layer = f"params/layer_{i}"
        mixer = f"{layer}/mixer"
        spec += [
            (f"{layer}/{norm}/w", (hidden,), "zeros", 0.0)
            for norm in ("input_norm", "mixer_out_norm", "post_norm", "ffn_out_norm")
        ]
        spec += [
            (f"{mixer}/q_proj", (hidden, nq * d * 2)) + mat,
            (f"{mixer}/k_proj", (hidden, nkv * d)) + mat,
            (f"{mixer}/v_proj", (hidden, nkv * d)) + mat,
            (f"{mixer}/o_proj", (nq * d, hidden)) + mat,
            (f"{mixer}/q_norm/w", (d,), "zeros", 0.0),
            (f"{mixer}/k_norm/w", (d,), "zeros", 0.0),
        ]
        if i < config["num_dense_layers"]:
            wide = config["intermediate_size"]
            spec += [
                (f"{layer}/mlp/gate", (hidden, wide)) + mat,
                (f"{layer}/mlp/up", (hidden, wide)) + mat,
                (f"{layer}/mlp/down", (wide, hidden)) + mat,
            ]
            continue
        moe = f"{layer}/moe"
        spec += [
            (f"{moe}/router", (hidden, m["router_experts"])) + mat,
            (f"{moe}/expert_bias", (m["router_experts"],), "zeros", 0.0),
            (f"{moe}/experts_gate", (held, hidden, f)) + mat,
            (f"{moe}/experts_up", (held, hidden, f)) + mat,
            (f"{moe}/experts_down", (held, f, hidden)) + mat,
            (f"{moe}/shared_gate", (hidden, shared)) + mat,
            (f"{moe}/shared_up", (hidden, shared)) + mat,
            (f"{moe}/shared_down", (shared, hidden)) + mat,
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


def gated_attention(p, x, config, local, query_block, fault=""):
    """x [B, T, H] -> y [B, T, H]; `local` says which kind the layer is."""
    batch, length, _ = x.shape
    nq, nkv, d = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    eps = config["rms_norm_eps"]
    qg = (x @ p["q_proj"]).reshape(batch, length, nq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (x @ p["k_proj"]).reshape(batch, length, nkv, d)
    v = (x @ p["v_proj"]).reshape(batch, length, nkv, d)
    q = rms(q, eps) * (1.0 + p["q_norm/w"])
    k = rms(k, eps) * (1.0 + p["k_norm/w"])
    if local or fault == "rotary_everywhere":
        q, k = rotate(q, config["rope_theta"]), rotate(k, config["rope_theta"])
    # query head i reads key/value head i // (nq / nkv): [B, T, group, head in it, d]
    q = q.reshape(batch, length, nkv, nq // nkv, d)
    window = config["sliding_window"] if local and fault != "no_window" else length
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
    o = o.reshape(batch, length, nq, d) * jax.nn.sigmoid(gate)
    return o.reshape(batch, length, nq * d) @ p["o_proj"]


def mixture(p, x, config, fault=""):
    """x [N, H]. Every token passes every expert held here; an expert the
    token was not routed to gets weight 0. The shared expert is added as
    it is."""
    first, held = config["model"]["experts_here"]
    top_k = config["num_experts_per_tok"]
    logits = (x @ p["router"]).astype(jnp.float32)
    if fault == "softmax_router":
        scores, scale = jax.nn.softmax(logits, axis=-1), 1.0
    else:
        scores, scale = jax.nn.sigmoid(logits), config["route_scale"]
    _, top_e = jax.lax.top_k(scores + jax.lax.stop_gradient(p["expert_bias"]), top_k)
    top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    if config["route_norm"]:
        top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
    top_p = top_p * scale
    weight = jnp.sum(
        jax.nn.one_hot(top_e, scores.shape[-1], dtype=jnp.float32)
        * top_p[..., None],
        axis=1,
    ).astype(x.dtype)  # [N, E]: the kept weight, 0 where not picked

    @jax.checkpoint
    def expert(e):
        out = swiglu(x, p["experts_gate"][e], p["experts_up"][e], p["experts_down"][e])
        return weight[:, first + e][:, None] * out

    y = jnp.zeros_like(x)
    if fault != "no_routed":
        y, _ = jax.lax.scan(lambda y, e: (y + expert(e), None), y, jnp.arange(held))
    return y + swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])


def sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix) + 1 :]: v for k, v in params.items() if k.startswith(prefix + "/")}


def layer_kinds(config: dict) -> list:
    """The kinds of the layers that are here: `model.layer_types_here`,
    the stretch of the published `layer_types` this stage holds."""
    kinds = config["model"]["layer_types_here"]
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError(f"{len(kinds)} layer kinds for {config['num_hidden_layers']} layers")
    return kinds


def forward_loss(params, ids, config, blocks, fault):
    """ids [B, T + 1] -> mean next-token cross-entropy."""
    eps = config["rms_norm_eps"]
    tokens, targets = ids[:, :-1], ids[:, 1:]
    scale = config["hidden_size"] ** 0.5 if config["mup_enabled"] else 1.0
    h = params["params/embed/table"][tokens] * scale

    def norm(p, name, u):
        return rms(u, eps) * (1.0 + p[f"{name}/w"])

    for i, kind in enumerate(layer_kinds(config)):
        attention = jax.checkpoint(
            lambda p, x, local=kind == LOCAL: gated_attention(
                p, x, config, local, blocks["query_block"], fault
            )
        )
        if i < config["num_dense_layers"]:
            feed = jax.checkpoint(lambda p, x: swiglu(x, p["mlp/gate"], p["mlp/up"], p["mlp/down"]))
        else:
            feed = jax.checkpoint(lambda p, x: mixture(sub(p, "moe"), x, config, fault))

        @jax.checkpoint
        def layer(h, p, attention=attention, feed=feed):
            y = attention(sub(p, "mixer"), norm(p, "input_norm", h))
            h = h + norm(p, "mixer_out_norm", y)
            x = norm(p, "post_norm", h)
            y = feed(p, x.reshape(-1, x.shape[-1])).reshape(h.shape)
            return h + norm(p, "ffn_out_norm", y)

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
        if fault not in FAULTS + ("no_routed",):
            raise ValueError(f"unknown fault {fault!r}")
        ids = sequences(tables, key, n, m["batch_size"], m["seq_len"], m["doc_len"])
        return forward_loss(params, ids, config, blocks, fault).astype(dtype)

    return tables, loss_fn
