"""Keye-VL-2.0-30B-A3B's language model written out plainly: the token
walks, grouped-query attention over the keys a learned indexer picks
(DeepSeek sparse attention), multi-axis rotary, the mixture of experts as
a weighted sum over the experts held here, the next-token cross-entropy
and the indexer's KL term. Imports nothing of the program. The vision
tower is not here (the catalog gives none of its widths); what it leaves
in the language model, three position axes, is.

With `rms(u) = u / sqrt(mean(u^2) + eps)` and norm weights `1 + w`, for
token t with x_t = (1 + w1) rms(h_t):

- attention inputs: `q_i = R3((1 + wq) rms(W_q x)_i)`, `k_g = R3((1 + wk)
  rms(W_k x)_g)`, `v_g = (W_v x)_g`, 32 heads i and 4 groups g of 128.
  `R3`: a position is (time, height, width); frequency pair j of the 64
  (dimension j with j + 64) turns by `p^{s(j)} theta^(-j/64)`, s(j) = 0
  for j < 16, 1 for 16 <= j < 40, 2 above (`mrope_section` [16, 24, 24] in
  consecutive runs: ASSUMED, Qwen2-VL's form). Text has all three = t.
- indexer, fed x with the gradient stopped: `qI_j = R(W_Iq x)_j` in R^64,
  j < 16; one key `kI = R((1 + wn) layernorm(W_Ik x) + bn)`; `R` turns the
  whole 64 by the time position (ASSUMED; DeepSeek turns half of a 128
  head); `w_j = (W_Iw x)_j 16^-0.5 64^-0.5`; `I[t, s] = sum_j w_j[t]
  relu(qI_j[t] . kI[s])` for s <= t.
- selection: S_t = the `topk` keys s <= t of largest I[t, s], by a stable
  sort (ties to the lower s); all t + 1 of them while t < topk.
- core: `o_i[t] = sum_{s in S_t} softmax_{S_t}(q_i[t] . k_g(i)[s] /
  sqrt(128)) v_g(i)[s]`, `y = W_o [o_0 .. o_31]`; no output gate.
- indexer loss: `L_I` = mean over layers and t of `KL(p_t || softmax_{S_t}
  I[t])`, p_t the 32 heads' probabilities over S_t summed and normalised,
  gradient stopped (ASSUMED: DeepSeek-V3.2's sparse stage, coefficient 1).
- `h += y`; then `h += MoE((1 + w2) rms(h))`: `p = softmax(x W_r)` over all
  128 experts; the top 8 kept and divided by their sum; `y = sum_{e kept
  and held} p_e E_e(x)`, `E(x) = (SiLU(x W_gate) * x W_up) W_down`; no
  shared expert. Experts not held add nothing.
- loss: mean over all positions of the cross-entropy of `norm(h) W_head`
  against the next token, plus `L_I`.

The draws follow the program's documented stream
(`DeviceSequenceFlow.sample`), as `reference/qwen3_next.py` writes it out.

What makes it fit beside 7.5 GB of float32 state at the timed size, and
changes no number: every layer, and inside it the mixer and the experts,
every block of `query_block` queries (its [block, T] index scores, sort
and 32 heads' scores against all T keys), every expert and every part of
the loss is rematerialised in the backward pass (`jax.checkpoint`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# recent_keys: the selection ignores the indexer and takes the last `topk`
# keys; no_index_loss: the indexer's KL term is left out of the loss
FAULTS = ("", "half_batch", "recent_keys", "no_index_loss")


def param_spec(config: dict, graph: dict) -> list:
    """(path, shape, init, scale) of every leaf, in the program's tree."""
    m, sa = config["model"], config["sa_config"]
    hidden = config["hidden_size"]
    nq, nkv, d = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    ni, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    held, f = m["experts_here"][1], config["moe_intermediate_size"]
    scales = config["assumed"]["weight_scales"]
    mat = ("normal", scales["matrix"])
    rows = -(-config["vocab_size"] // 128) * 128
    spec = [("params/embed/table", (rows, hidden), "normal", scales["embedding"])]
    for i in range(config["num_hidden_layers"]):
        layer = f"params/layer_{i}"
        mixer, moe = f"{layer}/mixer", f"{layer}/moe"
        spec += [
            (f"{layer}/input_norm/w", (hidden,), "zeros", 0.0),
            (f"{layer}/post_norm/w", (hidden,), "zeros", 0.0),
            (f"{mixer}/q_proj", (hidden, nq * d)) + mat,
            (f"{mixer}/k_proj", (hidden, nkv * d)) + mat,
            (f"{mixer}/v_proj", (hidden, nkv * d)) + mat,
            (f"{mixer}/o_proj", (nq * d, hidden)) + mat,
            (f"{mixer}/q_norm/w", (d,), "zeros", 0.0),
            (f"{mixer}/k_norm/w", (d,), "zeros", 0.0),
            (f"{mixer}/index_q", (hidden, ni * di)) + mat,
            (f"{mixer}/index_k", (hidden, di)) + mat,
            (f"{mixer}/index_w", (hidden, ni)) + mat,
            (f"{mixer}/index_k_norm_w", (di,), "zeros", 0.0),
            (f"{mixer}/index_k_norm_b", (di,), "zeros", 0.0),
            (f"{moe}/router", (hidden, m["router_experts"])) + mat,
            (f"{moe}/experts_gate", (held, hidden, f)) + mat,
            (f"{moe}/experts_up", (held, hidden, f)) + mat,
            (f"{moe}/experts_down", (held, f, hidden)) + mat,
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


def rotate(u, theta, positions, sections):
    """u [B, T, heads, d], turned over the whole of d; positions
    [axes, B, T]; pair j turns by the axis whose run of `sections` holds
    it."""
    half = u.shape[-1] // 2
    inv_freq = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    bounds = np.cumsum([0] + list(sections))
    if bounds[-1] != half:
        raise ValueError(f"sections {sections} do not add up to {half} pairs")
    angle = jnp.zeros(positions.shape[1:] + (half,), jnp.float32)
    for axis in range(len(sections)):
        mine = np.zeros(half, np.float32)
        mine[bounds[axis] : bounds[axis + 1]] = inv_freq[bounds[axis] : bounds[axis + 1]]
        angle = angle + positions[axis].astype(jnp.float32)[..., None] * mine
    cos = jnp.cos(angle).astype(u.dtype)[:, :, None, :]
    sin = jnp.sin(angle).astype(u.dtype)[:, :, None, :]
    a, b = u[..., :half], u[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def sparse_attention(p, x, positions, config, query_block, fault=""):
    """x [B, T, H], positions [3, B, T] -> (y [B, T, H], the sum over
    the queries of the indexer's KL term)."""
    batch, length, _ = x.shape
    sa = config["sa_config"]
    nq, nkv, d = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    ni, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    sections = config["rope_scaling"]["mrope_section"]
    q = (x @ p["q_proj"]).reshape(batch, length, nq, d)
    k = (x @ p["k_proj"]).reshape(batch, length, nkv, d)
    v = (x @ p["v_proj"]).reshape(batch, length, nkv, d)
    q = rotate(rms(q, eps) * (1.0 + p["q_norm/w"]), theta, positions, sections)
    k = rotate(rms(k, eps) * (1.0 + p["k_norm/w"]), theta, positions, sections)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    fed = jax.lax.stop_gradient(x)
    time = positions[:1]
    qi = rotate((fed @ p["index_q"]).reshape(batch, length, ni, di), theta, time, [di // 2])
    ki = fed @ p["index_k"]
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = rms(ki, eps) * (1.0 + p["index_k_norm_w"]) + p["index_k_norm_b"]
    ki = rotate(ki[:, :, None, :], theta, time, [di // 2])[:, :, 0]
    wi = (fed @ p["index_w"]) * ni**-0.5 * di**-0.5
    keys = jnp.arange(length)[None, :]

    @jax.checkpoint
    def rows(block):
        q_b, qi_b, wi_b, first = block
        at = first + jnp.arange(query_block)[:, None]
        seen = keys <= at  # [block, T]
        index = jnp.sum(
            wi_b[..., None] * jax.nn.relu(jnp.einsum("btjd,bsd->btjs", qi_b, ki)),
            axis=2,
        )
        index = jnp.where(seen, index.astype(jnp.float32), -jnp.inf)
        if fault == "recent_keys":
            keep = jnp.broadcast_to(seen & (keys > at - topk), index.shape)
        else:
            # descending, ties to the lower index; rank = place in that order
            order = jnp.argsort(-index, axis=-1, stable=True)
            rank = jnp.argsort(order, axis=-1)
            keep = seen & (rank < topk)
        scores = jnp.einsum("bthd,bshd->bhts", q_b, k) * d**-0.5
        scores = jnp.where(keep[:, None], scores.astype(jnp.float32), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhts,bshd->bthd", probs.astype(x.dtype), v)
        target = jax.lax.stop_gradient(jnp.sum(probs, axis=1))
        target = target / jnp.sum(target, axis=-1, keepdims=True)
        log_q = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), axis=-1)
        live = keep & (target > 0)
        kl = jnp.where(
            live,
            target * (jnp.log(jnp.where(live, target, 1.0)) - jnp.where(live, log_q, 0.0)),
            0.0,
        )
        return o, jnp.sum(kl)

    if length % query_block:
        raise ValueError(f"{length} positions are not whole blocks of {query_block}")
    blocks = length // query_block

    def split(a):  # [B, T, ...] -> [blocks, B, query_block, ...]
        return jnp.moveaxis(a.reshape((batch, blocks, query_block) + a.shape[2:]), 1, 0)

    o, kl = jax.lax.map(
        rows, (split(q), split(qi), split(wi), jnp.arange(0, length, query_block))
    )
    o = jnp.moveaxis(o, 0, 1).reshape(batch, length, nq * d)
    return o @ p["o_proj"], jnp.sum(kl)


def mixture(p, x, config, fault=""):
    """x [N, H]. Every token passes every expert held here; an expert the
    token was not routed to gets weight 0. No shared expert."""
    first, held = config["model"]["experts_here"]
    top_k = config["num_experts_per_tok"]
    probs = jax.nn.softmax((x @ p["router"]).astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    if config["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    weight = jnp.sum(
        jax.nn.one_hot(top_e, probs.shape[-1], dtype=jnp.float32)
        * top_p[..., None],
        axis=1,
    ).astype(x.dtype)  # [N, E]: the renormalised weight, 0 where not kept

    @jax.checkpoint
    def expert(e):
        out = (silu(x @ p["experts_gate"][e]) * (x @ p["experts_up"][e])) @ p[
            "experts_down"
        ][e]
        return weight[:, first + e][:, None] * out

    y, _ = jax.lax.scan(
        lambda y, e: (y + expert(e), None), jnp.zeros_like(x), jnp.arange(held)
    )
    return y


def sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix) + 1 :]: v for k, v in params.items() if k.startswith(prefix + "/")}


def forward_loss(params, ids, config, blocks, fault):
    """ids [B, T + 1] -> mean next-token cross-entropy + the indexer's
    loss."""
    eps = config["rms_norm_eps"]
    tokens, targets = ids[:, :-1], ids[:, 1:]
    h = params["params/embed/table"][tokens]
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), (3,) + tokens.shape)
    attention = jax.checkpoint(
        lambda p, x: sparse_attention(p, x, positions, config, blocks["query_block"], fault)
    )
    experts = jax.checkpoint(lambda p, x: mixture(p, x, config, fault))

    @jax.checkpoint
    def layer(h, p):
        x = rms(h, eps) * (1.0 + p["input_norm/w"])
        y, kl = attention(sub(p, "mixer"), x)
        h = h + y
        x = rms(h, eps) * (1.0 + p["post_norm/w"])
        y = experts(sub(p, "moe"), x.reshape(-1, x.shape[-1]))
        return h + y.reshape(h.shape), kl

    index_loss = 0.0
    for i in range(config["num_hidden_layers"]):
        h, kl = layer(h, sub(params, f"params/layer_{i}"))
        index_loss = index_loss + kl / (tokens.size * config["num_hidden_layers"])
    x = rms(h, eps) * (1.0 + params["params/final_norm/w"])
    keep = jnp.ones(targets.shape, jnp.float32)
    if fault == "half_batch":
        flat = jnp.arange(targets.size).reshape(targets.shape)
        keep = (flat < targets.size // 2).astype(jnp.float32)

    @jax.checkpoint
    def part(x_p, y_p, keep_p, w):
        logits = (x_p @ w).astype(jnp.float32)
        per = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, y_p[..., None], axis=-1
        )[..., 0]
        return jnp.sum(per * keep_p)

    parts = blocks["loss_parts"]
    total = sum(
        part(x_p, y_p, k_p, params["params/head"])
        for x_p, y_p, k_p in zip(
            jnp.split(x, parts, axis=1), jnp.split(targets, parts, axis=1),
            jnp.split(keep, parts, axis=1),
        )
    )
    loss = total / jnp.sum(keep)
    return loss if fault == "no_index_loss" else loss + index_loss


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
