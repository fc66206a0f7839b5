"""Qwen3-Next (HF `modeling_qwen3_next.py`) written out plainly: the
token walks, the gated-DeltaNet recurrence token by token, gated softmax
attention, the mixture of experts as a weighted sum over the experts held
here, and the next-token cross-entropy. Imports nothing of the program.

With `rms(u) = u / sqrt(mean(u^2) + eps)` and norm weights `1 + w`:

- layer l: `h += Mixer_l((1 + w1) rms(h))`, `h += MoE((1 + w2) rms(h))`;
  attention when `(l + 1) % full_attention_interval == 0`, else DeltaNet.
- DeltaNet: `[q, k, v, z] = x W_qkvz`, `[b, a] = x W_ba`; (q, k, v) pass a
  causal depthwise conv (4 taps, no bias) and SiLU; q, k L2-normalised per
  head, q times dk^-0.5, key head j//r serves value head j;
  `beta = sigmoid(b)`, `alpha = exp(-exp(A_log) softplus(a + dt_bias))`.
  Per value head, S from zero: `S~ = alpha_t S`, `d = beta_t (v_t - S~^T
  k_t)`, `S = S~ + k_t d^T`, `o_t = S^T q_t`. Then `y = ((1 + w)
  rms_head(o) SiLU(z)) W_out`.
- attention: `[q | gate]` per head from W_q, k, v; zero-centred RMSNorm on
  q and k heads; rotary (theta, first `rotary_dim` of the head, positions
  0..T-1, `rotate_half` pairing); causal softmax, scale d^-0.5, query head
  h reads key/value head h // (nq / nkv); `y = (o sigmoid(gate)) W_o`.
- MoE: `p = softmax(x W_r)` over all experts; the top-k kept and divided
  by their sum; `y = sum_{e kept and held} p_e E_e(x) + sigmoid(x . w_s)
  E_s(x)`, `E(x) = (SiLU(x W_gate) * x W_up) W_down`. Experts not held
  add nothing.
- loss: mean over all positions of the cross-entropy of `norm(h) W_head`
  against the next token, logits in float32.

The draws follow the program's documented stream
(`DeviceSequenceFlow.sample`): `split(key, 2)` gives root and walk keys;
roots are `randint(1, N+1)` ids; transition i draws slot `int(uniform *
deg)` under `split(walk_key, doc_len)[i]`; `docs_per_seq` walks of
`doc_len` nodes are laid end to end, the last walk's next node closes the
sequence; token = node id - 1 = node index.

What makes it fit beside 10 GB of float32 state at the timed size, and
changes no number: every layer, and inside it the mixer and the experts, every
block of `time_block` DeltaNet steps, block of attention queries, expert
and part of the loss is rematerialised in the backward pass
(`jax.checkpoint`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("", "half_batch", "no_routed")


def param_spec(config: dict, graph: dict) -> list:
    """(path, shape, init, scale) of every leaf, in the program's tree."""
    m = config["model"]
    hidden = config["hidden_size"]
    nk, nv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    conv_dim = 2 * nk * dk + nv * dv
    nq, nkv, d = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    held = m["experts_here"][1]
    f, fs = config["moe_intermediate_size"], config["shared_expert_intermediate_size"]
    w = config["assumed"]["weight_scales"]
    mat = ("normal", w["matrix"])
    rows = -(-config["vocab_size"] // 128) * 128
    spec = [("params/embed/table", (rows, hidden)) + mat]
    for i in range(config["num_hidden_layers"]):
        layer = f"params/layer_{i}"
        spec += [
            (f"{layer}/input_norm/w", (hidden,), "zeros", 0.0),
            (f"{layer}/post_norm/w", (hidden,), "zeros", 0.0),
        ]
        mixer = f"{layer}/mixer"
        if (i + 1) % config["full_attention_interval"] == 0:
            spec += [
                (f"{mixer}/q_proj", (hidden, 2 * nq * d)) + mat,
                (f"{mixer}/k_proj", (hidden, nkv * d)) + mat,
                (f"{mixer}/v_proj", (hidden, nkv * d)) + mat,
                (f"{mixer}/o_proj", (nq * d, hidden)) + mat,
                (f"{mixer}/q_norm/w", (d,), "zeros", 0.0),
                (f"{mixer}/k_norm/w", (d,), "zeros", 0.0),
            ]
        else:
            spec += [
                (f"{mixer}/in_proj_qkvz", (hidden, conv_dim + nv * dv)) + mat,
                (f"{mixer}/in_proj_ba", (hidden, 2 * nv)) + mat,
                (f"{mixer}/conv", (conv_dim, config["linear_conv_kernel_dim"]),
                 "normal", w["conv"]),
                (f"{mixer}/A_log", (nv,), "normal", w["A_log"]),
                (f"{mixer}/dt_bias", (nv,), "normal", w["dt_bias"]),
                (f"{mixer}/norm", (dv,), "zeros", 0.0),
                (f"{mixer}/out_proj", (nv * dv, hidden)) + mat,
            ]
        moe = f"{layer}/moe"
        spec += [
            (f"{moe}/router", (hidden, m["router_experts"])) + mat,
            (f"{moe}/experts_gate", (held, hidden, f)) + mat,
            (f"{moe}/experts_up", (held, hidden, f)) + mat,
            (f"{moe}/experts_down", (held, f, hidden)) + mat,
            (f"{moe}/shared_gate", (hidden, fs)) + mat,
            (f"{moe}/shared_up", (hidden, fs)) + mat,
            (f"{moe}/shared_down", (fs, hidden)) + mat,
            (f"{moe}/shared_mix", (hidden, 1)) + mat,
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


def delta_rule(q, k, v, alpha, beta, time_block: int):
    """The recurrence, one token at a time. q, k [B, T, nv, dk],
    v [B, T, nv, dv], alpha, beta [B, T, nv]. Returns o [B, T, nv, dv]."""
    batch, length, nv, dk = q.shape
    dv = v.shape[-1]

    def token(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs  # [B, nv, ...]
        state = state * a_t[..., None, None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        d_t = b_t[..., None] * (v_t - seen)
        state = state + k_t[..., :, None] * d_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = -length % time_block
    steps = tuple(
        jnp.pad(
            jnp.moveaxis(a, 1, 0), ((0, pad),) + ((0, 0),) * (a.ndim - 1),
            constant_values=fill,
        ).reshape((-1, time_block) + a.shape[:1] + a.shape[2:])
        for a, fill in ((q, 0), (k, 0), (v, 0), (alpha, 1), (beta, 0))
    )
    state = jnp.zeros((batch, nv, dk, dv), q.dtype)
    _, out = jax.lax.scan(block, state, steps)
    out = out.reshape((-1,) + out.shape[2:])[:length]
    return jnp.moveaxis(out, 0, 1)


def gated_delta_net(p, x, config, time_block):
    batch, length, _ = x.shape
    nk, nv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    key_dim, value_dim = nk * dk, nv * dv
    eps = config["rms_norm_eps"]
    qkvz = x @ p["in_proj_qkvz"]
    ba = x @ p["in_proj_ba"]
    mixed, z = qkvz[..., : 2 * key_dim + value_dim], qkvz[..., 2 * key_dim + value_dim :]
    taps = p["conv"].shape[1]
    padded = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
    mixed = silu(
        sum(padded[:, j : j + length] * p["conv"][:, j] for j in range(taps))
    )
    q = mixed[..., :key_dim].reshape(batch, length, nk, dk)
    k = mixed[..., key_dim : 2 * key_dim].reshape(batch, length, nk, dk)
    v = mixed[..., 2 * key_dim :].reshape(batch, length, nv, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk**-0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(q, nv // nk, axis=2)
    k = jnp.repeat(k, nv // nk, axis=2)
    beta = jax.nn.sigmoid(ba[..., :nv])
    alpha = jnp.exp(
        -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., nv:] + p["dt_bias"])
    )
    o = delta_rule(q, k, v, alpha, beta, time_block)
    o = rms(o, eps) * (1.0 + p["norm"]) * silu(z.reshape(batch, length, nv, dv))
    return o.reshape(batch, length, value_dim) @ p["out_proj"]


def rotate(u, theta, rotary_dim):
    """u [B, T, heads, d]."""
    half = rotary_dim // 2
    inv_freq = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    angle = np.arange(u.shape[1], dtype=np.float32)[:, None] * inv_freq
    cos = jnp.asarray(np.cos(angle), u.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), u.dtype)[None, :, None, :]
    a, b = u[..., :half], u[..., half:rotary_dim]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, u[..., rotary_dim:]], axis=-1
    )


def gated_attention(p, x, config, query_block):
    batch, length, _ = x.shape
    nq, nkv, d = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    eps = config["rms_norm_eps"]
    rotary_dim = int(d * config["partial_rotary_factor"])
    qg = (x @ p["q_proj"]).reshape(batch, length, nq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (x @ p["k_proj"]).reshape(batch, length, nkv, d)
    v = (x @ p["v_proj"]).reshape(batch, length, nkv, d)
    q = rotate(rms(q, eps) * (1.0 + p["q_norm/w"]), config["rope_theta"], rotary_dim)
    k = rotate(rms(k, eps) * (1.0 + p["k_norm/w"]), config["rope_theta"], rotary_dim)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)

    @jax.checkpoint
    def rows(block):
        q_b, first = block
        scores = jnp.einsum("bthd,bshd->bhts", q_b, k) * d**-0.5
        at = first + jnp.arange(query_block)[:, None]
        scores = jnp.where(jnp.arange(length)[None, :] <= at, scores, -jnp.inf)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", probs.astype(x.dtype), v)

    # one block of query rows against all keys at a time, one after another
    if length % query_block:
        raise ValueError(f"{length} positions are not whole blocks of {query_block}")
    q_blocks = jnp.moveaxis(
        q.reshape(batch, length // query_block, query_block, nq, d), 1, 0
    )
    firsts = jnp.arange(0, length, query_block)
    o = jnp.moveaxis(jax.lax.map(rows, (q_blocks, firsts)), 0, 1)
    o = o.reshape(batch, length, nq, d)
    o = o * jax.nn.sigmoid(gate)
    return o.reshape(batch, length, nq * d) @ p["o_proj"]


def mixture(p, x, config, fault):
    """x [N, H]. Every token passes every expert held here; an expert the
    token was not routed to gets weight 0."""
    m = config["model"]
    first, held = m["experts_here"]
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

    y = jnp.zeros_like(x)
    if fault != "no_routed":
        y, _ = jax.lax.scan(lambda y, e: (y + expert(e), None), y, jnp.arange(held))
    shared = (silu(x @ p["shared_gate"]) * (x @ p["shared_up"])) @ p["shared_down"]
    return y + jax.nn.sigmoid(x @ p["shared_mix"]) * shared


def sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix) + 1 :]: v for k, v in params.items() if k.startswith(prefix + "/")}


def forward_loss(params, ids, config, blocks, fault):
    """ids [B, T + 1] -> mean next-token cross-entropy."""
    eps = config["rms_norm_eps"]
    tokens, targets = ids[:, :-1], ids[:, 1:]
    h = params["params/embed/table"][tokens]
    # the mixer and the experts are rematerialised each for itself, inside
    # the layer's own rematerialisation, and a mixer takes the sequences of
    # the batch one after another (nothing in it ties one to another): what
    # is alive at once in the backward pass is one sequence in one mixer
    def by_sequence(mixer):
        def run(p, x):
            one = jax.checkpoint(lambda row: mixer(p, row[None])[0])
            return jax.lax.map(one, x)

        return jax.checkpoint(run)

    delta_net = by_sequence(
        lambda p, x: gated_delta_net(p, x, config, blocks["time_block"])
    )
    attention = by_sequence(
        lambda p, x: gated_attention(p, x, config, blocks["query_block"])
    )
    experts = jax.checkpoint(lambda p, x: mixture(p, x, config, fault))

    def layer(h, p, full):
        x = rms(h, eps) * (1.0 + p["input_norm/w"])
        h = h + (attention if full else delta_net)(sub(p, "mixer"), x)
        x = rms(h, eps) * (1.0 + p["post_norm/w"])
        y = experts(sub(p, "moe"), x.reshape(-1, x.shape[-1]))
        return h + y.reshape(h.shape)

    for i in range(config["num_hidden_layers"]):
        full = (i + 1) % config["full_attention_interval"] == 0
        h = jax.checkpoint(layer, static_argnums=(2,))(
            h, sub(params, f"params/layer_{i}"), full
        )
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
