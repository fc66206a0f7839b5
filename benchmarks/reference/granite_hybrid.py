"""Granite-4.0-H-Micro (IBM, HF `model_type` `granitemoehybrid`, no
experts) written out plainly: the token walks, Mamba-2's state-space
mixer in nine layers of ten as the per-token recurrence that defines it,
position-free grouped-query attention at a head of 64 in the tenth, the
dense SwiGLU in every layer, Granite's three multipliers, the head that
is the embedding table transposed and the next-token cross-entropy.
Imports nothing of the program.

With `rms(u) = u / sqrt(mean(u^2) + eps)`, eps 1e-5, and norm weights
written `1 + w`, `w` from zeros (HF writes `w` from ones: the same
function). Hidden 2,048. The equations are HF
`modeling_granitemoehybrid.py` and its Mamba-2 mixer from memory (no
network here); what the catalog's `config` has no key for is marked
ASSUMED, and the configuration file lists each under `assumed`.

- embedding: `h = 12 E[id]` (`embedding_multiplier`).
- layer l: `h += 0.22 Mixer_l((1 + w1) rms(h))`, `h += 0.22 MLP((1 + w2)
  rms(h))` (`residual_multiplier`).
- `mamba` mixer (64 heads of 64, state 128, one group, conv 4 with bias):
  `[z | xBC | dt] = x W_in` (4,096 | 4,352 | 64); `xBC = silu(sum_j
  w[:, j] xBC_{t-3+j} + b)` (zeros before the sequence); `[x | B | C] =
  xBC` (4,096 | 128 | 128); `dt = softplus(dt + dt_bias)`, `A =
  -exp(A_log)`; per head h, from `S = 0`: `S_t = exp(dt_t A_h) S_{t-1} +
  dt_t x_t B_t^T` ([64, 128]), `y_t = S_t C_t + D_h x_t`; `out =
  ((1 + wn) rms(y * silu(z))) W_out`, the norm over all 4,096. `A_log`,
  `dt_bias` and `D` are written as departures from Mamba-2's start, as
  the norms are: `A_log = log(1..64) + w`, `dt_bias = softplus^-1(dt0) +
  w` with `dt0` spread log-uniformly over [1e-3, 1e-1] by head, `D = 1 +
  w` (ASSUMED: the initialisation, `assumed.weights`).
- `attention` mixer: 32 query / 8 key-value heads of 64, no bias, no
  position, no head norms, no output gate; `o_i[t] = sum_{s <= t}
  softmax_s(q_i[t] . k_g(i)[s] 0.015625) v_g(i)[s]`
  (`attention_multiplier`), `y = W_o o`.
- MLP: `W_down (silu(W_gate x) * W_up x)` at width 8,192 (HF's
  `input_linear` holds gate and up in one matrix: a permutation of
  columns).
- loss: mean over all positions of the cross-entropy of `(1 + wf) rms(h)
  E^T / 8` (`logits_scaling`) against the next token: the head IS the
  embedding table (`tie_word_embeddings`), read a second time.

The draws follow the program's documented stream
(`DeviceSequenceFlow.sample`), as `reference/qwen3_next.py` writes it out.

What makes it fit beside 12.35 GB of float32 state at the timed size,
and changes no number: every layer, and inside it the mixer and the
MLP, every block of `time_block` tokens of a Mamba mixer (projections,
convolution, recurrence, gate and norm of those tokens, the state and
the convolution's last inputs carried from block to block), every block
of `query_block` queries (its 32 heads' scores against all T keys, the
causal line a mask over the full row), the MLP's row blocks and every
part of the loss is rematerialised in the backward pass
(`jax.checkpoint`), taken one after another (`lax.scan`, `lax.map`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# no_D_skip: y_t = S_t C_t alone; ungated_norm: the norm of y without
# silu(z); no_residual_multiplier: sublayers added times 1; conv_no_bias:
# the convolution without its bias; attn_sqrt_scale: the scores times
# 64^-0.5 in attention_multiplier's place
FAULTS = (
    "", "half_batch", "no_D_skip", "ungated_norm", "no_residual_multiplier",
    "conv_no_bias", "attn_sqrt_scale",
)
MAMBA, ATTENTION = "mamba", "attention"
DT_RANGE = (1e-3, 1e-1)  # ASSUMED: Mamba-2's dt_min, dt_max


def param_spec(config: dict, graph: dict) -> list:
    """(path, shape, init, scale) of every leaf, in the program's tree."""
    m = config["model"]
    hidden = config["hidden_size"]
    nq, nkv, d = config["num_attention_heads"], config["num_key_value_heads"], m["head_dim"]
    heads, p, n = config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"]
    inner, taps = heads * p, config["mamba_d_conv"]
    conv_dim = inner + 2 * n
    scales = config["assumed"]["weight_scales"]
    mat = ("normal", scales["matrix"])
    conv = ("normal", scales["conv"])
    wide = config["shared_intermediate_size"]
    rows = -(-config["vocab_size"] // 128) * 128
    spec = [("params/embed/table", (rows, hidden), "normal", scales["embedding"])]
    for i, kind in enumerate(layer_kinds(config)):
        layer = f"params/layer_{i}"
        spec += [
            (f"{layer}/input_norm/w", (hidden,), "zeros", 0.0),
            (f"{layer}/post_norm/w", (hidden,), "zeros", 0.0),
        ]
        if kind == MAMBA:
            spec += [
                (f"{layer}/mixer/in_proj", (hidden, inner + conv_dim + heads)) + mat,
                (f"{layer}/mixer/conv", (conv_dim, taps)) + conv,
                (f"{layer}/mixer/conv_bias", (conv_dim,)) + conv,
                (f"{layer}/mixer/A_log", (heads,), "zeros", 0.0),
                (f"{layer}/mixer/dt_bias", (heads,), "zeros", 0.0),
                (f"{layer}/mixer/D", (heads,), "zeros", 0.0),
                (f"{layer}/mixer/norm", (inner,), "zeros", 0.0),
                (f"{layer}/mixer/out_proj", (inner, hidden)) + mat,
            ]
        else:
            spec += [
                (f"{layer}/mixer/q_proj", (hidden, nq * d)) + mat,
                (f"{layer}/mixer/k_proj", (hidden, nkv * d)) + mat,
                (f"{layer}/mixer/v_proj", (hidden, nkv * d)) + mat,
                (f"{layer}/mixer/o_proj", (nq * d, hidden)) + mat,
            ]
        spec += [
            (f"{layer}/mlp/gate", (hidden, wide)) + mat,
            (f"{layer}/mlp/up", (hidden, wide)) + mat,
            (f"{layer}/mlp/down", (wide, hidden)) + mat,
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


def mamba_starts(heads: int):
    """(A_log, dt_bias) where Mamba-2 starts them: A = -(1..heads), dt
    at the heads' quantiles of the log-uniform law over DT_RANGE."""
    lo, hi = np.log(DT_RANGE)
    dt = np.exp(lo + (hi - lo) * (np.arange(heads) + 0.5) / heads)
    return np.log(np.arange(1, heads + 1, dtype=np.float32)), dt + np.log(-np.expm1(-dt))


def recurrence(state, x, dt, a, b, c):
    """The scan one token at a time, from `state` [B, H, P, N]. x [B, t,
    H, P], dt [B, t, H], a [H] (<= 0), b, c [B, t, N] -> (the state after
    the last token, y [B, t, H, P]), without the skip."""

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs  # [B, H, P], [B, H], [B, N], [B, N]
        state = state * jnp.exp(dt_t * a)[..., None, None] + (
            (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        )
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    state, y = jax.lax.scan(token, state, tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return state, jnp.moveaxis(y, 0, 1)


def mamba(p, x, config, time_block, fault=""):
    """x [B, T, H] -> y [B, T, H]: Mamba-2's mixer, one group, `time_block`
    tokens at a time — the state and the convolution's last inputs carried
    from block to block, every block rematerialised — so that no tensor
    of the whole sequence's width ([T, 8,512]) is ever made."""
    batch, length, hidden = x.shape
    heads, hp, n = config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"]
    inner = heads * hp
    conv_dim = inner + 2 * n
    eps = config["rms_norm_eps"]
    taps = p["conv"].shape[1]
    a_log, dt_bias = mamba_starts(heads)
    a = -jnp.exp(jnp.asarray(a_log, x.dtype) + p["A_log"])

    @jax.checkpoint
    def block(carry, x_b):
        state, tail = carry  # tail: the block before's last taps - 1 inputs of the conv
        zxbcdt = x_b @ p["in_proj"]
        z, xbc, dt = (
            zxbcdt[..., :inner], zxbcdt[..., inner : inner + conv_dim],
            zxbcdt[..., inner + conv_dim :],
        )
        seen = jnp.concatenate([tail, xbc], axis=1)
        mixed = sum(seen[:, j : j + time_block] * p["conv"][:, j] for j in range(taps))
        if fault != "conv_no_bias":  # DEPARTS when planted
            mixed = mixed + p["conv_bias"]
        xbc = silu(mixed)
        xs = xbc[..., :inner].reshape(batch, time_block, heads, hp)
        b, c = xbc[..., inner : inner + n], xbc[..., inner + n :]
        dt = jax.nn.softplus(dt + jnp.asarray(dt_bias, x.dtype) + p["dt_bias"])
        state, y = recurrence(state, xs, dt, a, b, c)
        if fault != "no_D_skip":  # DEPARTS when planted: y_t = S_t C_t
            y = y + (1.0 + p["D"])[:, None] * xs
        y = y.reshape(batch, time_block, inner)
        if fault != "ungated_norm":  # DEPARTS when planted: rms(y) without the gate
            y = y * silu(z)
        out = (rms(y, eps) * (1.0 + p["norm"])) @ p["out_proj"]
        return (state, seen[:, time_block:]), out

    if length % time_block:
        raise ValueError(f"{length} positions are not whole blocks of {time_block}")
    carry = (
        jnp.zeros((batch, heads, hp, n), x.dtype),
        jnp.zeros((batch, taps - 1, conv_dim), x.dtype),  # zeros before the sequence
    )
    blocks = jnp.moveaxis(x.reshape(batch, -1, time_block, hidden), 1, 0)
    _, out = jax.lax.scan(block, carry, blocks)
    return jnp.moveaxis(out, 0, 1).reshape(batch, length, hidden)


def attention(p, x, config, query_block, fault=""):
    """x [B, T, H] -> y [B, T, H]: every earlier key, full rows, no
    position."""
    batch, length, _ = x.shape
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["model"]["head_dim"]
    scale = config["attention_multiplier"]
    if fault == "attn_sqrt_scale":  # DEPARTS when planted
        scale = d**-0.5
    q = (x @ p["q_proj"]).reshape(batch, length, nkv, nq // nkv, d)
    k = (x @ p["k_proj"]).reshape(batch, length, nkv, d)
    v = (x @ p["v_proj"]).reshape(batch, length, nkv, d)
    keys = jnp.arange(length)[None, :]

    @jax.checkpoint
    def rows(block):
        q_b, first = block
        at = first + jnp.arange(query_block)[:, None]
        scores = jnp.einsum("btgrd,bsgd->bgrts", q_b, k) * scale
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


def dense(p, x, row_block):
    """The SwiGLU over x [N, H], `row_block` rows at a time."""
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
    if set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError(f"layer kinds {sorted(set(kinds) - {MAMBA, ATTENTION})} are not known")
    return kinds


def forward_loss(params, ids, config, blocks, fault):
    """ids [B, T + 1] -> mean next-token cross-entropy."""
    eps = config["rms_norm_eps"]
    residual = 1.0 if fault == "no_residual_multiplier" else config["residual_multiplier"]
    tokens, targets = ids[:, :-1], ids[:, 1:]
    table = params["params/embed/table"]
    h = table[tokens] * config["embedding_multiplier"]

    def norm(p, name, u):
        return rms(u, eps) * (1.0 + p[f"{name}/w"])

    for i, kind in enumerate(layer_kinds(config)):
        if kind == MAMBA:
            mixer = jax.checkpoint(
                lambda p, x: mamba(p, x, config, blocks["time_block"], fault)
            )
        else:
            mixer = jax.checkpoint(
                lambda p, x: attention(p, x, config, blocks["query_block"], fault)
            )
        feed = jax.checkpoint(lambda p, x: dense(sub(p, "mlp"), x, blocks["dense_rows"]))

        @jax.checkpoint
        def layer(h, p, mixer=mixer, feed=feed):
            h = h + residual * mixer(sub(p, "mixer"), norm(p, "input_norm", h))
            x = norm(p, "post_norm", h)
            return h + residual * feed(p, x.reshape(-1, x.shape[-1])).reshape(h.shape)

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
        logits = (x_p @ head).astype(jnp.float32) / config["logits_scaling"]
        per = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, y_p[..., None], axis=-1
        )[..., 0]
        return jnp.sum(per * keep_p)

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
