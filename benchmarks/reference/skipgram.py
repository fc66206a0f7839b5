"""DeepWalk: uniform random walks and skip-gram with negative sampling,
written out plainly.

Each step: draw B start nodes uniformly; walk L steps, each to a uniform
out-neighbour; every (centre, context) of one walk within `window`
positions is a training example; each draws `negatives` nodes uniformly
from all nodes. With target table T and context table C, an example's
logits are  T[c]·C[x]  for the context x and  T[c]·C[n_j]  for the
negatives, and its loss is the softmax cross-entropy with the context as
the true class (the program's `SkipGramModel`; word2vec's
sampled-softmax form). The step's loss is the mean over examples.

The draws follow the program's documented stream: `split(key, 3)` gives
root, negative and walk keys; roots and negatives are `randint(1, N+1)`
ids; walk step i draws slot `int(uniform * deg)` under
`split(walk_key, L)[i]`; pairs are laid out offset by offset
(-window..-1, 1..window), each padded to L+1 columns, and the padded
columns are masked out. Table row = node id = node index + 1. Imports
nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

T = "params/target/table"
C = "params/ctx_table/table"


def param_spec(config: dict, graph: dict) -> list:
    rows = -(-(graph["num_nodes"] + 1) // 128) * 128
    dim = config["model"]["dim"]
    return [
        (C, (rows, dim), "normal", 0.02),
        (T, (rows, dim), "normal", 0.02),
    ]


def pair_columns(walk_len: int, window: int):
    """(centre column, context column, valid) of every pair slot of one
    walk of `walk_len` steps."""
    length = walk_len + 1
    src, ctx, valid = [], [], []
    for off in list(range(-window, 0)) + list(range(1, window + 1)):
        lo, hi = max(0, -off), min(length, length - off)
        cols = np.arange(length)
        ok = cols < hi - lo
        src.append(np.where(ok, cols + lo, 0))
        ctx.append(np.where(ok, cols + lo + off, 0))
        valid.append(ok)
    return np.concatenate(src), np.concatenate(ctx), np.concatenate(valid)


def make(config: dict, mix: dict, graph: dict):
    m = config["model"]
    walks, walk_len = m["batch_size"], m["walk_len"]
    negatives = m["negatives"]
    n = graph["num_nodes"]
    src_cols, ctx_cols, col_valid = pair_columns(walk_len, m["window"])
    tables = {
        "indptr": jnp.asarray(graph["indptr"].astype(np.int32)),
        "dst": jnp.asarray(graph["dst"]),
    }

    def sample(tables, key):
        kroot, kneg, kwalk = jax.random.split(key, 3)
        cur = jax.random.randint(kroot, (walks,), 1, n + 1) - 1
        walk = [cur]
        for sk in jax.random.split(kwalk, walk_len):
            start = tables["indptr"][cur]
            deg = tables["indptr"][cur + 1] - start
            u = jax.random.uniform(sk, (walks, 1))
            slot = (u * deg[:, None]).astype(jnp.int32)
            slot = jnp.minimum(slot, jnp.maximum(deg[:, None] - 1, 0))
            cur = tables["dst"][start[:, None] + slot].reshape(-1)
            walk.append(cur)
        nodes = jnp.stack(walk, axis=1) + 1
        src = nodes[:, src_cols].reshape(-1)
        ctx = nodes[:, ctx_cols].reshape(-1)
        mask = jnp.tile(jnp.asarray(col_valid), walks)
        negs = jax.random.randint(
            kneg, (walks * len(src_cols) * negatives,), 1, n + 1
        ).reshape(-1, negatives)
        return src, ctx, negs, mask

    def loss_fn(params, tables, key, dtype, fault):
        src, ctx, negs, mask = sample(tables, key)
        if fault == "half_batch":
            mask = mask & (jnp.arange(mask.shape[0]) < mask.shape[0] // 2)
        e_src = params[T][src]
        e_pos = params[C][ctx]
        e_neg = params[C][negs]
        pos = jnp.sum(e_src * e_pos, axis=-1)
        neg = jnp.einsum("bd,bnd->bn", e_src, e_neg)
        logits = jnp.concatenate([pos[:, None], neg], axis=1).astype(
            jnp.float32
        )
        per = jax.nn.logsumexp(logits, axis=1) - logits[:, 0]
        w = mask.astype(jnp.float32)
        return (jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)).astype(dtype)

    return tables, loss_fn
