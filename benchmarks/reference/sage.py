"""GraphSAGE with an id embedding, written out plainly.

One training example is a root node of the train split. Each step:
draw B roots, then for each fanout k draw k out-neighbours of every
node of the hop before, uniformly with replacement. Every sampled node
is encoded as  table[id] + W_e · feature + b_e  (Euler's ShallowEncoder,
combiner "add"); layer l maps hop h to  W_l · [x_h ‖ mean_k x_{h+1}] +
b_l  with relu between layers (PyG `SAGEConv`, mean aggregator, one
weight over the concatenation); a last Dense gives class logits; the
loss is the sigmoid cross-entropy against the one-hot class, summed
over classes and averaged over roots.

The draws follow the program's documented stream, so that both sides
see the same rows: `split(key)` gives root and hop keys; roots are
`train[randint(0, len(train))]`; hop i draws slot `int(uniform * deg)`
of each node's out-list under `split(hop_key, hops)[i]`. Features are
the configuration's bf16 table. Imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

E = "params/net/encoder"
G = "params/net/gnn"


def param_spec(config: dict, graph: dict) -> list:
    m = config["model"]
    rows = -(-(graph["num_nodes"] + 1) // 128) * 128
    feat, enc = config["graph"]["feature_dim"], m["encoder_dim"]
    spec = [
        (f"{E}/Embedding_0/table", (rows, enc), "normal", 0.02),
        (f"{E}/Dense_0/kernel", (feat, enc), "normal", feat**-0.5),
        (f"{E}/Dense_0/bias", (enc,), "zeros", 0.0),
    ]
    width = enc
    for i, dim in enumerate(m["dims"]):
        spec += [
            (f"{G}/convs_{i}/Dense_0/kernel", (2 * width, dim), "normal",
             (2 * width) ** -0.5),
            (f"{G}/convs_{i}/Dense_0/bias", (dim,), "zeros", 0.0),
        ]
        width = dim
    classes = config["graph"]["num_classes"]
    spec += [
        ("params/out/kernel", (width, classes), "normal", width**-0.5),
        ("params/out/bias", (classes,), "zeros", 0.0),
    ]
    return spec


def make(config: dict, mix: dict, graph: dict):
    """(tables, loss_fn). Tables go to the device once and are arguments
    of the jitted step, never constants."""
    fanouts = tuple(config["model"]["fanouts"])
    batch = config["model"]["batch_size"]
    layers = len(config["model"]["dims"])
    tables = {
        "indptr": jnp.asarray(graph["indptr"].astype(np.int32)),
        "dst": jnp.asarray(graph["dst"]),
        "feat": jnp.asarray(graph["feat"]).astype(jnp.bfloat16),
        "classes": jnp.asarray(graph["classes"]),
        "train": jnp.asarray(graph["train"]),
    }
    num_classes = graph["num_classes"]

    def sample(tables, key):
        kroot, khops = jax.random.split(key)
        train = tables["train"]
        cur = train[jax.random.randint(kroot, (batch,), 0, train.shape[0])]
        hops = [cur]
        for k, hk in zip(fanouts, jax.random.split(khops, len(fanouts))):
            start = tables["indptr"][cur]
            deg = tables["indptr"][cur + 1] - start
            u = jax.random.uniform(hk, (cur.shape[0], k))
            slot = (u * deg[:, None]).astype(jnp.int32)
            slot = jnp.minimum(slot, jnp.maximum(deg[:, None] - 1, 0))
            cur = tables["dst"][start[:, None] + slot].reshape(-1)
            hops.append(cur)
        return hops

    def loss_fn(params, tables, key, dtype, fault):
        hops = sample(tables, key)
        xs = [
            params[f"{E}/Embedding_0/table"][h + 1]
            + tables["feat"][h].astype(dtype) @ params[f"{E}/Dense_0/kernel"]
            + params[f"{E}/Dense_0/bias"]
            for h in hops
        ]
        for layer in range(layers):
            w = params[f"{G}/convs_{layer}/Dense_0/kernel"]
            b = params[f"{G}/convs_{layer}/Dense_0/bias"]
            nxt = []
            for hop in range(layers - layer):
                k = fanouts[hop]
                mean = xs[hop + 1].reshape(-1, k, xs[hop + 1].shape[-1]).mean(1)
                h = jnp.concatenate([xs[hop], mean], axis=-1) @ w + b
                nxt.append(h if layer == layers - 1 else jax.nn.relu(h))
            xs = nxt
        z = (xs[0] @ params["params/out/kernel"] + params["params/out/bias"])
        z = z.astype(jnp.float32)
        y = jax.nn.one_hot(tables["classes"][hops[0]], num_classes)
        per = jnp.sum(
            jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))), axis=-1
        )
        if fault == "half_batch":
            per = per[: batch // 2]
        return jnp.mean(per).astype(dtype)

    return tables, loss_fn
