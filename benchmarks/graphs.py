"""Graph generators: the data set of a configuration, as plain arrays.

The real OGB graphs cannot be downloaded where the benchmark runs, so a
configuration names one of these generators and its parameters. Both are
copies of the program's own stand-ins (`euler_tpu/datasets/quality.py:
products_like_graph`, `datasets/synthetic.py:random_graph`), kept here so
that no later change to the program can move the yardstick. They return
0-based CSR arrays; the family files wrap them for the program and the
plain references read them as they are.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _chunks(num_nodes: int, parts: int):
    edges = np.linspace(0, num_nodes, parts + 1).astype(np.int64)
    return list(zip(edges[:-1], edges[1:]))


def products_like(
    num_nodes: int,
    num_classes: int,
    feature_dim: int,
    avg_degree: float,
    seed: int,
    homophily: float = 0.57,
    noise: float = 3.45,
    train_frac: float = 0.08,
) -> dict:
    """ogbn-products-shaped: Zipf-like class sizes, clipped log-normal
    out-degrees (2..120), homophilous endpoints, Gaussian class-centre
    features, an 8 % train split. Edges and features are drawn in 16
    node ranges, each from its own child stream of `seed`, on a few
    threads: the arrays depend on the seed alone."""
    root = np.random.SeedSequence(seed)
    rng = np.random.default_rng(root)
    mass = 1.0 / np.arange(1, num_classes + 1) ** 0.7
    classes = rng.choice(num_classes, size=num_nodes, p=mass / mass.sum())
    order = np.argsort(classes, kind="stable")
    counts = np.bincount(classes, minlength=num_classes)
    if counts.min() == 0:
        raise ValueError("a class drew no member: raise num_nodes")
    offsets = np.r_[0, np.cumsum(counts)]
    deg = np.clip(
        rng.lognormal(np.log(avg_degree * 0.7), 0.8, num_nodes), 2, 120
    ).astype(np.int64)
    indptr = np.r_[0, np.cumsum(deg)]
    centers = rng.normal(0.0, 1.0, (num_classes, feature_dim)).astype(
        np.float32
    )
    train = np.sort(
        rng.permutation(num_nodes)[: int(train_frac * num_nodes)]
    )
    dst = np.empty(int(indptr[-1]), np.int32)
    feat = np.empty((num_nodes, feature_dim), np.float32)

    def fill(span, child):
        lo, hi = span
        r = np.random.default_rng(child)
        e0, e1 = indptr[lo], indptr[hi]
        out = r.integers(0, num_nodes, e1 - e0, dtype=np.int32)
        same = r.random(e1 - e0, dtype=np.float32) < homophily
        cls = np.repeat(classes[lo:hi], deg[lo:hi])[same]
        pick = (r.random(len(cls)) * counts[cls]).astype(np.int64)
        out[same] = order[offsets[cls] + pick]
        dst[e0:e1] = out
        block = r.standard_normal((hi - lo, feature_dim), dtype=np.float32)
        block *= np.float32(noise)
        block += centers[classes[lo:hi]]
        feat[lo:hi] = block

    spans = _chunks(num_nodes, 16)
    with ThreadPoolExecutor(max_workers=8) as pool:
        for done in pool.map(fill, spans, root.spawn(len(spans))):
            pass
    return {
        "num_nodes": num_nodes,
        "indptr": indptr,
        "dst": dst,
        "feat": feat,
        "classes": classes.astype(np.int32),
        "num_classes": num_classes,
        "train": train.astype(np.int32),
    }


def regular(num_nodes: int, out_degree: int, seed: int) -> dict:
    """Every node draws `out_degree` uniform out-neighbours."""
    rng = np.random.default_rng(seed)
    return {
        "num_nodes": num_nodes,
        "indptr": np.arange(
            0, num_nodes * out_degree + 1, out_degree, dtype=np.int64
        ),
        "dst": rng.integers(
            0, num_nodes, num_nodes * out_degree, dtype=np.int32
        ),
    }


GENERATORS = {"products_like": products_like, "regular": regular}


def build(spec: dict) -> dict:
    """`spec` is a configuration's `graph` group: `generator`, then its
    keyword arguments, `graph_seed` among them."""
    args = {k: v for k, v in spec.items() if k not in ("generator", "graph_seed")}
    return GENERATORS[spec["generator"]](seed=spec["graph_seed"], **args)
