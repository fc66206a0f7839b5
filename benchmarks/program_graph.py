"""The generated arrays wrapped in the program's own GraphStore.

One partition, one node and edge type, unit weights, ids = index + 1.
The in-adjacency is left empty: no device-lane flow reads it.
"""

from __future__ import annotations

import numpy as np


def program_graph(graph: dict, dense: dict):
    """`dense`: {feature name: [N, d] float32 array}, in feature order."""
    from euler_tpu.graph.meta import FeatureSpec, GraphMeta
    from euler_tpu.graph.store import Graph, GraphStore

    n = graph["num_nodes"]
    indptr = graph["indptr"]
    e = int(indptr[-1])
    ids = np.arange(1, n + 1, dtype=np.uint64)
    dst = graph["dst"].astype(np.uint64) + np.uint64(1)
    ones = np.ones(e, np.float32)
    meta = GraphMeta(
        name="benchmark",
        num_partitions=1,
        num_node_types=1,
        num_edge_types=1,
        node_features={
            name: FeatureSpec(name, "dense", i, table.shape[1])
            for i, (name, table) in enumerate(dense.items())
        },
        edge_features={},
    )
    meta.node_weight_sums.append([float(n)])
    meta.edge_weight_sums.append([float(e)])
    arrays = {
        "node_ids": ids,
        "node_types": np.zeros(n, np.int32),
        "node_weights": np.ones(n, np.float32),
        "edge_src": np.repeat(ids, np.diff(indptr)),
        "edge_dst": dst,
        "edge_types": np.zeros(e, np.int32),
        "edge_weights": ones,
        "adj_0_indptr": indptr,
        "adj_0_dst": dst,
        "adj_0_w": ones,
        "adj_0_eidx": np.arange(e, dtype=np.int64),
        "inadj_0_indptr": np.zeros(n + 1, np.int64),
        "inadj_0_dst": np.zeros(0, np.uint64),
        "inadj_0_w": np.zeros(0, np.float32),
        "inadj_0_eidx": np.zeros(0, np.int64),
        "glabel_indptr": np.zeros(1, np.int64),
        "glabel_nodes": np.zeros(0, np.uint64),
    }
    for i, table in enumerate(dense.values()):
        arrays[f"nf_dense_{i}"] = table
    return Graph(meta, [GraphStore(meta, arrays, part=0)])
