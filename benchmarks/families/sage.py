"""GraphSAGE behind the id-embedding ShallowEncoder, on the device lane.

What the harness needs of a family: `build(config, mix, graph)` hands
the program its graph and returns the pieces `run.py` drives — the
Estimator's model, flow and feature cache — with the sizes the counts
and the window arithmetic use.
"""

from __future__ import annotations

import numpy as np

REFERENCE = "sage"
COUNTS = "sage"


def _program_graph(graph: dict):
    from program_graph import program_graph

    label = np.zeros((graph["num_nodes"], graph["num_classes"]), np.float32)
    label[np.arange(graph["num_nodes"]), graph["classes"]] = 1.0
    return program_graph(graph, {"feature": graph["feat"], "label": label})


def build(config: dict, mix: dict, graph: dict) -> dict:
    """Stages the graph on the device the way a user of the device lane
    does and returns what `Estimator(model, flow, cfg, feature_cache=)`
    takes."""
    from euler_tpu.dataflow import DeviceSageFlow
    from euler_tpu.estimator import DeviceFeatureCache
    from euler_tpu.models import GraphSAGESupervised

    m = config["model"]
    pgraph = _program_graph(graph)
    cache = DeviceFeatureCache(
        pgraph, ["feature"], quant=config["assumed"]["feature_table_dtype"]
    )
    flow = DeviceSageFlow(
        pgraph,
        fanouts=m["fanouts"],
        batch_size=m["batch_size"],
        label_feature="label",
        roots_pool=graph["train"].astype(np.uint64) + np.uint64(1),
        with_hop_ids=True,
        layout=config["assumed"]["layout"],
    )
    model = GraphSAGESupervised(
        dims=m["dims"],
        label_dim=graph["num_classes"],
        encoder_dim=m["encoder_dim"],
        max_id=graph["num_nodes"],
    )
    return {
        "model": model,
        "flow": flow,
        "feature_cache": cache,
        "examples_per_step": m["batch_size"],
        "facts": {"layout": flow.layout, "adjacency_shape": list(flow.adj.shape)},
    }
