"""DeepWalk skip-gram on the device lane: `DeviceWalkFlow` draws the
walks, `SkipGramModel` holds the target and context tables."""

from __future__ import annotations

REFERENCE = "skipgram"
COUNTS = "skipgram"


def valid_pairs_per_walk(walk_len: int, window: int) -> int:
    return 2 * sum(walk_len + 1 - off for off in range(1, window + 1))


def build(config: dict, mix: dict, graph: dict) -> dict:
    from euler_tpu.dataflow.device import DeviceWalkFlow
    from euler_tpu.models.embedding_models import SkipGramModel

    from program_graph import program_graph

    m = config["model"]
    flow = DeviceWalkFlow(
        program_graph(graph, {}),
        batch_size=m["batch_size"],
        walk_len=m["walk_len"],
        window=m["window"],
        num_negs=m["negatives"],
        p=m["p"],
        q=m["q"],
        layout=config["assumed"]["layout"],
    )
    model = SkipGramModel(
        num_nodes=graph["num_nodes"], dim=m["dim"], shared_context=False
    )
    # only unmasked pairs are examples: every node has out-neighbours,
    # so no walk dies and the mask is the static window mask
    examples = m["batch_size"] * valid_pairs_per_walk(m["walk_len"], m["window"])
    return {
        "model": model,
        "flow": flow,
        "feature_cache": None,
        "examples_per_step": examples,
        "facts": {
            "layout": flow.layout,
            "adjacency_shape": list(flow.adj.shape),
            "pair_slots_per_walk": int(flow.pairs_per_walk),
        },
    }
