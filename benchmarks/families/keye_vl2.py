"""Keye-VL-2.0's language model on the training path: `DeviceSequenceFlow`
draws the token sequences on the device (packed random walks over a
transition graph on the vocabulary slice), `KeyeVL2LM` is the model, and
the Estimator drives both as it drives every other model.

The configuration's top-level keys are the published `config.json` as it
is run (depth, experts held and vocabulary cut: `reduced`); `model` holds
the sizes of the run and what this chip holds: `experts_here` of
`router_experts`, the blocks.
"""

from __future__ import annotations

REFERENCE = "keye_vl2"
COUNTS = "keye_vl2"


def build(config: dict, mix: dict, graph: dict) -> dict:
    try:
        from euler_tpu.dataflow.device import DeviceSequenceFlow
        from euler_tpu.models.sequence_lm import KeyeVL2LM
    except ImportError as e:
        # a program from before indexed sparse attention cannot run this family
        raise SystemExit(f"the program has no indexed-sparse-attention model to run: {e}")

    from program_graph import program_graph

    m, sa = config["model"], config["sa_config"]
    flow = DeviceSequenceFlow(
        program_graph(graph, {}),
        batch_size=m["batch_size"],
        seq_len=m["seq_len"],
        doc_len=m["doc_len"],
        layout=config["assumed"]["layout"],
    )
    model = KeyeVL2LM(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        rope_sections=tuple(config["rope_scaling"]["mrope_section"]),
        attention_block=m["attention_block"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"],
        topk=sa["topk"],
        num_experts=m["router_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        experts_here=tuple(m["experts_here"]),
        rms_norm_eps=config["rms_norm_eps"],
        loss_chunks=m["loss_chunks"],
    )
    tokens = m["batch_size"] * m["seq_len"]
    held = m["experts_here"][1]
    assignments = tokens * config["num_experts_per_tok"]
    group = m["router_experts"] // held  # chips that share a layer's experts
    topk, length = sa["topk"], m["seq_len"]
    selecting = max(length - topk, 0)
    return {
        "model": model,
        "flow": flow,
        "feature_cache": None,
        "examples_per_step": tokens,
        "facts": {
            "layout": flow.layout,
            "adjacency_shape": list(flow.adj.shape),
            "tokens_per_step": tokens,
            "selecting_queries_per_sequence": selecting,
            "mean_keys_per_query": (
                min(length, topk) * (min(length, topk) + 1) / 2 + selecting * topk
            ) / length,
            "assignments_per_layer": assignments,
            "expected_routed_share": held / m["router_experts"],
            "expected_rows_per_expert": assignments / m["router_experts"],
            "deployment_rows_per_expert": group * assignments / m["router_experts"],
        },
    }
