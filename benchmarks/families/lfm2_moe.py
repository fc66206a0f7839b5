"""LFM2-24B-A2B (LiquidAI, HF `model_type` `lfm2_moe`) on the training
path: `DeviceSequenceFlow` draws the token sequences on the device
(packed random walks over a transition graph on the vocabulary slice),
`Lfm2MoeLM` is the model, and the Estimator drives both as it drives
every other model.

The configuration's top-level keys are the published `config.json` as it
is run (depth, dense layers, experts held and vocabulary cut: `reduced`);
`model` holds the sizes of the run, what the config has no key for (the
head of 64, the tie, the router's score and divisor: `assumed`) and what
this chip holds: the stretch of the published `layer_types` that is
here, `experts_here` of `router_experts`, the blocks.
"""

from __future__ import annotations

REFERENCE = "lfm2_moe"
COUNTS = "lfm2_moe"


def build(config: dict, mix: dict, graph: dict) -> dict:
    try:
        from euler_tpu.dataflow.device import DeviceSequenceFlow
        from euler_tpu.models.sequence_lm import Lfm2MoeLM
    except ImportError as e:
        # a program from before this model cannot run this family
        raise SystemExit(
            f"the program has no short-convolution mixer and no tied head to run: {e}"
        )

    from program_graph import program_graph

    m = config["model"]
    kinds = m["layer_types_here"]
    first = m["first_published_layer"]
    if kinds != config["layer_types"][first : first + config["num_hidden_layers"]]:
        raise SystemExit(
            f"model.layer_types_here is not layers {first}.. of the published layer_types"
        )
    if config["conv_bias"] or not config["use_expert_bias"]:
        raise SystemExit("this family's convolution has no bias and its router has one")
    flow = DeviceSequenceFlow(
        program_graph(graph, {}),
        batch_size=m["batch_size"],
        seq_len=m["seq_len"],
        doc_len=m["doc_len"],
        layout=config["assumed"]["layout"],
    )
    model = Lfm2MoeLM(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=m["head_dim"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        layer_types=tuple(kinds),
        conv_L_cache=config["conv_L_cache"],
        attention_block=m["attention_block"],
        num_experts=m["router_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        router_score=m["router_score"],
        router_norm_eps=m["router_norm_eps"],
        route_scale=float(config["routed_scaling_factor"]),
        experts_here=tuple(m["experts_here"]),
        num_dense_layers=config["num_dense_layers"],
        intermediate_size=config["intermediate_size"],
        rms_norm_eps=config["norm_eps"],
        loss_chunks=m["loss_chunks"],
        tie_embeddings=m["tie_embeddings"],
    )
    tokens = m["batch_size"] * m["seq_len"]
    held = m["experts_here"][1]
    assignments = tokens * config["num_experts_per_tok"]
    group = m["router_experts"] // held  # chips that share a layer's experts
    length = m["seq_len"]
    return {
        "model": model,
        "flow": flow,
        "feature_cache": None,
        "examples_per_step": tokens,
        "facts": {
            "layout": flow.layout,
            "adjacency_shape": list(flow.adj.shape),
            "tokens_per_step": tokens,
            "conv_layers": kinds.count("conv"),
            "full_layers": kinds.count("full_attention"),
            "dense_layers": config["num_dense_layers"],
            "head_dim": m["head_dim"],
            "causal_pairs_per_sequence": length * (length + 1) // 2,
            "assignments_per_layer": assignments,
            "expected_routed_share": held / m["router_experts"],
            "expected_rows_per_expert": assignments / m["router_experts"],
            "deployment_rows_per_expert": group * assignments / m["router_experts"],
        },
    }
