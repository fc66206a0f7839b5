"""SmallThinker (PowerInfer) on the training path: `DeviceSequenceFlow`
draws the token sequences on the device (packed random walks over a
transition graph on the vocabulary slice), `SmallThinkerLM` is the model,
and the Estimator drives both as it drives every other model.

The configuration's top-level keys are the published `config.json` as it
is run (depth, experts held and vocabulary cut: `reduced`); `model` holds
the sizes of the run and what this chip holds: the stretch of the two
published layouts that is here, `experts_here` of `router_experts`, the
blocks.
"""

from __future__ import annotations

REFERENCE = "smallthinker"
COUNTS = "smallthinker"
LAYOUTS = ("sliding_window_layout", "rope_layout")


def build(config: dict, mix: dict, graph: dict) -> dict:
    try:
        from euler_tpu.dataflow.device import DeviceSequenceFlow
        from euler_tpu.models.sequence_lm import SmallThinkerLM
    except ImportError as e:
        # a program from before this model cannot run this family
        raise SystemExit(
            f"the program has no model whose router reads the layer's input: {e}"
        )

    from program_graph import program_graph

    m = config["model"]
    here, first = m["layouts_here"], m["first_published_layer"]
    layers = config["num_hidden_layers"]
    if here != {name: config[name][first : first + layers] for name in LAYOUTS}:
        raise SystemExit(
            f"model.layouts_here is not layers {first}.. of the published layouts"
        )
    if not (config["moe_primary_router_apply_softmax"] and config["norm_topk_prob"]):
        raise SystemExit("this family's router is the softmax over the kept logits")
    flow = DeviceSequenceFlow(
        program_graph(graph, {}),
        batch_size=m["batch_size"],
        seq_len=m["seq_len"],
        doc_len=m["doc_len"],
        layout=config["assumed"]["layout"],
    )
    model = SmallThinkerLM(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=layers,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        sliding_window_layout=tuple(here["sliding_window_layout"]),
        rope_layout=tuple(here["rope_layout"]),
        sliding_window_size=config["sliding_window_size"],
        attention_block=m["attention_block"],
        num_experts=m["router_experts"],
        num_experts_per_tok=config["moe_num_active_primary_experts"],
        moe_intermediate_size=config["moe_ffn_hidden_size"],
        experts_here=tuple(m["experts_here"]),
        rms_norm_eps=config["rms_norm_eps"],
        loss_chunks=m["loss_chunks"],
    )
    tokens = m["batch_size"] * m["seq_len"]
    held = m["experts_here"][1]
    assignments = tokens * config["moe_num_active_primary_experts"]
    group = m["router_experts"] // held  # chips that share a layer's experts
    window, length = config["sliding_window_size"], m["seq_len"]
    seen = min(window, length)
    return {
        "model": model,
        "flow": flow,
        "feature_cache": None,
        "examples_per_step": tokens,
        "facts": {
            "layout": flow.layout,
            "adjacency_shape": list(flow.adj.shape),
            "tokens_per_step": tokens,
            "window_layers": sum(here["sliding_window_layout"]),
            "full_layers": layers - sum(here["sliding_window_layout"]),
            "rotary_layers": sum(here["rope_layout"]),
            "query_heads_per_key_head": config["num_attention_heads"]
            // config["num_key_value_heads"],
            "window_pairs_per_sequence": seen * (seen + 1) // 2 + (length - seen) * window,
            "causal_pairs_per_sequence": length * (length + 1) // 2,
            "assignments_per_layer": assignments,
            "expected_routed_share": held / m["router_experts"],
            "expected_rows_per_expert": assignments / m["router_experts"],
            "deployment_rows_per_expert": group * assignments / m["router_experts"],
        },
    }
