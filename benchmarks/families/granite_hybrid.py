"""Granite-4.0-H-Micro (IBM, HF `model_type` `granitemoehybrid` with no
experts) on the training path: `DeviceSequenceFlow` draws the token
sequences on the device (packed random walks over a transition graph on
the vocabulary slice), `GraniteHybridLM` is the model, and the Estimator
drives both as it drives every other model.

The configuration's top-level keys are the published `config.json` as it
is run (depth and vocabulary cut: `reduced`); `model` holds the sizes of
the run and what this chip holds: the stretch of the published
`layer_types` that is here, the blocks.
"""

from __future__ import annotations

REFERENCE = "granite_hybrid"
COUNTS = "granite_hybrid"


def build(config: dict, mix: dict, graph: dict) -> dict:
    try:
        from euler_tpu.dataflow.device import DeviceSequenceFlow
        from euler_tpu.models.sequence_lm import GraniteHybridLM
    except ImportError as e:
        # a program from before this model cannot run this family
        raise SystemExit(f"the program has no state-space mixer and no Granite model to run: {e}")

    from program_graph import program_graph

    m = config["model"]
    kinds = m["layer_types_here"]
    first = m["first_published_layer"]
    if kinds != config["layer_types"][first : first + config["num_hidden_layers"]]:
        raise SystemExit(
            f"model.layer_types_here is not layers {first}.. of the published layer_types"
        )
    if (
        config["mamba_n_groups"] != 1 or not config["mamba_conv_bias"]
        or config["mamba_proj_bias"] or config["attention_bias"]
        or config["position_embedding_type"] != "nope" or config["num_local_experts"]
        or not config["tie_word_embeddings"]
    ):
        raise SystemExit(
            "this family's mixer has one group, a conv bias and no projection bias, its "
            "attention no bias and no position, no experts, and a tied head"
        )
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    if inner != config["mamba_expand"] * config["hidden_size"]:
        raise SystemExit(f"mamba heads x head ({inner}) is not mamba_expand x hidden_size")
    flow = DeviceSequenceFlow(
        program_graph(graph, {}),
        batch_size=m["batch_size"],
        seq_len=m["seq_len"],
        doc_len=m["doc_len"],
        layout="auto",
    )
    model = GraniteHybridLM(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=m["head_dim"],
        layer_types=tuple(kinds),
        attention_multiplier=config["attention_multiplier"],
        attention_block=m["attention_block"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk_size=config["mamba_chunk_size"],
        intermediate_size=config["shared_intermediate_size"],
        embed_scale=float(config["embedding_multiplier"]),
        residual_multiplier=config["residual_multiplier"],
        logits_divisor=float(config["logits_scaling"]),
        rms_norm_eps=config["rms_norm_eps"],
        loss_chunks=m["loss_chunks"],
    )
    tokens = m["batch_size"] * m["seq_len"]
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
            "mamba_layers": kinds.count("mamba"),
            "attention_layers": kinds.count("attention"),
            "head_dim": m["head_dim"],
            "ssd_chunks_per_sequence": -(-length // config["mamba_chunk_size"]),
            "causal_pairs_per_sequence": length * (length + 1) // 2,
        },
    }
