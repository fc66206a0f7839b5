"""Operations and bytes one training step of Trinity-Mini needs, from
shapes alone, for what this chip holds.

FLOPs are forward + backward (3 x forward) of: the attention's
projections (query and gate in one matrix, key, value, output); the
attention core over the pairs a query may see — in a window layer the
`sum_t min(t + 1, window)` in-window pairs, never the stretch of keys the
program may compute them inside, in a full layer the causal half of the
square —; the dense layer's feed-forward; router, the routed experts at
the EXPECTED rows (tokens x top-k x held / router width: 1 expert a
token, not 8) and the shared expert; the head. The rematerialised forward
is not needed work and is not counted. Bytes: parameters read, gradients
written, Adam's p, m, v in and out, once each; the token rows of the
embedding; the layer boundaries' activations once each way.

`kernels(config)` gives the same for each kernel alone, forward +
backward, per step: `swa_core` and `attn_core` (Q K^T, softmax, P V over
the window layers' and the full layers' pairs; q, k, v and o once each
way), `moe_experts` (the grouped matmuls, per routed row, as
`counts/qwen3_next.py` has them).
"""

from __future__ import annotations

LOCAL = "sliding_attention"


def _sizes(config: dict) -> dict:
    m = config["model"]
    hidden = config["hidden_size"]
    nq, nkv, d = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    length, window = m["seq_len"], config["sliding_window"]
    seen = min(length, window)  # queries 0..seen-1 see all their keys
    kinds = m["layer_types_here"]
    dense = config["num_dense_layers"]
    return {
        "batch": m["batch_size"],
        "tokens": m["batch_size"] * length,
        "hidden": hidden,
        "layers": config["num_hidden_layers"],
        "swa_layers": kinds.count(LOCAL),
        "full_layers": len(kinds) - kinds.count(LOCAL),
        "dense_layers": dense,
        "expert_layers": config["num_hidden_layers"] - dense,
        "nq": nq, "nkv": nkv, "d": d,
        # W_q carries the output gate's columns beside the query's
        "attn_proj": hidden * (2 * nq * d + 2 * nkv * d) + nq * d * hidden,
        "window_pairs": seen * (seen + 1) // 2 + (length - seen) * window,
        "causal_pairs": length * (length + 1) // 2,
        "dense_mlp": 3 * hidden * config["intermediate_size"],
        "router": hidden * m["router_experts"],
        "router_experts": m["router_experts"],
        "expert": 3 * hidden * config["moe_intermediate_size"],
        "shared": config["num_shared_experts"] * 3 * hidden * config["moe_intermediate_size"],
        "held": m["experts_here"][1],
        "routed_per_token": config["num_experts_per_tok"]
        * m["experts_here"][1] / m["router_experts"],
        "top_k": config["num_experts_per_tok"],
        "vocab": config["vocab_size"],
    }


def core_flops_per_pair(s: dict) -> float:
    """Forward FLOPs of one (query, key) pair: Q K^T and P V, all query
    heads."""
    return 2 * 2 * s["d"] * s["nq"]


def kernels(config: dict) -> dict:
    """Per step, forward + backward: FLOPs and bytes of each kernel.
    `moe_experts` is per routed row (one token through one expert)."""
    s = _sizes(config)
    t, f32 = s["tokens"], 4
    # q, k, v and o once each way
    core_io = 2 * (2 * s["nq"] * s["d"] + 2 * s["nkv"] * s["d"]) * f32
    per_pair = 3 * s["batch"] * core_flops_per_pair(s)
    return {
        "swa_core": {
            "flops": s["swa_layers"] * per_pair * s["window_pairs"],
            "bytes": s["swa_layers"] * t * core_io,
        },
        "attn_core": {
            "flops": s["full_layers"] * per_pair * s["causal_pairs"],
            "bytes": s["full_layers"] * t * core_io,
        },
        "moe_experts": {
            "flops_per_row": 3 * 2 * s["expert"],
            "bytes_per_row": 2 * 2 * s["hidden"] * f32,
            "bytes": s["expert_layers"] * 3 * s["held"] * s["expert"] * f32,
            "layers": s["expert_layers"],
            "assignments": s["expert_layers"] * t * s["top_k"],
        },
    }


def parameters(config: dict) -> int:
    s = _sizes(config)
    norms = 4 * s["hidden"]  # sandwich norms: four a layer
    attention = s["attn_proj"] + 2 * s["d"]
    moe = s["router"] + s["router_experts"] + s["held"] * s["expert"] + s["shared"]
    rows = -(-s["vocab"] // 128) * 128
    return (
        s["layers"] * (attention + norms)
        + s["dense_layers"] * s["dense_mlp"] + s["expert_layers"] * moe
        + rows * s["hidden"] + s["hidden"] * s["vocab"] + s["hidden"]
    )


def per_step(config: dict) -> dict:
    s = _sizes(config)
    t = s["tokens"]
    expert_layer = s["router"] + s["routed_per_token"] * s["expert"] + s["shared"]
    per_token = 2 * (
        s["layers"] * s["attn_proj"]
        + s["dense_layers"] * s["dense_mlp"] + s["expert_layers"] * expert_layer
    )
    pairs = s["swa_layers"] * s["window_pairs"] + s["full_layers"] * s["causal_pairs"]
    head = 2 * s["hidden"] * s["vocab"]
    forward = t * (per_token + head) + s["batch"] * core_flops_per_pair(s) * pairs
    params = parameters(config)
    return {
        "flops": 3 * forward,
        # p read, g written, then g, p, m, v read and p, m, v written
        "bytes": params * 4 * 9 + t * s["hidden"] * 4 * 2 * (s["layers"] + 2),
        "examples": t,
        "parameters": params,
        "forward_flops_per_token": forward / t,
        "expected_expert_rows": s["expert_layers"] * t * s["routed_per_token"],
        "window_pairs": s["batch"] * s["window_pairs"],
        "causal_pairs": s["batch"] * s["causal_pairs"],
        "kernels": kernels(config),
    }
