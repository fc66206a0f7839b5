"""Operations and bytes one training step of SmallThinker needs, from
shapes alone, for what this chip holds.

FLOPs are forward + backward (3 x forward) of: the attention's
projections (query, key, value, output: no gate); the attention core over
the pairs a query may see — in a window layer the `sum_t min(t + 1,
window)` in-window pairs, never the tiles of keys the kernels compute
them inside, in a full layer the causal half of the square —; the router
and the routed experts at the EXPECTED rows (tokens x top-k x held /
router width: 1.5 experts a token, not 6); the head. The rematerialised
forward is not needed work and is not counted. Bytes: parameters read,
gradients written, Adam's p, m, v in and out, once each; the token rows
of the embedding; the layer boundaries' activations once each way.

`kernels(config)` gives the same for each kernel alone, forward +
backward, per step, in the shape `counts/trinity.py` gives them:
`swa_core` and `attn_core` (Q K^T, softmax, P V over the window layers'
and the full layers' pairs; q, k, v and o once each way), `moe_experts`
(the grouped matmuls, per routed row).
"""

from __future__ import annotations


def _sizes(config: dict) -> dict:
    m = config["model"]
    hidden = config["hidden_size"]
    nq, nkv, d = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    length, window = m["seq_len"], config["sliding_window_size"]
    seen = min(length, window)  # queries 0..seen-1 see all their keys
    layers = config["num_hidden_layers"]
    swa = sum(m["layouts_here"]["sliding_window_layout"])
    return {
        "batch": m["batch_size"],
        "tokens": m["batch_size"] * length,
        "hidden": hidden,
        "layers": layers,
        "swa_layers": swa,
        "full_layers": layers - swa,
        "nq": nq, "nkv": nkv, "d": d,
        "attn_proj": hidden * (nq * d + 2 * nkv * d) + nq * d * hidden,
        "window_pairs": seen * (seen + 1) // 2 + (length - seen) * window,
        "causal_pairs": length * (length + 1) // 2,
        "router": hidden * m["router_experts"],
        "expert": 3 * hidden * config["moe_ffn_hidden_size"],
        "held": m["experts_here"][1],
        "top_k": config["moe_num_active_primary_experts"],
        "routed_per_token": config["moe_num_active_primary_experts"]
        * m["experts_here"][1] / m["router_experts"],
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
            "bytes": s["layers"] * 3 * s["held"] * s["expert"] * f32,
            "layers": s["layers"],
            "assignments": s["layers"] * t * s["top_k"],
        },
    }


def parameters(config: dict) -> int:
    """Leaves of the program's tree: the embedding table's rows are
    padded to a multiple of 128."""
    s = _sizes(config)
    layer = s["attn_proj"] + s["router"] + s["held"] * s["expert"] + 2 * s["hidden"]
    rows = -(-s["vocab"] // 128) * 128
    return (
        s["layers"] * layer
        + rows * s["hidden"] + s["hidden"] * s["vocab"] + s["hidden"]
    )


def per_step(config: dict) -> dict:
    s = _sizes(config)
    t = s["tokens"]
    expert_layer = s["router"] + s["routed_per_token"] * s["expert"]
    per_token = 2 * s["layers"] * (s["attn_proj"] + expert_layer)
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
        "expected_expert_rows": s["layers"] * t * s["routed_per_token"],
        "window_pairs": s["batch"] * s["window_pairs"],
        "causal_pairs": s["batch"] * s["causal_pairs"],
        "kernels": kernels(config),
    }
