"""Operations and bytes one training step of LFM2-24B-A2B needs, from
shapes alone, for what this chip holds.

FLOPs are forward + backward (3 x forward) of: the short convolutions'
two projections and their gate, taps, gate chain; the attention's
projections (query, key, value, output: no gate) and its core over the
causal half of the square; the dense layer's feed-forward; router and the
routed experts at the EXPECTED rows (tokens x top-k x held / router
width: half an expert a token, not 4); the tied head. The rematerialised
forward is not needed work and is not counted. Bytes: parameters read,
gradients written, Adam's p, m, v in and out, once each — the tied table
once, not twice —; the token rows of the embedding; the layer boundaries'
activations once each way.

`kernels(config)` gives the same for each kernel alone, forward +
backward, per step: `sconv_mix` (the gate, taps, gate chain between the
two projections, whatever implements it: forward it reads `B`, `x~`, `C`
and writes `C * c`, each [T, hidden] float32 once; backward it reads the
same three and the result's cotangent and writes the three cotangents),
`attn_d64_core` (Q K^T, softmax, P V over the full layers' pairs at a
head of 64; q, k, v and o once each way, `counts/trinity.py:kernels`'
shape; also under the name `attn_core`, which is what
`attn_core_roofline_pct` reads, for the day its list takes this cell),
`moe_experts` (the grouped matmuls, per routed row).
"""

from __future__ import annotations

CONV, FULL = "conv", "full_attention"


def _sizes(config: dict) -> dict:
    m = config["model"]
    hidden = config["hidden_size"]
    nq, nkv, d = config["num_attention_heads"], config["num_key_value_heads"], m["head_dim"]
    length = m["seq_len"]
    kinds = m["layer_types_here"]
    dense = config["num_dense_layers"]
    taps = config["conv_L_cache"]
    return {
        "batch": m["batch_size"],
        "tokens": m["batch_size"] * length,
        "hidden": hidden,
        "layers": config["num_hidden_layers"],
        "conv_layers": kinds.count(CONV),
        "full_layers": kinds.count(FULL),
        "dense_layers": dense,
        "expert_layers": config["num_hidden_layers"] - dense,
        "nq": nq, "nkv": nkv, "d": d, "taps": taps,
        "conv_proj": hidden * 3 * hidden + hidden * hidden,
        "conv_taps": hidden * taps,
        "attn_proj": hidden * (nq * d + 2 * nkv * d) + nq * d * hidden,
        "causal_pairs": length * (length + 1) // 2,
        "dense_mlp": 3 * hidden * config["intermediate_size"],
        "router": hidden * m["router_experts"],
        "router_experts": m["router_experts"],
        "expert": 3 * hidden * config["moe_intermediate_size"],
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


def mix_flops_per_element(s: dict) -> float:
    """Forward FLOPs of the gate, taps, gate chain for one channel of one
    token: `B * x~`, `taps` products and `taps - 1` sums, `C *`."""
    return 2 * s["taps"] + 1


def kernels(config: dict) -> dict:
    """Per step, forward + backward: FLOPs and bytes of each kernel.
    `moe_experts` is per routed row (one token through one expert)."""
    s = _sizes(config)
    t, f32 = s["tokens"], 4
    # q, k, v and o once each way
    core_io = 2 * (2 * s["nq"] * s["d"] + 2 * s["nkv"] * s["d"]) * f32
    per_pair = 3 * s["batch"] * core_flops_per_pair(s)
    # forward: B, x~, C in, C * c out; backward: B, x~, C and the result's
    # cotangent in, the three cotangents out (the taps' [hidden, taps] is nothing)
    mix_io = (4 + 7) * s["hidden"] * f32
    core = {
        "flops": s["full_layers"] * per_pair * s["causal_pairs"],
        "bytes": s["full_layers"] * t * core_io,
    }
    return {
        "sconv_mix": {
            "flops": s["conv_layers"] * t * s["hidden"] * 3 * mix_flops_per_element(s),
            "bytes": s["conv_layers"] * t * mix_io,
        },
        "attn_d64_core": core,
        "attn_core": core,
        "moe_experts": {
            "flops_per_row": 3 * 2 * s["expert"],
            "bytes_per_row": 2 * 2 * s["hidden"] * f32,
            "bytes": s["expert_layers"] * 3 * s["held"] * s["expert"] * f32,
            "layers": s["expert_layers"],
            "assignments": s["expert_layers"] * t * s["top_k"],
        },
    }


def parameters(config: dict) -> int:
    """Leaves of the program's tree: two norms a layer, the mixer of the
    layer's kind, the dense SwiGLU or router, bias and held experts; the
    table once (it is the head too; its rows are padded to a multiple of
    128, which 8,192 is) and the final norm."""
    s = _sizes(config)
    conv = s["conv_proj"] + s["conv_taps"]
    attention = s["attn_proj"] + 2 * s["d"]
    moe = s["router"] + s["router_experts"] + s["held"] * s["expert"]
    rows = -(-s["vocab"] // 128) * 128
    return (
        s["layers"] * 2 * s["hidden"]
        + s["conv_layers"] * conv + s["full_layers"] * attention
        + s["dense_layers"] * s["dense_mlp"] + s["expert_layers"] * moe
        + rows * s["hidden"] + s["hidden"]
    )


def per_step(config: dict) -> dict:
    s = _sizes(config)
    t = s["tokens"]
    expert_layer = s["router"] + s["routed_per_token"] * s["expert"]
    per_token = 2 * (
        s["conv_layers"] * s["conv_proj"] + s["full_layers"] * s["attn_proj"]
        + s["dense_layers"] * s["dense_mlp"] + s["expert_layers"] * expert_layer
    ) + s["conv_layers"] * s["hidden"] * mix_flops_per_element(s)
    head = 2 * s["hidden"] * s["vocab"]
    pairs = s["full_layers"] * s["causal_pairs"]
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
        "causal_pairs": s["batch"] * s["causal_pairs"],
        "kernels": kernels(config),
    }
