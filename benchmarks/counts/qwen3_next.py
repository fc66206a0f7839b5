"""Operations and bytes one Qwen3-Next training step needs, from shapes
alone, for what this chip holds.

FLOPs are forward + backward (3 x forward) of: the projections of every
mixer; the DeltaNet's conv and chunked delta rule; the attention core over
the causal half of the square; router, shared expert and the routed
experts at the EXPECTED rows (tokens x top-k x held / router width — 0.625
experts a token, not 10); the head. The rematerialised forward is not
needed work and is not counted. Bytes: parameters read, gradients
written, Adam's p, m, v in and out, once each; the token rows of the
embedding; the layer boundaries' activations once each way.

`kernels(config)` gives the same for each new kernel alone, forward +
backward, per step: `gdn_scan` (the chunked delta rule of the 3 DeltaNet
layers), `attn_core` (QK^T, softmax, PV of the attention layer, causal),
`moe_experts` (the grouped matmuls, per routed row: the reader multiplies
by the rows really routed).
"""

from __future__ import annotations


def _sizes(config: dict) -> dict:
    m = config["model"]
    hidden = config["hidden_size"]
    nk, nv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    nq, nkv, d = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    layers = config["num_hidden_layers"]
    attn_layers = layers // config["full_attention_interval"]
    return {
        "tokens": m["batch_size"] * m["seq_len"],
        "seq_len": m["seq_len"],
        "hidden": hidden,
        "layers": layers,
        "attn_layers": attn_layers,
        "gdn_layers": layers - attn_layers,
        "nv": nv, "dk": dk, "dv": dv, "chunk": m["chunk"],
        "conv_dim": 2 * nk * dk + nv * dv,
        "gdn_proj": hidden * (2 * nk * dk + 2 * nv * dv + 2 * nv) + nv * dv * hidden,
        "nq": nq, "d": d,
        "attn_proj": hidden * (2 * nq * d + 2 * nkv * d) + nq * d * hidden,
        "router": hidden * m["router_experts"],
        "shared": 3 * hidden * config["shared_expert_intermediate_size"] + hidden,
        "expert": 3 * hidden * config["moe_intermediate_size"],
        "held": m["experts_here"][1],
        "routed_per_token": config["num_experts_per_tok"]
        * m["experts_here"][1] / m["router_experts"],
        "top_k": config["num_experts_per_tok"],
        "vocab": config["vocab_size"],
        "taps": config["linear_conv_kernel_dim"],
    }


def scan_flops_per_token(s: dict) -> float:
    """Forward FLOPs of the chunked delta rule per token, all value heads:
    per chunk of C and head, K K^T and Q K^T (2 C^2 dk each), the unit
    lower-triangular solve counted as a substitution (C^3 / 3 multiply-
    adds), its two products (2 C^2 dv, 2 C^2 dk), three products with the
    state (2 C dk dv each) and the intra-chunk output (2 C^2 dv)."""
    c, dk, dv = s["chunk"], s["dk"], s["dv"]
    per_chunk = (
        2 * 2 * c * c * dk + 2 * c**3 / 3 + 2 * c * c * (dv + dk)
        + 3 * 2 * c * dk * dv + 2 * c * c * dv
    )
    return s["nv"] * per_chunk / c


def core_flops_per_token(s: dict) -> float:
    """Forward FLOPs of causal attention per token: Q K^T and P V over
    the (T + 1) / 2 keys a query sees on average."""
    return s["nq"] * 2 * 2 * s["d"] * (s["seq_len"] + 1) / 2


def kernels(config: dict) -> dict:
    """Per step, forward + backward: FLOPs and bytes of each new kernel.
    `moe_experts` is per routed row (one token through one expert)."""
    s = _sizes(config)
    t, f32 = s["tokens"], 4
    scan_io = 2 * s["nv"] * (2 * s["dk"] + 2 * s["dv"]) * f32  # q, k, v, o, each way
    core_io = 2 * (2 * s["nq"] * s["d"] + 2 * 2 * s["d"]) * f32
    return {
        "gdn_scan": {
            "flops": 3 * s["gdn_layers"] * t * scan_flops_per_token(s),
            "bytes": s["gdn_layers"] * t * scan_io,
        },
        "attn_core": {
            "flops": 3 * s["attn_layers"] * t * core_flops_per_token(s),
            "bytes": s["attn_layers"] * t * core_io,
        },
        "moe_experts": {
            "flops_per_row": 3 * 2 * s["expert"],
            # a row in and out, each way; the experts' weights read twice
            # and their gradient written once a layer are in `bytes`
            "bytes_per_row": 2 * 2 * s["hidden"] * f32,
            "bytes": s["layers"] * 3 * s["held"] * s["expert"] * f32,
            "layers": s["layers"],
            "assignments": s["layers"] * t * s["top_k"],
        },
    }


def parameters(config: dict) -> int:
    s = _sizes(config)
    norms = 2 * s["hidden"]
    moe = s["router"] + s["shared"] + s["held"] * s["expert"]
    gdn = s["gdn_proj"] + s["conv_dim"] * s["taps"] + 2 * s["nv"] + s["dv"]
    attn = s["attn_proj"] + 2 * s["d"]
    rows = -(-s["vocab"] // 128) * 128
    return (
        s["gdn_layers"] * (gdn + moe + norms)
        + s["attn_layers"] * (attn + moe + norms)
        + rows * s["hidden"] + s["hidden"] * s["vocab"] + s["hidden"]
    )


def per_step(config: dict) -> dict:
    s = _sizes(config)
    t = s["tokens"]
    moe = 2 * (s["router"] + s["shared"] + s["routed_per_token"] * s["expert"])
    gdn = 2 * s["gdn_proj"] + 2 * s["conv_dim"] * s["taps"] + scan_flops_per_token(s) + moe
    attn = 2 * s["attn_proj"] + core_flops_per_token(s) + moe
    head = 2 * s["hidden"] * s["vocab"]
    forward = s["gdn_layers"] * gdn + s["attn_layers"] * attn + head
    params = parameters(config)
    return {
        "flops": 3 * t * forward,
        # p read, g written, then g, p, m, v read and p, m, v written
        "bytes": params * 4 * 9 + t * s["hidden"] * 4 * 2 * (s["layers"] + 2),
        "examples": t,
        "parameters": params,
        "forward_flops_per_token": forward,
        "expected_expert_rows": s["layers"] * t * s["routed_per_token"],
        "kernels": kernels(config),
    }
