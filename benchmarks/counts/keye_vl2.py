"""Operations and bytes one training step of Keye-VL-2.0's language model
needs, from shapes alone, for what this chip holds.

FLOPs are forward + backward (3 x forward) of: the projections of the
attention and of the indexer; the indexer's scores over the causal half
of the square (every pair is scored before any is picked); the attention
core over the SELECTED pairs only — sum_t min(t + 1, topk) of them, never
the masked square the program may compute them inside; router and the
routed experts at the EXPECTED rows (tokens x top-k x held / router width:
1 expert a token, not 8); the head. The rematerialised forward is not
needed work and is not counted. Bytes: parameters read, gradients
written, Adam's p, m, v in and out, once each; the token rows of the
embedding; the layer boundaries' activations once each way.

`kernels(config)` gives the same for each new kernel alone, forward +
backward, per step: `dsa_index` (scores and selection: q^I, k^I, w read,
the picked indices written), `dsa_core` (Q K^T, softmax, P V over the
selected pairs; q, k, v and o once each way), `moe_experts`
(the grouped matmuls, per routed row, as `counts/qwen3_next.py` has them).
"""

from __future__ import annotations


def _sizes(config: dict) -> dict:
    m, sa = config["model"], config["sa_config"]
    hidden = config["hidden_size"]
    nq, nkv, d = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    ni, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    length, topk = m["seq_len"], sa["topk"]
    full = min(length, topk)  # queries 0..full-1 see all their keys
    return {
        "batch": m["batch_size"],
        "tokens": m["batch_size"] * length,
        "seq_len": length,
        "topk": topk,
        "hidden": hidden,
        "layers": config["num_hidden_layers"],
        "nq": nq, "nkv": nkv, "d": d, "ni": ni, "di": di,
        "attn_proj": hidden * (nq * d + 2 * nkv * d) + nq * d * hidden,
        "index_proj": hidden * (ni * di + di + ni),
        "causal_pairs": length * (length + 1) // 2,
        "selected_pairs": full * (full + 1) // 2 + (length - full) * topk,
        "router": hidden * m["router_experts"],
        "expert": 3 * hidden * config["moe_intermediate_size"],
        "held": m["experts_here"][1],
        "routed_per_token": config["num_experts_per_tok"]
        * m["experts_here"][1] / m["router_experts"],
        "top_k": config["num_experts_per_tok"],
        "vocab": config["vocab_size"],
    }


def index_flops_per_sequence(s: dict) -> float:
    """Forward FLOPs of the indexer's scores, one sequence: a dot of
    `di` for each of `ni` heads over every causal pair (the ReLU, the
    head weights and their sum are not matmul work)."""
    return 2 * s["ni"] * s["di"] * s["causal_pairs"]


def core_flops_per_sequence(s: dict) -> float:
    """Forward FLOPs of the attention core, one sequence: Q K^T and P V
    over the selected pairs, all query heads."""
    return 2 * 2 * s["d"] * s["nq"] * s["selected_pairs"]


def kernels(config: dict) -> dict:
    """Per step, forward + backward: FLOPs and bytes of each new kernel.
    `moe_experts` is per routed row (one token through one expert)."""
    s = _sizes(config)
    t, f32, i32 = s["tokens"], 4, 4
    # forward and backward each read q^I, k^I and w; the indices of the
    # picked keys are written once
    index_io = 2 * (s["ni"] * s["di"] + s["di"] + s["ni"]) * f32
    picked = s["batch"] * s["selected_pairs"] * i32
    # q, k, v and o once each way. A kernel that gathers a query's own
    # 2,048 key and value rows moves 4,096 x 512 floats a query; the rows are
    # shared by the queries of a block, so that is one way to do the work
    # and no lower bound of it
    core_io = 2 * (2 * s["nq"] * s["d"] + 2 * s["nkv"] * s["d"]) * f32
    return {
        "dsa_index": {
            "flops": 3 * s["layers"] * s["batch"] * index_flops_per_sequence(s),
            "bytes": s["layers"] * (t * index_io + picked),
        },
        "dsa_core": {
            "flops": 3 * s["layers"] * s["batch"] * core_flops_per_sequence(s),
            "bytes": s["layers"] * t * core_io,
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
    s = _sizes(config)
    norms = 2 * s["hidden"]
    attention = s["attn_proj"] + 2 * s["d"]
    indexer = s["index_proj"] + 2 * s["di"]
    moe = s["router"] + s["held"] * s["expert"]
    rows = -(-s["vocab"] // 128) * 128
    return (
        s["layers"] * (attention + indexer + moe + norms)
        + rows * s["hidden"] + s["hidden"] * s["vocab"] + s["hidden"]
    )


def per_step(config: dict) -> dict:
    s = _sizes(config)
    t = s["tokens"]
    per_token = 2 * (
        s["attn_proj"] + s["index_proj"] + s["router"]
        + s["routed_per_token"] * s["expert"]
    )
    per_sequence = index_flops_per_sequence(s) + core_flops_per_sequence(s)
    head = 2 * s["hidden"] * s["vocab"]
    forward = s["layers"] * (t * per_token + s["batch"] * per_sequence) + t * head
    params = parameters(config)
    return {
        "flops": 3 * forward,
        # p read, g written, then g, p, m, v read and p, m, v written
        "bytes": params * 4 * 9 + t * s["hidden"] * 4 * 2 * (s["layers"] + 2),
        "examples": t,
        "parameters": params,
        "forward_flops_per_token": forward / t,
        "expected_expert_rows": s["layers"] * t * s["routed_per_token"],
        "selected_pairs": s["batch"] * s["selected_pairs"],
        "causal_pairs": s["batch"] * s["causal_pairs"],
        "kernels": kernels(config),
    }
