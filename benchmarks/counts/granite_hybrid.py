"""Operations and bytes one training step of Granite-4.0-H-Micro needs,
from shapes alone, for what this chip holds.

FLOPs are forward + backward (3 x forward) of: the Mamba-2 mixers'
two projections, their convolution and their chunked scan; the
attention's projections (query, key, value, output: no gate) and its
core over the causal half of the square; every layer's dense SwiGLU; the
tied head. The rematerialised forward is not needed work and is not
counted. Bytes: parameters read, gradients written, Adam's p, m, v in
and out, once each — the tied table once, not twice —; the token rows of
the embedding; the layer boundaries' activations once each way.

`kernels(config)` gives the same for each kernel alone, forward +
backward, per step: `ssd_scan` (the chunked state-space scan, whatever
implements it: a chunk's C B^T once for all heads, then per head its
((C B^T) * L) U, exp(G) C S^T and the state's update, three times over
for forward and backward; u = dt x, a = dt A, B and C read and y written
forward, the same read with y's cotangent and their four cotangents
written backward, each float32 once), `attn_d64_core` (Q K^T, softmax,
P V over the full layer's pairs at a head of 64; q, k, v and o once each
way, `counts/lfm2_moe.py:kernels`' shape; also under the name
`attn_core`, for the day `attn_core_roofline_pct`'s list takes this
cell).
"""

from __future__ import annotations

MAMBA, ATTENTION = "mamba", "attention"


def _sizes(config: dict) -> dict:
    m = config["model"]
    hidden = config["hidden_size"]
    heads, p, n = config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"]
    inner = heads * p
    conv_dim = inner + 2 * n
    length, chunk = m["seq_len"], config["mamba_chunk_size"]
    kinds = m["layer_types_here"]
    nq, nkv, d = config["num_attention_heads"], config["num_key_value_heads"], m["head_dim"]
    return {
        "batch": m["batch_size"],
        "tokens": m["batch_size"] * length,
        "length": length,
        "hidden": hidden,
        "layers": config["num_hidden_layers"],
        "mamba_layers": kinds.count(MAMBA),
        "attention_layers": kinds.count(ATTENTION),
        "heads": heads, "p": p, "n": n, "inner": inner, "conv_dim": conv_dim,
        "taps": config["mamba_d_conv"], "chunk": chunk, "chunks": -(-length // chunk),
        "mamba_proj": hidden * (inner + conv_dim + heads) + inner * hidden,
        "nq": nq, "nkv": nkv, "d": d,
        "attn_proj": hidden * (nq * d + 2 * nkv * d) + nq * d * hidden,
        "causal_pairs": length * (length + 1) // 2,
        "mlp": 3 * hidden * config["shared_intermediate_size"],
        "vocab": config["vocab_size"],
    }


def core_flops_per_pair(s: dict) -> float:
    """Forward FLOPs of one (query, key) pair: Q K^T and P V, all query
    heads."""
    return 2 * 2 * s["d"] * s["nq"]


def scan_flops_per_chunk(s: dict) -> float:
    """Forward FLOPs of one chunk of one sequence, all heads: C B^T once
    (2 C^2 N), then per head ((C B^T) * L) U (2 C^2 P), exp(G) C S^T
    (2 C P N) and U^T (exp(G_end - G) B) (2 C P N)."""
    c, p, n = s["chunk"], s["p"], s["n"]
    return 2 * c * c * n + s["heads"] * (2 * c * c * p + 4 * c * p * n)


def conv_flops_per_token(s: dict) -> float:
    """The taps' products and sums and the bias, per token, one layer."""
    return 2 * s["taps"] * s["conv_dim"]


def kernels(config: dict) -> dict:
    """Per step, forward + backward: FLOPs and bytes of each kernel."""
    s = _sizes(config)
    t, f32 = s["tokens"], 4
    scan_io = t * (s["inner"] + s["heads"] + 2 * s["n"]) * f32  # u, a, B, C
    core_io = 2 * (2 * s["nq"] * s["d"] + 2 * s["nkv"] * s["d"]) * f32
    core = {
        "flops": s["attention_layers"] * 3 * s["batch"] * core_flops_per_pair(s) * s["causal_pairs"],
        "bytes": s["attention_layers"] * t * core_io,
    }
    return {
        "ssd_scan": {
            "flops": s["mamba_layers"] * 3 * s["batch"] * s["chunks"] * scan_flops_per_chunk(s),
            # forward: u, a, B, C in, y out; backward: the same and y's
            # cotangent in, their four cotangents out
            "bytes": s["mamba_layers"] * (3 * scan_io + 2 * t * s["inner"] * f32),
        },
        "attn_d64_core": core,
        "attn_core": core,
    }


def parameters(config: dict) -> int:
    """Leaves of the program's tree: two norms and the SwiGLU a layer,
    the mixer of the layer's kind (a Mamba-2 mixer's projections, conv
    and its bias, A_log, dt_bias and D a head, the gated norm); the table
    once (it is the head too; its rows are padded to a multiple of 128,
    which 12,544 is) and the final norm."""
    s = _sizes(config)
    mamba = s["mamba_proj"] + (s["taps"] + 1) * s["conv_dim"] + 3 * s["heads"] + s["inner"]
    rows = -(-s["vocab"] // 128) * 128
    return (
        s["layers"] * (2 * s["hidden"] + s["mlp"])
        + s["mamba_layers"] * mamba + s["attention_layers"] * s["attn_proj"]
        + rows * s["hidden"] + s["hidden"]
    )


def per_step(config: dict) -> dict:
    s = _sizes(config)
    t = s["tokens"]
    per_token = 2 * (
        s["mamba_layers"] * s["mamba_proj"] + s["attention_layers"] * s["attn_proj"]
        + s["layers"] * s["mlp"]
    ) + s["mamba_layers"] * conv_flops_per_token(s)
    head = 2 * s["hidden"] * s["vocab"]
    pairs = s["attention_layers"] * s["causal_pairs"]
    scan = s["mamba_layers"] * s["batch"] * s["chunks"] * scan_flops_per_chunk(s)
    forward = t * (per_token + head) + s["batch"] * core_flops_per_pair(s) * pairs + scan
    params = parameters(config)
    return {
        "flops": 3 * forward,
        # p read, g written, then g, p, m, v read and p, m, v written
        "bytes": params * 4 * 9 + t * s["hidden"] * 4 * 2 * (s["layers"] + 2),
        "examples": t,
        "parameters": params,
        "forward_flops_per_token": forward / t,
        "causal_pairs": s["batch"] * s["causal_pairs"],
        "ssd_chunks": s["batch"] * s["chunks"],
        "kernels": kernels(config),
    }
