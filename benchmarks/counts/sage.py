"""Operations and bytes one GraphSAGE-with-id-embedding step needs,
from the configuration's shapes alone.

FLOPs are the matrix multiplications of the forward pass and of the
backward pass (twice the forward, except that the encoder's Dense needs
no gradient toward the features). Bytes are what the algorithm has to
move however it is implemented: each sampled node's feature row read
once, its embedding row read once, and Adam on each sampled embedding
row (weights, m, v read and written; the gradient row once); the dense
weights once each way. Sampled rows are counted with their repeats; the
dense Adam sweep over the untouched rows is not needed work.
"""

from __future__ import annotations


def hop_rows(batch: int, fanouts) -> list:
    rows = [batch]
    for k in fanouts:
        rows.append(rows[-1] * k)
    return rows


def per_step(config: dict) -> dict:
    m = config["model"]
    feat = config["graph"]["feature_dim"]
    classes = config["graph"]["num_classes"]
    enc, dims = m["encoder_dim"], m["dims"]
    rows = hop_rows(m["batch_size"], m["fanouts"])
    sampled = sum(rows)
    fwd_enc = 2 * sampled * feat * enc
    fwd_conv, width, weights = 0, enc, feat * enc + enc
    for layer, dim in enumerate(dims):
        fwd_conv += 2 * sum(rows[: len(dims) - layer]) * 2 * width * dim
        weights += 2 * width * dim + dim
        width = dim
    fwd_out = 2 * rows[0] * width * classes
    weights += width * classes + classes
    flops = fwd_enc * 2 + (fwd_conv + fwd_out) * 3
    table_row = enc * 4
    bytes_needed = (
        sampled * feat * 2  # bf16 feature rows
        + sampled * table_row  # embedding rows gathered
        + sampled * table_row * 7  # gradient row + Adam p, m, v in and out
        + weights * 4 * 7
        + sum(rows[1:]) * 4  # one adjacency slot per sampled edge
    )
    return {
        "flops": flops,
        "bytes": bytes_needed,
        "sampled_nodes": sampled,
        "sampled_edges": sum(rows[1:]),
    }
