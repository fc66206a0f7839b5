"""Operations and bytes one skip-gram step needs, from shapes alone.

Every valid pair takes 1 + negatives dot products of width dim,
forward and twice that backward; the masked pair slots the program's
static shapes also compute are not needed work. Bytes: each valid example's 2 + negatives table rows read
once, and Adam on each of them (gradient row, then p, m, v in and out).
Rows are counted with their repeats; the dense Adam sweep over untouched
rows is not needed work.
"""

from __future__ import annotations


def per_step(config: dict) -> dict:
    m = config["model"]
    length = m["walk_len"] + 1
    valid = 2 * sum(length - off for off in range(1, m["window"] + 1))
    examples = m["batch_size"] * valid
    dots = 1 + m["negatives"]
    flops = 3 * 2 * examples * dots * m["dim"]
    rows = examples * (1 + dots)
    return {
        "flops": flops,
        "bytes": rows * m["dim"] * 4 * 8 + m["batch_size"] * m["walk_len"] * 4,
        "examples": examples,
        "table_rows": rows,
    }
