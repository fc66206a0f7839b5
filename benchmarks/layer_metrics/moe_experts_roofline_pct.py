"""The grouped expert matmuls' share of their roofline, counted on the
rows really routed: the program's `routed_share` (the model's metric, in
the `train.dispatch` spans of the traced steps) times the step's
token-expert assignments, never the rows that fill up a tile; over the
time under `euler.moe.experts`. Leaves the share and the rows in `run["notes"]`."""

import kernel_share


def read(run: dict):
    kernel = run["counts"].get("kernels", {}).get("moe_experts")
    share = kernel_share.routed_share()
    if not kernel or share is None:
        return None
    rows = share * kernel["assignments"]
    run["notes"]["routed_share"] = share
    run["notes"]["routed_rows_per_step"] = rows
    return kernel_share.roofline_pct(
        run, "moe.experts",
        rows * kernel["flops_per_row"],
        rows * kernel["bytes_per_row"] + kernel["bytes"],
    )
