"""Device time per step under `euler.sconv.*`: the short-convolution
mixers' input projection (`.proj`: `[B | C | x~] = x W_in`), their gate,
taps, gate chain (`.mix`) and their output projection (`.out`); forward,
the layer's rematerialised forward (the mixer names no value to keep, so
all three run again) and backward. Also leaves the whole scope table, the
largest unscoped instructions and the idle gaps by program span in
`run["notes"]` (kernel_share.py), as `swa_ms` does in its cell, and the
program's `routed_share` with the rows it stands for, as `moe_plan_ms`
does in its cell."""

import kernel_share


def read(run: dict):
    layers = kernel_share.notes(run)
    if layers is not None:
        run["notes"]["layers"] = layers
    kernel = run["counts"].get("kernels", {}).get("moe_experts")
    share = kernel_share.routed_share()
    if kernel and share is not None:
        run["notes"]["routed_share"] = share
        run["notes"]["routed_rows_per_step"] = share * kernel["assignments"]
    return kernel_share.prefix_ms(run, "sconv")
