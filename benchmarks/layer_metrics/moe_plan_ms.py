"""Device time per step under `euler.moe.route` + `euler.moe.dispatch`,
forward and backward: the expert layer's plan — the router's matmul, the
pick and its softmax; the sort of the assignments by held expert, and
each tile's gather of its rows. In a model whose router reads the
layer's input (SmallThinker) the router and the sort depend on nothing
the attention makes, so this is the part of the expert layer that no
longer waits for it; the tiles' row gathers, also under `.dispatch`, read
the experts' input and still do. Also leaves the whole scope table, the
largest unscoped instructions and the idle gaps by program span in
`run["notes"]` (kernel_share.py), as `swa_ms` does in its cell, and the
program's `routed_share` with the rows it stands for, as
`moe_experts_roofline_pct` does in its cells."""

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
    found = [kernel_share.prefix_ms(run, scope) for scope in ("moe.route", "moe.dispatch")]
    return sum(ms for ms in found if ms) or None
