"""The indexer's share of its roofline: the scores of every causal pair
(16 heads of 64) and the reads of q^I, k^I, w and writes of the picked
indices, forward + backward (`counts/keye_vl2.py:kernels`), at the
peaks, over the time under `euler.dsa.index` + `euler.dsa.select` —
scoring and picking are one kernel's work. `run["notes"]` says which
bound."""

import kernel_share


def read(run: dict):
    kernel = run["counts"].get("kernels", {}).get("dsa_index")
    ms = [kernel_share.prefix_ms(run, scope) for scope in ("dsa.index", "dsa.select")]
    if not kernel or not any(ms):
        return None
    by_flops = kernel["flops"] / run["peak"]["flops_per_s"]
    by_bytes = kernel["bytes"] / run["peak"]["bytes_per_s"]
    run["notes"]["dsa.index_roofline_bound"] = (
        "memory" if by_bytes >= by_flops else "compute"
    )
    return 100.0 * max(by_flops, by_bytes) / (sum(m or 0.0 for m in ms) / 1e3)
