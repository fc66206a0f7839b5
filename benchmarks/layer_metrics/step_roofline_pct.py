"""The least time the chip could take for one step — the larger of the
needed FLOPs over peak FLOP/s and the needed bytes over peak bytes/s —
over the device time the step takes. `run["notes"]` says which bound."""

import tracered as tr


def read(run: dict):
    ns = tr.step_ns(run["trace"], run["step_program"], run["steps_per_program"])
    if not ns:
        return None
    by_flops = run["counts"]["flops"] / run["peak"]["flops_per_s"]
    by_bytes = run["counts"]["bytes"] / run["peak"]["bytes_per_s"]
    run["notes"]["step_roofline_bound"] = (
        "memory" if by_bytes >= by_flops else "compute"
    )
    return 100.0 * max(by_flops, by_bytes) / (ns / 1e9)
