"""The chunked gated delta rule's share of its roofline: the needed
FLOPs and bytes of the DeltaNet layers' rule, forward + backward
(`counts/qwen3_next.py:kernels`), over the time under `euler.gdn.scan`."""

import kernel_share


def read(run: dict):
    kernel = run["counts"].get("kernels", {}).get("gdn_scan")
    if not kernel:
        return None
    return kernel_share.roofline_pct(
        run, "gdn.scan", kernel["flops"], kernel["bytes"]
    )
