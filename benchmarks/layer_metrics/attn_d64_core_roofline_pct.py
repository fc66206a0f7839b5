"""Causal attention's share of its roofline at a head of 64: Q K^T and
P V over the causal half of the square, forward + backward, q, k, v and o
once each way (`counts/lfm2_moe.py:kernels`, the shape of
`counts/trinity.py:kernels`), over the time under `euler.attn.core`. At
this head a score's product is 64 deep, half a pass of the MXU's 128, so
the needed FLOPs are set against a peak the kernels can reach half of at
best in Q K^T."""

import kernel_share


def read(run: dict):
    kernel = run["counts"].get("kernels", {}).get("attn_d64_core")
    if not kernel:
        return None
    return kernel_share.roofline_pct(
        run, "attn.core", kernel["flops"], kernel["bytes"]
    )
