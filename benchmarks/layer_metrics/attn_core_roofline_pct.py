"""Causal attention's share of its roofline: Q K^T and P V over the
causal half of the square, forward + backward
(`counts/qwen3_next.py:kernels`), over the time under `euler.attn.core`."""

import kernel_share


def read(run: dict):
    kernel = run["counts"].get("kernels", {}).get("attn_core")
    if not kernel:
        return None
    return kernel_share.roofline_pct(
        run, "attn.core", kernel["flops"], kernel["bytes"]
    )
