"""Windowed attention's share of its roofline: Q K^T and P V over the
pairs inside the window — never the stretch of keys a block is scored
against — and q, k, v, o once each way, forward + backward
(`counts/trinity.py:kernels`), over the time under `euler.swa.core`."""

import kernel_share


def read(run: dict):
    kernel = run["counts"].get("kernels", {}).get("swa_core")
    if not kernel:
        return None
    return kernel_share.roofline_pct(
        run, "swa.core", kernel["flops"], kernel["bytes"]
    )
