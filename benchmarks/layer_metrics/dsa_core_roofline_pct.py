"""Selected-set attention's share of its roofline: Q K^T and P V over
the pairs the indexer picked — never the masked square they may be
computed inside — forward + backward (`counts/keye_vl2.py:kernels`),
over the time under `euler.dsa.core`."""

import kernel_share


def read(run: dict):
    kernel = run["counts"].get("kernels", {}).get("dsa_core")
    if not kernel:
        return None
    return kernel_share.roofline_pct(
        run, "dsa.core", kernel["flops"], kernel["bytes"]
    )
