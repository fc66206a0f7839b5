"""The chunked state-space scan's share of its roofline: the FLOPs and
bytes of its equations (`counts/granite_hybrid.py:kernels`: a chunk's
C B^T once for all heads, each head's in-chunk, carried and update
products, three times over; u, a, B, C and y and their cotangents once
each, float32), whatever implements it, over the time under
`euler.ssd.scan`, which holds the scan and the layout its inputs and
output take around it. The time includes the layer's rematerialised
forward; the FLOPs and bytes do not."""

import kernel_share


def read(run: dict):
    kernel = run["counts"].get("kernels", {}).get("ssd_scan")
    if not kernel:
        return None
    return kernel_share.roofline_pct(run, "ssd.scan", kernel["flops"], kernel["bytes"])
