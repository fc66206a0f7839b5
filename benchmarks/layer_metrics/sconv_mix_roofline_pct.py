"""The short convolution's gate, taps, gate chain's share of its
roofline: the bytes it must move — `B`, `x~`, `C` in and `C * c` out
forward, the same and their cotangents backward, each [T, hidden]
float32 once (`counts/lfm2_moe.py:kernels`), whatever implements it —
over the time under `euler.sconv.mix`, which holds that chain and
nothing else. The time includes the layer's rematerialised forward; the
bytes do not."""

import kernel_share


def read(run: dict):
    kernel = run["counts"].get("kernels", {}).get("sconv_mix")
    if not kernel:
        return None
    return kernel_share.roofline_pct(
        run, "sconv.mix", kernel["flops"], kernel["bytes"]
    )
