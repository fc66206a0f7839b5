"""Device time per step under `euler.moe.*`: router, dispatch, the
grouped expert matmuls, combine and the shared expert, of all layers;
forward, rematerialised forward and backward."""

import kernel_share


def read(run: dict):
    return kernel_share.prefix_ms(run, "moe")
