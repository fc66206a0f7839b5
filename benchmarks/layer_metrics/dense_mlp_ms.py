"""Device time per step under `euler.mlp`: the dense feed-forward of the
leading layers (`layers/moe.py:DenseMLP`), forward, the layer's second
forward and backward."""

import kernel_share


def read(run: dict):
    return kernel_share.prefix_ms(run, "mlp")
