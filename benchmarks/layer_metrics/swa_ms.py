"""Device time per step under `euler.swa.*`: the window layers'
projections (q with its gate, k, v, head norms, rotary), blockwise
softmax attention inside the window, and gated output projection;
forward, the layer's second forward of `.proj` and `.out`, each block's
own rematerialised forward and backward. Also leaves the whole scope
table, the largest unscoped instructions and the idle gaps by program
span in `run["notes"]` (kernel_share.py), as `dsa_ms` does in its cell."""

import kernel_share


def read(run: dict):
    layers = kernel_share.notes(run)
    if layers is not None:
        run["notes"]["layers"] = layers
    return kernel_share.prefix_ms(run, "swa")
