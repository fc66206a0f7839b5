"""Device time per step under `euler.attn.*`: the gated-attention
mixer's projections, head norms and rotary, the blockwise causal softmax
and the gated output; forward, rematerialised forward and backward."""

import kernel_share


def read(run: dict):
    return kernel_share.prefix_ms(run, "attn")
