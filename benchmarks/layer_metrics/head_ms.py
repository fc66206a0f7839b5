"""Device time per step under `euler.head`: the logits over the
vocabulary slice, forward, rematerialised forward and backward (the loss
itself stays under `euler.loss`)."""

import kernel_share


def read(run: dict):
    return kernel_share.prefix_ms(run, "head")
