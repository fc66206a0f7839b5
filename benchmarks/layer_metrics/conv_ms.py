"""Device time per step under `euler.conv`, forward and backward: the
conv stack's aggregation and matmuls."""

import scoped


def read(run: dict):
    return scoped.layer_ms(run, "conv.forward", "conv.backward")
