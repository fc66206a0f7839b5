"""Device time per step under `euler.optimizer`: the optimizer's sweep
over every leaf."""

import scoped


def read(run: dict):
    return scoped.layer_ms(run, "optimizer.forward", "optimizer.backward")
