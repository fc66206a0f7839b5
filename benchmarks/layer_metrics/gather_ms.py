"""Forward device time per step under `euler.hydrate` (the feature-row
gather) and `euler.embed` (the id-row gather from a trainable table)."""

import scoped


def read(run: dict):
    return scoped.layer_ms(run, "hydrate.forward", "embed.forward")
