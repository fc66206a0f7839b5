"""Backward device time per step under `euler.embed`: the scatter-add of
gradient rows into table-shaped gradients."""

import scoped


def read(run: dict):
    return scoped.layer_ms(run, "embed.backward")
