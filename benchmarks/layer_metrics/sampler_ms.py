"""Device self time under `euler.sample` per step, inside the step
program: the device sampler where it runs, not a second program. Also
leaves the whole scope table and the idle gaps by program span in
`run["notes"]` (scoped.py)."""

import scoped


def read(run: dict):
    layers = scoped.notes(run)
    if layers is not None:
        run["notes"]["layers"] = layers
    return scoped.layer_ms(run, "sample.forward", "sample.backward")
