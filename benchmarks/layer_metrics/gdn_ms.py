"""Device time per step under `euler.gdn.*`: the gated-DeltaNet mixers'
projections, conv, chunked delta rule and gated output norm, forward,
rematerialised forward and backward. Also leaves the whole scope table,
the largest unscoped instructions and the idle gaps by program span in
`run["notes"]` (kernel_share.py), as `sampler_ms` does in its cells."""

import kernel_share


def read(run: dict):
    layers = kernel_share.notes(run)
    if layers is not None:
        run["notes"]["layers"] = layers
    return kernel_share.prefix_ms(run, "gdn")
