"""Device time per step under `euler.ssd.*`: the Mamba-2 mixers' input
projection and step size (`.proj`), their convolution (`.conv`), their
chunked scan (`.scan`) and their skip, gate, norm and output projection
(`.out`); forward, the layer's rematerialised forward (the mixer names
no value to keep, so all four run again) and backward. Also leaves the
whole scope table, the largest unscoped instructions and the idle gaps
by program span in `run["notes"]` (kernel_share.py), as `sconv_ms` does
in its cell."""

import kernel_share


def read(run: dict):
    layers = kernel_share.notes(run)
    if layers is not None:
        run["notes"]["layers"] = layers
    return kernel_share.prefix_ms(run, "ssd")
