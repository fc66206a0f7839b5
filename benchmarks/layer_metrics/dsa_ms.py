"""Device time per step under `euler.dsa.*`: the indexed-sparse-attention
mixers' projections, indexer scores, selection, masked softmax
attention, indexer loss and output projection; forward, rematerialised
forward and backward. Also leaves the whole scope table, the largest
unscoped instructions and the idle gaps by program span in
`run["notes"]` (kernel_share.py), as `gdn_ms` does in its cell."""

import kernel_share


def read(run: dict):
    layers = kernel_share.notes(run)
    if layers is not None:
        run["notes"]["layers"] = layers
    return kernel_share.prefix_ms(run, "dsa")
