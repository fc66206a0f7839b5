"""Host time in the train loop's body per step, median over the traced
steps: the `euler.train.next_batch` and `.dispatch` spans of one step
summed — what a step would cost if the device were free. The call's
`.drain`, a wait for the device, is not in it."""

import scoped


def read(run: dict):
    ns = scoped.host_step_ns(scoped.events_of())
    return None if ns is None else ns / 1e6
