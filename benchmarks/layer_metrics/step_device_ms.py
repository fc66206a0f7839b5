"""Device-busy time inside the step program's executions, per step."""

import tracered as tr


def read(run: dict):
    ns = tr.step_ns(run["trace"], run["step_program"], run["steps_per_program"])
    return None if ns is None else ns / 1e6
