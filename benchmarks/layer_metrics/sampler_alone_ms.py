"""Device time of the flow's own jitted `sample(key)`, run alone at the
cell's shapes after the window: the mean over its traced executions."""

import tracered as tr

NEEDS = ("sampler_trace",)


def read(run: dict):
    events = run.get("sampler_trace")
    if not events:
        return None
    runs = tr.program_runs(events, run["sample_program"])
    if not runs:
        return None
    return tr.busy_inside(events, runs) / len(runs) / 1e6
