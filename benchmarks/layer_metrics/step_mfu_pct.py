"""The whole step's share of the chip's peak: forward and backward FLOPs
per step (counts/<family>.py) times the steps per second of the traced
stretch, over the peak in peaks.json."""


def read(run: dict):
    if not run["traced_steps"] or not run["traced_seconds"]:
        return None
    rate = run["traced_steps"] / run["traced_seconds"]
    return 100.0 * run["counts"]["flops"] * rate / run["peak"]["flops_per_s"]
