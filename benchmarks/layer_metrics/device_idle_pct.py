"""Share of the traced stretch in which no op ran on the device."""


def read(run: dict):
    if not run["traced_seconds"] or run["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - run["busy_s"] / run["traced_seconds"])
