"""Host-clock spread of the window's train calls: the 95th percentile
of their durations over the median, in percent (100 = no tail)."""

import statistics


def read(run: dict):
    calls = run["call_seconds"]
    if len(calls) < 2:
        return None
    p95 = statistics.quantiles(calls, n=20)[-1]
    return 100.0 * p95 / statistics.median(calls)
