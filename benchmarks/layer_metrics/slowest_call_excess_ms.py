"""The window's longest `Estimator.train` call less its median one, over
every call of the window, traced or not, from the program's record
(`record.py`): 0 in a window whose calls are alike, a router's step in
`keye`, 60 to 3,200 in a window that stalled. `run["notes"]
["slowest_call"]` says which call it was and where its time went beside
the median call's, span by span (each call's parts add up to its
length); `grew` names the span with the largest excess; the host's
interruption counters over the call; and both calls' `model_metric`
step by step, which tells a router's step from a stall."""

import statistics

import record


def read(run: dict):
    calls = record.window_calls(run)
    if calls is None:
        return None
    lengths = [c["span"].end_ns - c["span"].start_ns for c in calls]
    slow = calls[lengths.index(max(lengths))]
    middle = record.median_call(calls)
    slow_parts, middle_parts = record.call_parts(slow), record.call_parts(middle)
    excess = {
        name: slow_parts.get(name, 0) - middle_parts.get(name, 0)
        for name in {*slow_parts, *middle_parts}
    }
    run["notes"]["slowest_call"] = {
        "call": calls.index(slow),
        "ms": max(lengths) / 1e6,
        "median_call": calls.index(middle),
        "median_ms": (middle["span"].end_ns - middle["span"].start_ns) / 1e6,
        "calls": len(calls),
        "parts_ms": record.ms(slow_parts),
        "median_parts_ms": record.ms(middle_parts),
        "grew": max(excess, key=excess.get),
        "counters": record.counters(slow),
        "median_counters": record.counters(middle),
        "model_metric": record.model_metrics(slow),
        "median_model_metric": record.model_metrics(middle),
    }
    return (max(lengths) - statistics.median(lengths)) / 1e6
