"""Host time between two `Estimator.train` calls of the window: from the
end of one call's `train.drain.wait` (the device has finished the call's
last step) to the end of the next call's first `train.dispatch` (the
next step is enqueued) — what the device idles on between calls; median
over the window's consecutive calls, from the program's record
(`record.py`). Where the median turn's time went, by span, goes to
`run["notes"]`: the drain's copy, `train`'s own epilogue and prologue,
the benchmark's code between the calls, the next batch, the dispatch."""

import statistics

import record


def read(run: dict):
    calls = record.window_calls(run)
    if calls is None or len(calls) < 2:
        return None
    turns = []
    for before, after in zip(calls, calls[1:]):
        waits = [s for s in before["inside"] if s.name == "train.drain.wait"]
        sent = [s for s in after["inside"] if s.name == "train.dispatch"]
        if not waits or not sent:
            return None
        lo, hi = waits[-1].end_ns, min(s.end_ns for s in sent)
        spans = [before["span"], *before["inside"], after["span"], *after["inside"]]
        turns.append((hi - lo, spans, lo, hi))
    lengths = [t[0] for t in turns]
    _, spans, lo, hi = turns[lengths.index(statistics.median_low(lengths))]
    run["notes"]["call_turnaround"] = {
        "turns": len(turns),
        "longest_ms": max(lengths) / 1e6,
        "parts_ms": record.ms(record.by_span(spans, lo, hi)),
    }
    return statistics.median(lengths) / 1e6
