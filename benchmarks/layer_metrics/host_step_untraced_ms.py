"""Host time in the train loop's body per step with no profiler live:
median over the window's untraced steps of their `train.next_batch` and
`train.dispatch` spans summed — the sum `host_step_ms` takes from the
trace, under the profiler's Python tracer — read from the program's
record (`record.py`). `train.step`'s own self time, the loop's
bookkeeping, goes to `run["notes"]` beside it."""

import statistics

import record

BODY = ("train.next_batch", "train.dispatch")


def read(run: dict):
    calls = record.window_calls(run)
    steps = record.untraced_steps(run, calls) if calls else []
    if not steps:
        return None
    body = [
        sum(
            s.end_ns - s.start_ns for s in call["inside"]
            if s.parent == step.id and s.name in BODY
        )
        for call, step in steps
    ]
    own = [record.self_ns(step, call["inside"]) for call, step in steps]
    run["notes"]["host_step_untraced"] = {
        "steps": len(body),
        "step_self_ms": statistics.median(own) / 1e6,
        "longest_ms": max(body) / 1e6,
    }
    return statistics.median(body) / 1e6
