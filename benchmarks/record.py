"""The window's `Estimator.train` calls as the program's own record has
them, traced or not: `euler_tpu/utils/trace.py` keeps every host span in
memory in every run, and since PR 38 each step of the train loop is a
span `train.step` that parents its `train.next_batch` and
`train.dispatch` and whatever interrupted them (`gc` and `late_compile`
children), its `train.dispatch` holds the step's `model_metric`, the
call's `train.drain` has `.wait` and `.copy`, and `train` and
`train.drain` keep in their `args` the thread's context switches and
page faults that moved.

What the three readers of the record share (`host_step_untraced_ms`,
`slowest_call_excess_ms`, `call_turnaround_ms`): the window's calls
picked out of `scoped.program_spans()`, and where a stretch of the host's
time went, by span. A program from before `train.step` gives None
everywhere.
"""

from __future__ import annotations

import statistics

import scoped

COUNTERS = ("nivcsw", "nvcsw", "majflt", "minflt")
BETWEEN = "between_calls"  # host time under no span of the program's


def window_calls(run: dict, spans: list | None = None):
    """The window's calls, oldest first: `{"span", "steps", "inside"}`,
    `span` a top-level `train` span (the last `len(run["call_seconds"])`
    of the record), `steps` its `train.step` spans, `inside` every span
    below it. None where the record holds fewer calls than the window
    made, or no `train.step` in them (the parent's program)."""
    spans = scoped.program_spans() if spans is None else spans
    wanted = len(run["call_seconds"])
    tops = [s for s in spans if s.name == "train" and s.parent is None]
    if not wanted or len(tops) < wanted:
        return None
    kids: dict = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    calls = []
    for top in tops[-wanted:]:
        inside, frontier = [], [top]
        while frontier:
            frontier = [k for s in frontier for k in kids.get(s.id, ())]
            inside.extend(frontier)
        steps = [s for s in inside if s.name == "train.step" and s.parent == top.id]
        if not steps:
            return None
        calls.append({"span": top, "steps": steps, "inside": inside})
    return calls


def by_span(spans: list, lo: int, hi: int) -> dict:
    """Nanoseconds of [lo, hi) by the name of the innermost of `spans`
    that holds each instant (the one that began last), `BETWEEN` where
    none does: a partition, so the values add up to `hi - lo`. The sweep
    is the program's own (`trace.innermost`, which its `step.first_call`
    children are cut by): a record with `train.step` comes with it."""
    from euler_tpu.utils import trace

    out: dict = {}
    for a, b, name in trace.innermost(
        (max(s.start_ns, lo), min(s.end_ns, hi), s.name)
        for s in spans if s.end_ns > lo and s.start_ns < hi
    ):
        out[name] = out.get(name, 0) + (b - a)
    if sum(out.values()) < hi - lo:
        out[BETWEEN] = hi - lo - sum(out.values())
    return out


def call_parts(call: dict) -> dict:
    """A call's length by span, in ns: `train` is the call's own self
    time (`_ensure_init`, `_finish_train`), `train.step` the loop's
    bookkeeping, and so on down to `gc` and `late_compile`."""
    top = call["span"]
    return by_span([top, *call["inside"]], top.start_ns, top.end_ns)


def self_ns(span, inside: list) -> int:
    """A span's length less the part of it its child spans cover."""
    kids = [s for s in inside if s.parent == span.id]
    return by_span(kids, span.start_ns, span.end_ns).get(BETWEEN, 0)


def untraced_steps(run: dict, calls: list) -> list:
    """(call, `train.step` span) of the window's steps past its first
    `run["traced_steps"]`: those no profiler session watched."""
    out, seen = [], 0
    for call in calls:
        for step in call["steps"]:
            if seen >= run["traced_steps"]:
                out.append((call, step))
            seen += step.args.get("steps", 1)
    return out


def model_metrics(call: dict) -> list:
    """The model's metric of each dispatch of the call, as the drain
    fetched it (`routed_share` for the language models)."""
    return [
        s.args["model_metric"] for s in call["inside"]
        if s.name == "train.dispatch" and "model_metric" in s.args
    ]


def counters(call: dict) -> dict:
    """The interruption counters that moved over the call (`whole`, on
    its `train` span) and over its drains (`drain`, a part of `whole`):
    a thread that was descheduled or faulted while it waited for the
    device shows in both. None on a host that does not count them."""
    from euler_tpu.utils import trace

    if not getattr(trace, "INTERRUPTIONS_COUNTED", True):
        return None  # this host counts none: `{}` would read as "none moved"
    drains = [s for s in call["inside"] if s.name == "train.drain"]
    return {
        "whole": {n: call["span"].args[n] for n in COUNTERS if n in call["span"].args},
        "drain": {
            n: sum(s.args.get(n, 0) for s in drains)
            for n in COUNTERS if any(n in s.args for s in drains)
        },
    }


def ms(ns_by_name: dict) -> dict:
    return {
        k: v / 1e6 for k, v in sorted(ns_by_name.items(), key=lambda kv: -kv[1])
    }


def median_call(calls: list) -> dict:
    """The call whose length is the window's median (the lower of the
    two middle ones where the calls are even in number)."""
    low = statistics.median_low(c["span"].end_ns - c["span"].start_ns for c in calls)
    return next(c for c in calls if c["span"].end_ns - c["span"].start_ns == low)
