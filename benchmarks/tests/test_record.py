"""The readers of the program's record (`record.py`,
`host_step_untraced_ms`, `slowest_call_excess_ms`, `call_turnaround_ms`)
on a made-up record: a window of four one-step calls of 100 ms, one of
them with a planted 300 ms run of the collector under its
`train.next_batch`; and None, never 0, on a record without `train.step`
(the parent's program)."""

import itertools

import pytest

import record
import run as harness
import scoped
from euler_tpu.utils.trace import Span

MS = 1_000_000


def window(stall_ms=300, stalled_call=2, with_steps=True, calls=4, metric=0.14):
    """`calls` consecutive `train` calls of one step each, 1 ms apart:
    next_batch 1 ms, dispatch 2 ms, 1 ms of the loop's own, a drain of
    95 ms (94 waited, 0.5 copied), `train`'s own 1 ms; the stalled call
    holds a `gc` of `stall_ms` inside its next_batch."""
    ids = itertools.count(1)
    spans, at = [], 10 * MS

    def add(name, start, end, parent, **args):
        ident = next(ids)
        spans.append(Span(name, start, end, parent, args.get("step"), ident, 7, args))
        return ident

    for call in range(calls):
        stall = stall_ms * MS if call == stalled_call else 0
        t = at
        top = next(ids)
        loop = top if not with_steps else next(ids)
        batch = add("train.next_batch", t + MS // 2, t + MS // 2 + MS + stall, loop, step=call)
        if stall:
            add("gc", t + MS, t + MS + stall, batch, generation=2)
        t += stall
        add("train.dispatch", t + 3 * MS // 2, t + 7 * MS // 2, loop, step=call, model_metric=metric + call / 100)
        if with_steps:
            spans.append(Span("train.step", at + MS // 2, t + 9 * MS // 2, top, call, loop, 7, {"step": call}))
        # the drain blocks once in every call; the stalled call's thread was descheduled outside it
        drain = add("train.drain", t + 9 * MS // 2, t + 199 * MS // 2, top, step=call + 1, nvcsw=1)
        if with_steps:
            add("train.drain.wait", t + 5 * MS, t + 99 * MS, drain)
            add("train.drain.copy", t + 99 * MS, t + 199 * MS // 2, drain)
        counted = {"nvcsw": 1, **({"nivcsw": 3} if stall else {})}
        spans.append(Span("train", at, t + 100 * MS, None, None, top, 7, {"steps": 1, **counted}))
        at = t + 101 * MS
    return sorted(spans, key=lambda s: (s.start_ns, s.id))


def facts(calls=4, traced_steps=1):
    return {"call_seconds": [0.1] * calls, "traced_steps": traced_steps, "notes": {}}


def read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


@pytest.fixture
def recorded(monkeypatch):
    def plant(spans):
        monkeypatch.setattr(scoped, "program_spans", lambda: spans)

    return plant


def test_a_planted_collector_run_is_the_slowest_calls_excess(recorded):
    recorded(window())
    run = facts()
    assert read("slowest_call_excess_ms", run) == pytest.approx(300.0)
    note = run["notes"]["slowest_call"]
    assert note["grew"] == "gc"
    assert (note["call"], note["calls"]) == (2, 4)
    assert note["ms"] == pytest.approx(400.0) and note["median_ms"] == pytest.approx(100.0)
    assert note["parts_ms"]["gc"] == pytest.approx(300.0)
    assert "gc" not in note["median_parts_ms"]
    # a call's parts add up to its length
    assert sum(note["parts_ms"].values()) == pytest.approx(note["ms"])
    assert sum(note["median_parts_ms"].values()) == pytest.approx(note["median_ms"])
    assert note["parts_ms"]["train.drain.wait"] == pytest.approx(94.0)
    assert note["parts_ms"]["train.drain.copy"] == pytest.approx(0.5)
    assert note["parts_ms"]["train.next_batch"] == pytest.approx(1.0)
    assert note["parts_ms"]["train.step"] == pytest.approx(1.0)
    assert note["parts_ms"]["train"] == pytest.approx(1.0)
    assert note["counters"] == {"whole": {"nivcsw": 3, "nvcsw": 1}, "drain": {"nvcsw": 1}}
    assert note["median_counters"] == {"whole": {"nvcsw": 1}, "drain": {"nvcsw": 1}}
    assert note["model_metric"] == [pytest.approx(0.16)]
    assert len(note["median_model_metric"]) == 1


def test_on_a_host_that_counts_nothing_the_counters_read_none(recorded, monkeypatch):
    from euler_tpu.utils import trace

    monkeypatch.setattr(trace, "INTERRUPTIONS_COUNTED", False)
    recorded(window())
    run = facts()
    assert read("slowest_call_excess_ms", run) == pytest.approx(300.0)
    note = run["notes"]["slowest_call"]
    assert note["counters"] is None and note["median_counters"] is None


def test_a_window_of_like_calls_has_no_excess(recorded):
    recorded(window(stall_ms=0))
    run = facts()
    assert read("slowest_call_excess_ms", run) == 0.0
    assert "gc" not in run["notes"]["slowest_call"]["parts_ms"]


def test_untraced_host_step_leaves_out_the_traced_steps(recorded):
    recorded(window(stalled_call=0))  # the stalled call is the traced one
    run = facts(traced_steps=1)
    assert read("host_step_untraced_ms", run) == pytest.approx(3.0)
    note = run["notes"]["host_step_untraced"]
    assert note["steps"] == 3 and note["step_self_ms"] == pytest.approx(1.0)
    recorded(window(stalled_call=3))
    run = facts(traced_steps=1)
    assert read("host_step_untraced_ms", run) == pytest.approx(3.0)  # a median
    assert run["notes"]["host_step_untraced"]["longest_ms"] == pytest.approx(303.0)
    assert read("host_step_untraced_ms", facts(traced_steps=4)) is None  # all traced


def test_turnaround_runs_from_the_waits_end_to_the_next_dispatchs(recorded):
    recorded(window(stalled_call=1))
    run = facts()
    # wait's end -> call's end 1 ms, 1 ms between calls, 3.5 ms to the dispatch's end
    assert read("call_turnaround_ms", run) == pytest.approx(5.5)
    note = run["notes"]["call_turnaround"]
    assert note["turns"] == 3 and note["longest_ms"] == pytest.approx(305.5)
    assert note["parts_ms"] == {
        "train.dispatch": pytest.approx(2.0),
        "train.next_batch": pytest.approx(1.0),
        "train.drain.copy": pytest.approx(0.5),
        "train": pytest.approx(1.0),  # the epilogue's half, the prologue's half
        record.BETWEEN: pytest.approx(1.0),
    }
    assert sum(note["parts_ms"].values()) == pytest.approx(5.5)


@pytest.mark.parametrize(
    "name", ["host_step_untraced_ms", "slowest_call_excess_ms", "call_turnaround_ms"]
)
def test_a_record_without_train_step_reads_none_never_zero(recorded, name):
    recorded(window(with_steps=False))  # the parent's program
    run = facts()
    assert read(name, run) is None
    assert run["notes"] == {}
    recorded([])  # a program with no record at all
    assert read(name, facts()) is None
    recorded(window(calls=2))  # fewer calls than the window made
    assert read(name, facts(calls=4)) is None


def test_by_span_puts_an_instant_to_the_innermost_span():
    spans = [
        Span("a", 0, 100, None, None, 1, 7, {}),
        Span("b", 10, 60, 1, None, 2, 7, {}),
        Span("c", 20, 30, 2, None, 3, 7, {}),
        Span("b", 70, 80, 1, None, 4, 7, {}),
    ]
    assert record.by_span(spans, 0, 100) == {"a": 40, "b": 50, "c": 10}
    assert record.by_span(spans, 25, 75) == {"c": 5, "b": 35, "a": 10}
    assert record.by_span(spans, 90, 120) == {"a": 10, record.BETWEEN: 20}
    assert record.self_ns(spans[0], spans) == 40
    assert record.self_ns(spans[1], spans) == 40


def test_the_trinity_cell_still_reports_its_three_readers():
    """What `test_trinity.py`'s case of the cell's last three entries is
    there for, without the position: that case is red since PR 38, whose
    three appended entries report in every cell and so move the tail
    (PERF.md section 7)."""
    own = {"swa_ms", "swa_core_roofline_pct", "dense_mlp_ms"}
    cells = [w["name"] for w in harness.load_benchmark()["workloads"]]
    for cell in cells:
        names = {m["name"] for m in harness.resolve(cell)["per_layer"]}
        assert (own <= names) if cell.startswith("trinity-mini") else not (own & names)
        # the record's readers report in every `Estimator.train` cell
        assert {"host_step_untraced_ms", "slowest_call_excess_ms", "call_turnaround_ms"} <= names
