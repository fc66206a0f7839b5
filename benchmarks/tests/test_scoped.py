"""The per-layer reduction (`scoped.py`) and the readers on top of it:
on lists worked by hand, on a trace file encoded by hand, and on a
recording taken on the v5e with each op's `op_name` kept
(`recorded_v5e_scoped_events.json`: the first two step executions of a
traced sage-products-id.train-device run; names cut to 120 characters)."""

import json
import os
import struct

import pytest

import run as harness
import scoped
import tracered as tr

D, H = "/device:TPU:0", "/host:CPU"
NEW = (
    "sampler_ms", "gather_ms", "table_grad_ms", "conv_ms", "optimizer_ms",
    "host_step_ms", "stage_s", "step_compile_s",
)
HERE = os.path.dirname(__file__)


def recorded(name="recorded_v5e_scoped_events.json"):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def op(name, start, dur, op_name=None):
    return {"plane": D, "line": tr.OPS_LINE, "name": name, "start_ns": start,
            "dur_ns": dur, "op_name": op_name}


def host(name, start, dur, **args):
    return {"plane": H, "line": "python", "name": name, "start_ns": start,
            "dur_ns": dur, "args": args}


def module(start, dur, name="jit_train_step(1)"):
    return {"plane": D, "line": tr.MODULES_LINE, "name": name,
            "start_ns": start, "dur_ns": dur}


FWD = "jit(train_step)/jvp(M)/enc/euler.embed/gather"
BWD = "jit(train_step)/transpose(jvp(M))/enc/euler.embed/scatter-add"
HAND = [
    module(100, 400), module(600, 400),
    op("fusion.1", 100, 100, "jit(train_step)/euler.sample/jit(_take)/gather"),
    op("while.2", 200, 200, None),  # parent of the next two
    op("fusion.3", 210, 90, FWD),
    op("fusion.4", 300, 50, BWD),
    op("copy.5", 420, 60, None),
    op("fusion.1", 600, 150, "jit(train_step)/euler.sample/jit(_take)/gather"),
    op("fusion.6", 750, 200, "jit(train_step)/euler.optimizer/mul"),
    op("copy.9", 1100, 50, "jit(other)/euler.sample/x"),  # outside the program
    host("bench.traced", 0, 1200),
    host("euler.train", 50, 1000, steps=2),
    host("euler.train.next_batch", 60, 10, step=7),
    host("euler.train.dispatch", 70, 30, step=7),
    host("euler.train.next_batch", 510, 12, step=8),
    host("euler.train.dispatch", 522, 20, step=8),
    host("euler.train.drain", 560, 480, step=9),
]


@pytest.mark.parametrize("op_name,want", [
    (FWD, ("embed", "forward")),
    (BWD, ("embed", "backward")),
    ("jit(f)/while/body/closed_call/transpose(jvp(euler.embed))/scatter-add",
     ("embed", "backward")),
    ("jit(f)/jvp(euler.embed)/gather", ("embed", "forward")),
    ("jit(train_step)/euler.hydrate/euler.inner/gather", ("inner", "forward")),
    ("jit(train_step)/jvp(M)/Dense_0/dot_general", None),
    (None, None),
])
def test_scope_is_the_innermost_and_backward_is_under_transpose(op_name, want):
    assert scoped.scope_of(op_name) == want


def test_partition_by_hand():
    got = scoped.partition(HAND, "jit_train_step", 1)
    # two executions: every layer's self time over two steps
    assert got == {
        "sample.forward": (100 + 150) / 2,
        "unscoped": (200 - 90 - 50 + 60) / 2,
        "embed.forward": 90 / 2,
        "embed.backward": 50 / 2,
        "optimizer.forward": 200 / 2,
    }
    runs = tr.program_runs(HAND, "jit_train_step")
    assert sum(got.values()) * len(runs) == tr.busy_inside(HAND, runs)
    assert scoped.partition(HAND, "jit_train_step", 2)["optimizer.forward"] == 50
    assert scoped.partition(HAND, "jit_absent", 1) is None


def test_host_time_of_a_step_is_the_median_over_steps():
    # steps 7 and 8: 40 and 32; the call's drain, 480, is a wait and left out
    assert scoped.host_step_ns(HAND) == 36
    assert scoped.host_step_ns([e for e in HAND if e["plane"] == D]) is None


def test_idle_gaps_go_to_the_innermost_program_span():
    got = scoped.idle_by_span(HAND, 0, 1200)
    # [0,100) [400,420) [480,600) [950,1100) [1150,1200), each by its
    # middle; bench.* is not the program's, so what no euler span holds
    # is unannotated
    assert got == {
        "euler.train": 100 + 20,
        "euler.train.dispatch": 120,
        "euler.train.drain": 150,
        "unannotated": 50,
    }


# -- the trace file, encoded by hand --------------------------------------


def varint(n):
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(no, value):
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    if isinstance(value, float):
        return varint(no << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(no << 3 | 2) + varint(len(value)) + value


def entry(key, message):
    return field(1, key) + field(2, message)


def test_load_reads_op_names_and_host_arguments(tmp_path):
    stat_names = {1: "tf_op", 2: "step", 3: "ratio", 4: "a/euler.conv/dot:", 5: "delta"}
    stat_meta = b"".join(
        field(5, entry(k, field(1, k) + field(2, v))) for k, v in stat_names.items()
    )
    device = (
        field(2, D)
        + field(3, field(2, tr.OPS_LINE) + field(3, 1000)
                + field(4, field(1, 7) + field(2, 2_500_999) + field(3, 4_000_999))
                + field(4, field(1, 8) + field(2, 9_000_000) + field(3, 1_000_000)))
        + field(3, field(2, "Steps") + field(4, field(1, 7)))
        + field(4, entry(7, field(1, 7) + field(2, "%fusion.1 = f32[8]")
                         + field(5, field(1, 1) + field(5, "jit(f)/euler.sample/gather:Gather"))))
        + field(4, entry(8, field(1, 8) + field(2, "%dot.2")
                         + field(5, field(1, 1) + field(7, 4))))  # a ref value
        + stat_meta
    )
    python = (
        field(2, H)
        + field(3, field(2, "python") + field(3, 500)
                + field(4, field(1, 1) + field(2, 1_000_000) + field(3, 2_000_000)
                        + field(4, field(1, 2) + field(4, 41))
                        + field(4, field(1, 3) + field(2, 0.5))
                        + field(4, field(1, 5) + field(4, -3)))
                + field(4, field(1, 2) + field(2, 0) + field(3, 10)))
        + field(4, entry(1, field(1, 1) + field(2, "euler.train.dispatch")))
        + field(4, entry(2, field(1, 2) + field(2, "$threading.py:1 run")))
        + stat_meta
    )
    big = field(2, "/host:metadata") + field(3, b"\0" * 100_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, big) + field(1, device) + field(1, python))
    got = scoped.load(str(path))
    assert got == [
        {"plane": D, "line": tr.OPS_LINE, "name": "%fusion.1 = f32[8]",
         "start_ns": 3500, "dur_ns": 4000, "op_name": "jit(f)/euler.sample/gather"},
        {"plane": D, "line": tr.OPS_LINE, "name": "%dot.2",
         "start_ns": 10000, "dur_ns": 1000, "op_name": "a/euler.conv/dot"},
        {"plane": H, "line": "python", "name": "euler.train.dispatch",
         "start_ns": 1500, "dur_ns": 2000,
         "args": {"step": 41, "ratio": 0.5, "delta": -3}},
    ]


# -- the recording, and the readers ---------------------------------------


def test_partition_of_the_recording_sums_to_busy_inside_to_the_nanosecond():
    rec = recorded()
    events, want = rec["events"], rec["expect"]
    runs = tr.program_runs(events, "jit_train_step")
    assert len(runs) == want["runs"] == 2
    got = scoped.partition(events, "jit_train_step", 1)
    assert tr.busy_inside(events, runs) == want["busy_inside_ns"]
    assert round(sum(got.values()) * len(runs)) == want["busy_inside_ns"]
    scopes = {k.split(".")[0] for k in got}
    assert scopes == {"sample", "hydrate", "embed", "conv", "loss", "optimizer",
                      "unscoped"}
    # forward and backward: the table and the conv stack have both, the
    # sampler, the hydrate and the optimizer are not differentiated
    for both in ("embed", "conv"):
        assert got[f"{both}.forward"] > 0 and got[f"{both}.backward"] > 0
    for one in ("sample", "hydrate", "optimizer"):
        assert f"{one}.backward" not in got
    assert got["unscoped"] < 0.10 * sum(got.values())


def reader(name):
    return harness.load_module("layer_metrics", name)


def run_facts(**over):
    facts = {"step_program": "jit_train_step", "steps_per_program": 1, "notes": {}}
    facts.update(over)
    return facts


def test_readers_on_the_recording(monkeypatch):
    events = recorded()["events"]
    monkeypatch.setattr(scoped, "events_of", lambda: events)
    table = scoped.partition(events, "jit_train_step", 1)
    run = run_facts()
    ms = {name: reader(name).read(run) for name in NEW[:6]}
    assert ms["sampler_ms"] == table["sample.forward"] / 1e6
    assert ms["gather_ms"] == (table["hydrate.forward"] + table["embed.forward"]) / 1e6
    assert ms["table_grad_ms"] == table["embed.backward"] / 1e6
    assert ms["conv_ms"] == (table["conv.forward"] + table["conv.backward"]) / 1e6
    assert ms["optimizer_ms"] == table["optimizer.forward"] / 1e6
    assert 0 < ms["host_step_ms"] < 5
    layers = run["notes"]["layers"]
    assert layers["scope_ms_per_step"]["unscoped"] == table["unscoped"] / 1e6
    assert sum(layers["scope_ms_per_step"].values()) == pytest.approx(
        tr.step_ns(events, "jit_train_step", 1) / 1e6
    )
    assert set(layers["idle_ms_by_span"]) <= {
        "unannotated", "euler.train", "euler.train.next_batch",
        "euler.train.dispatch", "euler.train.drain",
    }


def test_a_trace_without_scopes_reads_as_nothing(monkeypatch):
    """The parent's trace, or an executable the compile cache kept from
    before the scopes: every new reader returns None, never 0."""
    old = recorded("recorded_v5e_events.json")["events"]
    assert tr.program_runs(old, "jit_train_step")
    monkeypatch.setattr(scoped, "events_of", lambda: old)
    monkeypatch.setattr(scoped, "program_spans", lambda: [])
    for name in NEW:
        run = run_facts()
        assert reader(name).read(run) is None, name
        assert run["notes"] == {}
    monkeypatch.setattr(scoped, "events_of", lambda: [])
    assert all(reader(name).read(run_facts()) is None for name in NEW)


class Span(dict):
    __getattr__ = dict.__getitem__


def span(ident, name, start_s, end_s, parent=None, **args):
    return Span(id=ident, name=name, start_ns=int(start_s * 1e9),
                end_ns=int(end_s * 1e9), parent=parent, args=args)


def test_set_up_readers_sum_the_programs_record(monkeypatch):
    spans = [
        span(1, "stage.features", 0, 8),
        span(2, "stage.graph", 8, 50),
        span(3, "stage.graph.sweep", 10, 40, parent=2),
        span(4, "stage.features", 50, 52),  # the flow's label table
        span(5, "stage.features", 60, 61, parent=9),  # staged inside another span
        span(6, "step.first_call", 70, 170, parent=20, program="train_step"),
        span(7, "step.first_call.trace", 70, 74, parent=6),
        span(8, "step.first_call.lower", 74, 80, parent=6),
        span(10, "step.first_call.compile", 80, 160, parent=6),
        span(11, "step.first_call.cache_fetch", 150, 160, parent=6),
        span(12, "step.first_call", 200, 230, parent=21, program="multi_step"),
    ]
    monkeypatch.setattr(scoped, "program_spans", lambda: spans)
    run = run_facts()
    assert reader("stage_s").read(run) == 8 + 42 + 2
    assert run["notes"]["stage_s"] == {
        "stage.features#1": 8.0, "stage.graph#2": 42.0,
        "stage.graph.sweep#3": 30.0, "stage.features#4": 2.0,
    }
    assert reader("step_compile_s").read(run) == 100.0
    assert run["notes"]["step_compile_s"] == {
        "trace_s": 4.0, "lower_s": 6.0, "compile_s": 80.0,
        "cache_fetch_s": 10.0, "first_run_s": 10.0,
    }
    assert reader("step_compile_s").read(run_facts(step_program="jit_multi_step")) == 30.0


def test_the_programs_record_is_read_where_the_program_keeps_one():
    from euler_tpu.utils import trace

    with trace.span("stage.t_scoped"):
        pass
    assert any(s.name == "stage.t_scoped" for s in scoped.program_spans())


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_found_by_name_in_cells_that_exist(name):
    bench = harness.load_benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    listed = entry.get("workloads", sorted(cells))  # no key: every cell
    assert set(listed) <= cells and listed
    assert callable(reader(name).read)
    moved = {m["name"] for m in bench["end_to_end"]}
    assert entry["moves"] in moved
    for cell in listed:
        assert name in [m["name"] for m in harness.resolve(cell)["per_layer"]]
