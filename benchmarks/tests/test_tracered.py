"""The trace reduction on a list worked by hand and on a recording
taken on the v5e (`recorded_v5e_events.json`: the first two step
executions of a traced sage-products-id.train-device run)."""

import json
import os

import tracered as tr

D, H = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start, "dur_ns": dur}


HAND = [
    ev(D, tr.MODULES_LINE, "jit_train_step(123)", 100, 400),
    ev(D, tr.MODULES_LINE, "jit_train_step(123)", 600, 400),
    ev(D, tr.MODULES_LINE, "jit_other(9)", 1100, 50),
    ev(D, tr.OPS_LINE, "while.1", 100, 300),  # parent of the next two
    ev(D, tr.OPS_LINE, "gather.2", 120, 100),
    ev(D, tr.OPS_LINE, "fusion.3", 250, 100),
    ev(D, tr.OPS_LINE, "scatter.4", 420, 60),
    ev(D, tr.OPS_LINE, "gather.2", 600, 350),
    ev(D, tr.OPS_LINE, "copy.5", 1100, 50),
    ev(H, "python", "bench.traced", 0, 1200),
    ev(H, "python", "bench.train_call", 50, 480),
    ev(H, "python", "bench.train_call", 540, 500),
]


def test_busy_is_the_union_of_op_intervals():
    # [100,400) + [420,480) + [600,950) + [1100,1150) = 300+60+350+50
    assert tr.busy_seconds(HAND, 0, 1200) == 760 / 1e9
    assert tr.busy_seconds(HAND, 0, 500) == 360 / 1e9


def test_program_runs_and_gaps():
    runs = tr.program_runs(HAND, "jit_train_step")
    assert runs == [(100, 500), (600, 1000)]
    assert tr.busy_inside(HAND, runs) == 360 + 350


def test_self_time_takes_children_out():
    own = tr.self_times(HAND, 0, 1200)
    assert own["while.1"] == 100
    assert own["gather.2"] == 100 + 350
    assert own["fusion.3"] == 100 and own["scatter.4"] == 60


def test_idle_gaps_are_named_by_the_host_span():
    gaps = tr.idle_gaps(HAND, 0, 1200)
    assert gaps[0] == (950, 1100)
    out = tr.breakdown(HAND, 0, 1200)
    assert out["device_ops"][0] == ["gather.2", 450 / 1e9]
    names = dict(out["idle_gaps"])
    assert set(names) == {"bench.train_call", "bench.traced"}


def test_layer_readers_on_the_hand_trace():
    import run as harness

    facts = {
        "trace": HAND, "step_program": "jit_train_step", "steps_per_program": 1,
        "call_seconds": [1.0, 1.0, 1.0, 1.1, 1.0], "traced_steps": 2,
        "traced_seconds": 1200 / 1e9, "busy_s": 760 / 1e9, "memory_peak_bytes": 2**31,
        "counts": {"flops": 1000, "bytes": 4000},
        "peak": {"flops_per_s": 1e12, "bytes_per_s": 1e12}, "notes": {},
    }

    def read(name):
        return harness.load_module("layer_metrics", name).read(facts)

    assert read("dispatch_gap_ms") == 100 / 1e6
    assert read("step_device_ms") == (710 / 2) / 1e6
    assert abs(read("device_idle_pct") - 100 * (1 - 760 / 1200)) < 1e-9
    assert read("hbm_peak_gib") == 2.0
    assert abs(read("step_roofline_pct") - 100 * (4000 / 1e12) / (355e-9)) < 1e-6
    assert facts["notes"]["step_roofline_bound"] == "memory"
    assert abs(read("step_mfu_pct") - 100 * 1000 * (2 / 1200e-9) / 1e12) < 1e-9


def test_recorded_v5e_trace():
    path = os.path.join(os.path.dirname(__file__), "recorded_v5e_events.json")
    with open(path) as f:
        rec = json.load(f)
    events, want = rec["events"], rec["expect"]
    runs = tr.program_runs(events, "jit_train_step")
    assert len(runs) == want["runs"]
    assert tr.busy_inside(events, runs) == want["busy_inside_ns"]
    lo, hi = runs[0][0], runs[-1][1]
    assert abs(tr.busy_seconds(events, lo, hi) - want["busy_s"]) < 1e-12
    top = tr.breakdown(events, lo, hi)["device_ops"][0][0]
    assert top == want["top_op"]
