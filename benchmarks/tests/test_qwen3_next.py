"""The Qwen3-Next cell's own arithmetic and proof at rehearsal size on
the CPU: the counts against shapes worked by hand, a sound run, the bf16
control and the planted faults (half of the batch left out; the routed
experts' sum left out), and the readers on a made-up scope table."""

import argparse

import pytest

import run as harness

CELL = "qwen3-next-80b-a3b-ep16.train-tokens"


def _config():
    return harness.load_json(f"{harness.HERE}/configs/qwen3-next-80b-a3b-ep16.json")


def test_counts_by_hand():
    counts = harness.load_module("counts", "qwen3_next")
    got = counts.per_step(_config())
    gdn = 2048 * (2048 + 2048 + 4096 + 4096 + 64) + 4096 * 2048
    attn = 2048 * (16 * 256 * 2 + 2 * 2 * 256) + 16 * 256 * 2048
    moe = 2048 * 512 + (3 * 2048 * 512 + 2048) + 32 * 3 * 2048 * 512
    params = (
        3 * (gdn + 8192 * 4 + 32 + 32 + 128 + moe + 2 * 2048)
        + (attn + 2 * 256 + moe + 2 * 2048)
        + 19072 * 2048 + 2048 * 18992 + 2048
    )
    assert got["parameters"] == params
    assert 10.0e9 < params * 16 < 10.05e9  # ISSUE 28's 10.0 GB of state
    assert got["examples"] == 2 * 8192
    # a token passes 10 x 32 / 512 = 0.625 held experts, not 10
    routed = 2 * (2048 * 512 + 3 * 2048 * 512 + 2048 + 0.625 * 3 * 2048 * 512)
    c = 64
    scan = 32 * (4 * c * c * 128 + 2 * c**3 / 3 + 2 * c * c * 256 + 6 * c * 128 * 128 + 2 * c * c * 128) / c
    core = 16 * 4 * 256 * 8193 / 2
    forward = (
        3 * (2 * gdn + 2 * 8192 * 4 + scan + routed)
        + (2 * attn + core + routed)
        + 2 * 2048 * 18992
    )
    assert got["forward_flops_per_token"] == pytest.approx(forward)
    assert got["flops"] == pytest.approx(3 * 16384 * forward)
    assert 22.5e12 < got["flops"] < 23.5e12  # ISSUE 28: 22.8 TFLOP a step
    assert got["expected_expert_rows"] == 4 * 16384 * 0.625
    kernels = got["kernels"]
    assert kernels["attn_core"]["flops"] == pytest.approx(3 * 16384 * core)
    assert kernels["gdn_scan"]["flops"] == pytest.approx(3 * 3 * 16384 * scan)
    assert kernels["moe_experts"]["flops_per_row"] == 3 * 2 * 3 * 2048 * 512
    assert kernels["moe_experts"]["assignments"] == 4 * 163840


def test_facts_state_the_share():
    import graphs

    r = harness.resolve(CELL)
    config = harness.merge(r["config"], r["config"]["rehearse"])
    built = harness.load_module("families", "qwen3_next").build(
        config, r["mix"], graphs.build(config["graph"])
    )
    facts = built["facts"]
    assert facts["expected_routed_share"] == 4 / 16
    assert facts["expected_rows_per_expert"] == 128 * 2 / 16
    assert facts["deployment_rows_per_expert"] == 4 * facts["expected_rows_per_expert"]
    full = r["config"]
    assert full["model"]["batch_size"] * full["model"]["seq_len"] * 10 / 512 == 320
    assert full["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert full["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}


def _args(seed):
    return argparse.Namespace(
        workload=CELL, seed=seed, seconds=0.3, trace=0, rehearse=True, keep_trace=""
    )


def test_sound_run_is_correct():
    out = harness.run(_args(2147483700))
    assert out["correct"], out["compared"]
    assert out["metrics"] == {}
    assert out["run"]["facts"]["expected_routed_share"] == 0.25


def _first_steps(seed, **kw):
    import graphs

    r = harness.resolve(CELL)
    config = harness.merge(r["config"], r["config"]["rehearse"])
    ref = harness.load_module("reference", "qwen3_next")
    train = harness.load_module("reference", "train")
    graph = graphs.build(config["graph"])
    spec = ref.param_spec(config, graph)
    tables, loss_fn = ref.make(config, r["mix"], graph)
    lr = config["optimizer"]["learning_rate"]
    want = train.first_steps(loss_fn, tables, spec, seed, lr)
    other = train.first_steps(loss_fn, tables, spec, seed, lr, **kw)
    compared = train.compare(other, want)
    compared.update(window_compiles=0, failed_steps=0)
    return harness.decide(compared, r["limits"])


@pytest.mark.parametrize("fault", ["half_batch", "no_routed"])
def test_planted_fault_is_not_correct(fault):
    table, ok = _first_steps(11, fault=fault)
    assert not ok, table


def test_bf16_control_is_not_correct():
    import jax.numpy as jnp

    table, ok = _first_steps(3, dtype=jnp.bfloat16)
    assert not ok, table


def test_readers_sum_by_prefix_and_return_none_on_nothing(monkeypatch):
    import kernel_share

    table = {
        "gdn.proj.forward": 2e6, "gdn.scan.backward": 3e6, "gdn.out.forward": 1e6,
        "attn.core.backward": 4e6, "moe.experts.forward": 5e6, "head.backward": 6e6,
        "embed.forward": 7e6, "unscoped": 8e6,
    }
    monkeypatch.setattr(kernel_share, "layers", lambda run: table)
    run = {"peak": {"flops_per_s": 1e12, "bytes_per_s": 1e9}, "notes": {}, "counts": {}}
    assert kernel_share.prefix_ms(run, "gdn") == 6.0
    assert kernel_share.prefix_ms(run, "head") == 6.0
    assert kernel_share.prefix_ms(run, "sample") is None
    # 3 ms under gdn.scan; 1e9 FLOP need 1 ms at the peak, 1e5 bytes 0.1 ms
    assert kernel_share.roofline_pct(run, "gdn.scan", 1e9, 1e5) == pytest.approx(100 / 3)
    assert run["notes"]["gdn.scan_roofline_bound"] == "compute"
    for name in ("gdn_scan_roofline_pct", "attn_core_roofline_pct", "moe_experts_roofline_pct"):
        assert harness.load_module("layer_metrics", name).read(run) is None  # no counts
    monkeypatch.setattr(kernel_share, "layers", lambda run: None)
    for name in ("gdn_ms", "attn_ms", "moe_ms", "head_ms"):
        assert harness.load_module("layer_metrics", name).read(dict(run, step_program="x", steps_per_program=1)) is None


def test_experts_roofline_counts_the_rows_really_routed(monkeypatch):
    import kernel_share

    monkeypatch.setattr(kernel_share, "layers", lambda run: {"moe.experts.kernel": 1.5e6, "moe.experts.backward": 0.5e6})
    monkeypatch.setattr(kernel_share, "routed_share", lambda: 0.05)
    run = {
        "peak": {"flops_per_s": 1e12, "bytes_per_s": 1e12}, "notes": {},
        "counts": {"kernels": {"moe_experts": {
            "flops_per_row": 1e6, "bytes_per_row": 0.0, "bytes": 0.0, "assignments": 20000,
        }}},
    }
    got = harness.load_module("layer_metrics", "moe_experts_roofline_pct").read(run)
    assert run["notes"]["routed_rows_per_step"] == 1000
    assert got == pytest.approx(100 * (1000 * 1e6 / 1e12) / 2e-3)
    monkeypatch.setattr(kernel_share, "routed_share", lambda: None)
    assert harness.load_module("layer_metrics", "moe_experts_roofline_pct").read(run) is None


def test_routed_share_is_the_mean_over_the_steps_that_kept_their_metric(monkeypatch):
    """The program keeps a step's metric in its `train.dispatch` span only
    under a live profiler session: the mean runs over those steps alone;
    a program that keeps none (the parent's) reads None."""
    import collections

    import kernel_share
    import scoped

    span = collections.namedtuple("span", "name args")
    record = [
        span("train.dispatch", {"step": 4}),  # set-up: no session, no metric
        span("train.dispatch", {"step": 5, "metric": 0.06}),
        span("train.drain", {"step": 6, "metric": 0.5}),  # not a dispatch
        span("train.dispatch", {"step": 6, "metric": 0.08}),
    ]
    monkeypatch.setattr(scoped, "program_spans", lambda: record)
    assert kernel_share.routed_share() == pytest.approx(0.07)
    monkeypatch.setattr(scoped, "program_spans", lambda: record[:1])
    assert kernel_share.routed_share() is None


def test_partition_puts_xla_named_kernels_down_to_their_layer():
    """By hand: two executions; a ragged dot XLA named itself counts
    under `moe.experts`, an op with no name at all under `unscoped`."""
    import kernel_share
    import tracered as tr

    dev = "/device:TPU:0"

    def op(name, start, dur, op_name):
        return {"plane": dev, "line": tr.OPS_LINE, "name": name + " = f32[8]", "start_ns": start,
                "dur_ns": dur, "op_name": op_name}

    def module(start, dur):
        return {"plane": dev, "line": tr.MODULES_LINE, "name": "jit_train_step(1)",
                "start_ns": start, "dur_ns": dur}

    scan = "jit(train_step)/jvp(M)/layer_0/mixer/euler.gdn.scan/while/body/dot_general"
    again = "jit(train_step)/transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/layer_0/mixer/euler.gdn.scan/mul"
    events = [
        module(0, 1000), module(2000, 1000),
        op("%fusion.1", 0, 300, scan), op("%ragged-dot-none.2", 300, 200, "ragged-dot-none"),
        op("%copy.3", 500, 100, None), op("%fusion.4", 600, 400, again),
        op("%fusion.1", 2000, 300, scan), op("%ragged-dot-none.2", 2300, 200, "ragged-dot-none"),
        op("%copy.3", 2500, 300, None), op("%fusion.4", 2800, 200, again),
    ]
    table, loose = kernel_share.partition(events, "jit_train_step", 1)
    assert table == {
        "gdn.scan.forward": 300.0, "moe.experts.kernel": 200.0,
        "unscoped": 200.0, "gdn.scan.backward": 300.0,  # the second forward reads as backward
    }
    assert loose == {"%copy.3": 200.0}
    assert sum(table.values()) == 1000.0  # the whole step, once
    assert kernel_share.partition(events[:2], "jit_train_step", 1) is None
