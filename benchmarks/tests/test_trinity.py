"""The Trinity-Mini cell's own arithmetic and proof at rehearsal size on
the CPU: the counts against shapes worked by hand, every catalog number
kept or listed as `reduced`, a sound run, the bf16 control and the planted
faults (half of the batch left out; the window layers without their
window; the full layers turned by the rotary too; a softmax router), and
the readers on a made-up scope table."""

import argparse

import pytest

import run as harness

CELL = "trinity-mini-ep8.train-long-tokens"
LOCAL, FULL = "sliding_attention", "full_attention"


def _config():
    return harness.load_json(f"{harness.HERE}/configs/trinity-mini-ep8.json")


def test_counts_by_hand():
    counts = harness.load_module("counts", "trinity")
    got = counts.per_step(_config())
    # q with its gate, k, v, o: ISSUE 34's 27.26 M a layer
    attention = 2048 * (2 * 32 * 128 + 2 * 4 * 128) + 32 * 128 * 2048
    assert attention == 27_262_976
    dense = 3 * 2048 * 6144
    expert = 3 * 2048 * 1024
    moe = 2048 * 128 + 128 + 16 * expert + expert  # router, bias, 16 held, 1 shared
    norms = 4 * 2048 + 2 * 128
    assert attention + dense == 65_011_712  # the dense layer: 65.02 M
    assert attention + moe == 134_480_000  # an expert layer: 134.49 M, 100.66 M in the 16
    assert 16 * expert == 100_663_296
    vocabulary = 25088 * 2048 + 2048 * 25024
    assert vocabulary == 102_629_376  # 102.63 M
    params = 5 * (attention + norms) + dense + 4 * moe + vocabulary + 2048
    assert got["parameters"] == params == 705_605_376
    assert 11.28e9 < params * 16 < 11.30e9  # 11.29 GB of state, 10.51 GiB
    assert got["examples"] == 16384
    window = 2048 * 2049 // 2 + (16384 - 2048) * 2048
    causal = 16384 * 16385 // 2
    assert (got["window_pairs"], got["causal_pairs"]) == (window, causal) == (31_458_304, 134_225_920)
    pair = 2 * 2 * 128 * 32
    # a token passes 8 x 16 / 128 = 1 held expert, not 8, and the shared one
    per_token = 2 * (5 * attention + dense + 4 * (2048 * 128 + expert + expert))
    forward = 16384 * (per_token + 2 * 2048 * 25024) + pair * (4 * window + causal)
    assert got["flops"] == pytest.approx(3 * forward)
    assert 13.32e12 < forward < 13.34e12 and 39.9e12 < got["flops"] < 40.05e12  # ISSUE 34: 39.98
    assert got["expected_expert_rows"] == 4 * 16384 * 1.0
    kernels = got["kernels"]
    assert kernels["swa_core"]["flops"] == 3 * 4 * pair * window
    assert kernels["attn_core"]["flops"] == 3 * 1 * pair * causal
    # q, k, v and o, once each way
    io = 16384 * 2 * (2 * 32 * 128 + 2 * 4 * 128) * 4
    assert (kernels["swa_core"]["bytes"], kernels["attn_core"]["bytes"]) == (4 * io, io)
    assert kernels["moe_experts"]["flops_per_row"] == 3 * 2 * expert
    assert kernels["moe_experts"]["assignments"] == 4 * 131072


def test_the_parameter_count_is_the_weight_spec():
    for config in (_config(), harness.merge(_config(), _config()["rehearse"])):
        spec = harness.load_module("reference", "trinity").param_spec(config, {})
        total = 0
        for _, shape, _, _ in spec:
            size = 1
            for n in shape:
                size *= n
            total += size
        assert total == harness.load_module("counts", "trinity").parameters(config)


def test_facts_state_the_share_and_the_two_kinds_of_layer():
    import graphs

    r = harness.resolve(CELL)
    config = harness.merge(r["config"], r["config"]["rehearse"])
    built = harness.load_module("families", "afmoe").build(
        config, r["mix"], graphs.build(config["graph"])
    )
    facts = built["facts"]
    assert (facts["window_layers"], facts["full_layers"], facts["dense_layers"]) == (2, 1, 1)
    assert facts["expected_routed_share"] == 4 / 16
    assert facts["deployment_rows_per_expert"] == 4 * facts["expected_rows_per_expert"]
    # rehearsal: a window shorter than the sequence and no multiple of the block
    m = config["model"]
    assert config["sliding_window"] < m["seq_len"] and config["sliding_window"] % m["attention_block"]
    assert facts["window_pairs_per_sequence"] == 24 * 25 // 2 + 40 * 24
    model = built["model"]
    kinds = [(mixer.window, mixer.rotary_dim) for mixer in map(model.mixer, range(3))]
    assert kinds == [(24, 16), (24, 16), (None, 0)]
    full = r["config"]
    m = full["model"]
    assert m["batch_size"] * m["seq_len"] * 8 / 128 == 1024  # rows an expert
    assert full["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert full["published"] == {
        "num_hidden_layers": 32, "num_dense_layers": 2, "num_experts": 128, "vocab_size": 200192,
    }
    assert (full["num_hidden_layers"], full["num_dense_layers"], full["num_experts"]) == (5, 1, 16)
    assert full["vocab_size"] * 8 == 200192
    # one leading dense layer (window), then window, full, window, window: a whole period
    assert m["layer_types_here"] == [LOCAL, LOCAL, FULL, LOCAL, LOCAL]
    assert m["layer_types_here"] == full["layer_types"][1:6] and m["first_published_layer"] == 1
    assert (m["layers_here"], m["router_experts"], m["experts_here"], m["vocab_here"]) == (5, 128, [0, 16], 25024)


def test_a_stage_that_is_no_stretch_of_the_published_layers_is_refused():
    config = harness.merge(_config(), _config()["rehearse"])
    config["model"]["layer_types_here"] = [FULL, FULL, FULL]
    with pytest.raises(SystemExit, match="layer_types_here"):
        harness.load_module("families", "afmoe").build(config, {}, {})


def test_every_catalog_number_is_kept_or_listed_as_reduced():
    """The catalog row's `config`, as ISSUE 34 quotes it."""
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "layer_types": ([LOCAL] * 3 + [FULL]) * 8,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
    }
    config = _config()
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"])
    assert {k: published[k] for k in config["reduced"]} == config["published"]
    for item in ("output_gate", "head_norms", "rotary", "sandwich_norms", "load_balance", "weight_scales"):
        assert item in config["assumed"]


def _args(seed):
    return argparse.Namespace(
        workload=CELL, seed=seed, seconds=0.3, trace=0, rehearse=True, keep_trace=""
    )


def test_sound_run_is_correct():
    out = harness.run(_args(2147483711))
    assert out["correct"], out["compared"]
    assert out["metrics"] == {}
    assert out["run"]["facts"]["expected_routed_share"] == 0.25


def _first_steps(seed, **kw):
    import graphs

    r = harness.resolve(CELL)
    config = harness.merge(r["config"], r["config"]["rehearse"])
    ref = harness.load_module("reference", "trinity")
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


def test_the_reference_names_its_faults():
    assert harness.load_module("reference", "trinity").FAULTS == (
        "", "half_batch", "no_window", "rotary_everywhere", "softmax_router"
    )


@pytest.mark.parametrize("fault", ["half_batch", "no_window", "rotary_everywhere", "softmax_router"])
def test_planted_fault_is_not_correct(fault):
    table, ok = _first_steps(11, fault=fault)
    assert not ok, table


def test_bf16_control_is_not_correct():
    import jax.numpy as jnp

    table, ok = _first_steps(3, dtype=jnp.bfloat16)
    assert not ok, table


def test_readers_read_the_swa_scopes_and_return_none_on_nothing(monkeypatch):
    import kernel_share

    table = {
        "swa.proj.forward": 1e6, "swa.proj.backward": 3e6,
        "swa.core.forward": 5e6, "swa.core.backward": 15e6,
        "swa.out.forward": 1e6, "swa.out.backward": 2e6,
        "attn.core.forward": 7e6, "attn.core.backward": 20e6,
        "mlp.forward": 2e6, "mlp.backward": 5e6,
        "moe.experts.forward": 5e6, "head.backward": 6e6, "unscoped": 8e6,
    }
    monkeypatch.setattr(kernel_share, "layers", lambda run: table)
    monkeypatch.setattr(kernel_share, "notes", lambda run: {"scope_ms_per_step": {}})
    counts = {"kernels": {"swa_core": {"flops": 1e9, "bytes": 1e7}}}  # 10 ms at the peak, by its bytes
    run = {"peak": {"flops_per_s": 1e12, "bytes_per_s": 1e9}, "notes": {}, "counts": counts}

    def read(name, run):
        return harness.load_module("layer_metrics", name).read(run)

    assert read("swa_ms", run) == 27.0  # the full layer's attn.* is not the window layers'
    assert run["notes"]["layers"] == {"scope_ms_per_step": {}}
    assert read("dense_mlp_ms", run) == 7.0
    assert read("swa_core_roofline_pct", run) == pytest.approx(100 * 10 / 20)
    assert run["notes"]["swa.core_roofline_bound"] == "memory"
    # a program whose counts name no such kernel: None, never 0
    assert read("swa_core_roofline_pct", dict(run, counts={}, notes={})) is None
    # a trace with no such scope (the parent's program, another cell's)
    monkeypatch.setattr(kernel_share, "layers", lambda run: {"attn.core.forward": 1e6, "moe.shared.forward": 1e6, "unscoped": 1e6})
    for name in ("swa_ms", "swa_core_roofline_pct", "dense_mlp_ms"):
        assert read(name, dict(run, notes={})) is None
    monkeypatch.setattr(kernel_share, "layers", lambda run: None)
    monkeypatch.setattr(kernel_share, "notes", lambda run: None)
    for name in ("swa_ms", "swa_core_roofline_pct", "dense_mlp_ms"):
        assert read(name, dict(run, notes={})) is None


def test_the_cell_reports_its_three_readers_and_the_accepted_cells_do_not():
    mine = [m["name"] for m in harness.resolve(CELL)["per_layer"]]
    assert mine[-3:] == ["swa_ms", "swa_core_roofline_pct", "dense_mlp_ms"]
    for metric in ("step_device_ms", "step_mfu_pct", "step_roofline_pct", "device_idle_pct", "hbm_peak_gib"):
        assert metric in mine
    other = [m["name"] for m in harness.resolve("keye-vl2-30b-a3b-ep8.train-long-tokens")["per_layer"]]
    assert not {"swa_ms", "swa_core_roofline_pct", "dense_mlp_ms"} & set(other)


def test_the_parent_program_cannot_run_the_family(monkeypatch):
    """A program from before the model exits at the import, with a
    message, before anything is staged or compiled."""
    import euler_tpu.models.sequence_lm as lm

    monkeypatch.delattr(lm, "TrinityLM")
    with pytest.raises(SystemExit, match="no window / full attention model"):
        harness.load_module("families", "afmoe").build({}, {}, {})
