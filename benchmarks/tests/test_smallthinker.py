"""The SmallThinker cell's own arithmetic and proof at rehearsal size on
the CPU: the counts against shapes worked by hand, every catalog number
kept or listed as `reduced`, a sound run, the bf16 control and the planted
faults (half of the batch left out; the router on the experts' input;
SiLU experts; the window layers without their window; the full layer
turned by the rotary too), and the reader on a made-up scope table."""

import argparse

import pytest

import run as harness

CELL = "smallthinker-21b-a3b-ep4.train-long-tokens"
FAULTS = ["half_batch", "router_after_attention", "silu_experts", "no_window", "rotary_everywhere"]


def _config():
    return harness.load_json(f"{harness.HERE}/configs/smallthinker-21b-a3b-ep4.json")


def test_counts_by_hand():
    counts = harness.load_module("counts", "smallthinker")
    got = counts.per_step(_config())
    # q, k, v, o with no gate: ISSUE 40's 20.97 M a layer
    attention = 2560 * 3584 * 2 + 2560 * 512 * 2
    assert attention == 20_971_520
    expert = 3 * 2560 * 768
    assert (expert, 16 * expert, 64 * expert) == (5_898_240, 94_371_840, 377_487_360)
    layer = attention + 2560 * 64 + 2 * 2560 + 16 * expert
    assert layer == 115_512_320 and 4 * layer == 462_049_280
    vocabulary = 2 * 37984 * 2560
    assert vocabulary == 194_478_080
    model = 4 * layer + vocabulary + 2560
    assert model == 656_529_920  # ISSUE 40's count: 10.50 GB at 16 bytes each
    assert 10.50e9 < model * 16 < 10.51e9
    # the program's table has 38,016 rows, 32 of them never read
    assert got["parameters"] == model + (38016 - 37984) * 2560 == 656_611_840
    assert got["examples"] == 16384
    window = 4096 * 4097 // 2 + (16384 - 4096) * 4096
    causal = 16384 * 16385 // 2
    assert (got["window_pairs"], got["causal_pairs"]) == (window, causal) == (58_722_304, 134_225_920)
    pair = 2 * 2 * 128 * 28
    # a token passes 6 x 16 / 64 = 1.5 held experts, not 6
    per_token = 2 * 4 * (attention + 2560 * 64 + 1.5 * expert)
    head = 2 * 2560 * 37984
    forward = 16384 * (per_token + head) + pair * (3 * window + causal)
    assert got["flops"] == pytest.approx(3 * forward)
    assert 3 * 16384 * head == pytest.approx(9.56e12, rel=1e-2)  # ISSUE 40: the head, 9.6 TFLOP
    assert got["expected_expert_rows"] == 4 * 16384 * 1.5 == 4 * 24576
    kernels = got["kernels"]
    assert kernels["swa_core"]["flops"] == 3 * 3 * pair * window
    assert kernels["attn_core"]["flops"] == 3 * 1 * pair * causal
    # q, k, v and o, once each way
    io = 16384 * 2 * (2 * 28 * 128 + 2 * 4 * 128) * 4
    assert (kernels["swa_core"]["bytes"], kernels["attn_core"]["bytes"]) == (3 * io, io)
    assert kernels["moe_experts"]["flops_per_row"] == 3 * 2 * expert
    assert kernels["moe_experts"]["assignments"] == 4 * 98304
    assert kernels["moe_experts"]["bytes"] == 4 * 3 * 16 * expert * 4
    # the same keys `counts/trinity.py` gives, so the seven readers need no edit
    trinity = harness.load_module("counts", "trinity").kernels(
        harness.load_json(f"{harness.HERE}/configs/trinity-mini-ep8.json")
    )
    assert {k: set(v) for k, v in kernels.items()} == {k: set(v) for k, v in trinity.items()}


def test_the_parameter_count_is_the_weight_spec():
    for config in (_config(), harness.merge(_config(), _config()["rehearse"])):
        spec = harness.load_module("reference", "smallthinker").param_spec(config, {})
        total = 0
        for _, shape, _, _ in spec:
            size = 1
            for n in shape:
                size *= n
            total += size
        assert total == harness.load_module("counts", "smallthinker").parameters(config)


def test_facts_state_the_share_and_the_two_kinds_of_layer():
    import graphs

    r = harness.resolve(CELL)
    config = harness.merge(r["config"], r["config"]["rehearse"])
    built = harness.load_module("families", "smallthinker").build(
        config, r["mix"], graphs.build(config["graph"])
    )
    facts = built["facts"]
    assert (facts["window_layers"], facts["full_layers"], facts["rotary_layers"]) == (3, 1, 3)
    assert facts["query_heads_per_key_head"] == 7  # kept at rehearsal size too
    assert facts["expected_routed_share"] == 4 / 8
    assert facts["deployment_rows_per_expert"] == 2 * facts["expected_rows_per_expert"]
    # rehearsal: a window shorter than the sequence and no multiple of the block
    m = config["model"]
    assert config["sliding_window_size"] < m["seq_len"]
    assert config["sliding_window_size"] % m["attention_block"]
    assert facts["window_pairs_per_sequence"] == 24 * 25 // 2 + 40 * 24
    model = built["model"]
    kinds = [(mixer.window, mixer.rotary_dim) for mixer in map(model.mixer, range(4))]
    assert kinds == [(None, 0), (24, 16), (24, 16), (24, 16)]
    assert (model.route_on_input, model.expert_activation) == (True, "relu")
    assert (model.router_score, model.norm_topk_prob, model.num_dense_layers) == ("softmax", True, 0)
    assert (model.shared_expert_intermediate_size, model.embed_scale) == (0, 1.0)
    full = r["config"]
    m = full["model"]
    assert m["batch_size"] * m["seq_len"] * 6 / 64 == 1536  # rows an expert
    assert r["cell"]["chips"] == 1 and r["mix"]["name"] == "train-long-tokens"
    assert m["seq_len"] == full["max_position_embeddings"] == 16384
    assert full["reduced"] == ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert full["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64, "vocab_size": 151936,
    }
    assert (full["num_hidden_layers"], full["moe_num_primary_experts"]) == (4, 16)
    assert full["vocab_size"] * 4 == 151936
    # a full layer, then three window layers: one whole period, from published layer 0
    assert m["layouts_here"] == {"sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1]}
    assert m["first_published_layer"] == 0
    assert (m["layers_here"], m["router_experts"], m["experts_here"], m["vocab_here"]) == (4, 64, [0, 16], 37984)
    # a chunk's logits stay under 1 GiB
    assert m["seq_len"] // m["loss_chunks"] * full["vocab_size"] * 4 < 2**30


def test_a_stage_that_is_no_stretch_of_the_published_layouts_is_refused():
    config = harness.merge(_config(), _config()["rehearse"])
    config["model"]["layouts_here"] = {"sliding_window_layout": [1, 1, 1, 1], "rope_layout": [0, 1, 1, 1]}
    with pytest.raises(SystemExit, match="layouts_here"):
        harness.load_module("families", "smallthinker").build(config, {}, {})
    config = harness.merge(_config(), _config()["rehearse"])
    config["moe_primary_router_apply_softmax"] = False
    with pytest.raises(SystemExit, match="softmax over the kept logits"):
        harness.load_module("families", "smallthinker").build(config, {}, {})


def test_every_catalog_number_is_kept_or_listed_as_reduced():
    """The catalog row's `config`, as ISSUE 40 quotes it."""
    published = {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
        "sliding_window_size": 4096, "tie_word_embeddings": False, "vocab_size": 151936,
    }
    config = _config()
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"])
    assert {k: published[k] for k in config["reduced"]} == config["published"]
    for item in ("router_input", "router", "experts", "attention", "rotary", "window", "weight_scales"):
        assert item in config["assumed"]
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert (entry["source"], entry["reduced"]) == (config["source"], config["reduced"])


def test_the_cell_runs_are_the_configurations_own():
    """`model.run_seed`: weights, batches and sampling keys are the
    configuration's, as `keye`'s are, with the readings that forced it
    written beside it; no `--seed` moves the cell."""
    import weights

    config = _config()
    assert config["model"]["run_seed"] == 4000000240
    assert {weights.run_seed(config, seed) for seed in (0, 7, 2**31 + 5)} == {4000000240}
    said = config["assumed"]["run_seed"]
    for word in ("routed_share", "23,557.7-24,131.7", "1.12 %", "4000000240", "call_seconds"):
        assert word in said, word
    rehearsal = harness.merge(config, config["rehearse"])
    assert weights.run_seed(rehearsal, 3) == 4000000240


def _args(seed):
    return argparse.Namespace(
        workload=CELL, seed=seed, seconds=0.3, trace=0, rehearse=True, keep_trace=""
    )


def test_sound_run_is_correct():
    out = harness.run(_args(2147483711))
    assert out["correct"], out["compared"]
    assert out["metrics"] == {} and out["run"]["run_seed"] == 4000000240
    assert out["run"]["facts"]["expected_routed_share"] == 0.5


def _first_steps(seed, **kw):
    import graphs

    r = harness.resolve(CELL)
    config = harness.merge(r["config"], r["config"]["rehearse"])
    ref = harness.load_module("reference", "smallthinker")
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
    assert harness.load_module("reference", "smallthinker").FAULTS == ("", *FAULTS)


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(fault):
    table, ok = _first_steps(11, fault=fault)
    assert not ok, table


def test_bf16_control_is_not_correct():
    import jax.numpy as jnp

    table, ok = _first_steps(3, dtype=jnp.bfloat16)
    assert not ok, table


def test_the_reader_reads_the_plan_scopes_and_returns_none_on_nothing(monkeypatch):
    import kernel_share

    table = {
        "moe.route.forward": 1e6, "moe.route.backward": 3e6,
        "moe.dispatch.forward": 2e6, "moe.dispatch.backward": 5e6,
        "moe.experts.forward": 5e6, "moe.experts.backward": 9e6, "moe.combine.backward": 4e6,
        "swa.core.forward": 5e6, "attn.core.backward": 20e6, "head.backward": 6e6, "unscoped": 8e6,
    }
    monkeypatch.setattr(kernel_share, "layers", lambda run: table)
    monkeypatch.setattr(kernel_share, "notes", lambda run: {"scope_ms_per_step": {}})
    monkeypatch.setattr(kernel_share, "routed_share", lambda: 0.25)
    counts = {"kernels": {"moe_experts": {"assignments": 4000}}}
    run = {"notes": {}, "counts": counts}
    reader = harness.load_module("layer_metrics", "moe_plan_ms")
    assert reader.read(run) == 11.0  # route + dispatch, both ways; not the experts, not the combine
    assert run["notes"] == {
        "layers": {"scope_ms_per_step": {}}, "routed_share": 0.25, "routed_rows_per_step": 1000.0,
    }
    # a program that records no share (the parent's), counts that name no such kernel
    monkeypatch.setattr(kernel_share, "routed_share", lambda: None)
    run = {"notes": {}, "counts": {}}
    assert reader.read(run) == 11.0 and set(run["notes"]) == {"layers"}
    # a trace with no such scope, and one with no scope at all: None, never 0
    monkeypatch.setattr(kernel_share, "layers", lambda run: {"attn.core.forward": 1e6, "unscoped": 1e6})
    assert reader.read({"notes": {}, "counts": counts}) is None
    monkeypatch.setattr(kernel_share, "layers", lambda run: None)
    monkeypatch.setattr(kernel_share, "notes", lambda run: None)
    run = {"notes": {}, "counts": counts}
    assert reader.read(run) is None and run["notes"] == {}


def test_the_cell_reports_its_reader_and_the_accepted_cells_do_not():
    mine = [m["name"] for m in harness.resolve(CELL)["per_layer"]]
    assert mine[-1] == "moe_plan_ms"
    for metric in ("step_device_ms", "step_mfu_pct", "step_roofline_pct", "device_idle_pct", "hbm_peak_gib"):
        assert metric in mine
    # the seven readers whose lists this cell joins at the next `benchmark` issue
    waiting = {"swa_ms", "attn_ms", "moe_ms", "head_ms", "swa_core_roofline_pct",
               "attn_core_roofline_pct", "moe_experts_roofline_pct"}
    assert not waiting & set(mine)
    for cell in harness.load_benchmark()["workloads"]:
        if cell["name"] != CELL:
            assert "moe_plan_ms" not in [m["name"] for m in harness.resolve(cell["name"])["per_layer"]]


def test_the_parent_program_cannot_run_the_family(monkeypatch):
    """A program from before the model exits at the import, with a
    message, before anything is staged or compiled."""
    import euler_tpu.models.sequence_lm as lm

    monkeypatch.delattr(lm, "SmallThinkerLM")
    with pytest.raises(SystemExit, match="no model whose router reads the layer's input"):
        harness.load_module("families", "smallthinker").build({}, {}, {})
