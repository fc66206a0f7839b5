"""The LFM2 cell's own arithmetic and proof at rehearsal size on the CPU:
the counts against the table worked by hand (469,285,248 parameters; the
tree with its padded rows, of which 8,192 has none), the two kernels'
operations and bytes from the equations, every catalog number kept or
listed as `reduced`, the family found by discovery, a sound run, the bf16
control and the planted faults (half of the batch left out; the
convolution without its second gate; two taps; a softmax router; no head
norms), and the three readers on a made-up scope table."""

import argparse

import pytest

import run as harness

CELL = "lfm2-24b-a2b-ep8.train-long-tokens"
NAME = "lfm2-24b-a2b-ep8"
FAULTS = ["half_batch", "conv_no_out_gate", "conv_two_taps", "router_softmax", "no_head_norms"]
READERS = ["sconv_ms", "sconv_mix_roofline_pct", "attn_d64_core_roofline_pct"]


def _config():
    return harness.load_json(f"{harness.HERE}/configs/{NAME}.json")


def test_counts_by_hand():
    """ISSUE 42's table, line by line."""
    counts = harness.load_module("counts", "lfm2_moe")
    got = counts.per_step(_config())
    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert (conv, attention) == (16_783_360, 10_485_888)
    norms, router = 2 * 2048, 2048 * 64 + 64
    assert (norms, router) == (4_096, 131_136)
    expert = 3 * 2048 * 1536
    assert (expert, 8 * expert, 64 * expert) == (9_437_184, 75_497_472, 603_979_776)
    dense = 3 * 2048 * 11776
    assert dense == 72_351_744
    layer_1 = conv + norms + dense
    layer_2 = attention + norms + router + 8 * expert
    layer_3 = conv + norms + router + 8 * expert
    assert (layer_1, layer_2, layer_3) == (89_139_200, 86_118_592, 92_416_064)
    table = 8192 * 2048
    model = layer_1 + layer_2 + 3 * layer_3 + table + 2048
    assert model == 469_285_248  # ISSUE 42's count: 7.51 GB at 16 bytes each
    assert 7.50e9 < model * 16 < 7.52e9 and 6.98 < model * 16 / 2**30 < 7.00
    # 8,192 rows are whole 128s: the program's table pads none, and it is the head
    assert got["parameters"] == counts.parameters(_config()) == model
    assert got["examples"] == 16384
    causal = 16384 * 16385 // 2
    assert got["causal_pairs"] == causal == 134_225_920
    pair = 2 * 2 * 64 * 32
    mix = 1 + 3 + 2 + 1  # B * x~, three products, two sums, C *
    # a token passes 4 x 8 / 64 = half a held expert, not 4
    per_token = 2 * (
        4 * (2048 * 6144 + 2048 * 2048) + (attention - 128)
        + dense + 4 * (2048 * 64 + 0.5 * expert)
    ) + 4 * 2048 * mix
    head = 2 * 2048 * 8192
    forward = 16384 * (per_token + head) + pair * causal
    assert got["flops"] == pytest.approx(3 * forward)
    assert got["expected_expert_rows"] == 4 * 16384 * 0.5 == 4 * 8192
    # p, g, and Adam's sweep once each: the tied table once
    assert got["bytes"] == model * 4 * 9 + 16384 * 2048 * 4 * 2 * 7


def test_the_two_kernels_from_the_equations():
    kernels = harness.load_module("counts", "lfm2_moe").kernels(_config())
    rows = 16384 * 2048 * 4  # one [T, 2,048] float32 pass
    # forward: B, x~, C in and C * c out; backward: those three and the
    # result's cotangent in, three cotangents out; four conv layers
    assert kernels["sconv_mix"]["bytes"] == 4 * (4 + 7) * rows == 5_905_580_032
    assert kernels["sconv_mix"]["flops"] == 4 * 16384 * 2048 * 3 * 7
    # bound by memory by three orders of magnitude: 5.9 GB at 819 GB/s is 7.2 ms
    peak = harness.load_json(f"{harness.HERE}/peaks.json")["TPU v5 lite"]
    by_bytes = kernels["sconv_mix"]["bytes"] / peak["bytes_per_s"]
    assert by_bytes > 100 * kernels["sconv_mix"]["flops"] / peak["flops_per_s"]
    assert 6e-3 < by_bytes < 8e-3
    causal = 16384 * 16385 // 2
    assert kernels["attn_d64_core"]["flops"] == 3 * 2 * 2 * 64 * 32 * causal
    # q, k, v and o, once each way, at 32 / 8 heads of 64
    assert kernels["attn_d64_core"]["bytes"] == 16384 * 2 * (2 * 32 * 64 + 2 * 8 * 64) * 4
    # the accepted reader's name for the same kernel, for when its list takes the cell
    assert kernels["attn_core"] == kernels["attn_d64_core"]
    # half the products of the same pairs at trinity's head of 128
    trinity = harness.load_module("counts", "trinity").kernels(
        harness.load_json(f"{harness.HERE}/configs/trinity-mini-ep8.json")
    )
    assert 2 * kernels["attn_d64_core"]["flops"] == trinity["attn_core"]["flops"]
    assert kernels["moe_experts"]["flops_per_row"] == 3 * 2 * 3 * 2048 * 1536
    assert kernels["moe_experts"]["assignments"] == 4 * 16384 * 4
    assert kernels["moe_experts"]["bytes"] == 4 * 3 * 8 * 3 * 2048 * 1536 * 4
    assert set(kernels["moe_experts"]) == set(trinity["moe_experts"])


def test_the_parameter_count_is_the_weight_spec():
    for config in (_config(), harness.merge(_config(), _config()["rehearse"])):
        spec = harness.load_module("reference", "lfm2_moe").param_spec(config, {})
        assert not [path for path, *_ in spec if path.endswith("/head")]
        total = 0
        for _, shape, _, _ in spec:
            size = 1
            for n in shape:
                size *= n
            total += size
        assert total == harness.load_module("counts", "lfm2_moe").parameters(config)


def test_the_family_is_found_by_discovery_and_states_its_share():
    import graphs

    r = harness.resolve(CELL)
    assert r["config"]["family"] == "lfm2_moe"
    family = harness.load_module("families", r["config"]["family"])
    assert (family.REFERENCE, family.COUNTS) == ("lfm2_moe", "lfm2_moe")
    config = harness.merge(r["config"], r["config"]["rehearse"])
    built = family.build(config, r["mix"], graphs.build(config["graph"]))
    facts = built["facts"]
    assert (facts["conv_layers"], facts["full_layers"], facts["dense_layers"]) == (4, 1, 1)
    assert facts["head_dim"] == 64  # the width that is the point, kept at rehearsal size too
    assert facts["expected_routed_share"] == 4 / 8
    assert facts["deployment_rows_per_expert"] == 2 * facts["expected_rows_per_expert"]
    model = built["model"]
    assert (model.head_dim, model.tie_embeddings, model.conv_L_cache) == (64, True, 3)
    assert (model.router_score, model.router_norm_eps, model.route_scale) == ("sigmoid", 1e-6, 1.0)
    assert (model.shared_expert_intermediate_size, model.num_dense_layers) == (0, 1)
    assert (model.sandwich_norms, model.embed_scale, model.rms_norm_eps) == (False, 1.0, 1e-5)
    full = r["config"]
    m = full["model"]
    assert m["batch_size"] * m["seq_len"] * 4 / 64 == 1024  # rows an expert
    assert r["cell"]["chips"] == 1 and r["mix"]["name"] == "train-long-tokens"
    assert (m["seq_len"], m["doc_len"], m["attention_block"], m["loss_chunks"]) == (16384, 4096, 512, 4)
    # the second dense layer, then one whole period, from published layer 1
    assert m["layer_types_here"] == ["conv", "full_attention", "conv", "conv", "conv"]
    assert m["layer_types_here"] == full["layer_types"][1:6] and m["first_published_layer"] == 1
    assert (m["layers_here"], m["router_experts"], m["experts_here"], m["vocab_here"]) == (5, 64, [0, 8], 8192)
    assert full["graph"]["graph_seed"] == harness.load_json(
        f"{harness.HERE}/configs/trinity-mini-ep8.json"
    )["graph"]["graph_seed"]
    # a chunk's logits stay under 1 GiB
    assert m["seq_len"] // m["loss_chunks"] * full["vocab_size"] * 4 < 2**30


def test_a_stage_that_is_no_stretch_of_the_published_layer_types_is_refused():
    config = harness.merge(_config(), _config()["rehearse"])
    config["model"]["layer_types_here"] = ["conv"] * 5
    with pytest.raises(SystemExit, match="layer_types_here"):
        harness.load_module("families", "lfm2_moe").build(config, {}, {})
    config = harness.merge(_config(), _config()["rehearse"])
    config["conv_bias"] = True
    with pytest.raises(SystemExit, match="no bias"):
        harness.load_module("families", "lfm2_moe").build(config, {}, {})


def test_every_catalog_number_is_kept_or_listed_as_reduced():
    """The catalog row's `config`, as ISSUE 42 quotes it."""
    period = ["full_attention", "conv", "conv", "conv"]
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
        "layer_types": ["conv", "conv"] + period * 9 + ["full_attention", "conv"],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
        "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    }
    assert len(published["layer_types"]) == 40
    assert published["layer_types"].count("conv") == 30
    config = _config()
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"])
    assert {k: published[k] for k in config["reduced"]} == config["published"]
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (5, 1)
    assert (config["num_experts"], config["vocab_size"] * 8) == (8, 65536)
    for item in (
        "tie", "head_dim", "intermediate_size", "conv", "attention", "router", "expert_bias",
        "packing", "optimizer", "router_precision", "weights", "weight_scales", "run_seed",
    ):
        assert item in config["assumed"], item
    assert config["assumed"]["weight_scales"] == {"matrix": 0.02, "embedding": 0.02, "conv": 0.3333}
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert (entry["source"], entry["reduced"]) == (config["source"], config["reduced"])
    assert bench["configs"][-1] == entry and bench["workloads"][-1]["name"] == CELL
    # the form's limit on a `why`, which no other test holds
    assert len(entry["why"]) <= 200 and len(bench["workloads"][-1]["why"]) <= 200


def test_the_cell_runs_are_the_configurations_own():
    """`model.run_seed`: weights, batches and sampling keys are the
    configuration's, as `keye`'s and `smallthinker`'s are, with the
    readings that forced it written beside it; no `--seed` moves the
    cell."""
    import weights

    config = _config()
    assert config["model"]["run_seed"] == 4200000542
    assert {weights.run_seed(config, seed) for seed in (0, 7, 2**31 + 5)} == {4200000542}
    said = config["assumed"]["run_seed"]
    for word in ("routed_share", "35,068.8", "35,645.9", "1.35 %", "4200000542", "call_seconds"):
        assert word in said, word
    rehearsal = harness.merge(config, config["rehearse"])
    assert weights.run_seed(rehearsal, 3) == 4200000542


def _args(seed):
    return argparse.Namespace(
        workload=CELL, seed=seed, seconds=0.3, trace=0, rehearse=True, keep_trace=""
    )


def test_sound_run_is_correct():
    import weights

    out = harness.run(_args(2147483713))
    assert out["correct"], out["compared"]
    assert out["metrics"] == {}
    assert out["run"]["run_seed"] == weights.run_seed(_config(), 2147483713) == 4200000542
    assert out["run"]["facts"]["expected_routed_share"] == 0.5


def _first_steps(seed, **kw):
    import graphs

    r = harness.resolve(CELL)
    config = harness.merge(r["config"], r["config"]["rehearse"])
    ref = harness.load_module("reference", "lfm2_moe")
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
    assert harness.load_module("reference", "lfm2_moe").FAULTS == ("", *FAULTS)


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(fault):
    table, ok = _first_steps(11, fault=fault)
    assert not ok, table


def test_bf16_control_is_not_correct():
    import jax.numpy as jnp

    table, ok = _first_steps(3, dtype=jnp.bfloat16)
    assert not ok, table


def test_the_readers_read_their_scopes_and_return_none_on_nothing(monkeypatch):
    import kernel_share

    table = {
        "sconv.proj.forward": 4e6, "sconv.proj.backward": 12e6,
        "sconv.mix.forward": 2e6, "sconv.mix.backward": 6e6,
        "sconv.out.forward": 1e6, "sconv.out.backward": 4e6,
        "attn.core.forward": 10e6, "attn.core.backward": 30e6, "attn.proj.backward": 9e6,
        "moe.experts.backward": 9e6, "head.backward": 6e6, "unscoped": 8e6,
    }
    monkeypatch.setattr(kernel_share, "layers", lambda run: table)
    monkeypatch.setattr(kernel_share, "notes", lambda run: {"scope_ms_per_step": {}})
    monkeypatch.setattr(kernel_share, "routed_share", lambda: 0.125)
    counts = harness.load_module("counts", "lfm2_moe").per_step(_config())
    peak = harness.load_json(f"{harness.HERE}/peaks.json")["TPU v5 lite"]
    run = {"notes": {}, "counts": counts, "peak": peak}
    sconv, mix, core = (harness.load_module("layer_metrics", name) for name in READERS)
    assert sconv.read(run) == 29.0  # the three scopes, both ways; nothing of another layer
    assert run["notes"]["layers"] == {"scope_ms_per_step": {}}
    assert run["notes"]["routed_share"] == 0.125
    assert run["notes"]["routed_rows_per_step"] == 0.125 * 4 * 16384 * 4
    kernels = counts["kernels"]
    want = 100 * (kernels["sconv_mix"]["bytes"] / peak["bytes_per_s"]) / 8e-3
    assert mix.read(run) == pytest.approx(want) and 0 < want < 100
    assert run["notes"]["sconv.mix_roofline_bound"] == "memory"
    want = 100 * (kernels["attn_d64_core"]["flops"] / peak["flops_per_s"]) / 40e-3
    assert core.read(run) == pytest.approx(want) and 0 < want < 100
    assert run["notes"]["attn.core_roofline_bound"] == "compute"
    # counts that name no such kernel (another family's): None, and no note
    other = {"notes": {}, "counts": {"kernels": {"attn_core": {}}}, "peak": peak}
    assert mix.read(other) is None and core.read(other) is None and other["notes"] == {}
    # a trace with no such scope, and one with no scope at all: None, never 0
    monkeypatch.setattr(kernel_share, "layers", lambda run: {"swa.core.forward": 1e6, "unscoped": 1e6})
    for reader in (sconv, mix, core):
        assert reader.read({"notes": {}, "counts": counts, "peak": peak}) is None
    monkeypatch.setattr(kernel_share, "layers", lambda run: None)
    monkeypatch.setattr(kernel_share, "notes", lambda run: None)
    monkeypatch.setattr(kernel_share, "routed_share", lambda: None)
    run = {"notes": {}, "counts": counts, "peak": peak}
    assert [reader.read(run) for reader in (sconv, mix, core)] == [None] * 3 and run["notes"] == {}


def test_the_cell_reports_its_three_readers_and_no_other_cell_does():
    mine = [m["name"] for m in harness.resolve(CELL)["per_layer"]]
    assert set(READERS) <= set(mine)
    for metric in ("step_device_ms", "step_mfu_pct", "step_roofline_pct", "device_idle_pct", "hbm_peak_gib"):
        assert metric in mine
    # the readers whose lists this cell joins at the next `benchmark` issue
    waiting = {"attn_ms", "moe_ms", "head_ms", "dense_mlp_ms", "attn_core_roofline_pct",
               "moe_experts_roofline_pct"}
    assert not waiting & set(mine)
    bench = harness.load_benchmark()
    for entry in bench["per_layer"]:
        if entry["name"] in READERS:
            assert entry["workloads"] == [CELL] and entry["moves"] == "examples_per_s"
    for cell in bench["workloads"]:
        if cell["name"] != CELL:
            assert not set(READERS) & {m["name"] for m in harness.resolve(cell["name"])["per_layer"]}


def test_the_parent_program_cannot_run_the_family(monkeypatch):
    """A program from before the model exits at the import, with a
    message, before anything is staged or compiled."""
    import euler_tpu.models.sequence_lm as lm

    monkeypatch.delattr(lm, "Lfm2MoeLM")
    with pytest.raises(SystemExit, match="no short-convolution mixer and no tied head"):
        harness.load_module("families", "lfm2_moe").build({}, {}, {})
