"""The Granite-4.0-H-Micro cell's own arithmetic and proof at rehearsal
size on the CPU: the counts against the table worked by hand
(772,160,448 parameters; the tree, whose 12,544 rows need no padding),
the scan's operations and bytes from the equations, every catalog number
kept or listed as `reduced`, the family found by discovery, a sound run,
the bf16 control and the planted faults (half of the batch left out; no
skip D x; the norm without its gate; no residual multiplier; the
convolution without its bias; the scores at 64^-0.5), and the two
readers on a made-up scope table, in this cell alone."""

import argparse

import pytest

import run as harness

CELL = "granite-4.0-h-micro-vp8.train-long-tokens"
NAME = "granite-4.0-h-micro-vp8"
FAULTS = [
    "half_batch", "no_D_skip", "ungated_norm", "no_residual_multiplier", "conv_no_bias",
    "attn_sqrt_scale",
]
READERS = ["ssd_ms", "ssd_scan_roofline_pct"]


def _config():
    return harness.load_json(f"{harness.HERE}/configs/{NAME}.json")


def test_counts_by_hand():
    """The parameter table of the configuration's file, line by line."""
    counts = harness.load_module("counts", "granite_hybrid")
    got = counts.per_step(_config())
    in_proj, conv, heads, norm, out_proj = 2048 * 8512, 4352 * 4 + 4352, 3 * 64, 4096, 4096 * 2048
    assert (in_proj, conv, out_proj) == (17_432_576, 21_760, 8_388_608)
    mixer = in_proj + conv + heads + norm + out_proj
    mlp = 2048 * 16384 + 8192 * 2048
    assert (mixer, mlp) == (25_847_232, 50_331_648)
    mamba = mixer + mlp + 2 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + mlp + 2 * 2048
    assert (mamba, attention) == (76_182_976, 60_821_504)
    period = 9 * mamba + attention
    assert period == 746_468_288
    table = 12544 * 2048
    model = period + table + 2048
    assert (table, model) == (25_690_112, 772_160_448)
    assert 12.35e9 < model * 16 < 12.36e9 and 11.50 < model * 16 / 2**30 < 11.51
    assert got["parameters"] == counts.parameters(_config()) == model
    assert got["examples"] == 16384 and got["ssd_chunks"] == 64
    causal = 16384 * 16385 // 2
    assert got["causal_pairs"] == causal
    pair = 2 * 2 * 64 * 32
    chunk = 2 * 256 * 256 * 128 + 64 * (2 * 256 * 256 * 64 + 4 * 256 * 64 * 128)
    per_token = 2 * (
        9 * (in_proj + out_proj) + (attention - mlp - 4096) + 10 * 3 * 2048 * 8192
    ) + 9 * 2 * 4 * 4352
    head = 2 * 2048 * 12544
    forward = 16384 * (per_token + head) + pair * causal + 9 * 64 * chunk
    assert got["flops"] == 3 * forward
    # about 100 TFLOP a step once the rematerialised forward is added
    assert 70e12 < got["flops"] < 4 / 3 * 100e12


def test_the_scan_kernel_from_the_equations():
    """C B^T once a chunk for all 64 heads, each head's three products,
    three times over; u, a, B, C in and y out, the same and y's cotangent
    in and four cotangents out."""
    kernels = harness.load_module("counts", "granite_hybrid").kernels(_config())
    scan = kernels["ssd_scan"]
    per_chunk = 2 * 256**2 * 128 + 64 * (2 * 256**2 * 64 + 2 * 256 * 64 * 128 * 2)
    assert scan["flops"] == 9 * 3 * 64 * per_chunk
    io = 16384 * (4096 + 64 + 256) * 4
    assert scan["bytes"] == 9 * (3 * io + 2 * 16384 * 4096 * 4)
    # the shared C B^T is a sixty-fourth of what it would be once a head
    assert 2 * 256**2 * 128 < 0.02 * 64 * 2 * 256**2 * 64 * 2
    core = kernels["attn_d64_core"]
    assert core == kernels["attn_core"] and core["flops"] == 3 * 2 * 2 * 64 * 32 * 16384 * 16385 // 2


def test_the_parameter_count_is_the_weight_spec():
    r = harness.resolve(CELL)
    for config in (r["config"], harness.merge(r["config"], r["config"]["rehearse"])):
        spec = harness.load_module("reference", "granite_hybrid").param_spec(config, None)
        total = 0
        for _, shape, _, _ in spec:
            size = 1
            for n in shape:
                size *= n
            total += size
        assert total == harness.load_module("counts", "granite_hybrid").parameters(config)


def test_the_family_is_found_by_discovery_and_states_its_stage():
    import graphs

    r = harness.resolve(CELL)
    assert r["config"]["family"] == "granite_hybrid"
    family = harness.load_module("families", r["config"]["family"])
    assert (family.REFERENCE, family.COUNTS) == ("granite_hybrid", "granite_hybrid")
    config = harness.merge(r["config"], r["config"]["rehearse"])
    built = family.build(config, r["mix"], graphs.build(config["graph"]))
    facts = built["facts"]
    assert (facts["mamba_layers"], facts["attention_layers"], facts["ssd_chunks_per_sequence"]) == (9, 1, 4)
    model = built["model"]
    assert (model.embed_scale, model.residual_multiplier, model.logits_divisor) == (12.0, 0.22, 8.0)
    assert (model.attention_multiplier, model.tie_embeddings, model.rms_norm_eps) == (0.015625, True, 1e-5)
    full = r["config"]
    m = full["model"]
    assert r["cell"]["chips"] == 1 and r["mix"]["name"] == "train-long-tokens"
    assert (m["seq_len"], m["doc_len"], m["attention_block"], m["loss_chunks"]) == (16384, 4096, 512, 4)
    # one whole period from published layer 0: five mamba, attention, four mamba
    assert m["layer_types_here"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert m["layer_types_here"] == full["layer_types"][0:10] and m["first_published_layer"] == 0
    assert (m["layers_here"], m["vocab_here"], m["head_dim"]) == (10, 12544, 64)
    assert "run_seed" not in m  # free seeds: no router's draw moves a dense step
    assert m["seq_len"] // m["loss_chunks"] * full["vocab_size"] * 4 < 2**30


def test_a_stage_or_a_mixer_the_family_cannot_run_is_refused():
    family = harness.load_module("families", "granite_hybrid")
    config = harness.merge(_config(), _config()["rehearse"])
    config["model"]["layer_types_here"] = ["mamba"] * 10
    with pytest.raises(SystemExit, match="layer_types_here"):
        family.build(config, {}, {})
    for key, value in (("mamba_n_groups", 2), ("mamba_conv_bias", False), ("position_embedding_type", "rope")):
        config = harness.merge(_config(), _config()["rehearse"])
        config[key] = value
        with pytest.raises(SystemExit, match="one group"):
            family.build(config, {}, {})


def test_every_catalog_number_is_kept_or_listed_as_reduced():
    """The catalog row's `config`, as published."""
    kinds = ["attention" if i in (5, 15, 25, 35) else "mamba" for i in range(40)]
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192, "layer_types": kinds,
        "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
        "num_attention_heads": 32, "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352,
    }
    config = _config()
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert {k: published[k] for k in config["reduced"]} == config["published"]
    assert (config["num_hidden_layers"], config["vocab_size"] * 8) == (10, 100352)
    assert config["vocab_size"] % 128 == 0
    for item in (
        "weights", "weight_scales", "head_dim", "mamba", "attention", "mlp", "multipliers",
        "packing", "optimizer", "stage", "run_seed",
    ):
        assert item in config["assumed"], item
    assert config["assumed"]["weight_scales"] == {"matrix": 0.02, "embedding": 0.02, "conv": 0.2887}
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert (entry["source"], entry["reduced"]) == (config["source"], config["reduced"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200


def _args(seed):
    return argparse.Namespace(
        workload=CELL, seed=seed, seconds=0.3, trace=0, rehearse=True, keep_trace=""
    )


def test_sound_run_is_correct():
    out = harness.run(_args(2147483713))
    assert out["correct"], out["compared"]
    assert out["metrics"] == {}
    assert out["run"]["run_seed"] == 2147483713  # the run's own seed: nothing fixes it
    assert out["run"]["facts"]["mamba_layers"] == 9


def _first_steps(seed, **kw):
    import graphs

    r = harness.resolve(CELL)
    config = harness.merge(r["config"], r["config"]["rehearse"])
    ref = harness.load_module("reference", "granite_hybrid")
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
    assert harness.load_module("reference", "granite_hybrid").FAULTS == ("", *FAULTS)


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
        "ssd.proj.forward": 4e6, "ssd.proj.backward": 16e6,
        "ssd.conv.forward": 2e6, "ssd.conv.backward": 9e6,
        "ssd.scan.forward": 7e6, "ssd.scan.backward": 15e6,
        "ssd.out.forward": 6e6, "ssd.out.backward": 15e6,
        "attn.core.forward": 10e6, "attn.core.backward": 30e6, "mlp.backward": 50e6,
        "head.backward": 6e6, "unscoped": 8e6,
    }
    monkeypatch.setattr(kernel_share, "layers", lambda run: table)
    monkeypatch.setattr(kernel_share, "notes", lambda run: {"scope_ms_per_step": {}})
    counts = harness.load_module("counts", "granite_hybrid").per_step(_config())
    peak = harness.load_json(f"{harness.HERE}/peaks.json")["TPU v5 lite"]
    run = {"notes": {}, "counts": counts, "peak": peak}
    ssd, scan = (harness.load_module("layer_metrics", name) for name in READERS)
    assert ssd.read(run) == 74.0  # the four scopes, both ways; nothing of another layer
    assert run["notes"]["layers"] == {"scope_ms_per_step": {}}
    kernel = counts["kernels"]["ssd_scan"]
    want = 100 * (kernel["bytes"] / peak["bytes_per_s"]) / 22e-3
    assert scan.read(run) == pytest.approx(want) and 0 < want < 100
    assert run["notes"]["ssd.scan_roofline_bound"] == "memory"
    # counts that name no such kernel (another family's): None, and no note
    other = {"notes": {}, "counts": {"kernels": {"attn_core": {}}}, "peak": peak}
    assert scan.read(other) is None and other["notes"] == {}
    # a trace with no such scope, and one with no scope at all: None, never 0
    monkeypatch.setattr(kernel_share, "layers", lambda run: {"gdn.scan.forward": 1e6, "unscoped": 1e6})
    for reader in (ssd, scan):
        assert reader.read({"notes": {}, "counts": counts, "peak": peak}) is None
    monkeypatch.setattr(kernel_share, "layers", lambda run: None)
    monkeypatch.setattr(kernel_share, "notes", lambda run: None)
    run = {"notes": {}, "counts": counts, "peak": peak}
    assert [reader.read(run) for reader in (ssd, scan)] == [None] * 2 and run["notes"] == {}


def test_the_cell_reports_its_two_readers_and_no_other_cell_does():
    mine = [m["name"] for m in harness.resolve(CELL)["per_layer"]]
    assert set(READERS) <= set(mine)
    for metric in ("step_device_ms", "step_mfu_pct", "step_roofline_pct", "device_idle_pct", "hbm_peak_gib"):
        assert metric in mine
    # the readers whose lists this cell joins at the next `benchmark` issue
    waiting = {"attn_d64_core_roofline_pct", "dense_mlp_ms", "head_ms", "moe_ms", "sconv_ms"}
    assert not waiting & set(mine)
    bench = harness.load_benchmark()
    for entry in bench["per_layer"]:
        if entry["name"] in READERS:
            assert entry["workloads"] == [CELL] and entry["moves"] == "examples_per_s"
            assert entry["layer"] in ("model step", "kernels")
    for cell in bench["workloads"]:
        if cell["name"] != CELL:
            assert not set(READERS) & {m["name"] for m in harness.resolve(cell["name"])["per_layer"]}


def test_the_parent_program_cannot_run_the_family(monkeypatch):
    """A program from before the model exits at the import, with a
    message, before anything is staged or compiled."""
    import euler_tpu.models.sequence_lm as lm

    monkeypatch.delattr(lm, "GraniteHybridLM")
    with pytest.raises(SystemExit, match="no state-space mixer"):
        harness.load_module("families", "granite_hybrid").build({}, {}, {})
