"""The Keye-VL-2.0 cell's own arithmetic and proof at rehearsal size on
the CPU: the counts against shapes worked by hand, a sound run, the bf16
control and the planted faults (half of the batch left out; the selection
that ignores the indexer; the indexer's loss left out), and the readers
on a made-up scope table."""

import argparse

import pytest

import run as harness

CELL = "keye-vl2-30b-a3b-ep8.train-long-tokens"


def _config():
    return harness.load_json(f"{harness.HERE}/configs/keye-vl2-30b-a3b-ep8.json")


def test_counts_by_hand():
    counts = harness.load_module("counts", "keye_vl2")
    got = counts.per_step(_config())
    attention = 2048 * (32 * 128 + 2 * 4 * 128) + 32 * 128 * 2048
    indexer = 2048 * (16 * 64 + 64 + 16)
    expert = 3 * 2048 * 768
    layer = (attention + 2 * 128) + (indexer + 2 * 64) + 2048 * 128 + 16 * expert + 2 * 2048
    assert layer == 96_899_456  # ISSUE 32's count, term by term
    params = 4 * layer + 19072 * 2048 + 2048 * 18992 + 2048
    assert got["parameters"] == params == 465_554_944
    assert 7.44e9 < params * 16 < 7.46e9  # 7.45 GB of state
    assert got["examples"] == 16384
    causal = 16384 * 16385 // 2
    selected = 2048 * 2049 // 2 + (16384 - 2048) * 2048
    assert (got["causal_pairs"], got["selected_pairs"]) == (causal, selected) == (134_225_920, 31_458_304)
    index = 2 * 16 * 64 * causal
    core = 2 * 2 * 128 * 32 * selected
    # a token passes 8 x 16 / 128 = 1 held expert, not 8
    per_token = 2 * (attention + indexer + 2048 * 128 + 1.0 * expert)
    forward = 4 * (16384 * per_token + index + core) + 16384 * 2 * 2048 * 18992
    assert got["flops"] == pytest.approx(3 * forward)
    assert 23.5e12 < got["flops"] < 23.7e12  # ISSUE 32: 23.6 TFLOP a step
    assert got["expected_expert_rows"] == 4 * 16384 * 1.0
    kernels = got["kernels"]
    assert kernels["dsa_index"]["flops"] == 3 * 4 * index
    assert kernels["dsa_core"]["flops"] == 3 * 4 * core
    # q^I, k^I and w read forward and backward, the picked indices written
    assert kernels["dsa_index"]["bytes"] == 4 * (16384 * 2 * (16 * 64 + 64 + 16) * 4 + selected * 4)
    # q, k, v and o, once each way
    assert kernels["dsa_core"]["bytes"] == 4 * 16384 * 2 * (2 * 32 * 128 + 2 * 4 * 128) * 4
    assert kernels["moe_experts"]["flops_per_row"] == 3 * 2 * expert
    assert kernels["moe_experts"]["assignments"] == 4 * 131072


def test_facts_state_the_share_and_the_selection():
    import graphs

    r = harness.resolve(CELL)
    config = harness.merge(r["config"], r["config"]["rehearse"])
    built = harness.load_module("families", "keye_vl2").build(
        config, r["mix"], graphs.build(config["graph"])
    )
    facts = built["facts"]
    assert facts["expected_routed_share"] == 4 / 16
    assert facts["expected_rows_per_expert"] == 64 * 2 * 2 / 16
    assert facts["deployment_rows_per_expert"] == 4 * facts["expected_rows_per_expert"]
    assert facts["selecting_queries_per_sequence"] == 64 - 8  # most queries select
    full = r["config"]
    m, sa = full["model"], full["sa_config"]
    assert m["batch_size"] * m["seq_len"] * 8 / 128 == 1024  # rows an expert
    assert m["seq_len"] - sa["topk"] == 14336
    mean_keys = (2048 * 2049 / 2 + 14336 * 2048) / 16384
    assert 1920 < mean_keys < 1921
    assert full["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert full["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert (full["num_hidden_layers"], full["num_experts"], full["vocab_size"]) == (4, 16, 18992)
    assert (m["layers_here"], m["router_experts"], m["experts_here"], m["vocab_here"]) == (4, 128, [0, 16], 18992)


def test_every_catalog_number_is_kept_or_listed_as_reduced():
    """The published language-model keys, as ISSUE 32 quotes the
    catalog's row."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                      "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936,
    }
    config = _config()
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"])


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
    ref = harness.load_module("reference", "keye_vl2")
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


@pytest.mark.parametrize("fault", ["half_batch", "recent_keys", "no_index_loss"])
def test_planted_fault_is_not_correct(fault):
    table, ok = _first_steps(11, fault=fault)
    assert not ok, table


def test_bf16_control_is_not_correct():
    import jax.numpy as jnp

    table, ok = _first_steps(3, dtype=jnp.bfloat16)
    assert not ok, table


def test_readers_read_the_dsa_scopes_and_return_none_on_nothing(monkeypatch):
    import kernel_share

    table = {
        "dsa.proj.forward": 1e6, "dsa.proj.backward": 3e6,
        "dsa.index.forward": 2e6, "dsa.index.backward": 4e6, "dsa.select.backward": 2e6,
        "dsa.core.forward": 5e6, "dsa.core.backward": 15e6,
        "dsa.aux.backward": 1e6, "dsa.out.forward": 1e6,
        "moe.experts.forward": 5e6, "head.backward": 6e6, "unscoped": 8e6,
    }
    monkeypatch.setattr(kernel_share, "layers", lambda run: table)
    monkeypatch.setattr(kernel_share, "notes", lambda run: {"scope_ms_per_step": {}})
    counts = {"kernels": {
        "dsa_index": {"flops": 4e9, "bytes": 1e5},  # 4 ms at the peak
        "dsa_core": {"flops": 1e9, "bytes": 1e7},  # 10 ms at the peak, by its bytes
    }}
    run = {"peak": {"flops_per_s": 1e12, "bytes_per_s": 1e9}, "notes": {}, "counts": counts}

    def read(name, run):
        return harness.load_module("layer_metrics", name).read(run)

    assert read("dsa_ms", run) == 34.0
    assert run["notes"]["layers"] == {"scope_ms_per_step": {}}
    # 8 ms under dsa.index + dsa.select; 20 ms under dsa.core
    assert read("dsa_index_roofline_pct", run) == pytest.approx(100 * 4 / 8)
    assert run["notes"]["dsa.index_roofline_bound"] == "compute"
    assert read("dsa_core_roofline_pct", run) == pytest.approx(100 * 10 / 20)
    assert run["notes"]["dsa.core_roofline_bound"] == "memory"
    # a program whose counts name no such kernel; a trace with no such scope
    bare = dict(run, counts={}, notes={})
    assert read("dsa_index_roofline_pct", bare) is None
    assert read("dsa_core_roofline_pct", bare) is None
    monkeypatch.setattr(kernel_share, "layers", lambda run: {"gdn.scan.forward": 1e6, "unscoped": 1e6})
    for name in ("dsa_ms", "dsa_index_roofline_pct", "dsa_core_roofline_pct"):
        assert read(name, dict(run, notes={})) is None
    monkeypatch.setattr(kernel_share, "layers", lambda run: None)
    monkeypatch.setattr(kernel_share, "notes", lambda run: None)
    for name in ("dsa_ms", "dsa_index_roofline_pct", "dsa_core_roofline_pct"):
        assert read(name, dict(run, notes={})) is None


def test_the_parent_program_cannot_run_the_family(monkeypatch):
    """A program from before the model exits at the import, with a
    message, before anything is staged or compiled."""
    import euler_tpu.models.sequence_lm as lm

    monkeypatch.delattr(lm, "KeyeVL2LM")
    with pytest.raises(SystemExit, match="no indexed-sparse-attention model"):
        harness.load_module("families", "keye_vl2").build({}, {}, {})
