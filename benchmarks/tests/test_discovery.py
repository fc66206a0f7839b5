"""A configuration, a mix, limits, a counts file, a family and a reader
dropped into a copy of the benchmark are found by name, with no edit to
a file that was there."""

import json
import os
import shutil

import pytest

import run as harness


def test_dropped_in_files_are_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(
        harness.HERE, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".cache", ".jax_cache"),
    )
    here = root / "benchmarks"
    before = {
        p: p.read_bytes() for p in here.rglob("*") if p.is_file()
    }
    bench = harness.load_benchmark()
    cfg = harness.load_json(f"{harness.HERE}/configs/deepwalk-products.json")
    cfg["name"] = "deepwalk-tiny"
    cfg["family"] = "skipgram2"
    (here / "configs" / "deepwalk-tiny.json").write_text(json.dumps(cfg))
    (here / "traffic" / "train-other.json").write_text(
        (here / "traffic" / "train-device.json").read_text().replace('"train-device"', '"train-other"')
    )
    shutil.copy(here / "limits" / "deepwalk-products.train-device.json",
                here / "limits" / "deepwalk-tiny.train-other.json")
    (here / "families" / "skipgram2.py").write_text(
        (here / "families" / "skipgram.py").read_text().replace('COUNTS = "skipgram"', 'COUNTS = "skipgram2"')
    )
    shutil.copy(here / "counts" / "skipgram.py", here / "counts" / "skipgram2.py")
    (here / "layer_metrics" / "calls_in_window.py").write_text(
        "def read(run):\n    return len(run['call_seconds'])\n"
    )
    bench["configs"].append({"name": "deepwalk-tiny", "source": "x", "file": "benchmarks/configs/deepwalk-tiny.json", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "deepwalk-tiny.train-other", "config": "deepwalk-tiny", "traffic": "train-other", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "calls_in_window", "unit": "calls", "better": "higher", "source": "program_counter",
                               "layer": "estimator", "moves": "examples_per_s", "workloads": ["deepwalk-tiny.train-other"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    got = harness.resolve("deepwalk-tiny.train-other", root=str(root))
    assert got["config"]["name"] == "deepwalk-tiny"
    assert got["mix"]["name"] == "train-other"
    assert [m["name"] for m in got["per_layer"]][-1] == "calls_in_window"
    fam = harness.load_module("families", got["config"]["family"], got["here"])
    assert harness.load_module("counts", fam.COUNTS, got["here"]).per_step(cfg)["examples"] > 0
    layer = harness.read_layer_metrics(
        {"per_layer": [m for m in got["per_layer"] if m["name"] == "calls_in_window"], "here": got["here"]},
        {"call_seconds": [1, 2, 3]},
    )
    assert layer == {"calls_in_window": {"value": 3.0, "unit": "calls"}}
    # the old cell does not report the new metric, and no old file changed
    old = harness.resolve("deepwalk-products.train-device", root=str(root))
    assert "calls_in_window" not in [m["name"] for m in old["per_layer"]]
    assert all(p.read_bytes() == data for p, data in before.items())


def test_benchmark_json_names_only_files_that_exist():
    bench = harness.load_benchmark()
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
    for w in bench["workloads"]:
        harness.resolve(w["name"])


@pytest.mark.parametrize("metric", harness.load_benchmark()["per_layer"], ids=lambda m: m["name"])
def test_a_per_layer_entry_lists_cells_that_exist_and_has_its_reader(metric):
    """An edit that orphans a reader, or lists a cell that is gone, is
    caught here on the CPU and not by a traced run on the chip."""
    cells = {w["name"] for w in harness.load_benchmark()["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    reader = harness.load_module("layer_metrics", metric["name"])
    assert callable(reader.read)


SAGE, DEEPWALK = "sage-products-id.train-device", "deepwalk-products.train-device"
QWEN3 = "qwen3-next-80b-a3b-ep16.train-tokens"
KEYE, TRINITY = "keye-vl2-30b-a3b-ep8.train-long-tokens", "trinity-mini-ep8.train-long-tokens"
TABLE_AND_SETUP = (
    "sampler_ms", "gather_ms", "table_grad_ms", "optimizer_ms", "host_step_ms",
    "stage_s", "step_compile_s",
)


@pytest.mark.parametrize(
    "metrics, cells",
    [
        (("moe_ms", "head_ms", "moe_experts_roofline_pct"), {QWEN3, KEYE, TRINITY}),
        (("attn_ms", "attn_core_roofline_pct"), {QWEN3, TRINITY}),
        (TABLE_AND_SETUP, {SAGE, DEEPWALK, QWEN3, KEYE, TRINITY}),
        (("conv_ms",), {SAGE}),
        (("dsa_ms",), {KEYE}),
    ],
)
def test_a_reader_is_selected_in_the_cells_its_entry_lists(metrics, cells):
    reports = {
        w["name"]: {m["name"] for m in harness.resolve(w["name"])["per_layer"]}
        for w in harness.load_benchmark()["workloads"]
    }
    for name in metrics:
        assert {cell for cell, names in reports.items() if name in names} == cells, name


def test_the_second_sampler_program_is_gone():
    """`sampler_alone_ms` timed a second program from outside the step;
    `sampler_ms` reads the layer where the work happens."""
    bench = harness.load_benchmark()
    assert "sampler_alone_ms" not in [m["name"] for m in bench["per_layer"]]
    assert not os.path.exists(os.path.join(harness.HERE, "layer_metrics", "sampler_alone_ms.py"))
    assert not hasattr(harness, "sampler_alone")
    for cell in bench["workloads"]:
        mix = harness.resolve(cell["name"])["mix"]
        assert not {"sampler_alone_calls", "sample_program"} & (set(mix) | set(mix["why"]))
