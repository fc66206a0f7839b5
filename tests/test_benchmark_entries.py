"""`BENCHMARK.json`'s per-layer entries against the files they name, one
case an entry: the tier-1 copy of
`benchmarks/tests/test_discovery.py::test_a_per_layer_entry_lists_cells_that_exist_and_has_its_reader`,
so that a cell or a reader added later cannot be forgotten outside
`python benchmarks/run.py --selftest`."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)


@pytest.fixture(scope="module")
def harness():
    """`benchmarks/run.py`, with its directory importable as its readers
    import one another; taken off the path again afterwards."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_run", os.path.join(BENCH, "run.py")
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        yield module
    finally:
        sys.path.remove(BENCH)


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"], ids=lambda m: m["name"])
def test_a_per_layer_entry_lists_cells_that_exist_and_has_its_reader(harness, metric):
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    ends = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert metric["moves"] in ends
    reader = harness.load_module("layer_metrics", metric["name"])
    assert callable(reader.read)
