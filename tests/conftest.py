"""Test harness: force an 8-device virtual CPU mesh before JAX is imported.

Mirrors the driver's multi-chip dry-run environment — sharding/pjit tests run
against 8 virtual CPU devices; the chip is reached only through chip_smoke.py
and benchmarks/run.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Persistent compilation cache (test-gate budget): many
# tests jit byte-identical Estimator/train-step programs — the disk cache
# dedupes those compiles within a single cold run, and spawned subprocess
# tests (multihost, service CLIs) inherit it through the env vars.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", "/tmp/euler_tpu_test_jax_cache"
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _fixture_graph_dict():
    """A small deterministic property graph covering every feature kind.

    Shaped like the reference's canonical 2-partition test fixture
    (tools/test_data/graph.json): 2 node types, 2 edge types, dense/sparse/
    binary features, graph labels — but generated in-code.
    """
    nodes = []
    edges = []
    for i in range(1, 7):
        nodes.append(
            {
                "id": i,
                "type": i % 2,
                "weight": float(i),
                "features": [
                    {"name": "sp", "type": "sparse", "value": [10 * i + 1, 10 * i + 2]},
                    {"name": "dense2", "type": "dense", "value": [i + 0.1, i + 0.2]},
                    {"name": "dense3", "type": "dense", "value": [i + 0.3, i + 0.4, i + 0.5]},
                    {"name": "blob", "type": "binary", "value": f"{i}a"},
                    {"name": "graph_label", "type": "binary", "value": str(1 + (i - 1) // 3)},
                ],
            }
        )
    pairs = [
        (1, 2, 0, 2.0),
        (1, 3, 1, 3.0),
        (2, 3, 0, 1.0),
        (2, 4, 1, 2.0),
        (3, 4, 0, 3.0),
        (3, 1, 1, 1.0),
        (4, 5, 0, 2.0),
        (4, 6, 1, 1.0),
        (5, 6, 0, 3.0),
        (5, 1, 1, 2.0),
        (6, 1, 0, 1.0),
        (6, 2, 1, 3.0),
    ]
    for s, d, t, w in pairs:
        edges.append(
            {
                "src": s,
                "dst": d,
                "type": t,
                "weight": w,
                "features": [
                    {"name": "e_dense", "type": "dense", "value": [s + d / 10.0]},
                    {"name": "e_sp", "type": "sparse", "value": [100 * s + d]},
                ],
            }
        )
    return {"nodes": nodes, "edges": edges}


@pytest.fixture(scope="session")
def fixture_graph_dict():
    return _fixture_graph_dict()


@pytest.fixture(scope="session")
def graph1(fixture_graph_dict):
    """Single-shard in-memory graph."""
    from euler_tpu.graph import Graph

    return Graph.from_json(fixture_graph_dict, num_partitions=1)


@pytest.fixture(scope="session")
def graph2(fixture_graph_dict):
    """Two-shard in-memory graph (exercises scatter/gather paths)."""
    from euler_tpu.graph import Graph

    return Graph.from_json(fixture_graph_dict, num_partitions=2)
