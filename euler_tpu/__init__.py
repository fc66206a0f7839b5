"""euler_tpu — a TPU-native graph learning framework.

A brand-new JAX/XLA implementation with the capabilities of Euler 2.0
(reference: /root/reference — see SURVEY.md). The host side is a columnar,
shardable property-graph store with weighted sampling and batch query APIs
(reference parity surface: euler/core/api/api.h:44-92 plus the tf_euler op set);
the device side is static-shape padded subgraph batches consumed by jitted
message-passing programs over `jax.sharding` meshes.

Public surface (mirrors tf_euler/python/euler_ops + model libs):

    euler_tpu.graph      — graph store, binary format, converter
    euler_tpu.ops        — device message-passing primitives (gather/segment_*)
    euler_tpu.dataflow   — padded subgraph batch builders (sage/gcn/layerwise/...)
    euler_tpu.layers     — convolution layers (GCN/SAGE/GAT/GIN/...)
    euler_tpu.nn         — GNN nets, heads, encoders, aggregators, metrics
    euler_tpu.estimator  — train/evaluate/infer drivers
    euler_tpu.serving    — online model server (micro-batched predict RPCs)
    euler_tpu.parallel   — mesh/sharding helpers, sharded embedding tables
    euler_tpu.datasets   — auto-download dataset pipelines
"""

__version__ = "0.1.0"

from euler_tpu.graph import (  # noqa: F401
    Graph,
    GraphMeta,
    GraphStore,
    build_from_json,
    convert_json,
)
