"""Distributed training demo — the reference's dist_tf_euler.sh topology
(scripts/dist_tf_euler.sh:2-43) mapped onto this framework: graph-server
processes per shard + a trainer that discovers them through the registry
and trains GraphSAGE over remote queries.

    python -m euler_tpu.examples.run_distributed --shards 2 --steps 50

Spawns one `euler_tpu.distributed.service` subprocess per shard on a
synthetic graph, waits for registry membership, trains, then tears down.
In a real deployment each service runs on its own host and the trainer
uses open_graph("remote://<registry>?shards=N").
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu) before device init")
    args = ap.parse_args(argv)

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    import numpy as np

    from euler_tpu.datasets.synthetic import random_graph
    from euler_tpu.graph import open_graph
    from euler_tpu.graph import format as tformat

    work = tempfile.mkdtemp(prefix="etpu_dist_")
    data = os.path.join(work, "data")
    reg = os.path.join(work, "registry")

    graph = random_graph(
        num_nodes=4000, out_degree=8, feat_dim=16, seed=0,
        num_partitions=args.shards,
    )
    for p, shard in enumerate(graph.shards):
        tformat.write_arrays(os.path.join(data, f"part_{p}"), shard.arrays)
    graph.meta.save(data)

    procs = [
        subprocess.Popen(
            [
                sys.executable, "-m", "euler_tpu.distributed.service",
                "--data", data, "--shard", str(s), "--registry", reg,
            ]
        )
        for s in range(args.shards)
    ]
    try:
        remote = open_graph(f"remote://{reg}?shards={args.shards}")
        print(f"connected to {args.shards} graph servers via {reg}")

        from euler_tpu.dataflow import SageDataFlow
        from euler_tpu.estimator import (
            DeviceFeatureCache,
            Estimator,
            EstimatorConfig,
        )
        from euler_tpu.models import GraphSAGESupervised

        rng = np.random.default_rng(0)
        # full hot path against the cluster: each batch is ONE RPC — the
        # serving shard samples roots, coordinates the multi-hop fanout
        # next to the data, and returns the LEAN wire (int32 feature-cache
        # rows + labels only); features stay device-side in the cache
        cache = DeviceFeatureCache(remote, ["feat"])
        flow = SageDataFlow(
            remote, ["feat"], fanouts=[5, 5], label_feature="label", rng=rng,
            feature_mode="rows", lean=True,
        )
        model = GraphSAGESupervised(dims=[32, 32], label_dim=2)
        est = Estimator(
            model,
            lambda: (flow.minibatch(args.batch_size),),
            EstimatorConfig(
                model_dir=os.path.join(work, "model"),
                total_steps=args.steps,
                log_steps=max(args.steps // 5, 1),
            ),
            feature_cache=cache,
        )
        est.train()
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


if __name__ == "__main__":
    main()
