"""Multi-host data-parallel training demo (dist_tf_euler.sh parity).

Worker mode — one process per host, same script everywhere:
    python -m euler_tpu.examples.run_multihost \
        --coordinator host0:12345 --num-processes 2 --process-id {0,1}

Spawn mode is a CPU-ONLY test launcher: the parent (which never touches
JAX) starts N worker subprocesses on localhost, each pinned to
JAX_PLATFORMS=cpu with two virtual devices, collects each worker's loss
trajectory, and checks every process agrees. It cannot drive a chip — a
chip belongs to one process, so N local workers could not share it:
    python -m euler_tpu.examples.run_multihost --spawn 2 --steps 8

The training batch is DETERMINISTIC (round-robin roots + full-neighbor
expansion), so an N-process run must produce exactly the same loss
trajectory as a single-process run — the test asserts that.

Remote-graph mode (--remote-data/--remote-registry) is the full reference
deployment in miniature (scripts/dist_tf_euler.sh:2-43 + separate graph
servers via euler/python/start_service.py:70-80): jax.distributed trainer
processes pull LEAN one-RPC minibatches from GraphService processes. The
global batch stream is defined as `--slots` server-coordinated pulls per
step with per-(step, slot) seeds; an N-process run takes slot
`process_index` of each step, a 1-process run pulls every slot and
concatenates — so both see the same global batches and the loss
trajectories must match exactly.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def build_step(model, tx):
    import jax
    import optax

    from euler_tpu.dataflow.base import hydrate_blocks

    def step(params, opt_state, batch):
        def loss_fn(p):
            _, loss, _, metric = model.apply(p, hydrate_blocks(batch))
            return loss, metric

        (loss, metric), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss, metric

    return jax.jit(step, donate_argnums=(0, 1))


def concat_lean_minibatches(mbs, fanouts):
    """Concatenate LEAN grid minibatches along the root axis.

    Valid because each piece's hop-h width (per·k^h) is a multiple of the
    fanout, so the grid mapping src j → dst j//k stays aligned after
    concatenation — the single-process trajectory can replay the exact
    global batch an N-process run assembles via put_global."""
    import numpy as np

    from euler_tpu.dataflow.base import MiniBatch, fanout_block

    n = sum(len(mb.root_idx) for mb in mbs)
    feats = tuple(
        np.concatenate([mb.feats[h] for mb in mbs])
        for h in range(len(fanouts) + 1)
    )
    blocks = []
    width = n
    for k in fanouts:
        blocks.append(
            fanout_block(
                width, k, None, None, lazy=True, ship_w=False,
                ship_mask=False,
            )
        )
        width *= k
    return MiniBatch(
        feats=feats,
        masks=None,
        blocks=tuple(blocks),
        root_idx=np.concatenate([mb.root_idx for mb in mbs]),
        labels=np.concatenate([mb.labels for mb in mbs]),
        hop_ids=None,
    )


def worker(args) -> list[float]:
    import jax

    from euler_tpu.parallel import multihost

    multihost.initialize(
        args.coordinator, args.num_processes, args.process_id
    )
    import numpy as np
    import optax

    from euler_tpu.dataflow import FullNeighborDataFlow
    from euler_tpu.datasets.synthetic import random_graph
    from euler_tpu.nn import SuperviseModel

    pc, pid = jax.process_count(), jax.process_index()
    mesh = multihost.data_mesh()
    if args.batch % pc:
        raise ValueError("batch must divide evenly over processes")
    per = args.batch // pc

    if args.remote_data:
        return _remote_worker(args, mesh, pc, pid)

    # every host loads the (same) graph; real deployments point this at a
    # shared data dir or a remote:// cluster — sampling stays host-local
    graph = random_graph(num_nodes=600, out_degree=6, feat_dim=8, seed=0)
    flow = FullNeighborDataFlow(
        graph, ["feat"], num_hops=1, max_degree=6, label_feature="label"
    )
    model = SuperviseModel(conv="sage", dims=[16], label_dim=2)

    all_ids = np.arange(1, 601, dtype=np.uint64)

    def local_roots(step_k: int) -> np.ndarray:
        # deterministic global batch; this process takes its slice
        start = step_k * args.batch
        g = all_ids[(start + np.arange(args.batch)) % len(all_ids)]
        return g[pid * per : (pid + 1) * per]

    import jax.numpy as jnp  # noqa: F401  (backend init before tracing)

    params = model.init(jax.random.PRNGKey(0), flow.query(local_roots(0)))
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)
    params = multihost.replicate_global(mesh, params)
    opt_state = multihost.replicate_global(mesh, opt_state)
    step = build_step(model, tx)

    losses = []
    for k in range(args.steps):
        batch = multihost.put_global(mesh, flow.query(local_roots(k)))
        params, opt_state, loss, _ = step(params, opt_state, batch)
        losses.append(float(loss))
    print(json.dumps({"process": pid, "of": pc, "losses": losses}), flush=True)
    return losses


def _remote_worker(args, mesh, pc, pid) -> list[float]:
    """Trainer pulling lean one-RPC minibatches from GraphService
    processes — the reference's trainers-plus-graph-servers topology
    (dist_tf_euler.sh + start_service.py) on jax.distributed."""
    import jax
    import numpy as np
    import optax

    from euler_tpu.dataflow import SageDataFlow
    from euler_tpu.distributed import connect
    from euler_tpu.estimator import DeviceFeatureCache
    from euler_tpu.graph import Graph
    from euler_tpu.nn import SuperviseModel
    from euler_tpu.parallel import multihost

    slots = args.slots or pc
    if slots % pc:
        raise ValueError("slots must divide evenly over processes")
    if args.batch % slots:
        raise ValueError("batch must divide evenly over slots")
    per = args.batch // slots
    fanouts = [4, 4]

    remote = connect(
        registry_path=args.remote_registry, num_shards=args.remote_shards
    )
    # feature cache bootstraps from the local shard files (one-time
    # deployment step); per-batch wire traffic afterwards is rows-only
    local = Graph.load(args.remote_data, native=False)
    cache = DeviceFeatureCache(local, ["feat"])
    flow = SageDataFlow(
        remote, ["feat"], fanouts=fanouts, label_feature="label",
        feature_mode="rows", lean=True,
    )
    model = SuperviseModel(conv="sage", dims=[16, 16], label_dim=2)

    def pull(step_k: int, slot: int):
        # per-(step, slot) seed defines the global stream independently of
        # the process topology; the server coordinates root sampling +
        # fused fanout from this seed deterministically
        flow.rng = np.random.default_rng(90_000 + step_k * 1024 + slot)
        mb = flow.minibatch(per)
        assert mb.masks is None, "lean wire downgraded mid-test"
        return mb

    my_slots = list(range(pid * (slots // pc), (pid + 1) * (slots // pc)))

    def local_batch(step_k: int):
        return concat_lean_minibatches(
            [pull(step_k, s) for s in my_slots], fanouts
        )

    tx = optax.adam(1e-2)

    from euler_tpu.dataflow.base import hydrate_blocks

    def step(params, opt_state, batch):
        def loss_fn(p):
            hyd = cache.hydrate(hydrate_blocks(batch))
            _, loss, _, metric = model.apply(p, hyd)
            return loss, metric

        (loss, metric), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss, metric

    step = jax.jit(step, donate_argnums=(0, 1))

    params = model.init(
        jax.random.PRNGKey(0), cache.hydrate(hydrate_blocks(local_batch(0)))
    )
    opt_state = tx.init(params)
    params = multihost.replicate_global(mesh, params)
    opt_state = multihost.replicate_global(mesh, opt_state)

    losses = []
    for k in range(args.steps):
        batch = multihost.put_global(mesh, local_batch(k))
        params, opt_state, loss, _ = step(params, opt_state, batch)
        losses.append(float(loss))
    print(
        json.dumps({"process": pid, "of": pc, "losses": losses}), flush=True
    )
    return losses


def spawn(args) -> int:
    port = args.port
    env_base = dict(os.environ, JAX_PLATFORMS="cpu")
    env_base["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    procs = []
    for pid in range(args.spawn):
        cmd = [
            sys.executable, "-m", "euler_tpu.examples.run_multihost",
            "--coordinator", f"localhost:{port}",
            "--num-processes", str(args.spawn),
            "--process-id", str(pid),
            "--steps", str(args.steps), "--batch", str(args.batch),
        ]
        if args.remote_data:
            cmd += [
                "--remote-data", args.remote_data,
                "--remote-registry", args.remote_registry,
                "--remote-shards", str(args.remote_shards),
                "--slots", str(args.slots or args.spawn),
            ]
        procs.append(
            subprocess.Popen(
                cmd, env=env_base, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
        )
    outs = [p.communicate(timeout=600)[0] for p in procs]
    losses = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("{"):
                rec = json.loads(line)
                losses[rec["process"]] = rec["losses"]
    if len(losses) != args.spawn:
        print("worker output:\n" + "\n".join(out[-3000:] for out in outs))
        raise SystemExit("not all workers reported losses")
    ref = losses[0]
    for pid, ls in losses.items():
        if not all(abs(a - b) < 1e-6 for a, b in zip(ref, ls)):
            raise SystemExit(f"process {pid} diverged: {ls} vs {ref}")
    print(json.dumps({"multihost_losses": ref}))
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--spawn", type=int, default=0,
                    help="parent mode: launch N localhost workers")
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--port", type=int, default=12377)
    ap.add_argument("--remote-data", default=None,
                    help="graph data dir: pull lean one-RPC minibatches "
                         "from GraphService processes instead of sampling "
                         "in-process")
    ap.add_argument("--remote-registry", default=None)
    ap.add_argument("--remote-shards", type=int, default=2)
    ap.add_argument("--slots", type=int, default=0,
                    help="global stream slots per step (default: process "
                         "count); a 1-process run replays all slots")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.spawn:
        return spawn(args)
    worker(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
