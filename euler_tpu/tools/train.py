"""Durable trainer CLI — the process `TrainerSupervisor` supervises.

Boots a GraphSAGE supervised trainer over a local graph dir or a remote
cluster, wrapped in a `TrainingSession` (atomic retained checkpoints,
async save, SIGTERM drain, anomaly guard, watchdog):

    python -m euler_tpu.tools.train --data DIR --model-dir CKPT \
        --total-steps 200 --checkpoint-every 20 [--resume]

`--resume` restores the newest COMPLETE retained checkpoint — params,
opt_state, step, and the batch-source cursor — so a respawn after
`kill -9` continues the run bit-exactly under the standing seed
contract. Exit codes: 0 = target step reached, 3 = preempted (SIGTERM
drain flushed a final checkpoint first), anything else = crash (the
supervisor respawns with `--resume`).

`--mutate-spec FILE` replays a deterministic graph-mutation schedule:
a JSON list of `{"step": S, "upsert_edges": [[src, dst, type, w], ...]}`
entries, each published when global step S is reached (entries at or
before the resumed step are applied at boot — the resumed process
reconstructs the same data-version timeline the uninterrupted run saw).
This pins the resume-across-a-mutation-epoch proof: the batch stream,
the RNG streams, AND the graph epoch schedule are all functions of the
global step, so kill -9 anywhere leaves nothing to lose.

`--losses-out FILE` appends one JSON line per run segment with the
per-step losses — the bit-parity oracle the tier-1 resume proof diffs
against an uninterrupted run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _load_graph(args):
    from euler_tpu.graph import Graph

    if args.cluster:
        from euler_tpu.distributed import connect

        spec = json.loads(args.cluster)
        cluster = {
            int(k): [(h, int(p)) for h, p in v] for k, v in spec.items()
        }
        return connect(cluster=cluster)
    if args.registry:
        from euler_tpu.distributed import connect

        return connect(registry_path=args.registry, num_shards=args.shards)
    return Graph.load(args.data, native=None if args.native else False)


def build_trainer(args, graph=None):
    """(session, est, source, graph) for the CLI args — importable so
    the tier-1 proof builds the bit-identical in-process reference."""
    from euler_tpu.dataflow import FullNeighborDataFlow
    from euler_tpu.estimator import Estimator, EstimatorConfig
    from euler_tpu.models import GraphSAGESupervised
    from euler_tpu.training import (
        SessionConfig,
        TrainingSession,
        resumable_node_batches,
    )

    if graph is None:
        graph = _load_graph(args)
    dims = [int(x) for x in args.dims.split(",")]
    features = args.features.split(",") if args.features else []
    # full-neighbor flow: deterministic per root set, so the batch
    # stream is a pure function of (source seed, cursor)
    flow = FullNeighborDataFlow(
        graph,
        features,
        num_hops=len(dims),
        max_degree=args.max_degree,
        label_feature=args.label_feature,
    )
    source = resumable_node_batches(
        graph, flow, args.batch_size, seed=args.source_seed
    )
    model = GraphSAGESupervised(
        dims=dims, label_dim=args.label_dim, conv=args.conv
    )
    est = Estimator(
        model,
        source,
        EstimatorConfig(
            model_dir=args.model_dir,
            total_steps=args.total_steps,
            log_steps=args.log_steps,
            learning_rate=args.learning_rate,
            seed=args.seed,
            keep_checkpoints=args.keep,
        ),
    )
    session = TrainingSession(
        est,
        source=source,
        graph=graph,
        cfg=SessionConfig(
            checkpoint_every=args.checkpoint_every,
            keep=args.keep,
            async_save=not args.sync_save,
            anomaly_policy=args.anomaly_policy,
            max_strikes=args.max_strikes,
            step_deadline_s=args.step_deadline_s,
        ),
    )
    return session, est, source, graph


def apply_local_mutation(graph, spec: dict) -> dict:
    """Publish one edge-upsert wave on an in-process graph: per-shard
    DeltaStore staged + merge_delta + one store-reference swap — the
    same copy-on-write publish the wire path uses, so the data version
    the trainer reads changes atomically at a step boundary."""
    import numpy as np

    from euler_tpu.graph.delta import DeltaStore

    rows = spec.get("upsert_edges") or []
    if not rows:
        return {}
    arr = np.asarray(rows, dtype=np.float64)
    src = arr[:, 0].astype(np.uint64)
    dst = arr[:, 1].astype(np.uint64)
    tt = arr[:, 2].astype(np.int32)
    w = arr[:, 3].astype(np.float32)
    parts = len(graph.shards)
    epochs = {}
    for p in range(parts):
        osel = (src.astype(np.int64) % parts) == p
        isel = (dst.astype(np.int64) % parts) == p
        if not osel.any() and not isel.any():
            continue
        delta = DeltaStore(p, parts)
        delta.stage_edges(
            src[osel], dst[osel], tt[osel], w[osel],
            src[isel], dst[isel], tt[isel], w[isel],
        )
        new_store, _rows, _ids = graph.shards[p].merge_delta(delta)
        graph.shards[p] = new_store  # one reference: no torn snapshot
        epochs[p] = int(new_store.graph_epoch)
    graph.refresh_shard_weights()
    return epochs


def apply_remote_mutation(graph, spec: dict) -> dict:
    """The same wave through the wire write path (remote clusters)."""
    import numpy as np

    from euler_tpu.distributed.writer import GraphWriter

    rows = spec.get("upsert_edges") or []
    if not rows:
        return {}
    arr = np.asarray(rows, dtype=np.float64)
    with GraphWriter(graph) as w:
        w.upsert_edges(
            arr[:, 0].astype(np.uint64),
            arr[:, 1].astype(np.uint64),
            arr[:, 2].astype(np.int32),
            arr[:, 3].astype(np.float32),
        )
        res = w.publish()
    return res.get("epochs", {})


def main(argv=None) -> int:
    from euler_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--data", help="local graph directory (Graph.load)")
    ap.add_argument("--cluster", default=None,
                    help='remote cluster JSON {"0": [["host", port]], ...}')
    ap.add_argument("--registry", default=None)
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--model-dir", required=True)
    ap.add_argument("--total-steps", type=int, default=100)
    ap.add_argument("--checkpoint-every", type=int, default=20)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--dims", default="8,8")
    ap.add_argument("--features", default="feat")
    ap.add_argument("--label-feature", default="label")
    ap.add_argument("--label-dim", type=int, default=2)
    ap.add_argument("--conv", default="sage")
    ap.add_argument("--max-degree", type=int, default=4)
    ap.add_argument("--learning-rate", type=float, default=0.05)
    ap.add_argument("--log-steps", type=int, default=10**9)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--source-seed", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest complete retained checkpoint")
    ap.add_argument("--sync-save", action="store_true",
                    help="inline checkpoint writes (A/B the async writer)")
    ap.add_argument("--anomaly-policy", default="skip",
                    choices=("off", "skip", "rollback", "abort"))
    ap.add_argument("--max-strikes", type=int, default=3)
    ap.add_argument("--step-deadline-s", type=float, default=0.0)
    ap.add_argument("--mutate-spec", default=None,
                    help="JSON schedule of step-aligned graph mutations")
    ap.add_argument("--losses-out", default=None,
                    help="append one JSON line of per-step losses per segment")
    ap.add_argument("--native", action="store_true")
    args = ap.parse_args(argv)
    if not (args.data or args.cluster or args.registry):
        ap.error("one of --data / --cluster / --registry is required")

    session, est, source, graph = build_trainer(args)
    resume_report = None
    if args.resume:
        resume_report = session.restore()

    schedule = []
    if args.mutate_spec:
        with open(args.mutate_spec, encoding="utf-8") as f:
            schedule = sorted(json.load(f), key=lambda m: int(m["step"]))
    apply_fn = (
        apply_remote_mutation
        if (args.cluster or args.registry)
        else apply_local_mutation
    )
    # catch-up: waves the pre-crash run already published are re-applied
    # at boot, so the resumed graph sits at the same data version the
    # uninterrupted run had at this step
    for m in schedule:
        if int(m["step"]) <= est.step:
            apply_fn(graph, m)
    pending = [m for m in schedule if int(m["step"]) > est.step]

    segments = []
    preempted = False
    targets = [int(m["step"]) for m in pending] + [args.total_steps]
    for i, target in enumerate(targets):
        remaining = target - est.step
        if remaining > 0:
            rep = session.run(remaining)
            segments.append(rep)
            if rep["preempted"]:
                preempted = True
                break
        if i < len(pending):
            apply_fn(graph, pending[i])

    if args.losses_out and segments:
        with open(args.losses_out, "a", encoding="utf-8") as f:
            for rep in segments:
                f.write(json.dumps({
                    "start_step": rep["start_step"],
                    "loss_steps": rep["loss_steps"],
                    "losses": rep["losses"],
                    "resumed_from": rep["resumed_from"],
                }) + "\n")
            f.flush()
            os.fsync(f.fileno())

    done = est.step >= args.total_steps
    print(json.dumps({
        "done": done,
        "preempted": preempted,
        "step": int(est.step),
        "resumed": resume_report,
        "telemetry": segments[-1]["telemetry"] if segments else None,
    }), flush=True)
    return 0 if done else 3


if __name__ == "__main__":
    sys.exit(main())
