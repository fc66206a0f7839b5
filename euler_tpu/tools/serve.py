"""Model-serving CLI (`euler.start` parity for the online path).

Boots one ModelServer — or a replicated fleet — over a graph dir + Orbax
checkpoint:

    python -m euler_tpu.tools.serve --data DIR --model-dir CKPT \
        --dims 128,128 --label-dim 2 --port 9200 --replicas 4

Graph queries run in-process against the local shard files (native
engine when available); model config must match the checkpoint. With
`--registry REG` the servers heartbeat into the same registry the graph
services use, so clients discover model replicas the way they discover
shards. `--replicas N` boots N servers (consecutive ports when --port is
pinned, ephemeral otherwise), each with its own runtime + batcher —
clients front them with a ServingRouter (`ServingClient(addrs,
routing="consistent_hash")`). `--hedge MS` is the fleet's recommended
hedge delay, printed with the topology (and exercised by the fleet
selftest). `--reload` watches the checkpoint path and hot-swaps every
replica — zero downtime — when a new checkpoint lands.

`--selftest` is the smoke mode: builds a tiny synthetic graph + trains a
2-step checkpoint in a temp dir, boots server + client in-process,
asserts served predictions match direct inference bit-for-bit, prints a
JSON summary, and exits 0 — wired into the fast test gate. With
`--replicas N` the selftest boots the whole fleet and additionally
proves routed parity, per-replica fleet stats, and hot-reload canary
parity.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading


def build_runtime(args, graph=None):
    import numpy as np

    from euler_tpu.dataflow import FullNeighborDataFlow, SageDataFlow
    from euler_tpu.estimator import EstimatorConfig
    from euler_tpu.graph import Graph
    from euler_tpu.models import GraphSAGESupervised
    from euler_tpu.serving import InferenceRuntime

    if graph is None:
        graph = Graph.load(args.data, native=None if args.native else False)
    features = args.features.split(",") if args.features else []
    dims = [int(x) for x in args.dims.split(",")]
    # each replica gets its OWN flow over the shared graph: a flow is
    # only ever queried from its replica's single batcher thread
    if args.full_neighbor:
        flow = FullNeighborDataFlow(
            graph,
            features,
            num_hops=len(dims),
            max_degree=args.max_degree,
            label_feature=args.label_feature,
        )
    else:
        flow = SageDataFlow(
            graph,
            features,
            fanouts=[int(x) for x in args.fanouts.split(",")],
            label_feature=args.label_feature,
            rng=np.random.default_rng(args.seed),
        )
    model = GraphSAGESupervised(
        dims=dims, label_dim=args.label_dim, conv=args.conv
    )
    return InferenceRuntime(
        model,
        flow,
        EstimatorConfig(model_dir=args.model_dir),
        buckets=tuple(int(b) for b in args.buckets.split(",")),
    )


def serve_fleet(args) -> list:
    """Boot args.replicas ModelServers over one shared graph."""
    from euler_tpu.distributed.rendezvous import make_registry
    from euler_tpu.graph import Graph
    from euler_tpu.serving import ModelServer

    registry = make_registry(args.registry) if args.registry else None
    graph = Graph.load(args.data, native=None if args.native else False)
    servers = []
    for i in range(args.replicas):
        runtime = build_runtime(args, graph=graph)
        port = args.port + i if args.port else 0
        server = ModelServer(
            runtime,
            host=args.host,
            port=port,
            max_batch=args.max_batch,
            max_wait_us=args.max_wait_us,
            max_queue=args.max_queue,
            registry=registry,
            shard=args.replica + i,
        )
        runtime.warmup()
        servers.append(server.start())
    return servers


def _ckpt_signature(model_dir: str) -> tuple:
    """Change token for the reload watcher: moves ONLY when a new
    COMPLETE checkpoint commits (training/checkpoint.py COMMIT marker),
    so a poll landing mid-write — a trainer still fsync'ing a
    `ckpt_*.tmp-*` dir, or a torn dir left by a kill -9 — can never
    trigger a swap onto a torn checkpoint. Legacy single-path Orbax
    dirs keep the old newest-entry-mtime behavior."""
    from euler_tpu.training.checkpoint import watch_signature

    return watch_signature(model_dir)


def watch_reload(servers, model_dir: str, stop_event, poll_s: float):
    """Hot-swap every replica whenever a new COMPLETE checkpoint lands
    under model_dir — the serving fleet never restarts for a deploy,
    and never loads a half-written one."""
    last = _ckpt_signature(model_dir)
    while not stop_event.wait(poll_s):
        now = _ckpt_signature(model_dir)
        if now == last:
            continue
        last = now
        for server in servers:
            try:
                report = server.runtime.swap()
                print(
                    f"hot-reloaded {server.host}:{server.port}: "
                    f"{json.dumps(report)}",
                    flush=True,
                )
            except Exception as e:  # keep serving the old checkpoint
                print(
                    f"hot-reload FAILED on {server.host}:{server.port}: "
                    f"{e!r} (replica keeps its current checkpoint)",
                    flush=True,
                )


def _durability_probe(graph_json: dict, watch_ids, replication: int = 1) -> dict:
    """Boot one DURABLE graph shard (WAL + snapshots) in a temp dir,
    stream a couple of mutations through the wire, and report the
    operator-facing durability stats — the selftest's proof that
    `wal_bytes` / `last_snapshot_epoch` / `recovering` surface end to
    end, and what a fleet's `graph_shards` section will carry. With
    `replication > 1` the shard is a lease-coordinated replica group
    instead: R members, quorum-acked writes, and the probe additionally
    proves every follower converged bit-identical to the primary."""
    import shutil
    import tempfile
    import time as _time

    import numpy as np

    from euler_tpu.distributed import connect
    from euler_tpu.distributed.service import serve_shard
    from euler_tpu.distributed.writer import GraphWriter
    from euler_tpu.graph.builder import convert_json

    tmp = tempfile.mkdtemp(prefix="etpu_serve_durability_")
    svcs = []
    try:
        data_dir = f"{tmp}/graph"
        convert_json(graph_json, data_dir, num_partitions=1)
        if replication > 1:
            for r in range(replication):
                svcs.append(serve_shard(
                    data_dir, 0, native=False,
                    registry_path=f"{tmp}/reg",
                    wal_dir=f"{tmp}/wal_r{r}",
                    replica=r, group_size=replication, lease_ttl=2.0,
                ))
            deadline = _time.monotonic() + 15.0
            while _time.monotonic() < deadline and not any(
                s.repl_status()["role"] == "primary" for s in svcs
            ):
                _time.sleep(0.05)
            graph = connect(registry_path=f"{tmp}/reg", num_shards=1)
        else:
            svcs.append(serve_shard(
                data_dir, 0, native=False, wal_dir=f"{tmp}/wal",
            ))
            graph = connect(cluster={0: [(svcs[0].host, svcs[0].port)]})
        with GraphWriter(graph) as w:
            w.upsert_edges(
                np.asarray(watch_ids, np.uint64),
                np.roll(np.asarray(watch_ids, np.uint64), 1),
                None,
                np.full(len(watch_ids), 2.0, np.float32),
            )
            w.flush()
            pre = graph.shards[0].stats()
            w.publish()
        primary = next(
            (s for s in svcs if s.repl_status()["role"] == "primary"),
            svcs[0],
        )
        primary.snapshot_now()
        post = graph.shards[0].stats()
        out = {
            "wal_bytes": int(pre.get("wal_bytes", 0)),
            "wal_bytes_after_snapshot": int(post.get("wal_bytes", 0)),
            "last_snapshot_epoch": post.get("last_snapshot_epoch"),
            "recovering": post.get("recovering"),
            "graph_epoch": post.get("graph_epoch"),
        }
        if replication > 1:
            deadline = _time.monotonic() + 10.0
            while _time.monotonic() < deadline and any(
                s._wal.tell() != primary._wal.tell() for s in svcs
            ):
                _time.sleep(0.05)
            ref = primary.store.arrays
            parity = all(
                sorted(s.store.arrays) == sorted(ref)
                and all(
                    np.array_equal(
                        np.asarray(s.store.arrays[k]), np.asarray(ref[k])
                    )
                    for k in ref
                )
                for s in svcs
            )
            st = primary.repl_status()
            out["replication"] = {
                "group_size": replication,
                "term": st["term"],
                "ack_mode": st["ack_mode"],
                "bit_parity": bool(parity),
            }
        if hasattr(graph, "stop_topology_watch"):
            graph.stop_topology_watch()
        return out
    except Exception as e:  # surfaced in the JSON, fails the selftest
        return {"error": repr(e)[:200]}
    finally:
        for svc in svcs:
            svc.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def selftest(
    replicas: int = 1,
    hedge_ms: float | None = None,
    replication: int = 1,
) -> int:
    """In-process boot: synthetic graph → 2-step checkpoint → fleet +
    concurrent clients → bit-parity vs direct inference. Exit 0 = the
    serving path works end to end on this host. replicas > 1 also proves
    routed parity, fleet stats, and hot-reload canary parity."""
    import tempfile

    import numpy as np

    from euler_tpu.dataflow import FullNeighborDataFlow
    from euler_tpu.estimator import (
        Estimator,
        EstimatorConfig,
        id_batches,
        node_batches,
    )
    from euler_tpu.graph import Graph
    from euler_tpu.models import GraphSAGESupervised
    from euler_tpu.serving import (
        InferenceRuntime,
        ModelServer,
        ServingClient,
    )

    rng = np.random.default_rng(0)
    n = 48
    nodes = [
        {
            "id": i + 1,
            "type": 0,
            "weight": 1.0,
            "features": [
                {"name": "feat", "type": "dense",
                 "value": rng.normal(size=4).tolist()},
                {"name": "label", "type": "dense", "value": [1.0, 0.0]},
            ],
        }
        for i in range(n)
    ]
    edges = [
        {"src": i + 1, "dst": (i + d) % n + 1, "type": 0, "weight": 1.0,
         "features": []}
        for i in range(n)
        for d in (1, 2, 3)
    ]
    graph = Graph.from_json({"nodes": nodes, "edges": edges})

    def mkflow():
        return FullNeighborDataFlow(
            graph, ["feat"], num_hops=2, max_degree=4, label_feature="label"
        )

    flow = mkflow()
    model = GraphSAGESupervised(dims=[8, 8], label_dim=2)
    cfg = EstimatorConfig(
        model_dir=tempfile.mkdtemp(prefix="etpu_serve_selftest_"),
        total_steps=2,
        log_steps=10**9,
    )
    est = Estimator(
        model, node_batches(graph, flow, 16, rng=np.random.default_rng(1)),
        cfg,
    )
    est.train(log=False)

    all_ids = np.arange(1, n + 1, dtype=np.uint64)
    batches, chunks = id_batches(flow, all_ids, 16)
    _, direct = est.infer(batches, chunks)

    servers = []
    for i in range(max(1, replicas)):
        runtime = InferenceRuntime(model, mkflow(), cfg, buckets=(16,))
        runtime.warmup()
        servers.append(
            ModelServer(runtime, max_wait_us=5000, shard=i).start()
        )
    addrs = [(s.host, s.port) for s in servers]
    results: dict = {}

    def worker(k: int):
        client = ServingClient(
            addrs,
            routing="consistent_hash" if len(addrs) > 1 else None,
            hedge_ms=hedge_ms,
        )
        try:
            ids = all_ids[k * 6 : (k + 1) * 6]
            results[k] = (ids, client.predict(ids))
        finally:
            client.close()

    threads = [
        threading.Thread(target=worker, args=(k,)) for k in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ok = len(results) == 8 and all(
        np.array_equal(emb, direct[ids.astype(np.int64) - 1])
        for ids, emb in results.values()
    )
    stats_client = ServingClient(addrs)
    stats = stats_client.stats()
    fleet = stats_client.fleet_stats()
    reload_parity = None
    if len(addrs) > 1:
        # rolling hot reload of the same checkpoint: canary rows must be
        # bit-identical pre/post swap on every replica
        reports = stats_client.reload(canary_ids=all_ids[:16])
        reload_parity = all(
            r.get("canary_parity") is True for r in reports.values()
        )
        ok = ok and reload_parity and len(fleet) == len(addrs)
    stats_client.close()
    requests = sum(
        s.get("requests", 0) for s in fleet.values() if "error" not in s
    )
    batches_n = sum(
        s.get("batches", 0) for s in fleet.values() if "error" not in s
    )
    for s in servers:
        s.stop()
    durability = _durability_probe(
        {"nodes": nodes, "edges": edges}, all_ids[:4],
        replication=replication,
    )
    ok = ok and durability.get("wal_bytes", 0) > 0
    ok = ok and durability.get("recovering") is False
    if replication > 1:
        ok = ok and (
            durability.get("replication", {}).get("bit_parity") is True
        )
    out = {
        "selftest": "ok" if ok else "MISMATCH",
        "durability": durability,
        "replicas": len(addrs),
        "requests": requests if len(addrs) > 1 else stats["requests"],
        "batches": batches_n if len(addrs) > 1 else stats["batches"],
        "coalesced": (
            (batches_n if len(addrs) > 1 else stats["batches"])
            < (requests if len(addrs) > 1 else stats["requests"])
        ),
    }
    if reload_parity is not None:
        out["reload_parity"] = reload_parity
    print(json.dumps(out))
    return 0 if ok else 1


def main(argv=None) -> int:
    from euler_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--selftest", action="store_true",
                    help="in-process server+client smoke; exit 0 on parity")
    ap.add_argument("--data", help="graph directory (Graph.load)")
    ap.add_argument("--model-dir", help="EstimatorConfig.model_dir (ckpt)")
    ap.add_argument("--features", default="feat")
    ap.add_argument("--label-feature", default=None)
    ap.add_argument("--dims", default="128,128")
    ap.add_argument("--label-dim", type=int, default=2)
    ap.add_argument("--conv", default="sage")
    ap.add_argument("--fanouts", default="10,10")
    ap.add_argument("--full-neighbor", action="store_true",
                    help="deterministic full-neighbor flow (replayable)")
    ap.add_argument("--max-degree", type=int, default=32)
    ap.add_argument("--buckets", default="8,32,128",
                    help="padded batch-size buckets, comma-separated")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--max-wait-us", type=int, default=2000)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--registry", default=None)
    ap.add_argument("--replica", type=int, default=0,
                    help="shard index of the FIRST replica (registry key)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="number of ModelServer replicas to boot")
    ap.add_argument("--replication", type=int, default=1, metavar="R",
                    help="graph-shard replica-group size for the "
                         "selftest durability probe (R>1 proves "
                         "quorum-acked writes + follower bit-parity)")
    ap.add_argument("--hedge", type=float, default=None, metavar="MS",
                    help="recommended client hedge delay for this fleet "
                         "(ms; default p95-tracked, EULER_TPU_HEDGE_MS)")
    ap.add_argument("--reload", action="store_true",
                    help="watch --model-dir and hot-swap every replica "
                         "when a new checkpoint lands (zero downtime)")
    ap.add_argument("--native", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.selftest:
        return selftest(
            replicas=args.replicas,
            hedge_ms=args.hedge,
            replication=args.replication,
        )
    if not args.data or not args.model_dir:
        ap.error("--data and --model-dir are required (or --selftest)")
    servers = serve_fleet(args)
    for server in servers:
        print(
            f"serving model on {server.host}:{server.port} "
            f"(replica {server.shard}, buckets {server.runtime.buckets}, "
            f"max_batch {server.batcher.max_batch}, max_wait "
            f"{int(server.batcher.max_wait_s * 1e6)}us)",
            flush=True,
        )
    print(
        json.dumps({
            "fleet": [f"{s.host}:{s.port}" for s in servers],
            "routing": "consistent_hash",
            "hedge_ms": args.hedge,
            "hot_reload": bool(args.reload),
        }),
        flush=True,
    )
    stop_event = threading.Event()
    if args.reload:
        threading.Thread(
            target=watch_reload,
            args=(servers, args.model_dir, stop_event,
                  float(os.environ.get("EULER_TPU_RELOAD_POLL_S", 10.0))),
            daemon=True,
            name="ckpt-reload-watch",
        ).start()
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        stop_event.set()
        for server in servers:
            server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
