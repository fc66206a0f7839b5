"""Headline benchmark: sampled edges/sec training GraphSAGE on one chip.

Trains supervised GraphSAGE (fanout sampling + mean-aggregator convs) on a
synthetic random graph. The local leg samples ON DEVICE by default
(DeviceSageFlow: HBM-resident adjacency, per-step PRNG keys, zero wire
bytes); EULER_BENCH_DEVICE_FLOW=0 forces the host path (sampling on
prefetch worker threads + lean int32-rows wire). The remote leg always
exercises the host wire. Metric matches the north star in BASELINE.json:
sampled edges/sec/chip (target 2M on v5e).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "edges/s", "vs_baseline": N/2e6}

The device run has no fallback: without --smoke the bench exits non-zero
unless `jax.devices()[0].platform == "tpu"`, builds the native engine from
cpp/graph_engine.cc (a failure is an error), and a failed or timed-out
headline leg exits non-zero. `--smoke` is the CPU contract run (tiny
sizes, `"backend": "cpu"`) that tests/test_bench_contract.py gates; its
numbers are not device metrics. This process is the only one that touches
JAX — its children are shard services, which never import it — so it owns
the chip from first touch to exit. The backend is first touched on the
MAIN thread, before any prefetch worker can call device_put.

Leg ordering: the LOCAL leg runs first and emits its JSON line
immediately, so an external timeout during the remote leg can never void
it. The remote leg then runs under an internal wall-clock budget
(EULER_BENCH_REMOTE_BUDGET, default 420s) enforced by a watchdog thread
that force-emits partial results and exits 124 — hang-proof even if the
main thread is stuck in a blocked C call. The final line re-emits the
local headline (with remote_edges_per_sec attached when available) so both
first-line and last-line parsers see the verified local number.

Usage: python bench.py [--smoke] [--bf16]   (--smoke: tiny sizes, forced CPU)
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

SMOKE = "--smoke" in sys.argv
BF16 = "--bf16" in sys.argv
BASELINE_EDGES_PER_SEC = 2_000_000.0

# the flagship configuration: the headline leg, the serving lane, the model
# half of the remote leg and chip_smoke.py all size themselves from it
FLAGSHIP = {
    "num_nodes": 200_000, "out_degree": 15, "feat_dim": 64,
    "dims": [128, 128], "batch_size": 1024, "fanouts": [10, 10],
}

# internal wall-clock budget for the remote leg: the remote leg must never
# be the reason the artifact is empty. A watchdog thread force-emits
# partial results and exits the process if this expires — os._exit works
# even when the main thread is stuck in a blocked C call.
REMOTE_BUDGET_S = float(os.environ.get("EULER_BENCH_REMOTE_BUDGET", 420.0))

# server processes spawned by the remote leg, killable from the watchdog
_REMOTE_PROCS: list = []


def emit(
    value: float,
    extra: dict | None = None,
    metric: str = "graphsage_sampled_edges_per_sec_per_chip",
    unit: str = "edges/s",
    baseline: float | None = BASELINE_EDGES_PER_SEC,
) -> None:
    rec = {
        "metric": metric,
        "value": round(float(value), 1),
        "unit": unit,
    }
    if baseline:
        rec["vs_baseline"] = round(float(value) / baseline, 4)
    if extra:
        rec.update(extra)
    print(json.dumps(rec))
    sys.stdout.flush()


def warm_backend() -> str:
    """First touch of the backend, on the main thread; returns the
    platform. Everything after this (incl. prefetch worker threads calling
    device_put) sees an initialized backend. Without --smoke anything but
    a TPU is an error: a CPU number is never printed under a device
    metric's name."""
    import jax

    if SMOKE:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if not SMOKE and platform != "tpu":
        raise SystemExit(
            f"bench.py: jax platform is {platform!r}, not 'tpu' — the "
            "device bench has no CPU fallback (--smoke is the CPU "
            "contract run)"
        )
    import jax.numpy as jnp

    jnp.zeros((8, 8)).block_until_ready()
    return platform


def _measure_training(
    batch_fn,
    cache,
    dims,
    batch_size,
    fanouts,
    warmup,
    steps,
    steps_per_call,
    bf16,
    model_dir,
):
    """Shared GraphSAGE measurement harness for both bench legs:
    optional bf16 convs, prefetched K-step scan dispatch, timed
    steady-state window. Returns (edges_per_sec, edges_per_step)."""
    import jax

    from euler_tpu.estimator import Estimator, EstimatorConfig
    from euler_tpu.estimator.estimator import stack_batches
    from euler_tpu.estimator.prefetch import Prefetcher
    from euler_tpu.models import GraphSAGESupervised

    conv_kwargs = None
    if bf16:
        import jax.numpy as jnp

        conv_kwargs = {"dtype": jnp.bfloat16}
    model = GraphSAGESupervised(dims=dims, label_dim=2, conv_kwargs=conv_kwargs)
    if getattr(batch_fn, "is_device_flow", False):
        # on-device sampling: batches are traced inside the scanned train
        # step from PRNG keys — no host sampling, no prefetch, no wire
        prefetch = batch_fn
    else:
        # workers stage K-step stacked batches onto the device so H2D and
        # host sampling overlap the scanned device steps
        prefetch = Prefetcher(
            stack_batches(batch_fn, steps_per_call),
            depth=4,
            workers=4,
            device_put=True,
        )
    try:
        est = Estimator(
            model,
            prefetch,
            EstimatorConfig(
                model_dir=model_dir,
                learning_rate=0.01,
                log_steps=10**9,
                steps_per_call=steps_per_call,
            ),
            feature_cache=cache,
        )
        # edges sampled per step: every hop's sample_neighbor draws
        edges_per_step = 0
        width = batch_size
        for k in fanouts:
            edges_per_step += width * k
            width *= k
        est.train(total_steps=warmup, log=False, save=False)  # compile+warm
        t0 = time.perf_counter()
        est.train(total_steps=steps, log=False, save=False)
        jax.block_until_ready(est.params)
        dt = time.perf_counter() - t0
    finally:
        if hasattr(prefetch, "close"):
            prefetch.close()
    return steps * edges_per_step / dt, edges_per_step


def _skewed_weighted_graph(num_nodes: int, seed: int):
    """Power-law-ish weighted digraph, arrays built directly: most nodes
    keep a small out-degree, a hub tier fans ~10× wider — the degree
    regime the paged device lane exists for (dense pays the hub width on
    EVERY row's draw scan; paged pays ⌈deg/P⌉ pages only on hub rows)."""
    from euler_tpu.datasets.synthetic import synthetic_meta
    from euler_tpu.graph.store import Graph, GraphStore

    rng = np.random.default_rng(seed)
    n = int(num_nodes)
    deg = rng.integers(8, 16, n)
    hubs = rng.choice(n, max(n // 100, 1), replace=False)
    deg[hubs] = rng.integers(96, 160, len(hubs))
    ids = np.arange(1, n + 1, dtype=np.uint64)
    e = int(deg.sum())
    dst = rng.integers(1, n + 1, size=e).astype(np.uint64)
    ew = rng.uniform(0.5, 2.0, size=e).astype(np.float32)
    feat_dim, label_dim = 16, 2
    meta = synthetic_meta(feat_dim, label_dim, 1)
    arrays = {
        "node_ids": ids,
        "node_types": np.zeros(n, dtype=np.int32),
        "node_weights": np.ones(n, dtype=np.float32),
        "edge_src": np.repeat(ids, deg),
        "edge_dst": dst,
        "edge_types": np.zeros(e, dtype=np.int32),
        "edge_weights": ew,
        "adj_0_indptr": np.r_[0, np.cumsum(deg)].astype(np.int64),
        "adj_0_dst": dst,
        "adj_0_w": ew,
        "adj_0_eidx": np.arange(e, dtype=np.int64),
        "nf_dense_0": rng.normal(0.0, 1.0, (n, feat_dim)).astype(np.float32),
        "nf_dense_1": np.zeros((n, label_dim), np.float32),
        "glabel_indptr": np.zeros(1, dtype=np.int64),
        "glabel_nodes": np.zeros(0, dtype=np.uint64),
    }
    meta.node_weight_sums.append([float(n)])
    meta.edge_weight_sums.append([float(ew.sum())])
    return Graph(meta, [GraphStore(meta, arrays, part=0)])


def _paged_device_ab(smoke: bool) -> dict:
    """Paged vs dense device-lane sampling A/B on a skewed weighted
    graph (EULER_BENCH_PAGED=0 skips). Measures pure traced-sampling
    throughput — the quantity the layouts differ on — plus the standing
    bit-identity oracle (paged and dense draw the same batch from the
    same key)."""
    import jax

    from euler_tpu.dataflow import DeviceSageFlow

    n, batch, fanouts, reps = (
        (4_000, 64, [5, 5], 10) if smoke else (50_000, 512, [10, 10], 30)
    )
    g = _skewed_weighted_graph(n, seed=13)
    flows = {
        lay: DeviceSageFlow(
            g, fanouts=fanouts, batch_size=batch, layout=lay,
            max_degree=4096,
        )
        for lay in ("dense", "paged")
    }
    edges_per_step = 0
    width = batch
    for k in fanouts:
        edges_per_step += width * k
        width *= k
    # the A/B oracle the parity tests pin, re-checked in the artifact
    leaves = {
        lay: jax.tree_util.tree_leaves(
            jax.jit(f.sample)(jax.random.PRNGKey(0))
        )
        for lay, f in flows.items()
    }
    identical = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(leaves["dense"], leaves["paged"])
    )

    def measure(flow) -> float:
        fn = jax.jit(flow.sample)
        jax.block_until_ready(
            jax.tree_util.tree_leaves(fn(jax.random.PRNGKey(1)))
        )
        t0 = time.perf_counter()
        out = None
        for t in range(reps):
            out = fn(jax.random.PRNGKey(100 + t))
        jax.block_until_ready(jax.tree_util.tree_leaves(out))
        return reps * edges_per_step / (time.perf_counter() - t0)

    # interleaved best-of-2 so one GC pause can't decide the ratio
    dense_eps = max(measure(flows["dense"]), measure(flows["dense"]))
    paged_eps = max(measure(flows["paged"]), measure(flows["paged"]))

    return {
        "paged": True,
        "paged_sample_edges_per_sec": round(paged_eps, 1),
        "dense_sample_edges_per_sec": round(dense_eps, 1),
        "paged_over_dense": round(paged_eps / max(dense_eps, 1e-9), 3),
        "paged_bit_identical": bool(identical),
        "paged_hub_degree": int(flows["paged"].max_deg),
        "page_size": int(flows["paged"].page_size),
    }


def _mutation_lane(smoke: bool) -> dict:
    """Streaming-mutation lane (ISSUE 8; EULER_BENCH_MUTATION=0 opt-out):
    sustained writer upserts/s into the per-shard delta buffers, publish
    latency at two delta sizes, post-publish read recovery (the first
    read pays the merged store's lazy sampler/index rebuilds), and the
    standing merged == from-scratch bit-parity oracle — reads stay
    epoch-consistent while the writer streams, and every published
    epoch equals a cold build of the mutated graph."""
    from euler_tpu.distributed.writer import GraphWriter
    from euler_tpu.graph import Graph
    from euler_tpu.graph.builder import build_from_json

    n, stream_small, stream_large = (
        (400, 400, 2000) if smoke else (5000, 5000, 25000)
    )
    rng = np.random.default_rng(11)
    nodes = [
        {"id": i + 1, "type": 0, "weight": 1.0,
         "features": [{"name": "feat", "type": "dense",
                       "value": rng.normal(size=8).tolist()}]}
        for i in range(n)
    ]
    # unique (src, dst, type) keys by construction: upsert semantics
    # target ONE edge per key, so the from-scratch replay must too
    edges = [
        {"src": s, "dst": (s + off) % n + 1, "type": 0,
         "weight": float(rng.integers(1, 5)), "features": []}
        for s in range(1, n + 1)
        for off in (1, 3, 7)
    ]
    data = {"nodes": nodes, "edges": edges}
    g = Graph.from_json(data, num_partitions=2)
    read_ids = np.arange(1, min(n, 256) + 1, dtype=np.uint64)

    def read_rate(reps: int = 10) -> float:
        t0 = time.perf_counter()
        for k in range(reps):
            g.get_dense_feature(read_ids, ["feat"])
            g.sample_neighbor(
                read_ids, None, 5, rng=np.random.default_rng(k)
            )
        return reps / (time.perf_counter() - t0)

    pre_rate = read_rate()

    def mk_stream(k: int, seed: int):
        r = np.random.default_rng(seed)
        return (
            r.integers(1, n + 1, size=k).astype(np.uint64),
            r.integers(1, n + 1, size=k).astype(np.uint64),
            r.integers(1, 9, size=k).astype(np.float32),
        )

    writer = GraphWriter(g, batch_rows=1024)
    streams = [mk_stream(stream_large, 21), mk_stream(stream_small, 22)]
    # sustained staging throughput: client batching + scatter + per-shard
    # delta appends, publish excluded
    src, dst, w = streams[0]
    t0 = time.perf_counter()
    for lo in range(0, stream_large, 1024):
        writer.upsert_edges(
            src[lo : lo + 1024], dst[lo : lo + 1024], None,
            w[lo : lo + 1024],
        )
    writer.flush()
    stage_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    writer.publish()
    publish_large_ms = (time.perf_counter() - t0) * 1e3
    src, dst, w = streams[1]
    writer.upsert_edges(src, dst, None, w)
    writer.flush()
    t0 = time.perf_counter()
    writer.publish()
    publish_small_ms = (time.perf_counter() - t0) * 1e3
    # post-publish read recovery: the first read batch pays the merged
    # store's lazy rebuilds (edge-key index, samplers), then steady state
    t0 = time.perf_counter()
    g.get_dense_feature(read_ids, ["feat"])
    g.sample_neighbor(read_ids, None, 5, rng=np.random.default_rng(0))
    recovery_ms = (time.perf_counter() - t0) * 1e3
    post_rate = read_rate()
    # bit parity: replay the same streams onto the JSON, rebuild cold
    ref_edges = [dict(e) for e in edges]
    index = {(e["src"], e["dst"], e["type"]): e for e in ref_edges}
    for src, dst, w in streams:
        for s_, d_, w_ in zip(src, dst, w):
            key = (int(s_), int(d_), 0)
            rec = index.get(key)
            if rec is None:
                rec = {"src": key[0], "dst": key[1], "type": 0,
                       "weight": float(w_), "features": []}
                ref_edges.append(rec)
                index[key] = rec
            else:
                rec["weight"] = float(w_)
    _, ref_shards = build_from_json(
        {"nodes": nodes, "edges": ref_edges}, 2
    )
    parity = all(
        np.array_equal(
            np.asarray(g.shards[p].arrays[k]), np.asarray(ref_shards[p][k])
        )
        for p in range(2)
        for k in ref_shards[p]
    )
    return {
        "mutation": True,
        "mutation_upserts_per_sec": round(stream_large / stage_s, 1),
        "mutation_publish_ms_small": round(publish_small_ms, 2),
        "mutation_publish_ms_large": round(publish_large_ms, 2),
        "mutation_publish_rows_small": int(stream_small),
        "mutation_publish_rows_large": int(stream_large),
        "mutation_read_recovery_ms": round(recovery_ms, 2),
        "mutation_read_rate_post_over_pre": round(
            post_rate / max(pre_rate, 1e-9), 3
        ),
        "mutation_bit_parity": bool(parity),
    }


def _durability_lane(smoke: bool) -> dict:
    """Durability lane (ISSUE 9; EULER_BENCH_DURABILITY=0 opt-out):
    acked-writes/s through the full stage+WAL path with fsync on vs off
    (the fsync-cadence vs write-throughput tradeoff SCALE.md documents),
    snapshot cost at the publish cadence, crash→recovered-first-read
    latency, and the recovered == pre-crash bit-parity oracle."""
    import shutil
    import tempfile

    from euler_tpu.distributed.service import GraphService
    from euler_tpu.graph import Graph
    from euler_tpu.graph import wal as walmod
    from euler_tpu.graph.store import GraphStore

    n, batches, rows_per = (50, 40, 64) if smoke else (2000, 200, 256)
    rng = np.random.default_rng(17)
    nodes = [
        {"id": i + 1, "type": 0, "weight": 1.0,
         "features": [{"name": "feat", "type": "dense",
                       "value": rng.normal(size=8).tolist()}]}
        for i in range(n)
    ]
    edges = [
        {"src": s, "dst": s % n + 1, "type": 0, "weight": 1.0,
         "features": []}
        for s in range(1, n + 1)
    ]
    data = {"nodes": nodes, "edges": edges}
    tmp = tempfile.mkdtemp(prefix="etpu_bench_wal_")
    old_fsync = os.environ.get("EULER_TPU_WAL_FSYNC")
    try:

        def acked_writes_per_sec(mode: str) -> tuple[float, GraphService]:
            os.environ["EULER_TPU_WAL_FSYNC"] = mode
            g = Graph.from_json(data, num_partitions=1)
            svc = GraphService(
                g.shards[0], g.meta, 0,
                wal_dir=os.path.join(tmp, f"wal_{mode}"),
            )
            r = np.random.default_rng(5)
            reqs = []
            for b in range(batches):
                src = r.integers(1, n + 1, rows_per).astype(np.uint64)
                dst = r.integers(1, n + 1, rows_per).astype(np.uint64)
                reqs.append([
                    f"bench:{mode}:{b}", src, dst,
                    np.zeros(rows_per, np.int32),
                    r.random(rows_per).astype(np.float32),
                    np.empty(0, np.uint64), np.empty(0, np.uint64),
                    np.empty(0, np.int32), np.empty(0, np.float32),
                ])
            t0 = time.perf_counter()
            for a in reqs:
                svc.dispatch("upsert_edges", a)  # staged + logged + synced
            dt = time.perf_counter() - t0
            return batches * rows_per / dt, svc

        fsync_rate, svc = acked_writes_per_sec("batch")
        nofsync_rate, svc_off = acked_writes_per_sec("off")
        svc_off.stop()

        # snapshot cost at the cadence point: publish, then serialize the
        # published store + applied window and trim the WAL
        svc.dispatch("publish_epoch", ["bench:pub"])
        t0 = time.perf_counter()
        assert svc.snapshot_now()
        snapshot_ms = (time.perf_counter() - t0) * 1e3
        # a post-snapshot acked suffix, so recovery replays WAL too
        svc.dispatch("upsert_edges", [
            "bench:suffix",
            np.asarray([1], np.uint64), np.asarray([2], np.uint64),
            np.zeros(1, np.int32), np.asarray([2.0], np.float32),
            np.empty(0, np.uint64), np.empty(0, np.uint64),
            np.empty(0, np.int32), np.empty(0, np.float32),
        ])
        live = {
            k: np.array(v) for k, v in svc.store.arrays.items()
        }
        # crash: no graceful stop — recovery gets only what hit the disk
        svc.server.shutdown()
        svc.server.server_close()
        g2 = Graph.from_json(data, num_partitions=1)
        t0 = time.perf_counter()
        rec = walmod.recover(
            g2.meta, 0, os.path.join(tmp, "wal_batch"), g2.shards[0]
        )
        rec.store.get_dense_feature(
            np.arange(1, min(n, 64) + 1, dtype=np.uint64), ["feat"]
        )
        recovery_ms = (time.perf_counter() - t0) * 1e3
        parity = set(live) == set(rec.store.arrays) and all(
            np.array_equal(np.asarray(rec.store.arrays[k]), live[k])
            for k in live
        )
        return {
            "durability": True,
            "durability_acked_writes_per_sec_fsync": round(fsync_rate, 1),
            "durability_acked_writes_per_sec_nofsync": round(
                nofsync_rate, 1
            ),
            "durability_fsync_overhead_x": round(
                nofsync_rate / max(fsync_rate, 1e-9), 3
            ),
            "durability_snapshot_ms": round(snapshot_ms, 2),
            "durability_recovery_ms": round(recovery_ms, 2),
            "durability_recovered_bit_parity": bool(parity),
        }
    finally:
        if old_fsync is None:
            os.environ.pop("EULER_TPU_WAL_FSYNC", None)
        else:
            os.environ["EULER_TPU_WAL_FSYNC"] = old_fsync
        shutil.rmtree(tmp, ignore_errors=True)


def _availability_lane(smoke: bool) -> dict:
    """Availability lane (ISSUE 13; EULER_BENCH_AVAILABILITY=0 opt-out):
    replica-group cost/benefit on the artifact — acked-rows/s under
    quorum vs async vs solo acks (what a follower ack on the commit path
    costs), the write-unavailability window from a primary kill to the
    first accepted post-failover write (lease-bounded), follower
    catch-up MB/s over `wal_ship`, and the caught-up follower ==
    primary bit-parity oracle."""
    import shutil
    import tempfile

    from euler_tpu.distributed.registry import Registry
    from euler_tpu.distributed.service import GraphService
    from euler_tpu.graph import Graph

    n, batches, rows_per = (50, 30, 64) if smoke else (1000, 150, 256)
    ttl = 1.0
    rng = np.random.default_rng(23)
    nodes = [
        {"id": i + 1, "type": 0, "weight": 1.0,
         "features": [{"name": "feat", "type": "dense",
                       "value": rng.normal(size=8).tolist()}]}
        for i in range(n)
    ]
    edges = [
        {"src": s, "dst": s % n + 1, "type": 0, "weight": 1.0,
         "features": []}
        for s in range(1, n + 1)
    ]
    data = {"nodes": nodes, "edges": edges}
    tmp = tempfile.mkdtemp(prefix="etpu_bench_avail_")
    old_ack = os.environ.get("EULER_TPU_REPL_ACK")

    def reqs(tag):
        r = np.random.default_rng(5)
        out = []
        for b in range(batches):
            src = r.integers(1, n + 1, rows_per).astype(np.uint64)
            dst = r.integers(1, n + 1, rows_per).astype(np.uint64)
            out.append([
                f"avail:{tag}:{b}", src, dst,
                np.zeros(rows_per, np.int32),
                r.random(rows_per).astype(np.float32),
                np.empty(0, np.uint64), np.empty(0, np.uint64),
                np.empty(0, np.int32), np.empty(0, np.float32),
            ])
        return out

    def acked_rows_per_sec(svc, tag):
        rs = reqs(tag)
        t0 = time.perf_counter()
        for a in rs:
            svc.dispatch("upsert_edges", a)
        return batches * rows_per / (time.perf_counter() - t0)

    def boot_member(sub, rid, mode, group_size=2):
        os.environ["EULER_TPU_REPL_ACK"] = mode
        g = Graph.from_json(data, num_partitions=1)
        return GraphService(
            g.shards[0], g.meta, 0,
            registry=Registry(os.path.join(tmp, sub, "reg"), ttl=2.0),
            wal_dir=os.path.join(tmp, sub, f"wal_r{rid}"),
            replica=rid, group_size=group_size, lease_ttl=ttl,
        ).start()

    def wait_role(svc, role, timeout_s=20.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if svc.repl_status()["role"] == role:
                return
            time.sleep(0.02)
        raise TimeoutError(f"replica never became {role}")

    def hard_kill(svc):
        svc._repl._stop.set()
        svc.server.shutdown()
        svc.server.server_close()
        if svc._beat is not None:
            svc._beat.set()

    svcs = []
    try:
        # solo baseline: same batches, no replica group on the ack path
        solo = GraphService(
            Graph.from_json(data, num_partitions=1).shards[0],
            Graph.from_json(data, num_partitions=1).meta, 0,
            wal_dir=os.path.join(tmp, "solo_wal"),
        )
        svcs.append(solo)
        solo_rate = acked_rows_per_sec(solo, "solo")

        # async group: the primary writes alone first (follower joins
        # late), so the same run also times follower catch-up
        pri_a = boot_member("a", 0, "async")
        svcs.append(pri_a)
        wait_role(pri_a, "primary")
        async_rate = acked_rows_per_sec(pri_a, "async")
        shipped_bytes = pri_a._wal.tell()
        t0 = time.perf_counter()
        fol_a = boot_member("a", 1, "async")
        svcs.append(fol_a)
        deadline = time.monotonic() + 60
        while fol_a._wal.tell() < shipped_bytes:
            if time.monotonic() > deadline:
                raise TimeoutError("follower catch-up stalled")
            time.sleep(0.005)
        catchup_s = time.perf_counter() - t0
        parity = set(pri_a.store.arrays) == set(fol_a.store.arrays) and all(
            np.array_equal(
                np.asarray(fol_a.store.arrays[k]),
                np.asarray(pri_a.store.arrays[k]),
            )
            for k in pri_a.store.arrays
        )

        # quorum group: every ack waits for the follower's durable ship
        pri_q = boot_member("q", 0, "quorum")
        fol_q = boot_member("q", 1, "quorum")
        svcs += [pri_q, fol_q]
        wait_role(pri_q, "primary")
        pri_q.dispatch("upsert_edges", reqs("warm")[0])  # follower attach
        quorum_rate = acked_rows_per_sec(pri_q, "quorum")

        # unavailability window: kill the primary, poll the survivor
        # with ONE idempotency-keyed row until the promotion accepts it
        hard_kill(pri_q)
        fol_q._repl.ack_mode = "async"  # sole survivor: no quorum left
        probe = reqs("failover")[0]
        t0 = time.perf_counter()
        deadline = time.monotonic() + 60
        while True:
            try:
                fol_q.dispatch("upsert_edges", probe)
                break
            except Exception:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.005)
        window_ms = (time.perf_counter() - t0) * 1e3
        return {
            "availability": True,
            "availability_bit_parity": bool(parity),
            "availability_unavail_window_ms": round(window_ms, 1),
            "availability_quorum_rows_per_sec": round(quorum_rate, 1),
            "availability_async_rows_per_sec": round(async_rate, 1),
            "availability_solo_rows_per_sec": round(solo_rate, 1),
            "availability_quorum_overhead_x": round(
                solo_rate / max(quorum_rate, 1e-9), 3
            ),
            "availability_catchup_mb_per_sec": round(
                shipped_bytes / 1e6 / max(catchup_s, 1e-9), 2
            ),
        }
    finally:
        if old_ack is None:
            os.environ.pop("EULER_TPU_REPL_ACK", None)
        else:
            os.environ["EULER_TPU_REPL_ACK"] = old_ack
        for svc in svcs:
            try:
                svc.stop()
            except OSError:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def _bytes_lane(smoke: bool) -> dict:
    """Byte-path lane (ISSUE 16; EULER_BENCH_BYTES=0 opt-out): what the
    compact encodings actually save on the artifact, A/B'd in one run —
    dense wire bytes/batch f32 vs bf16 vs int8 (real client wire
    counters), warm-cache resident bytes per dtype, neighbor planes raw
    vs delta+varint, and replication catch-up MB/s / quorum acked-rows
    overhead with the identity codec + lockstep shipping vs the default
    compressed + pipelined path."""
    import shutil
    import tempfile

    from euler_tpu.distributed.client import RemoteShard
    from euler_tpu.distributed.registry import Registry
    from euler_tpu.distributed.service import GraphService
    from euler_tpu.graph import Graph

    n, dim, ids_per, batches, rows_per = (
        (64, 32, 48, 60, 64) if smoke else (2000, 64, 256, 150, 256)
    )
    # small ship batches force a multi-batch catch-up stream even at
    # smoke sizing — that is the regime the pipelined path exists for
    ship_max = 32768 if smoke else 262144
    ttl = 1.0
    rng = np.random.default_rng(16)
    nodes = [
        {"id": i + 1, "type": 0, "weight": 1.0,
         "features": [{"name": "feat", "type": "dense",
                       "value": rng.normal(size=dim).tolist()}]}
        for i in range(n)
    ]
    edges = [
        {"src": s, "dst": s % n + 1, "type": 0, "weight": 1.0,
         "features": []}
        for s in range(1, n + 1)
    ]
    data = {"nodes": nodes, "edges": edges}
    tmp = tempfile.mkdtemp(prefix="etpu_bench_bytes_")
    knobs = (
        "EULER_TPU_PAGE_DTYPE", "EULER_TPU_WIRE_CODEC",
        "EULER_TPU_SHIP_PIPELINE", "EULER_TPU_REPL_ACK",
        "EULER_TPU_SHIP_MAX_BYTES",
    )
    saved = {k: os.environ.get(k) for k in knobs}
    svcs = []

    def reqs(tag):
        r = np.random.default_rng(7)
        out = []
        for b in range(batches):
            src = r.integers(1, n + 1, rows_per).astype(np.uint64)
            dst = r.integers(1, n + 1, rows_per).astype(np.uint64)
            out.append([
                f"bytes:{tag}:{b}", src, dst,
                np.zeros(rows_per, np.int32),
                r.random(rows_per).astype(np.float32),
                np.empty(0, np.uint64), np.empty(0, np.uint64),
                np.empty(0, np.int32), np.empty(0, np.float32),
            ])
        return out

    def acked_rows_per_sec(svc, tag):
        rs = reqs(tag)
        t0 = time.perf_counter()
        for a in rs:
            svc.dispatch("upsert_edges", a)
        return batches * rows_per / (time.perf_counter() - t0)

    def boot_member(sub, rid, mode, group_size=2):
        os.environ["EULER_TPU_REPL_ACK"] = mode
        g = Graph.from_json(data, num_partitions=1)
        return GraphService(
            g.shards[0], g.meta, 0,
            registry=Registry(os.path.join(tmp, sub, "reg"), ttl=2.0),
            wal_dir=os.path.join(tmp, sub, f"wal_r{rid}"),
            replica=rid, group_size=group_size, lease_ttl=ttl,
        ).start()

    def wait_role(svc, role, timeout_s=20.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if svc.repl_status()["role"] == role:
                return
            time.sleep(0.02)
        raise TimeoutError(f"replica never became {role}")

    try:
        # -- dense wire + warm-cache A/B: one server, fresh client per
        # page dtype so the sticky negotiation flag and cache reset
        g = Graph.from_json(data, num_partitions=1)
        read_svc = GraphService(g.shards[0], g.meta, 0).start()
        svcs.append(read_svc)
        ids = np.arange(1, ids_per + 1, dtype=np.uint64)

        def dense_leg(kind):
            os.environ["EULER_TPU_PAGE_DTYPE"] = kind
            rs = RemoteShard(0, [(read_svc.host, read_svc.port)])
            try:
                a = rs.get_dense_feature(ids, ["feat"])
                wire = int(rs.wire_bytes_in.get("get_dense_feature", 0))
                rs.get_dense_feature(ids, ["feat"])  # warm: cache hit
                rewire = (
                    int(rs.wire_bytes_in.get("get_dense_feature", 0))
                    - wire
                )
                resident = rs._cache.nbytes if rs._cache else 0
            finally:
                rs.close()
            return np.asarray(a), wire, resident, rewire

        f32_vals, f32_wire, f32_res, f32_rewire = dense_leg("f32")
        bf_vals, bf_wire, bf_res, _ = dense_leg("bf16")
        _, i8_wire, _, _ = dense_leg("int8")
        os.environ.pop("EULER_TPU_PAGE_DTYPE", None)
        bf_err = float(np.max(np.abs(bf_vals - f32_vals)))

        # neighbor planes: identity codec (raw u64 wire) vs the default
        # delta+varint offer — exact either way, bytes differ
        def nb_leg(codec_name):
            os.environ["EULER_TPU_WIRE_CODEC"] = codec_name
            rs = RemoteShard(0, [(read_svc.host, read_svc.port)])
            try:
                rs.get_full_neighbor(ids, [0])
                return int(rs.wire_bytes_in.get("get_full_neighbor", 0))
            finally:
                rs.close()

        nb_raw = nb_leg("id")
        nb_delta = nb_leg("zlib")

        # -- replication A/B: identity + lockstep vs zlib + pipelined.
        # Each leg measures quorum acked-rows/s (vs one solo baseline)
        # and follower catch-up MB/s with a late-joining follower.
        solo = GraphService(
            Graph.from_json(data, num_partitions=1).shards[0],
            Graph.from_json(data, num_partitions=1).meta, 0,
            wal_dir=os.path.join(tmp, "solo_wal"),
        )
        svcs.append(solo)
        solo_rate = acked_rows_per_sec(solo, "solo")

        def finished(members):
            for svc in members:
                svcs.remove(svc)
                try:
                    svc.stop()
                except OSError:
                    pass

        def catchup_once(sub):
            # async primary writes a backlog alone (2x the quorum
            # traffic so shipping dominates follower boot cost), then
            # the follower joins late and streams it
            pri_a = boot_member(sub, 0, "async")
            svcs.append(pri_a)
            wait_role(pri_a, "primary")
            for tag in (f"w1{sub}", f"w2{sub}", f"w3{sub}", f"w4{sub}"):
                acked_rows_per_sec(pri_a, tag)
            shipped = pri_a._wal.tell()
            t0 = time.perf_counter()
            fol_a = boot_member(sub, 1, "async")
            svcs.append(fol_a)
            deadline = time.monotonic() + 60
            while fol_a._wal.tell() < shipped:
                if time.monotonic() > deadline:
                    raise TimeoutError("follower catch-up stalled")
                time.sleep(0.0005)  # fine: the whole stream is ~50ms
            mbps = shipped / 1e6 / max(time.perf_counter() - t0, 1e-9)
            st = fol_a.repl_status()
            finished([pri_a, fol_a])
            return mbps, st

        def quorum_once(sub):
            pri_q = boot_member(sub, 0, "quorum")
            fol_q = boot_member(sub, 1, "quorum")
            svcs.extend([pri_q, fol_q])
            wait_role(pri_q, "primary")
            pri_q.dispatch("upsert_edges", reqs(f"warm{sub}")[0])
            rate = acked_rows_per_sec(pri_q, sub)
            finished([pri_q, fol_q])
            return rate

        def ship_leg(sub, codec_name, pipeline):
            # best-of-N: single-run numbers at smoke sizing are noisy
            # (fsync and scheduler variance swamp a ~50ms stream)
            os.environ["EULER_TPU_WIRE_CODEC"] = codec_name
            os.environ["EULER_TPU_SHIP_PIPELINE"] = pipeline
            os.environ["EULER_TPU_SHIP_MAX_BYTES"] = str(ship_max)
            q_rate = max(quorum_once(f"q{sub}{i}") for i in range(3))
            mbps, st = max(
                (catchup_once(f"a{sub}{i}") for i in range(4)),
                key=lambda r: r[0],
            )
            return q_rate, mbps, st

        id_rate, id_mbps, _ = ship_leg("id", "id", "0")
        zl_rate, zl_mbps, zl_st = ship_leg("zl", "zlib", "1")
        wire_ratio = zl_st["ship_bytes"] / max(
            zl_st["ship_wire_bytes"], 1
        )
        return {
            "bytes": True,
            "bytes_dense_f32_per_batch": int(f32_wire),
            "bytes_dense_bf16_per_batch": int(bf_wire),
            "bytes_dense_int8_per_batch": int(i8_wire),
            "bytes_dense_reduction_pct": round(
                100.0 * (1 - bf_wire / max(f32_wire, 1)), 1
            ),
            "bytes_dense_bf16_max_err": round(bf_err, 6),
            "bytes_warm_cache_f32": int(f32_res),
            "bytes_warm_cache_bf16": int(bf_res),
            "bytes_warm_cache_saved_pct": round(
                100.0 * (1 - bf_res / max(f32_res, 1)), 1
            ),
            "bytes_warm_rewire": int(f32_rewire),  # 0 == cache held
            "bytes_full_nb_raw": int(nb_raw),
            "bytes_full_nb_delta": int(nb_delta),
            "bytes_catchup_mb_per_sec_id": round(id_mbps, 2),
            "bytes_catchup_mb_per_sec_zlib": round(zl_mbps, 2),
            "bytes_quorum_overhead_x_id": round(
                solo_rate / max(id_rate, 1e-9), 3
            ),
            "bytes_quorum_overhead_x_zlib": round(
                solo_rate / max(zl_rate, 1e-9), 3
            ),
            "bytes_ship_compression_ratio": round(wire_ratio, 2),
            "bytes_ship_pipelined_batches": int(zl_st["ship_pipelined"]),
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for svc in svcs:
            try:
                svc.stop()
            except OSError:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def _retrieval_lane(smoke: bool) -> dict:
    """Retrieval-serving lane (ISSUE 17; EULER_BENCH_RETRIEVAL=0
    opt-out): filtered/unfiltered top-K queries/s and latency tails over
    a 2-shard fleet, the router's fan-out-vs-merge split, and the
    standing `retrieval_bit_parity` oracle — every measured answer is
    also checked bit-for-bit against the single-process NumPy reference,
    so a throughput number from a wrong answer can never land on the
    artifact."""
    from euler_tpu.retrieval import EmbeddingCorpus, numpy_topk_oracle
    from euler_tpu.retrieval.client import RetrievalClient
    from euler_tpu.retrieval.server import RetrievalServer

    n, dim, queries, k = (300, 16, 40, 8) if smoke else (20_000, 64, 300, 32)
    rng = np.random.default_rng(17)
    ids = np.sort(
        rng.choice(max(10 * n, 1000), size=n, replace=False).astype(np.uint64)
    )
    vecs = rng.standard_normal((n, dim)).astype(np.float32)
    attrs = {"cat": rng.integers(0, 4, size=n)}
    corpus = EmbeddingCorpus.build(ids, vecs, attrs=attrs, metric="cosine")
    dnf = [[("cat", "in", [0, 2])]]
    mask = np.isin(np.asarray(attrs["cat"]), [0, 2])
    servers, shard_addrs = [], []
    cli = None
    try:
        for part in range(2):
            srv = RetrievalServer(
                corpus=corpus, part=part, num_parts=2, warm_k=k
            ).start()
            servers.append(srv)
            shard_addrs.append([(srv.host, srv.port)])
        cli = RetrievalClient(shard_addrs)
        qs = rng.standard_normal((queries, 4, dim)).astype(np.float32)
        parity = True

        def measure(use_dnf):
            nonlocal parity
            lat = []
            cli.retrieve(qs[0], k, dnf=dnf if use_dnf else None)  # warm
            for q in qs:
                t1 = time.perf_counter()
                got = cli.retrieve(q, k, dnf=dnf if use_dnf else None)
                lat.append((time.perf_counter() - t1) * 1e3)
                # oracle check OUTSIDE the timed span: throughput must
                # not price the referee in
                want = numpy_topk_oracle(
                    ids, vecs, q, k, metric="cosine",
                    mask=mask if use_dnf else None,
                )
                parity = parity and all(
                    np.array_equal(np.asarray(g), np.asarray(w))
                    for g, w in zip(got, want)
                )
            total = sum(lat) / 1e3
            lat = np.sort(np.asarray(lat))
            return (
                queries / total,
                float(lat[len(lat) // 2]),
                float(lat[min(len(lat) - 1, int(len(lat) * 0.99))]),
            )

        qps, p50, p99 = measure(False)
        fqps, _, _ = measure(True)
        rst = cli.router.stats()
        busy = rst["fanout_s"] + rst["merge_s"]
        return {
            "retrieval": True,
            "retrieval_rows": n,
            "retrieval_queries_per_sec": round(qps, 1),
            "retrieval_p50_ms": round(p50, 3),
            "retrieval_p99_ms": round(p99, 3),
            "retrieval_filtered_over_unfiltered": round(
                fqps / max(qps, 1e-9), 3
            ),
            "retrieval_merge_overhead_pct": round(
                100.0 * rst["merge_s"] / max(busy, 1e-9), 2
            ),
            "retrieval_bit_parity": bool(parity),
        }
    finally:
        if cli is not None:
            cli.close()
        for srv in servers:
            srv.stop()


def _reshard_lane(smoke: bool) -> dict:
    """Elastic-reshard lane (ISSUE 19; EULER_BENCH_RESHARD=0 opt-out):
    what a live 2 -> 3 shard split costs on the artifact — pure
    repartition throughput (rows/s through `repartition_arrays`), the
    coordinator's fence-to-commit cutover window, the writer-OBSERVED
    write-unavailability gap (a client hammering single-row upserts
    straight through the cutover, fence absorption + topology-watch
    re-route included), and the `reshard_bit_parity` oracle — the
    resharded cluster must hash identically to a from-scratch build of
    exactly the acked mutations at the new shard count."""
    import shutil
    import tempfile
    import threading

    from euler_tpu.distributed import connect
    from euler_tpu.distributed.registry import Registry
    from euler_tpu.distributed.reshard import (
        ReshardCoordinator, cluster_signature, repartition_arrays,
    )
    from euler_tpu.distributed.service import GraphService
    from euler_tpu.distributed.writer import GraphWriter
    from euler_tpu.graph import Graph
    from euler_tpu.graph.builder import build_from_json

    n = 300 if smoke else 3000
    rng = np.random.default_rng(29)
    nodes = [
        {"id": i + 1, "type": 0, "weight": 1.0,
         "features": [{"name": "feat", "type": "dense",
                       "value": rng.normal(size=8).tolist()}]}
        for i in range(n)
    ]
    edges = [
        {"src": s, "dst": (s + off) % n + 1, "type": 0,
         "weight": float(1 + (s + off) % 3), "features": []}
        for s in range(1, n + 1)
        for off in (1, 5)
    ]
    # canonical edge order: bit parity with a from-scratch build is
    # defined over the canonically-ordered equivalent graph.json
    edges.sort(key=lambda e: (e["src"], e["dst"], e["type"]))
    data = {"nodes": nodes, "edges": edges}

    # pure repartition throughput, no wire involved
    meta_b, parts_b = build_from_json(data, 2)
    t0 = time.perf_counter()
    repartition_arrays(meta_b, parts_b, 3)
    repart_s = time.perf_counter() - t0
    rows_per_sec = (len(nodes) + len(edges)) / max(repart_s, 1e-9)

    tmp = tempfile.mkdtemp(prefix="etpu_bench_reshard_")
    reg = os.path.join(tmp, "reg")
    old_refresh = os.environ.get("EULER_TPU_TOPOLOGY_REFRESH_S")
    os.environ["EULER_TPU_TOPOLOGY_REFRESH_S"] = "0.2"
    svcs, g, writer, co = [], None, None, None
    try:
        src = Graph.from_json(data, num_partitions=2)
        for s in range(2):
            svcs.append(
                GraphService(
                    src.shards[s], src.meta, s,
                    registry=Registry(reg, ttl=10.0),
                    wal_dir=os.path.join(tmp, f"wal_{s}"),
                ).start()
            )
        g = connect(registry_path=reg, num_shards=2)
        writer = GraphWriter(g)

        # acked-write timeline straight through the cutover: the max
        # inter-ack gap IS the client-observed unavailability window
        acked: dict = {}
        stop = threading.Event()
        fail: list = []

        def hammer():
            try:
                i = 0
                stamps = [time.perf_counter()]
                while not stop.is_set():
                    s = int(rng.integers(1, n + 1))
                    d = int(rng.integers(1, n + 1))
                    w = float(i % 7 + 1)
                    writer.upsert_edges([s], [d], [0], [w])
                    writer.flush()
                    acked[(s, d, 0)] = w
                    stamps.append(time.perf_counter())
                    i += 1
                acked["_stamps"] = stamps
            except Exception as e:  # noqa: BLE001
                fail.append(repr(e))

        th = threading.Thread(target=hammer, daemon=True)
        th.start()
        co = ReshardCoordinator(reg, 2, 3, os.path.join(tmp, "rs"))
        report = co.run()
        stop.set()
        th.join(timeout=60)
        if fail or report.get("outcome") != "done":
            raise RuntimeError(f"reshard failed: {fail or report}")
        stamps = acked.pop("_stamps")
        gaps = np.diff(np.asarray(stamps))
        unavail_ms = float(gaps.max()) * 1e3 if len(gaps) else 0.0
        writer.publish()
        writer.close()

        # oracle: base + the acked upserts, from scratch at 3 shards
        by_key = {(e["src"], e["dst"], e["type"]): e for e in data["edges"]}
        for (s, d, t), w in acked.items():
            if (s, d, t) in by_key:
                by_key[(s, d, t)]["weight"] = w
            else:
                data["edges"].append(
                    {"src": s, "dst": d, "type": t, "weight": w,
                     "features": []}
                )
                by_key[(s, d, t)] = data["edges"][-1]
        for proc in co._dest_procs:
            proc.kill()
            proc.wait(timeout=10)
        gen1 = os.path.join(tmp, "rs", "gen_1")
        from euler_tpu.graph import format as tformat
        from euler_tpu.graph import wal as _wal
        from euler_tpu.graph.meta import GraphMeta as _Meta
        from euler_tpu.graph.store import GraphStore as _Store

        meta_r = _Meta.load(os.path.join(gen1, "data"))
        parts_r = []
        for p in range(3):
            arrays = tformat.read_arrays(
                os.path.join(gen1, "data", f"part_{p}"), mmap=False
            )
            rec = _wal.recover(
                meta_r, p, os.path.join(gen1, f"wal_{p}"),
                _Store(meta_r, arrays, p),
            )
            parts_r.append(rec.store.arrays)
        parity = cluster_signature(meta_r, parts_r) == cluster_signature(
            *build_from_json(data, 3)
        )
        return {
            "reshard": True,
            "reshard_bit_parity": bool(parity),
            "reshard_rows_per_sec": round(rows_per_sec, 1),
            "reshard_cutover_ms": round(float(report["cutover_ms"]), 1),
            "reshard_unavail_ms": round(unavail_ms, 1),
        }
    finally:
        if old_refresh is None:
            os.environ.pop("EULER_TPU_TOPOLOGY_REFRESH_S", None)
        else:
            os.environ["EULER_TPU_TOPOLOGY_REFRESH_S"] = old_refresh
        if g is not None:
            g.stop_topology_watch()
        if co is not None:
            for proc in co._dest_procs:
                try:
                    proc.kill()
                except (OSError, ProcessLookupError):
                    pass
        for svc in svcs:
            try:
                svc.stop()
            except OSError:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def _resume_lane(smoke: bool) -> dict:
    """Durable-training lane (ISSUE 10; EULER_BENCH_RESUME=0 opt-out):
    checkpoint cost on the step path with the async writer vs inline
    sync commits (the save-cadence vs step-time tradeoff SCALE.md
    documents), resume-to-first-step latency, retained-checkpoint disk
    footprint, and the `resume_bit_parity` oracle — train 2N straight vs
    train N + fresh-process restore + N, params and per-step losses
    bit-identical under the standing seed contract."""
    import shutil
    import tempfile

    import jax

    from euler_tpu.dataflow import FullNeighborDataFlow
    from euler_tpu.estimator import Estimator, EstimatorConfig
    from euler_tpu.graph import Graph
    from euler_tpu.models import GraphSAGESupervised
    from euler_tpu.training import (
        CheckpointStore,
        SessionConfig,
        TrainingSession,
        resumable_node_batches,
    )

    n, feat_dim, dims, half, cadence = (
        (48, 8, [16, 16], 8, 4) if smoke else (400, 32, [64, 64], 24, 8)
    )
    rng = np.random.default_rng(11)
    nodes = [
        {"id": i + 1, "type": 0, "weight": 1.0,
         "features": [
             {"name": "feat", "type": "dense",
              "value": rng.normal(size=feat_dim).tolist()},
             {"name": "label", "type": "dense",
              "value": [1.0, 0.0] if i % 2 else [0.0, 1.0]},
         ]}
        for i in range(n)
    ]
    edges = [
        {"src": s, "dst": (s + d) % n + 1, "type": 0, "weight": 1.0,
         "features": []}
        for s in range(1, n + 1)
        for d in (1, 2, 3)
    ]
    graph = Graph.from_json({"nodes": nodes, "edges": edges})
    model = GraphSAGESupervised(dims=dims, label_dim=2)
    tmp = tempfile.mkdtemp(prefix="etpu_bench_resume_")

    def make(subdir: str, async_save: bool):
        flow = FullNeighborDataFlow(
            graph, ["feat"], num_hops=len(dims), max_degree=4,
            label_feature="label",
        )
        source = resumable_node_batches(graph, flow, 16, seed=5)
        est = Estimator(
            model, source,
            EstimatorConfig(
                model_dir=os.path.join(tmp, subdir), log_steps=10**9
            ),
        )
        return TrainingSession(
            est, source=source, graph=graph,
            cfg=SessionConfig(
                checkpoint_every=cadence, async_save=async_save,
                anomaly_policy="off",
            ),
        )

    try:
        # step-path checkpoint stall: inline sync commit vs host-snapshot
        # + background writer (same cadence, same state size)
        s_sync = make("sync", async_save=False)
        s_sync.run(2 * half)
        t_sync = s_sync.telemetry
        sync_ms = t_sync["save_stall_ms_total"] / max(t_sync["saves"], 1)

        s_straight = make("straight", async_save=True)
        rep_a = s_straight.run(2 * half)
        t_async = s_straight.telemetry
        async_ms = (
            t_async["save_stall_ms_total"] / max(t_async["saves"], 1)
        )

        # the kill/resume half: fresh session objects over the same
        # model_dir = everything a dead process would have lost
        s_b1 = make("resumed", async_save=True)
        s_b1.run(half)
        s_b2 = make("resumed", async_save=True)
        t0 = time.perf_counter()
        s_b2.restore()
        s_b2.run(1)
        resume_first_ms = (time.perf_counter() - t0) * 1e3
        rep_b = s_b2.run(half - 1)

        la = jax.tree_util.tree_leaves(s_straight.est.params)
        lb = jax.tree_util.tree_leaves(s_b2.est.params)
        parity = all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(la, lb)
        ) and rep_a["losses"][half + 1:] == rep_b["losses"]

        store = CheckpointStore(os.path.join(tmp, "straight"))
        ckpt_bytes = 0
        for step in store.steps():
            d = store._path(step)
            ckpt_bytes += sum(
                os.path.getsize(os.path.join(d, f)) for f in os.listdir(d)
            )
        return {
            "resume": True,
            "resume_save_sync_ms": round(sync_ms, 3),
            "resume_save_async_stall_ms": round(async_ms, 3),
            "resume_to_first_step_ms": round(resume_first_ms, 2),
            "resume_ckpt_bytes": int(ckpt_bytes),
            "resume_retained_ckpts": len(store.steps()),
            "resume_bit_parity": bool(parity),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _analytics_lane(smoke: bool) -> dict:
    """Whole-graph analytics lane (ISSUE 12; EULER_BENCH_ANALYTICS=0
    opt-out): PageRank BSP sweep rate over the 2-shard engine, frontier
    exchange bytes, the incremental-vs-full recompute speedup after a
    live publish, and the `analytics_bit_parity` oracle — 1-shard and
    2-shard runs (and the incremental rerun) must agree bit-for-bit."""
    from euler_tpu.analytics import (
        WholeGraphEngine,
        pagerank,
        rerun_incremental,
    )
    from euler_tpu.distributed.writer import GraphWriter
    from euler_tpu.graph import Graph

    n = 300 if smoke else 3000
    nodes = [
        {"id": i, "type": 0, "weight": 1.0, "features": []}
        for i in range(1, n + 1)
    ]
    edges = [
        {"src": s, "dst": (s + off) % n + 1, "type": off % 2,
         "weight": float(1 + (s + off) % 4), "features": []}
        for s in range(1, n + 1)
        for off in (1, 3, 7)
    ]
    data = {"nodes": nodes, "edges": edges}
    g2 = Graph.from_json(data, num_partitions=2)
    eng = WholeGraphEngine(g2)
    t0 = time.perf_counter()
    r2 = pagerank(g2, engine=eng, max_iters=50, tol=1e-10)
    sweep_s = time.perf_counter() - t0
    r1 = pagerank(Graph.from_json(data, num_partitions=1), max_iters=50,
                  tol=1e-10)
    parity = np.array_equal(
        r1.by_id()[1].view(np.uint64), r2.by_id()[1].view(np.uint64)
    )
    # live publish, then incremental replay vs from-scratch at the new
    # epoch — parity extends to the rerun, speedup is wall-clock
    w = GraphWriter(g2)
    w.upsert_edges([5, 9], [12, max(n // 2, 13)], [0, 1], [9.0, 3.5])
    pub = w.publish()
    t0 = time.perf_counter()
    r_full = pagerank(g2, max_iters=50, tol=1e-10)
    t_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    r_inc = rerun_incremental(g2, r2, publish=pub, engine=eng)
    t_inc = time.perf_counter() - t0
    parity = parity and np.array_equal(
        r_full.values.view(np.uint64), r_inc.values.view(np.uint64)
    )
    return {
        "analytics": True,
        "analytics_bit_parity": bool(parity),
        "analytics_pagerank_sweeps_per_sec": round(
            r2.iterations / max(sweep_s, 1e-9), 2
        ),
        "analytics_exchange_bytes": int(r2.stats["exchange_bytes"]),
        "analytics_incremental_speedup_x": round(
            t_full / max(t_inc, 1e-9), 2
        ),
        "analytics_rows_recomputed_ratio": round(
            r_inc.stats["rows_recomputed"]
            / max(r_full.stats["rows_recomputed"], 1),
            4,
        ),
    }


def _dr_lane(smoke: bool) -> dict:
    """Disaster-recovery lane (ISSUE 15; EULER_BENCH_DR=0 opt-out):
    epoch-consistent backup throughput, total-loss restore-to-first-read
    latency, at-rest scrub throughput and its interference with a live
    reader, and the `dr_bit_parity` oracle — the restored cluster must
    be bit-identical to the one that was archived."""
    import shutil
    import tempfile
    import threading

    from euler_tpu.distributed.service import GraphService
    from euler_tpu.graph import Graph
    from euler_tpu.graph import backup as bk
    from euler_tpu.graph import wal as walmod

    n, batches, rows_per = (60, 24, 64) if smoke else (2000, 120, 256)
    rng = np.random.default_rng(23)
    nodes = [
        {"id": i + 1, "type": 0, "weight": 1.0,
         "features": [{"name": "feat", "type": "dense",
                       "value": rng.normal(size=8).tolist()}]}
        for i in range(n)
    ]
    edges = [
        {"src": s, "dst": s % n + 1, "type": 0, "weight": 1.0,
         "features": []}
        for s in range(1, n + 1)
    ]
    data = {"nodes": nodes, "edges": edges}
    tmp = tempfile.mkdtemp(prefix="etpu_bench_dr_")

    def tree_bytes(root: str) -> int:
        total = 0
        for dirpath, _dirs, files in os.walk(root):
            for f in files:
                total += os.path.getsize(os.path.join(dirpath, f))
        return total

    svc = None
    # deterministic capture: snapshot explicitly mid-stream instead of
    # letting the background cadence thread race the archive step
    old_snap_every = os.environ.get("EULER_TPU_SNAPSHOT_EVERY")
    os.environ["EULER_TPU_SNAPSHOT_EVERY"] = "0"
    try:
        wal_root = os.path.join(tmp, "wal")
        g = Graph.from_json(data, num_partitions=1)
        svc = GraphService(
            g.shards[0], g.meta, 0,
            wal_dir=os.path.join(wal_root, "shard_0"),
        )
        r = np.random.default_rng(7)
        for b in range(batches):
            src = r.integers(1, n + 1, rows_per).astype(np.uint64)
            dst = r.integers(1, n + 1, rows_per).astype(np.uint64)
            svc.dispatch("upsert_edges", [
                f"dr:{b}", src, dst, np.zeros(rows_per, np.int32),
                r.random(rows_per).astype(np.float32),
                np.empty(0, np.uint64), np.empty(0, np.uint64),
                np.empty(0, np.int32), np.empty(0, np.float32),
            ])
            if b % 6 == 5:
                svc.dispatch("publish_epoch", [f"dr:pub:{b}"])
            if b == batches // 2:
                # mixed archive anchor: committed snapshot + WAL suffix
                assert svc.snapshot_now()
        svc.dispatch("publish_epoch", ["dr:pub:final"])
        live = {k: np.array(v) for k, v in svc.store.arrays.items()}
        live_epoch = svc.store.graph_epoch

        # backup throughput over the durable footprint it archives
        arch = os.path.join(tmp, "arch")
        t0 = time.perf_counter()
        bk.backup_cluster(bk.collect_shard_dirs(wal_root), arch)
        backup_s = time.perf_counter() - t0
        arch_mb = tree_bytes(arch) / 1e6

        # total loss: the cluster's durable state is gone; restore, boot
        # a fresh service on the materialized dirs (ctor auto-recovers),
        # and serve a first read
        svc.stop()
        svc = None
        shutil.rmtree(wal_root)
        g2 = Graph.from_json(data, num_partitions=1)
        t0 = time.perf_counter()
        bk.restore_cluster(arch, wal_root)
        svc = GraphService(
            g2.shards[0], g2.meta, 0,
            wal_dir=os.path.join(wal_root, "shard_0"),
        )
        svc.store.get_dense_feature(
            np.arange(1, min(n, 64) + 1, dtype=np.uint64), ["feat"]
        )
        restore_ms = (time.perf_counter() - t0) * 1e3
        parity = (
            svc.store.graph_epoch == live_epoch
            and set(live) == set(svc.store.arrays)
            and all(
                np.array_equal(np.asarray(svc.store.arrays[k]), live[k])
                for k in live
            )
        )

        # at-rest scrub throughput over snapshots + WAL on the restored
        # shard, then back-to-back passes looping in the background while
        # a reader hammers the store — the WORST-CASE interference ratio
        # SCALE.md quotes (a real deployment scrubs on EULER_TPU_SCRUB_S
        # cadence, so the amortized cost scales with the duty cycle)
        shard_dir = os.path.join(wal_root, "shard_0")
        t0 = time.perf_counter()
        rep = svc.scrub_now()
        scrub_s = time.perf_counter() - t0
        scrubbed_mb = (
            rep["wal_bytes_checked"]
            + sum(
                tree_bytes(os.path.join(shard_dir, d))
                for d in os.listdir(shard_dir)
                if walmod.is_committed_snapshot_name(d)
            )
        ) / 1e6

        ids = np.arange(1, min(n, 64) + 1, dtype=np.uint64)

        def read_rate(seconds: float) -> float:
            count, t0 = 0, time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                svc.store.get_dense_feature(ids, ["feat"])
                count += 1
            return count / (time.perf_counter() - t0)

        window = 0.3 if smoke else 1.0
        idle_rate = read_rate(window)
        stop = threading.Event()

        def scrub_loop():
            while not stop.is_set():
                svc.scrub_now()

        t = threading.Thread(target=scrub_loop, daemon=True)
        t.start()
        try:
            busy_rate = read_rate(window)
        finally:
            stop.set()
            t.join(timeout=10)
        return {
            "dr": True,
            "dr_bit_parity": bool(parity),
            "dr_backup_mb_per_sec": round(
                arch_mb / max(backup_s, 1e-9), 2
            ),
            "dr_archive_mb": round(arch_mb, 3),
            "dr_restore_to_first_read_ms": round(restore_ms, 2),
            "dr_scrub_mb_per_sec": round(
                scrubbed_mb / max(scrub_s, 1e-9), 2
            ),
            "dr_read_rate_scrub_over_idle": round(
                busy_rate / max(idle_rate, 1e-9), 3
            ),
        }
    finally:
        if old_snap_every is None:
            os.environ.pop("EULER_TPU_SNAPSHOT_EVERY", None)
        else:
            os.environ["EULER_TPU_SNAPSHOT_EVERY"] = old_snap_every
        if svc is not None:
            svc.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def run(platform: str) -> tuple[float, dict]:
    from euler_tpu.dataflow import SageDataFlow
    from euler_tpu.datasets.synthetic import random_graph

    # sampling runs on device by default (the production path, which
    # --smoke covers too); EULER_BENCH_DEVICE_FLOW=0 forces the host path
    _df_default = os.environ.get("EULER_BENCH_DEVICE_FLOW") != "0"
    if SMOKE:
        num_nodes, out_degree, feat_dim = 2000, 10, 16
        batch_size, fanouts, dims = 64, [5, 5], [32, 32]
        warmup, steps, steps_per_call = 2, 8, 2
    else:
        # K optimizer steps ride one lax.scan dispatch (steps_per_call)
        # and batch 1024 keeps the MXU matmuls large; the metric is
        # absolute edges/s vs the fixed 2M north star, not an A/B of
        # configs. Enough measured calls (30) that steady-state host
        # sampling, not the prefetch queue's head start, dominates the
        # window. EULER_BENCH_FEAT_DIM / EULER_BENCH_DIMS override the
        # model widths for A/B runs.
        num_nodes, out_degree = FLAGSHIP["num_nodes"], FLAGSHIP["out_degree"]
        feat_dim = int(
            os.environ.get("EULER_BENCH_FEAT_DIM", FLAGSHIP["feat_dim"])
        )
        env_dims = os.environ.get("EULER_BENCH_DIMS")
        dims = (
            [int(x) for x in env_dims.split(",")]
            if env_dims else FLAGSHIP["dims"]
        )
        # batch 1024 is the round-comparable headline config;
        # EULER_BENCH_BATCH raises it for max-throughput rows
        batch_size = int(
            os.environ.get("EULER_BENCH_BATCH", FLAGSHIP["batch_size"])
        )
        fanouts = FLAGSHIP["fanouts"]
        # EULER_BENCH_STEPS_PER_CALL: scan depth per dispatch. The
        # device-flow default is 64, the host path keeps 16 (its per-step
        # host sampling cost sits outside the scan, so depth buys nothing
        # there). Neither default has been measured on this chip.
        env_k = os.environ.get("EULER_BENCH_STEPS_PER_CALL")
        steps_per_call = int(env_k) if env_k else (64 if _df_default else 16)
        warmup, steps = 2 * steps_per_call, 30 * steps_per_call

    rng = np.random.default_rng(0)
    graph = random_graph(
        num_nodes=num_nodes, out_degree=out_degree, feat_dim=feat_dim, seed=0
    )
    # round-trip through the on-disk shard format so the C++ engine serves
    # the hot sampling path. The device run builds it from source here and
    # a failure is an error; --smoke takes the engine if it builds.
    import tempfile

    from euler_tpu.graph import Graph
    from euler_tpu.graph import format as tformat
    from euler_tpu.graph.native import NativeGraphStore, build_engine

    if not SMOKE:
        build_engine(force=True)
    d = tempfile.mkdtemp(prefix="etpu_bench_")
    tformat.write_arrays(os.path.join(d, "part_0"), graph.shards[0].arrays)
    graph.meta.save(d)
    graph = Graph.load(d, native=None if SMOKE else True)
    native = isinstance(graph.shards[0], NativeGraphStore)
    # features live in HBM (DeviceFeatureCache); batches ship int32 rows
    from euler_tpu.estimator import DeviceFeatureCache

    cache = DeviceFeatureCache(graph, ["feat"])
    bf16 = BF16 or (not SMOKE and "--fp32" not in sys.argv)

    # device flow: adjacency lives in HBM next to the features and the
    # only per-step input is a PRNG key (see _df_default above)
    device_flow = _df_default
    if device_flow:
        from euler_tpu.dataflow import DeviceSageFlow

        batch_fn = DeviceSageFlow(
            graph, fanouts=fanouts, batch_size=batch_size,
            label_feature="label",
        )
    else:
        # lean wire: ship int32 rows + labels only; edge ids, masks, and
        # the (uniform) weights are rebuilt on device — ~3x fewer H2D bytes
        flow = SageDataFlow(
            graph, ["feat"], fanouts=fanouts, label_feature="label", rng=rng,
            feature_mode="rows", lean=True,
        )

        # fresh Generator per call because batch_fn runs on prefetch
        # producer threads (a shared Generator would race); seeded from an
        # atomic counter so the root stream is reproducible run-to-run
        import itertools

        _root_seq = itertools.count()

        def batch_fn():
            root_rng = np.random.default_rng(
                np.random.SeedSequence([17, next(_root_seq)])
            )
            roots = graph.sample_node(batch_size, rng=root_rng)
            return (flow.query(roots),)

    value, _ = _measure_training(
        batch_fn, cache, dims, batch_size, fanouts,
        warmup, steps, steps_per_call, bf16, "/tmp/euler_tpu_bench",
    )
    extra = {"backend": platform,
             "native_engine": bool(native), "bf16": bool(bf16),
             "steps_per_call": steps_per_call, "device_flow": device_flow,
             "batch_size": batch_size}
    # paged vs dense device-lane A/B on a skewed weighted graph
    # (EULER_BENCH_PAGED=0 opt-out) — the lane the bench-contract test
    # gates: `paged` must not silently vanish from the artifact
    if os.environ.get("EULER_BENCH_PAGED", "1") != "0":
        try:
            extra.update(_paged_device_ab(SMOKE))
        except Exception as e:  # the A/B must never void the headline
            import traceback

            traceback.print_exc()
            extra.update({"paged": False, "paged_error": repr(e)[:300]})
    # streaming-mutation lane (ISSUE 8) — writer throughput, publish
    # latency, read recovery, and the merged==from-scratch parity oracle
    if os.environ.get("EULER_BENCH_MUTATION", "1") != "0":
        try:
            extra.update(_mutation_lane(SMOKE))
        except Exception as e:  # the lane must never void the headline
            import traceback

            traceback.print_exc()
            extra.update({"mutation": False, "mutation_error": repr(e)[:300]})
    # durability lane (ISSUE 9) — acked-writes/s fsync A/B, snapshot
    # cost, crash→recovered-first-read, recovered bit-parity oracle
    if os.environ.get("EULER_BENCH_DURABILITY", "1") != "0":
        try:
            extra.update(_durability_lane(SMOKE))
        except Exception as e:  # the lane must never void the headline
            import traceback

            traceback.print_exc()
            extra.update(
                {"durability": False, "durability_error": repr(e)[:300]}
            )
    # availability lane (ISSUE 13) — quorum/async/solo acked-rows/s,
    # failover write-unavailability window, follower catch-up MB/s, and
    # the caught-up follower == primary bit-parity oracle
    if os.environ.get("EULER_BENCH_AVAILABILITY", "1") != "0":
        try:
            extra.update(_availability_lane(SMOKE))
        except Exception as e:  # the lane must never void the headline
            import traceback

            traceback.print_exc()
            extra.update(
                {"availability": False,
                 "availability_error": repr(e)[:300]}
            )
    # durable-training resume lane (ISSUE 10) — save-stall sync vs async,
    # resume-to-first-step latency, retained-ckpt bytes, bit-parity oracle
    if os.environ.get("EULER_BENCH_RESUME", "1") != "0":
        try:
            extra.update(_resume_lane(SMOKE))
        except Exception as e:  # the lane must never void the headline
            import traceback

            traceback.print_exc()
            extra.update({"resume": False, "resume_error": repr(e)[:300]})
    # whole-graph analytics lane (ISSUE 12) — PageRank sweep rate,
    # exchange bytes, incremental-vs-full speedup, bit-parity oracle
    if os.environ.get("EULER_BENCH_ANALYTICS", "1") != "0":
        try:
            extra.update(_analytics_lane(SMOKE))
        except Exception as e:  # the lane must never void the headline
            import traceback

            traceback.print_exc()
            extra.update(
                {"analytics": False, "analytics_error": repr(e)[:300]}
            )
    # disaster-recovery lane (ISSUE 15) — backup MB/s, total-loss
    # restore-to-first-read, scrub MB/s + reader interference, bit parity
    if os.environ.get("EULER_BENCH_DR", "1") != "0":
        try:
            extra.update(_dr_lane(SMOKE))
        except Exception as e:  # the lane must never void the headline
            import traceback

            traceback.print_exc()
            extra.update({"dr": False, "dr_error": repr(e)[:300]})
    # byte-path lane (ISSUE 16) — dense wire f32/bf16/int8 A/B, varint
    # neighbor planes, compressed+pipelined catch-up vs identity lockstep
    if os.environ.get("EULER_BENCH_BYTES", "1") != "0":
        try:
            extra.update(_bytes_lane(SMOKE))
        except Exception as e:  # the lane must never void the headline
            import traceback

            traceback.print_exc()
            extra.update({"bytes": False, "bytes_error": repr(e)[:300]})
    # retrieval-serving lane (ISSUE 17) — fleet top-K queries/s, latency
    # tails, merge overhead, and the bitwise parity oracle
    if os.environ.get("EULER_BENCH_RETRIEVAL", "1") != "0":
        try:
            extra.update(_retrieval_lane(SMOKE))
        except Exception as e:  # the lane must never void the headline
            import traceback

            traceback.print_exc()
            extra.update(
                {"retrieval": False, "retrieval_error": repr(e)[:300]}
            )
    # elastic-reshard lane (ISSUE 19) — repartition rows/s, cutover
    # window, writer-observed unavailability, bit-parity oracle
    if os.environ.get("EULER_BENCH_RESHARD", "1") != "0":
        try:
            extra.update(_reshard_lane(SMOKE))
        except Exception as e:  # the lane must never void the headline
            import traceback

            traceback.print_exc()
            extra.update(
                {"reshard": False, "reshard_error": repr(e)[:300]}
            )
    return value, extra


def run_serving(platform: str) -> tuple[float, dict]:
    """The online-serving lane (ISSUE 2): a ModelServer over a trained
    checkpoint, hammered by concurrent clients through the wire protocol.
    Reports steady-state request throughput as the headline value, with
    p50/p99 request latency and `batches_per_100_requests` — the measured
    coalescing ratio of the micro-batcher (100 = no coalescing at all;
    the whole point of serving on an accelerator is driving it far below
    that)."""
    import tempfile
    import threading

    from euler_tpu.dataflow import SageDataFlow
    from euler_tpu.datasets.synthetic import random_graph
    from euler_tpu.estimator import Estimator, EstimatorConfig, node_batches
    from euler_tpu.models import GraphSAGESupervised
    from euler_tpu.serving import InferenceRuntime, ModelServer, ServingClient

    if SMOKE:
        num_nodes, feat_dim, dims = 2000, 16, [32, 32]
        fanouts, bucket, ids_per_req = [5, 5], 32, 8
        clients, reqs_per_client = 8, 6
    else:
        num_nodes, feat_dim = FLAGSHIP["num_nodes"], FLAGSHIP["feat_dim"]
        dims, fanouts = FLAGSHIP["dims"], FLAGSHIP["fanouts"]
        bucket, ids_per_req = 128, 16
        clients, reqs_per_client = 16, 50
    graph = random_graph(
        num_nodes=num_nodes, out_degree=10, feat_dim=feat_dim, seed=3
    )
    flow = SageDataFlow(
        graph, ["feat"], fanouts=fanouts, label_feature="label",
        rng=np.random.default_rng(5),
    )
    model = GraphSAGESupervised(dims=dims, label_dim=2)
    cfg = EstimatorConfig(
        model_dir=tempfile.mkdtemp(prefix="etpu_serve_bench_"),
        log_steps=10**9,
    )
    est = Estimator(
        model, node_batches(graph, flow, bucket, rng=np.random.default_rng(7)),
        cfg,
    )
    est.train(total_steps=1, log=False)  # a real (if brief) checkpoint
    runtime = InferenceRuntime(model, flow, cfg, buckets=(bucket,))
    runtime.warmup()
    server = ModelServer(runtime, max_wait_us=2000).start()
    latencies_ms: list[list[float]] = [[] for _ in range(clients)]
    errors: list = []

    def worker(k: int):
        client = ServingClient((server.host, server.port))
        rng = np.random.default_rng(100 + k)
        try:
            for _ in range(reqs_per_client):
                ids = rng.integers(
                    1, num_nodes + 1, size=ids_per_req
                ).astype(np.uint64)
                t0 = time.perf_counter()
                client.predict(ids)
                latencies_ms[k].append((time.perf_counter() - t0) * 1e3)
        except Exception as e:  # lane must report, not die
            errors.append(repr(e)[:200])
        finally:
            client.close()

    try:
        # warm the serving path end to end once before timing
        probe = ServingClient((server.host, server.port))
        probe.predict(np.arange(1, ids_per_req + 1, dtype=np.uint64))
        threads = [
            threading.Thread(target=worker, args=(k,))
            for k in range(clients)
        ]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t_start
        stats = probe.stats()
        probe.close()
    finally:
        server.stop()
    lat = np.asarray([x for chunk in latencies_ms for x in chunk])
    if errors or len(lat) == 0:
        raise RuntimeError(f"serving lane failed: {errors[:3]}")
    total = len(lat)
    extra = {
        "backend": platform,
        "p50_ms": round(float(np.percentile(lat, 50)), 2),
        "p99_ms": round(float(np.percentile(lat, 99)), 2),
        "batches_per_100_requests": round(
            100.0 * stats["batches"] / max(stats["requests"], 1), 1
        ),
        "requests": total,
        "clients": clients,
        "ids_per_request": ids_per_req,
        "bucket": bucket,
        "max_wait_us": stats["max_wait_us"],
        "rejected_overload": stats["rejected_overload"],
        "rejected_deadline": stats["rejected_deadline"],
    }
    return total / elapsed, extra


def _emit_serving(value: float, extra: dict) -> None:
    emit(
        value, extra,
        metric="gnn_serving_requests_per_sec",
        unit="req/s",
        baseline=None,
    )


def run_recovery(platform: str) -> tuple[float, dict]:
    """The recovery lane (ISSUE 4): time-to-first-successful-batch after a
    seeded replica kill, plus the steady-state overhead of the
    deadline/retry plumbing (envelope on vs off on the same stream — must
    stay within noise, or the remote lane just paid for robustness).

    A 1-shard x 2-replica in-process cluster is enough: the lane measures
    failover latency and client-side plumbing cost, not graph throughput
    (the remote leg owns that)."""
    import tempfile

    from euler_tpu.dataflow import SageDataFlow
    from euler_tpu.datasets.synthetic import random_graph
    from euler_tpu.distributed import (
        Fault,
        FaultPlan,
        chaos,
        connect,
        serve_shard,
    )
    from euler_tpu.graph import format as tformat

    num_nodes = 2000 if SMOKE else 20_000
    batch, steps = (32, 6) if SMOKE else (256, 20)
    g = random_graph(
        num_nodes=num_nodes, out_degree=10, feat_dim=16, seed=9
    )
    d = tempfile.mkdtemp(prefix="etpu_recovery_")
    tformat.write_arrays(os.path.join(d, "part_0"), g.shards[0].arrays)
    g.meta.save(d)
    s_a = serve_shard(d, 0, native=False)
    s_b = serve_shard(d, 0, native=False)
    try:
        remote = connect(
            cluster={
                0: [("127.0.0.1", s_a.port), ("127.0.0.1", s_b.port)]
            }
        )
        shard = remote.shards[0]
        shard.QUARANTINE_S = 0.5
        flow = SageDataFlow(
            remote, ["feat"], fanouts=[10], label_feature="label",
            rng=np.random.default_rng(0), feature_mode="rows", lean=True,
        )

        def measure(n):
            t0 = time.perf_counter()
            for _ in range(n):
                flow.minibatch(batch)
            return (time.perf_counter() - t0) / n * 1e3  # ms/batch

        measure(3)  # warm sockets + caches
        per_batch_on_ms = measure(steps)  # deadline envelope on (default)
        shard._deadline_wire = False
        per_batch_off_ms = measure(steps)  # plain ops: pre-PR-4 wire
        shard._deadline_wire = True
        overhead_pct = (
            (per_batch_on_ms - per_batch_off_ms)
            / max(per_batch_off_ms, 1e-9) * 100.0
        )

        # seeded replica kill: replica A resets on every touch from now
        # on; the NEXT batch must fail over inside the deadline
        retries_before = shard.retry_count
        chaos.install(
            FaultPlan(
                [Fault(site="client", kind="reset",
                       replica=("127.0.0.1", s_a.port))],
                seed=1,
            )
        )
        try:
            t0 = time.perf_counter()
            flow.minibatch(batch)
            ttfb_ms = (time.perf_counter() - t0) * 1e3
            post_kill_ms = measure(steps)  # steady state on the survivor
        finally:
            chaos.uninstall()
        extra = {
            "backend": platform,
            "per_batch_ms": round(per_batch_on_ms, 3),
            "per_batch_ms_no_deadline_wire": round(per_batch_off_ms, 3),
            "deadline_wire_overhead_pct": round(overhead_pct, 2),
            "post_kill_per_batch_ms": round(post_kill_ms, 3),
            "failover_retries": shard.retry_count - retries_before,
            "rpc_count": shard.rpc_count,
        }
        return ttfb_ms, extra
    finally:
        s_a.stop()
        s_b.stop()


def _emit_recovery(value: float, extra: dict) -> None:
    emit(
        value, extra,
        metric="rpc_recovery_time_to_first_batch_ms",
        unit="ms",
        baseline=None,
    )


def run_fleet(platform: str) -> tuple[float, dict]:
    """The serving-fleet lane (ISSUE 7): 4 replicated ModelServers behind
    a consistent-hash ServingRouter, hammered by concurrent closed-loop
    clients. Reports aggregate fleet req/s as the headline, plus:

      fleet_scaling_4x — aggregate req/s at 4 replicas over 1 replica.
        Replicas are in-process (device steps release the GIL), so the
        ratio reflects real parallel headroom: ~4x needs >= 4 cores, and
        `fleet_cores` records what this host could physically show.
      hedged_p99_ms / unhedged_p99_ms — p99 with one seeded straggler
        replica (chaos `server delay` on its predict dispatch) with and
        without budget-capped hedging; hedge telemetry proves the hedges
        stayed inside the token bucket.
      reload_parity — zero-downtime hot reload of the same checkpoint on
        one replica, canary rows bit-identical pre/post swap through the
        live batcher.
    """
    import tempfile
    import threading

    from euler_tpu.dataflow import FullNeighborDataFlow
    from euler_tpu.datasets.synthetic import random_graph
    from euler_tpu.distributed import Fault, FaultPlan, chaos
    from euler_tpu.estimator import (
        Estimator,
        EstimatorConfig,
        id_batches,
        node_batches,
    )
    from euler_tpu.models import GraphSAGESupervised
    from euler_tpu.serving import (
        InferenceRuntime,
        ModelServer,
        ServingClient,
        ServingRouter,
    )

    replicas = 4
    if SMOKE:
        num_nodes, feat_dim, dims = 2000, 16, [16, 16]
        bucket, ids_per_req = 16, 16
        clients, reqs = 8, 16
        straggler_reqs = 10
    else:
        num_nodes, feat_dim, dims = 8000, 32, [32, 32]
        bucket, ids_per_req = 32, 32
        clients, reqs = 12, 30
        straggler_reqs = 16
    straggler_delay_s = 0.25
    graph = random_graph(
        num_nodes=num_nodes, out_degree=8, feat_dim=feat_dim, seed=11
    )

    def mkflow():
        # deterministic per root: the precondition for the hedged ==
        # unhedged == offline-infer bit-parity claim
        return FullNeighborDataFlow(
            graph, ["feat"], num_hops=2, max_degree=6, label_feature="label"
        )

    flow = mkflow()
    model = GraphSAGESupervised(dims=dims, label_dim=2)
    cfg = EstimatorConfig(
        model_dir=tempfile.mkdtemp(prefix="etpu_fleet_bench_"),
        log_steps=10**9,
    )
    est = Estimator(
        model,
        node_batches(graph, flow, bucket, rng=np.random.default_rng(13)),
        cfg,
    )
    est.train(total_steps=1, log=False)  # a real (if brief) checkpoint

    servers = []
    for i in range(replicas):
        runtime = InferenceRuntime(model, mkflow(), cfg, buckets=(bucket,))
        runtime.warmup()
        servers.append(ModelServer(runtime, max_wait_us=2000, shard=i).start())
    addrs = [(s.host, s.port) for s in servers]

    def hammer(client, n_clients, n_reqs, seed0):
        lats = [[] for _ in range(n_clients)]
        errors: list = []

        def worker(k):
            rng = np.random.default_rng(
                np.random.SeedSequence([17, seed0, k])
            )
            try:
                for _ in range(n_reqs):
                    ids = rng.integers(
                        1, num_nodes + 1, size=ids_per_req
                    ).astype(np.uint64)
                    t0 = time.perf_counter()
                    client.predict(ids)
                    lats[k].append((time.perf_counter() - t0) * 1e3)
            except Exception as e:  # lane must report, not die
                errors.append(repr(e)[:200])

        threads = [
            threading.Thread(target=worker, args=(k,))
            for k in range(n_clients)
        ]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t_start
        lat = np.asarray([x for chunk in lats for x in chunk])
        if errors or len(lat) == 0:
            raise RuntimeError(f"fleet lane failed: {errors[:3]}")
        return len(lat) / elapsed, lat

    try:
        # bit-parity anchor: routed predictions == offline Estimator.infer
        probe_ids = np.arange(1, min(num_nodes, 64) + 1, dtype=np.uint64)
        batches, chunks = id_batches(flow, probe_ids, bucket)
        _, direct = est.infer(batches, chunks)
        parity_client = ServingClient(addrs, routing="consistent_hash")
        routed = parity_client.predict(probe_ids)
        bit_parity = bool(np.array_equal(routed, direct))
        parity_client.close()

        # warm each replica's wire + flow path once before timing
        for addr in addrs:
            w = ServingClient(addr)
            w.predict(np.arange(1, ids_per_req + 1, dtype=np.uint64))
            w.close()

        # ---- scaling: 1 replica vs 4 replicas, hedging off so the
        # ratio measures routing spread, not duplicate hedge load
        solo_client = ServingClient(
            [addrs[0]],
            routing=ServingRouter([addrs[0]], hedge=False),
        )
        solo_rps, _ = hammer(solo_client, clients, reqs, seed0=1)
        solo_client.close()
        fleet_client = ServingClient(
            addrs,
            routing=ServingRouter(
                addrs, policy="consistent_hash", hedge=False
            ),
        )
        fleet_rps, fleet_lat = hammer(fleet_client, clients, reqs, seed0=2)
        fleet_client.close()

        # ---- hedging under one seeded straggler replica: the chaos
        # `server delay` fault stalls every predict dispatched on the
        # last replica; consistent-hash routing keeps sending ~1/4 of
        # requests into it, so the unhedged p99 IS the straggler
        chaos.install(FaultPlan([
            Fault(site="server", kind="delay", op="predict",
                  shard=replicas - 1, delay_s=straggler_delay_s),
        ], seed=23))
        try:
            unhedged = ServingRouter(
                addrs, policy="consistent_hash", hedge=False
            )
            unhedged_client = ServingClient(addrs, routing=unhedged)
            _, unhedged_lat = hammer(
                unhedged_client, clients, straggler_reqs, seed0=3
            )
            unhedged_client.close()
            # pinned hedge delay (the EULER_TPU_HEDGE_MS shape): with a
            # SEEDED straggler owning ~1/4 of the traffic, the p95 of
            # observed latencies converges onto the straggler itself, so
            # the adaptive delay is the wrong tool for this measurement
            hedged = ServingRouter(
                addrs, policy="consistent_hash", hedge=True,
                hedge_ms=straggler_delay_s * 1e3 * 0.25,
            )
            hedge_cap = hedged._hedge_budget.cap
            hedged_client = ServingClient(addrs, routing=hedged)
            _, hedged_lat = hammer(
                hedged_client, clients, straggler_reqs, seed0=4
            )
            hstats = hedged.stats()
            hedged_client.close()
        finally:
            chaos.uninstall()

        # within-budget proof: every hedge spent a token the bucket
        # could cover (cap + refill-per-success), and none were denied
        # by a dry bucket mid-measurement
        hedged_within_budget = bool(
            hstats["hedges"]
            <= hedge_cap + 0.5 * max(hstats["requests"], 1)
        )

        # ---- zero-downtime hot reload: same checkpoint back in, canary
        # rows through the live batcher must be bit-identical pre/post
        reload_client = ServingClient(addrs[0])
        report = reload_client.reload(
            canary_ids=probe_ids[: min(len(probe_ids), bucket)]
        )
        reload_client.close()
        reload_parity = bool(
            all(
                r.get("canary_parity") is True
                for r in report.values()
            )
        )

        unhedged_p99 = float(np.percentile(unhedged_lat, 99))
        hedged_p99 = float(np.percentile(hedged_lat, 99))
        extra = {
            "backend": platform,
            "replicas": replicas,
            "fleet_cores": os.cpu_count() or 1,
            "routing": "consistent_hash",
            "fleet_req_per_sec": round(fleet_rps, 1),
            "solo_req_per_sec": round(solo_rps, 1),
            "fleet_scaling_4x": round(fleet_rps / max(solo_rps, 1e-9), 3),
            "fleet_p50_ms": round(float(np.percentile(fleet_lat, 50)), 2),
            "fleet_p99_ms": round(float(np.percentile(fleet_lat, 99)), 2),
            "straggler_delay_ms": round(straggler_delay_s * 1e3, 1),
            "unhedged_p99_ms": round(unhedged_p99, 2),
            "hedged_p99_ms": round(hedged_p99, 2),
            "hedge_p99_cut": round(
                unhedged_p99 / max(hedged_p99, 1e-9), 3
            ),
            "hedges_issued": int(hstats["hedges"]),
            "hedges_won": int(hstats["hedges_won"]),
            "hedges_denied": int(hstats["hedges_denied"]),
            "hedge_budget_cap": hedge_cap,
            "hedged_within_budget": hedged_within_budget,
            "reload_parity": reload_parity,
            "fleet_bit_parity": bit_parity,
            "clients": clients,
            "ids_per_request": ids_per_req,
            "bucket": bucket,
        }
        return fleet_rps, extra
    finally:
        for s in servers:
            s.stop()


def _emit_fleet(value: float, extra: dict) -> None:
    emit(
        value, extra,
        metric="gnn_fleet_requests_per_sec",
        unit="req/s",
        baseline=None,
    )


_DATASET_GEN_V = 2  # bump when the synthetic generator changes, so cached
# /tmp datasets from older generator code are never silently reused


def _build_remote_dataset(
    num_nodes, out_degree, feat_dim, shards, weighted=False
) -> str:
    """Materialize (once) a sharded on-disk graph for the remote bench."""
    import tempfile

    from euler_tpu.datasets.synthetic import random_graph
    from euler_tpu.graph import format as tformat

    d = os.path.join(
        tempfile.gettempdir(),
        f"etpu_rbench_v{_DATASET_GEN_V}"
        f"_{num_nodes}_{out_degree}_{feat_dim}_{shards}"
        + ("_w" if weighted else ""),
    )
    if os.path.exists(os.path.join(d, "euler.meta.json")):
        return d
    t0 = time.time()
    g = random_graph(
        num_nodes=num_nodes,
        out_degree=out_degree,
        feat_dim=feat_dim,
        num_partitions=shards,
        seed=0,
        weighted=weighted,
    )
    # build in a temp dir and rename into place: a kill mid-build (driver
    # timeout / watchdog os._exit) must not leave a half-written dataset
    # behind the cache marker — that would poison every later bench run
    # at this deterministic /tmp path
    import shutil

    tmp_d = d + ".build"
    if os.path.exists(tmp_d):
        shutil.rmtree(tmp_d)
    os.makedirs(tmp_d)
    for p, sh in enumerate(g.shards):
        tformat.write_arrays(os.path.join(tmp_d, f"part_{p}"), sh.arrays)
    g.meta.save(tmp_d)
    # a stale dir without the marker (pre-atomic-build kill) blocks the
    # rename; clear it. If a concurrent run renamed a COMPLETE dataset in
    # meanwhile, keep theirs.
    if os.path.exists(d) and not os.path.exists(
        os.path.join(d, "euler.meta.json")
    ):
        shutil.rmtree(d)
    try:
        os.rename(tmp_d, d)
    except OSError:
        if not os.path.exists(os.path.join(d, "euler.meta.json")):
            raise
        shutil.rmtree(tmp_d)
    print(
        f"# remote bench dataset built: {num_nodes} nodes x{out_degree}"
        f" deg, {shards} shards ({time.time() - t0:.0f}s)",
        file=sys.stderr,
    )
    return d


def run_remote(platform: str) -> tuple[float, dict]:
    """The distributed north-star leg: GraphService processes (native
    engine inside) serve a sharded graph over the socket protocol; the
    trainer pulls fused one-RPC minibatches (server-side root sampling +
    multi-hop fanout + labels) while training on the chip.

    This is the reference's core deployment (remote_op.cc:60-120,
    grpc_worker.cc:40-96): graph engine in separate processes, trainer a
    pure client.
    """
    import subprocess
    import tempfile

    from euler_tpu.dataflow import SageDataFlow
    from euler_tpu.distributed import Registry, connect
    from euler_tpu.estimator import DeviceFeatureCache
    from euler_tpu.graph import Graph

    shards = int(os.environ.get("EULER_BENCH_REMOTE_SHARDS", 2))
    if SMOKE:
        num_nodes, out_degree, feat_dim = 2000, 10, 16
        batch_size, fanouts, dims = 64, [5, 5], [32, 32]
        warmup, steps, steps_per_call = 2, 8, 2
    else:
        # >=20M edges served remotely; 1M nodes is a ~130MB bf16 device
        # feature cache. 480 steps = 30 measured scan calls, same window
        # rule as the local leg: steady-state host/RPC sampling, not the
        # prefetch queue's head start, must dominate what is being
        # claimed.
        num_nodes, out_degree = 1_000_000, 20
        feat_dim, dims = FLAGSHIP["feat_dim"], FLAGSHIP["dims"]
        batch_size, fanouts = FLAGSHIP["batch_size"], FLAGSHIP["fanouts"]
        warmup, steps, steps_per_call = 48, 480, 16

    leg_t0 = time.monotonic()

    def note(msg):
        print(f"# remote[{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr)
        sys.stderr.flush()

    # EULER_BENCH_WEIGHTED=1: non-unit edge weights → the weighted-lean
    # wire (bf16 weights next to the rows) instead of the unit-lean wire
    weighted = os.environ.get("EULER_BENCH_WEIGHTED", "0") == "1"
    data = _build_remote_dataset(
        num_nodes, out_degree, feat_dim, shards, weighted=weighted
    )
    reg = tempfile.mkdtemp(prefix="etpu_rbench_reg_")
    global _REMOTE_PROCS
    procs = _REMOTE_PROCS = [
        subprocess.Popen(
            [
                sys.executable, "-m", "euler_tpu.distributed.service",
                "--data", data, "--shard", str(i), "--registry", reg,
            ]
            + (["--no-native"] if SMOKE else []),
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        for i in range(shards)
    ]
    try:
        cluster = Registry(reg).wait_for(
            shards, timeout=min(120.0, REMOTE_BUDGET_S / 2)
        )
        remote = connect(cluster=cluster)
        note(f"{shards} shard servers up")
        # the device feature cache bootstraps from the local mmap of the
        # same shard files (a one-time deployment step — trainers stream
        # or mount the feature table once); per-batch traffic afterwards
        # is int32 rows only
        local = Graph.load(data, native=False)
        import jax.numpy as _jnp

        cache = DeviceFeatureCache(
            local,
            ["feat"],
            dtype=_jnp.float32 if SMOKE else _jnp.bfloat16,
        )
        import jax as _jax

        _jax.block_until_ready(cache.table)
        note(f"feature cache staged ({cache.table.nbytes >> 20}MB)")
        rng = np.random.default_rng(0)
        flow = SageDataFlow(
            remote, ["feat"], fanouts=fanouts, label_feature="label",
            rng=rng, feature_mode="rows", lean=True,
        )
        bf16 = not SMOKE

        # overlapped one-RPC minibatches (EULER_BENCH_INFLIGHT outstanding
        # requests per shard) — the async completion-queue client parity
        inflight = int(os.environ.get("EULER_BENCH_INFLIGHT", "4"))
        # the per-shard executor must be at least as deep as the request
        # window, or the recorded "inflight" would overstate true overlap
        os.environ.setdefault("EULER_TPU_INFLIGHT", str(inflight))
        if inflight > 1:
            from euler_tpu.estimator import pipelined_batches

            batch_fn = pipelined_batches(flow, batch_size, depth=inflight)
        else:
            def batch_fn():
                return (flow.minibatch(batch_size),)

        note("warmup + measure")
        value, _ = _measure_training(
            batch_fn, cache, dims, batch_size, fanouts,
            warmup, steps, steps_per_call, bf16, "/tmp/euler_tpu_rbench",
        )
        if flow._lean_off:
            raise RuntimeError(
                "remote lean wire downgraded during the run — fix before"
                " trusting the number"
            )

        # ---- planner RPC-count lane: measure (not assert) the L×P → P
        # reduction of the fused SPLIT→exec_plan→MERGE fanout vs the
        # per-op per-hop path, on the same roots/config ----
        from euler_tpu.query.plan import plan_mode

        probe_batches = 4
        probe_roots = remote.sample_node(
            batch_size, rng=np.random.default_rng(11)
        )

        def _plan_probe(mode: str) -> tuple[float, float]:
            prev = os.environ.get("EULER_TPU_FUSED_PLAN")
            os.environ["EULER_TPU_FUSED_PLAN"] = mode
            try:
                before = sum(sh.rpc_count for sh in remote.shards)
                t0 = time.perf_counter()
                for k in range(probe_batches):
                    remote.fanout_with_rows(
                        probe_roots, None, fanouts,
                        rng=np.random.default_rng(100 + k),
                    )
                dt = time.perf_counter() - t0
                rpcs = sum(sh.rpc_count for sh in remote.shards) - before
                return rpcs / probe_batches, dt / probe_batches
            finally:
                if prev is None:
                    os.environ.pop("EULER_TPU_FUSED_PLAN", None)
                else:
                    os.environ["EULER_TPU_FUSED_PLAN"] = prev

        fused_rpcs, fused_s = _plan_probe("1")
        perop_rpcs, perop_s = _plan_probe("0")
        note(
            f"plan lane: fused {fused_rpcs:.1f} rpc/batch"
            f" ({fused_s * 1e3:.0f}ms) vs per-op {perop_rpcs:.1f}"
            f" ({perop_s * 1e3:.0f}ms)"
        )

        # ---- client read-cache lane (EULER_BENCH_CACHE=0 opt-out): the
        # dense-feature remote SAGE path, measured uncached (kill switch)
        # vs warm-cache on the SAME roots and seeds. Warm batches serve
        # hot feature rows client-side and dedup ids before the wire —
        # the repeated-hot-node regime every power-law graph lives in.
        # Results are bit-identical across all three passes (the cached
        # lane's standing contract, pinned by tests/test_read_cache.py).
        cache_extra = {}
        if os.environ.get("EULER_BENCH_CACHE", "1") != "0":
            from euler_tpu.distributed.cache import (
                GATHER_DEDUP,
                clear_graph_caches,
                graph_cache_stats,
            )

            gd_before = dict(GATHER_DEDUP)

            ab_batches = 2 if SMOKE else 4
            dense_flow = SageDataFlow(
                remote, ["feat"], fanouts=fanouts, label_feature="label",
                rng=np.random.default_rng(31), feature_mode="dense",
            )
            ab_roots = [
                remote.sample_node(
                    batch_size, rng=np.random.default_rng(300 + i)
                )
                for i in range(ab_batches)
            ]

            def ab_pass():
                dense_flow.rng = np.random.default_rng(77)
                t0 = time.perf_counter()
                for r in ab_roots:
                    dense_flow.query(r)
                return time.perf_counter() - t0

            saved = [sh._cache for sh in remote.shards]
            for sh in remote.shards:
                sh._cache = None
            uncached_s = ab_pass()
            for sh, c in zip(remote.shards, saved):
                sh._cache = c
            clear_graph_caches(remote)
            cold_s = ab_pass()  # miss pass: dedup + write-back only
            warm_s = ab_pass()  # same roots/seeds → hot rows hit
            st = graph_cache_stats(remote) or {}
            edges_ab = 0
            width = batch_size
            for k in fanouts:
                edges_ab += width * k
                width *= k
            edges_ab *= ab_batches
            # dedup savings = cache-layer residual dedup + the dataflow
            # layer's cross-hop unique-ID coalescing (gather_unique)
            dedup_saved = int(st.get("dedup_bytes_saved", 0)) + (
                GATHER_DEDUP["bytes_saved"] - gd_before["bytes_saved"]
            )
            cache_extra = {
                "cache_hit_rate": st.get("hit_rate", 0.0),
                "dedup_bytes_saved": dedup_saved,
                "cache_bytes_saved": int(st.get("bytes_saved", 0)),
                "cache_uncached_edges_per_sec": round(edges_ab / uncached_s, 1),
                "cache_cold_edges_per_sec": round(edges_ab / cold_s, 1),
                "cache_warm_edges_per_sec": round(edges_ab / warm_s, 1),
                "cache_warm_over_uncached": round(uncached_s / warm_s, 3),
            }
            note(
                f"cache lane: warm {uncached_s / warm_s:.2f}x uncached"
                f" (hit rate {st.get('hit_rate', 0.0):.2f},"
                f" dedup saved {dedup_saved >> 20}MB)"
            )

        # ---- paged device sub-lane (EULER_BENCH_PAGED=0 opt-out): stage
        # the ragged paged adjacency FROM THE REMOTE CLUSTER over the wire
        # (ids_by_rows + get_full_neighbor sweeps, deterministic verbs →
        # read-cache-served on repeats), then sample fully on device —
        # zero wire bytes per step — and drive residual feature-row
        # re-fetches through the ReadCache-backed double-buffer ring.
        def _paged_remote_lane() -> dict:
            import jax as _jx

            from euler_tpu.dataflow import DeviceSageFlow
            from euler_tpu.estimator import ResidualFetchRing

            t0 = time.perf_counter()
            dflow = DeviceSageFlow(
                remote, fanouts=fanouts, batch_size=batch_size,
                label_feature="label", layout="paged",
            )
            stage_s = time.perf_counter() - t0
            fn = _jx.jit(dflow.sample)
            _jx.block_until_ready(
                _jx.tree_util.tree_leaves(fn(_jx.random.PRNGKey(0)))
            )
            reps = 4 if SMOKE else 20
            t0 = time.perf_counter()
            out = None
            for t in range(reps):
                out = fn(_jx.random.PRNGKey(1 + t))
            _jx.block_until_ready(_jx.tree_util.tree_leaves(out))
            dt = time.perf_counter() - t0
            eps_step = 0
            width = batch_size
            for k in fanouts:
                eps_step += width * k
                width *= k
            ring = ResidualFetchRing(cache, remote)
            try:
                rows = np.arange(min(4096, num_nodes), dtype=np.int64)
                for _ in range(2):  # pass 1 fills the read cache, 2 hits
                    ring.prefetch(rows)
                    ring.flush()
                rst = ring.stats()
            finally:
                ring.close()
            note(
                f"paged device lane: staged in {stage_s:.1f}s,"
                f" {reps * eps_step / dt:.0f} edges/s on-device,"
                f" residual hit rate {rst['residual_fetch_hit_rate']:.2f}"
            )
            return {
                "device_flow": True,
                "paged": True,
                "paged_stage_s": round(stage_s, 2),
                "paged_device_edges_per_sec": round(
                    reps * eps_step / dt, 1
                ),
                "residual_fetch_hit_rate": rst["residual_fetch_hit_rate"],
                "residual_rows_refetched": rst["fetched_rows"],
            }

        paged_extra = {}
        if os.environ.get("EULER_BENCH_PAGED", "1") != "0":
            if time.monotonic() - leg_t0 > REMOTE_BUDGET_S * 0.5:
                # never let the sub-lane push the leg past the watchdog
                paged_extra = {"paged": False, "paged_skipped": "budget"}
            else:
                try:
                    paged_extra = _paged_remote_lane()
                except Exception as e:  # must never void the remote number
                    import traceback

                    traceback.print_exc()
                    paged_extra = {
                        "paged": False, "paged_error": repr(e)[:300],
                    }
        extra = {
            "backend": platform,
            "shards": shards,
            "server_processes": shards,
            "edges_total": num_nodes * out_degree,
            "steps_per_call": steps_per_call,
            "bf16": bool(bf16),
            "weighted_lean": bool(weighted),
            "inflight": inflight,
            "remote_fused": plan_mode() == "fused",
            "remote_rpcs_per_batch": round(fused_rpcs, 2),
            "remote_rpcs_per_batch_per_op": round(perop_rpcs, 2),
            "remote_plan_ms_fused": round(fused_s * 1e3, 1),
            "remote_plan_ms_per_op": round(perop_s * 1e3, 1),
            **cache_extra,
            **paged_extra,
        }
        return value, extra
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                pass


def _emit_remote(value: float, extra: dict) -> None:
    emit(value, extra, metric="graphsage_remote_edges_per_sec_per_chip")


def main():
    from euler_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    platform = warm_backend()
    remote_enabled = os.environ.get("EULER_BENCH_REMOTE", "1") != "0"
    serving_enabled = os.environ.get("EULER_BENCH_SERVING", "1") != "0"
    recovery_enabled = os.environ.get("EULER_BENCH_RECOVERY", "1") != "0"
    fleet_enabled = os.environ.get("EULER_BENCH_FLEET", "1") != "0"

    # ---- fleet-only mode: just the serving-fleet lane (its own JSON
    # contract line), for the fleet gate in tests/test_bench_contract.py
    if "--fleet-only" in sys.argv:
        try:
            f_value, f_extra = run_fleet(platform)
            _emit_fleet(f_value, f_extra)
        except Exception as e:
            import traceback

            traceback.print_exc()
            _emit_fleet(0.0, {"backend": platform, "error": repr(e)[:300]})
        return

    # ---- LOCAL leg first: the headline artifact is emitted before the
    # remote leg can spend a second of the driver's timeout. A failure
    # here propagates: a failed headline leg exits non-zero.
    value, extra = None, {}
    if "--remote-only" not in sys.argv:
        value, extra = run(platform)
        emit(value, extra)

    # ---- SERVING lane: in-process server + concurrent wire clients.
    # Cheap relative to the legs (seconds of requests against a tiny
    # checkpoint), and emitted immediately like the local leg so a later
    # timeout can't void it.
    if serving_enabled and "--remote-only" not in sys.argv:
        try:
            s_value, s_extra = run_serving(platform)
            _emit_serving(s_value, s_extra)
            extra = dict(
                extra,
                serving_requests_per_sec=round(float(s_value), 1),
                serving_p50_ms=s_extra["p50_ms"],
                serving_p99_ms=s_extra["p99_ms"],
                serving_batches_per_100_requests=s_extra[
                    "batches_per_100_requests"
                ],
            )
        except Exception as e:
            import traceback

            traceback.print_exc()
            _emit_serving(0.0, {"backend": platform, "error": repr(e)[:300]})

    # ---- RECOVERY lane: seeded replica kill against a tiny in-process
    # replica pair — seconds of wall clock, emitted immediately.
    if recovery_enabled and "--remote-only" not in sys.argv:
        try:
            r_value, r_extra = run_recovery(platform)
            _emit_recovery(r_value, r_extra)
            extra = dict(
                extra,
                recovery_ttfb_ms=round(float(r_value), 1),
                recovery_deadline_wire_overhead_pct=r_extra[
                    "deadline_wire_overhead_pct"
                ],
            )
        except Exception as e:
            import traceback

            traceback.print_exc()
            _emit_recovery(
                0.0, {"backend": platform, "error": repr(e)[:300]}
            )

    # ---- FLEET lane: 4 in-process replicas behind the router, seeded
    # straggler + hedging, hot reload — seconds of wall clock, emitted
    # immediately like the lanes above.
    if fleet_enabled and "--remote-only" not in sys.argv:
        try:
            f_value, f_extra = run_fleet(platform)
            _emit_fleet(f_value, f_extra)
            extra = dict(
                extra,
                fleet_req_per_sec=round(float(f_value), 1),
                fleet_scaling_4x=f_extra["fleet_scaling_4x"],
                hedged_p99_ms=f_extra["hedged_p99_ms"],
                reload_parity=f_extra["reload_parity"],
            )
        except Exception as e:
            import traceback

            traceback.print_exc()
            _emit_fleet(0.0, {"backend": platform, "error": repr(e)[:300]})

    if not remote_enabled:
        if "--remote-only" in sys.argv:
            raise SystemExit("bench.py: --remote-only with EULER_BENCH_REMOTE=0")
        if (
            serving_enabled or recovery_enabled or fleet_enabled
        ) and value is not None:
            # the serving lane printed after the headline; re-emit the
            # headline (serving summary attached) so BOTH first-line and
            # last-line parsers still read the local number
            emit(value, extra)
        return

    # ---- REMOTE leg under an internal wall-clock budget. The watchdog
    # force-emits partial results and exits 124 on expiry; anything
    # already printed (the local line above) is preserved.
    import threading

    done = threading.Event()

    def _watchdog():
        if done.wait(REMOTE_BUDGET_S):
            return
        _emit_remote(0.0, {
            "error": f"remote leg exceeded internal budget"
                     f" ({REMOTE_BUDGET_S:.0f}s)",
        })
        if value is not None:  # re-emit the headline as the final line
            emit(value, extra)
        for p in _REMOTE_PROCS:
            try:
                p.kill()
            except Exception:
                pass
        os._exit(124)

    threading.Thread(target=_watchdog, daemon=True).start()
    try:
        remote_value, remote_extra = run_remote(platform)
    finally:
        done.set()
    _emit_remote(remote_value, remote_extra)
    if "--remote-only" in sys.argv or value is None:
        return
    # final combined headline line: whichever line the driver parses (first
    # or last), it carries the verified local number
    emit(value, dict(extra, remote_edges_per_sec=round(float(remote_value), 1)))


if __name__ == "__main__":
    main()
