"""The quickest proof that euler_tpu still starts on the chip.

    python chip_smoke.py          # on a machine with one TPU chip

ONE process, which owns the chip from first touch to exit, drives the
repo's main paths once through the entry points a user calls:

  device   jax.devices(); anything but a TPU exits non-zero at once
  engine   cpp/graph_engine.cc built in this run, Graph.load(native=True)
  trainer  GraphSAGESupervised(dims=[128,128], bf16) on the flagship graph
           (200 k nodes x 15, feat 64, batch 1024, fanout [10,10],
           16 steps per dispatch) — the device lane (DeviceSageFlow) and
           the host lane (SageDataFlow rows/lean + 4 prefetch workers
           that device_put)
  server   the model the device lane trained, checkpointed, restored by an
           InferenceRuntime over a sampled [10,10] flow on the same graph
           with the default buckets, served by a ModelServer; a
           ServingClient's predictions must equal offline Estimator.infer
           bit for bit. Then tools.serve.selftest (concurrent clients,
           coalescing and the durability probe, at toy width)
  frontier the analytics f64 multiply against numpy, within the bound
           dataflow/device.py states for an emulated f64
  cache    the device-lane train step compiled a second time after
           jax.clear_caches() must come from the persistent compile cache

Any phase that fails ends the run with a non-zero exit code and no result
line. A passing run prints one `report: {...}` line (per-phase status,
cache directory and hits; its seconds are set-up times for the record,
not metrics) and then, as the last stdout line, exactly

    {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}

with the device as JAX reports it.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import tempfile
import time

import numpy as np

from bench import FLAGSHIP

NUM_NODES, OUT_DEGREE = FLAGSHIP["num_nodes"], FLAGSHIP["out_degree"]
FEAT_DIM, DIMS = FLAGSHIP["feat_dim"], FLAGSHIP["dims"]
BATCH, FANOUTS = FLAGSHIP["batch_size"], FLAGSHIP["fanouts"]
STEPS_PER_CALL = 16
WARM_DISPATCHES, MORE_DISPATCHES = 2, 3
# served request sizes: one above the top bucket (chunked 128+128+44), one
# in the middle bucket, one in the smallest
SERVE_REQUESTS = (300, 20, 5)


def phase_engine(workdir: str):
    """Flagship graph, round-tripped through the on-disk shard format so
    the C++ engine — built here, from source — serves it."""
    from euler_tpu.datasets.synthetic import random_graph
    from euler_tpu.graph import Graph
    from euler_tpu.graph import format as tformat
    from euler_tpu.graph.native import NativeGraphStore, build_engine

    t0 = time.perf_counter()
    so_path = build_engine(force=True)
    build_s = time.perf_counter() - t0
    graph = random_graph(
        num_nodes=NUM_NODES, out_degree=OUT_DEGREE, feat_dim=FEAT_DIM, seed=0
    )
    os.makedirs(workdir)
    tformat.write_arrays(
        os.path.join(workdir, "part_0"), graph.shards[0].arrays
    )
    graph.meta.save(workdir)
    graph = Graph.load(workdir, native=True)
    assert isinstance(graph.shards[0], NativeGraphStore), type(graph.shards[0])
    return graph, {"ok": True, "build_s": round(build_s, 2), "so": so_path}


def _on_tpu(tree) -> bool:
    import jax

    return all(
        d.platform == "tpu"
        for leaf in jax.tree_util.tree_leaves(tree)
        for d in leaf.devices()
    )


def _train_and_check(est, cache) -> dict:
    """Two warm dispatches (the first compiles), then a few more; every
    loss finite, the loss falling, params moving and resident on the TPU."""
    import jax

    k = STEPS_PER_CALL
    t0 = time.perf_counter()
    losses = est.train(total_steps=WARM_DISPATCHES * k, log=False, save=False)
    jax.block_until_ready(est.params)
    first_s = time.perf_counter() - t0
    before = jax.device_get(est.params)
    t0 = time.perf_counter()
    losses += est.train(total_steps=MORE_DISPATCHES * k, log=False, save=False)
    jax.block_until_ready(est.params)
    warm_s = time.perf_counter() - t0
    after = jax.device_get(est.params)

    losses = np.asarray(losses, np.float64)
    assert losses.shape == ((WARM_DISPATCHES + MORE_DISPATCHES) * k,), (
        losses.shape
    )
    assert np.isfinite(losses).all(), losses
    assert losses[-k:].mean() < losses[:k].mean(), (
        "loss did not fall", losses[:k].mean(), losses[-k:].mean()
    )
    moved = [
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(after)
        )
    ]
    assert all(moved), f"{moved.count(False)} param leaves did not change"
    assert _on_tpu(est.params), "params are not on a TPU device"
    assert _on_tpu(cache.table), "feature table is not on a TPU device"
    return {
        "ok": True,
        "steps": int(len(losses)),
        "loss_first": round(float(losses[:k].mean()), 4),
        "loss_last": round(float(losses[-k:].mean()), 4),
        "setup_first_two_dispatches_s": round(first_s, 2),
        "setup_next_three_dispatches_s": round(warm_s, 3),
    }


def _model():
    import jax.numpy as jnp

    from euler_tpu.models import GraphSAGESupervised

    return GraphSAGESupervised(
        dims=DIMS, label_dim=2, conv_kwargs={"dtype": jnp.bfloat16}
    )


def _estimator(batch_fn, cache, model_dir: str):
    from euler_tpu.estimator import Estimator, EstimatorConfig

    return Estimator(
        _model(),
        batch_fn,
        EstimatorConfig(
            model_dir=model_dir,
            learning_rate=0.01,
            log_steps=10**9,
            steps_per_call=STEPS_PER_CALL,
        ),
        feature_cache=cache,
    )


def phase_device_lane(graph, cache, workdir: str):
    from euler_tpu.dataflow import DeviceSageFlow

    flow = DeviceSageFlow(
        graph, fanouts=FANOUTS, batch_size=BATCH, label_feature="label"
    )
    est = _estimator(flow, cache, os.path.join(workdir, "ckpt_device"))
    return flow, est, _train_and_check(est, cache)


def phase_host_lane(graph, cache, workdir: str) -> dict:
    import itertools

    from euler_tpu.dataflow import SageDataFlow
    from euler_tpu.estimator.estimator import stack_batches
    from euler_tpu.estimator.prefetch import Prefetcher

    flow = SageDataFlow(
        graph, ["feat"], fanouts=FANOUTS, label_feature="label",
        rng=np.random.default_rng(0), feature_mode="rows", lean=True,
    )
    # batch_fn runs on the prefetch worker threads: a fresh Generator per
    # call (a shared one would race), seeded from an atomic counter
    seq = itertools.count()

    def batch_fn():
        rng = np.random.default_rng(np.random.SeedSequence([17, next(seq)]))
        return (flow.query(graph.sample_node(BATCH, rng=rng)),)

    prefetch = Prefetcher(
        stack_batches(batch_fn, STEPS_PER_CALL),
        depth=4, workers=4, device_put=True,
    )
    try:
        est = _estimator(prefetch, cache, os.path.join(workdir, "ckpt_host"))
        return _train_and_check(est, cache)
    finally:
        prefetch.close()


class _CacheLog(logging.Handler):
    """Module names of jax's persistent-cache hits and misses, read from
    its compiler log: jax.monitoring counts the events but does not say
    which program each one was."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.hits: list[str] = []
        self.misses: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = str(record.msg)
        if msg.startswith("Persistent compilation cache hit"):
            self.hits.append(record.args[0])
        elif msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.misses.append(record.args[0])


def phase_cache(flow, cache, workdir: str, log: _CacheLog) -> dict:
    """The second identical program: with the in-memory executables
    dropped, the device-lane train step — by name — must load from the
    persistent cache instead of compiling again."""
    import jax

    step = "jit_multi_step"
    n_hits, n_misses = len(log.hits), len(log.misses)
    jax.clear_caches()
    est = _estimator(flow, cache, os.path.join(workdir, "ckpt_cache"))
    t0 = time.perf_counter()
    losses = est.train(total_steps=STEPS_PER_CALL, log=False, save=False)
    jax.block_until_ready(est.params)
    again_s = time.perf_counter() - t0
    assert np.isfinite(losses).all(), losses
    hits, misses = log.hits[n_hits:], log.misses[n_misses:]
    assert step in hits and step not in misses, (
        f"second compile of {step} did not come from the cache", hits, misses
    )
    return {
        "ok": True,
        "second_program": step,
        "second_program_cache_hit": True,
        "programs_from_cache": hits,
        "setup_second_program_s": round(again_s, 2),
    }


def phase_server(graph, cache, est) -> dict:
    """Serve what the device lane trained, at full width. The flow samples,
    so a prediction is replayable only from the same Generator state: the
    served flow is re-seeded after warm-up, the requests go one at a time,
    and the offline reference draws from an identically seeded flow in the
    same order."""
    import jax

    from euler_tpu.dataflow import SageDataFlow
    from euler_tpu.estimator import EstimatorConfig, id_batches
    from euler_tpu.serving import InferenceRuntime, ModelServer, ServingClient

    seed = 5

    def sampled_flow():
        return SageDataFlow(
            graph, ["feat"], fanouts=FANOUTS, label_feature="label",
            rng=np.random.default_rng(seed), feature_mode="rows",
        )

    rng = np.random.default_rng(11)
    requests = [
        rng.integers(1, NUM_NODES + 1, size=n).astype(np.uint64)
        for n in SERVE_REQUESTS
    ]
    est.save()
    t0 = time.perf_counter()
    flow = sampled_flow()
    runtime = InferenceRuntime(
        _model(), flow, EstimatorConfig(model_dir=est.cfg.model_dir),
        feature_cache=cache,
    )
    runtime.warmup()
    setup_s = time.perf_counter() - t0
    restored = jax.tree_util.tree_leaves(jax.device_get(runtime.params))
    trained = jax.tree_util.tree_leaves(jax.device_get(est.params))
    assert len(restored) == len(trained) and all(
        np.array_equal(a, b) for a, b in zip(restored, trained)
    ), "restored checkpoint differs from the trained params"
    assert _on_tpu(runtime.params), "served params are not on a TPU device"

    flow.rng = np.random.default_rng(seed)
    server = ModelServer(runtime).start()
    try:
        client = ServingClient((server.host, server.port))
        try:
            t0 = time.perf_counter()
            served = [client.predict(ids) for ids in requests]
            answer_s = time.perf_counter() - t0
            stats = client.stats()
        finally:
            client.close()
    finally:
        server.stop()

    ref_flow = sampled_flow()
    for ids, emb in zip(requests, served):
        _, ref = est.infer(
            *id_batches(ref_flow, ids, runtime.bucket_for(len(ids)))
        )
        assert emb.shape == (len(ids), DIMS[-1]), emb.shape
        assert emb.dtype == ref.dtype, (emb.dtype, ref.dtype)
        assert np.isfinite(emb).all()
        assert np.array_equal(emb, ref), (
            f"served prediction for {len(ids)} ids differs from offline infer"
        )
    assert stats["requests"] == len(requests), stats
    return {
        "ok": True,
        "buckets": list(runtime.buckets),
        "request_sizes": list(SERVE_REQUESTS),
        "device_batches": int(runtime.device_batches),
        "bit_parity_with_infer": True,
        "setup_restore_and_warm_s": round(setup_s, 2),
        "setup_answer_s": round(answer_s, 3),
    }


def phase_serve_selftest() -> dict:
    from euler_tpu.tools.serve import selftest

    t0 = time.perf_counter()
    rc = selftest(replicas=1)
    if rc != 0:
        raise SystemExit(f"chip_smoke: serving selftest exited {rc}")
    return {"ok": True, "setup_total_s": round(time.perf_counter() - t0, 2)}


def phase_frontier() -> dict:
    """dataflow/device.py's contract for the analytics device lane: the
    f64 gather-multiply stays within FRONTIER_F64_RTOL of numpy on a chip
    that emulates f64."""
    from euler_tpu.dataflow.device import FRONTIER_F64_RTOL, frontier_contrib

    rng = np.random.default_rng(3)
    vec, w = rng.random(5_000), rng.random(40_000) * 4.0
    src = rng.integers(0, len(vec), len(w))
    out, ref = frontier_contrib(w, vec, src), w * vec[src]
    assert out.dtype == np.float64 and out.shape == ref.shape
    err = float(np.max(np.abs(out - ref) / ref))
    assert err <= FRONTIER_F64_RTOL, (err, FRONTIER_F64_RTOL)
    return {"ok": True, "max_rel_err": err, "bound": FRONTIER_F64_RTOL}


def result_line(devices) -> str:
    """The last stdout line of a passing run: these keys and no others,
    the device as JAX reports it."""
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    return json.dumps({"ok": True, "device": device})


def main() -> int:
    t_start = time.perf_counter()
    from euler_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax
    import jaxlib

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(
            f"chip_smoke: jax platform is {platform!r}, not 'tpu' — this "
            "script only passes on the chip and has no CPU fallback",
            file=sys.stderr,
        )
        return 2
    print(
        f"device: platform={platform} device_kind={devices[0].device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} compile_cache={cache_dir}",
        flush=True,
    )
    # jax logs cache hits and misses at DEBUG; keep them from its own
    # stderr handler (that logger says nothing above DEBUG by default)
    cache_log = _CacheLog()
    compiler_log = logging.getLogger("jax._src.compiler")
    compiler_log.addHandler(cache_log)
    compiler_log.setLevel(logging.DEBUG)
    compiler_log.propagate = False

    phases: dict = {}

    def done(name: str, report: dict) -> None:
        phases[name] = report
        print(f"phase {name}: {json.dumps(report)}", flush=True)

    with tempfile.TemporaryDirectory(prefix="etpu_chip_smoke_") as workdir:
        from euler_tpu.estimator import DeviceFeatureCache

        graph, report = phase_engine(os.path.join(workdir, "graph"))
        done("engine", report)
        cache = DeviceFeatureCache(graph, ["feat"])
        flow, est, report = phase_device_lane(graph, cache, workdir)
        done("trainer_device_lane", report)
        done("trainer_host_lane", phase_host_lane(graph, cache, workdir))
        done("server", phase_server(graph, cache, est))
        done("serve_selftest", phase_serve_selftest())
        done("frontier_f64", phase_frontier())
        done("compile_cache", phase_cache(flow, cache, workdir, cache_log))

    report = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "compile_cache_dir": cache_dir,
        # lookups count every jitted program, hits only those that took
        # jax's >= 1 s to compile and so were stored
        "compile_cache_hits": len(cache_log.hits),
        "compile_cache_lookups": len(cache_log.hits) + len(cache_log.misses),
        "total_s": round(time.perf_counter() - t_start, 1),
        "phases": phases,
    }
    print(f"report: {json.dumps(report)}", flush=True)
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
