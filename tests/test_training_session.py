"""Durable training sessions (ISSUE 10): atomic retained checkpoints,
preemption-safe bit-exact resume, anomaly guard, watchdog, and
supervised trainer restart under live traffic.

The acceptance proofs pinned here:

- a crash injected at ANY point of a checkpoint save never loses the
  previous complete checkpoint (torn-dir sweep, test_wal.py style);
- train 2N straight == train N + kill -9 + SUPERVISED resume N: params
  and per-step losses bit-identical under the standing seed contract,
  including across a concurrent graph-mutation publish;
- serving reload provably never swaps in an incomplete checkpoint;
- non-finite bursts and hung steps fail TYPED (AnomalyError /
  HungStepError) instead of poisoning params or hanging silently;
- the PR 9-style chaos scenario: seeded kill -9 of the trainer under a
  live mutation stream + 2-replica fleet serving, final recovered
  params bit-identical to the uninterrupted run, zero typed-error
  leaks.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from euler_tpu.estimator import Estimator, EstimatorConfig
from euler_tpu.graph import Graph
from euler_tpu.graph.builder import convert_json
from euler_tpu.models import GraphSAGESupervised
from euler_tpu.training import (
    AnomalyError,
    CheckpointStore,
    HungStepError,
    ResumableSource,
    SessionConfig,
    TrainingSession,
    resumable_node_batches,
)
from euler_tpu.training import checkpoint as ckptmod


def _graph_dict(n=24, feat_dim=4, seed=0):
    rng = np.random.default_rng(seed)
    nodes = [
        {
            "id": i,
            "type": 0,
            "weight": 1.0,
            "features": [
                {"name": "feat", "type": "dense",
                 "value": rng.normal(size=feat_dim).tolist()},
                {"name": "label", "type": "dense",
                 "value": [1.0, 0.0] if i % 2 else [0.0, 1.0]},
            ],
        }
        for i in range(1, n + 1)
    ]
    edges = [
        {"src": s, "dst": (s + off) % n + 1, "type": 0,
         "weight": 1.0, "features": []}
        for s in range(1, n + 1)
        for off in (1, 2, 3)
    ]
    return {"nodes": nodes, "edges": edges}


MODEL = GraphSAGESupervised(dims=[8, 8], label_dim=2)


def _flow(graph):
    from euler_tpu.dataflow import FullNeighborDataFlow

    return FullNeighborDataFlow(
        graph, ["feat"], num_hops=2, max_degree=4, label_feature="label"
    )


def _session(graph, model_dir, cadence=4, source=None, **cfg_kw):
    source = source if source is not None else resumable_node_batches(
        graph, _flow(graph), 8, seed=3
    )
    est = Estimator(
        MODEL, source,
        EstimatorConfig(model_dir=str(model_dir), log_steps=10**9, seed=0),
    )
    sess = TrainingSession(
        est, source=source, graph=graph,
        cfg=SessionConfig(checkpoint_every=cadence, **cfg_kw),
    )
    return sess, est, source


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


# ---------------------------------------------------------------------------
# checkpoint store: atomicity, retention, torn-dir sweep
# ---------------------------------------------------------------------------


def test_checkpoint_store_roundtrip_and_gc(tmp_path):
    store = CheckpointStore(str(tmp_path / "m"), keep=2)
    p = [np.arange(6, dtype=np.float32).reshape(2, 3),
         np.asarray(0.5, np.float64)]
    o = [np.asarray(3, np.int32), np.arange(4, dtype=np.int64)]
    for step in (2, 4, 6):
        store.save_leaves(step, p, o, {"cursor": step + 1})
    # keep=2: the oldest complete checkpoint was reaped
    assert store.steps() == [4, 6]
    got = store.load()
    assert got["step"] == 6 and got["meta"]["cursor"] == 7
    for a, b in zip(got["params"], p):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    for a, b in zip(got["opt_state"], o):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    # re-saving a committed step is a no-op, not a torn rewrite
    store.save_leaves(6, p, o)
    assert store.steps() == [4, 6]


def test_torn_checkpoint_sweep_never_loses_previous_good(tmp_path):
    """Simulate a crash at every distinguishable point of the save
    protocol: whatever survives on disk, the previous complete
    checkpoint remains the one restore sees, and gc reaps the wreck."""
    root = str(tmp_path / "m")
    store = CheckpointStore(root, keep=3)
    p = [np.arange(8, dtype=np.float32)]
    o = [np.asarray(1, np.int32)]
    store.save_leaves(4, p, o, {"cursor": 5})
    good = store._path(4)

    def crash_states():
        # a committed template to mutilate into each crash state
        tpl = str(tmp_path / "tpl")
        if not os.path.isdir(tpl):
            store.save_leaves(8, p, o, {"cursor": 9})
            shutil.copytree(store._path(8), tpl)
            shutil.rmtree(store._path(8))
        wreck = os.path.join(root, f"{ckptmod.PREFIX}{8:012d}")
        # crash before any tensor bytes: bare tmp dir
        yield "tmp-only", os.path.join(root, f"{ckptmod.PREFIX}{8:012d}.tmp-9")
        # crash after arrays, before meta/marker
        shutil.copytree(tpl, wreck)
        os.remove(os.path.join(wreck, "meta.json"))
        os.remove(os.path.join(wreck, ckptmod.MARKER))
        yield "no-meta-no-marker", wreck
        # crash after meta, before the marker fsync'd
        shutil.copytree(tpl, wreck)
        os.remove(os.path.join(wreck, ckptmod.MARKER))
        yield "no-marker", wreck
        # torn payload with a live marker name but garbage marker bytes
        shutil.copytree(tpl, wreck)
        with open(os.path.join(wreck, ckptmod.MARKER), "wb") as f:
            f.write(b"\x00\x13garbage")
        yield "garbage-marker", wreck
        # torn tensors under a dir that never got its marker
        shutil.copytree(tpl, wreck)
        os.remove(os.path.join(wreck, ckptmod.MARKER))
        with open(os.path.join(wreck, "tensors.bin"), "r+b") as f:
            f.truncate(3)
        yield "torn-tensors", wreck

    for label, wreck in crash_states():
        if "tmp-" in os.path.basename(wreck):
            os.makedirs(wreck, exist_ok=True)
        assert store.latest_step() == 4, label
        got = store.load()
        assert got["step"] == 4 and np.array_equal(got["params"][0], p[0]), (
            label
        )
        store.gc()
        assert not os.path.exists(wreck), label
        assert os.path.isdir(good), label
    # and a REAL save after all that wreckage commits cleanly
    store.save_leaves(8, p, o)
    assert store.steps() == [4, 8]


def test_estimator_retained_save_and_restore(tmp_path):
    g = Graph.from_json(_graph_dict())
    src = resumable_node_batches(g, _flow(g), 8, seed=1)
    cfg = EstimatorConfig(model_dir=str(tmp_path / "r"), log_steps=10**9)
    est = Estimator(MODEL, src, cfg)
    est.train(total_steps=3, log=False)  # save=True → retained ckpt_3
    store = CheckpointStore(cfg.model_dir)
    assert store.steps() == [3]
    assert ckptmod.is_complete(store._path(3))
    est2 = Estimator(MODEL, src, cfg)
    assert est2.restore() and est2.step == 3
    assert _leaves_equal(est.params, est2.params)
    assert _leaves_equal(est.opt_state, est2.opt_state)

    # a model_dir with no complete checkpoint restores nothing
    empty = EstimatorConfig(model_dir=str(tmp_path / "empty"), log_steps=10**9)
    assert Estimator(MODEL, src, empty).restore() is False


# ---------------------------------------------------------------------------
# bit-exact resume (in-process, across a mutation epoch)
# ---------------------------------------------------------------------------


def test_resume_bit_exact_across_mutation_epoch(tmp_path):
    """train 2N straight (with a mutation published at step N) equals
    train N + 'process death' (fresh objects) + restore + the same
    mutation + train N — params AND per-step losses bit-identical, and
    the checkpointed graph-epoch book records the data version each
    segment trained against."""
    from euler_tpu.tools.train import apply_local_mutation

    data = _graph_dict()
    spec = {"upsert_edges": [[1, 5, 0, 3.5], [2, 9, 0, 1.25],
                             [3, 20, 0, 2.5]]}
    n = 8

    # straight 2N
    gA = Graph.from_json(data)
    sA, estA, _ = _session(gA, tmp_path / "a")
    repA1 = sA.run(n)
    assert apply_local_mutation(gA, spec) == {0: 1}
    repA2 = sA.run(n)

    # N, then everything in-memory is lost
    gB = Graph.from_json(data)
    sB, estB, _ = _session(gB, tmp_path / "b")
    repB1 = sB.run(n)
    assert repB1["losses"] == repA1["losses"]

    gB2 = Graph.from_json(data)  # the restarted process reloads the graph
    sB2, estB2, _ = _session(gB2, tmp_path / "b")
    rep = sB2.restore()
    assert rep["resumed"] and rep["step"] == n and rep["cursor"] == n + 1
    assert rep["epoch_match"] is True  # pre-mutation ckpt, pre-mutation graph
    apply_local_mutation(gB2, spec)
    repB2 = sB2.run(n)

    assert repB2["losses"] == repA2["losses"]
    assert _leaves_equal(estA.params, estB2.params)
    assert _leaves_equal(estA.opt_state, estB2.opt_state)
    # the final checkpoint's epoch book recorded the post-publish epoch
    sA.flush()
    book = CheckpointStore(str(tmp_path / "a")).load()["meta"]["graph_epochs"]
    assert book == {"0": 1}


# ---------------------------------------------------------------------------
# kill -9 + supervised restart (the pinned acceptance proof)
# ---------------------------------------------------------------------------


def _write_graph_dir(tmp_path, parts=1):
    d = str(tmp_path / "graph")
    convert_json(_graph_dict(), d, num_partitions=parts)
    return d


def _cli_args(data, model_dir, total, cadence, losses_out=None,
              mutate_spec=None, extra=()):
    args = [
        "--data", data, "--model-dir", str(model_dir),
        "--total-steps", str(total), "--checkpoint-every", str(cadence),
        "--batch-size", "8", "--dims", "8,8", "--max-degree", "4",
    ]
    if losses_out:
        args += ["--losses-out", str(losses_out)]
    if mutate_spec:
        args += ["--mutate-spec", str(mutate_spec)]
    return args + list(extra)


def _losses_by_step(path):
    out = {}
    with open(path, encoding="utf-8") as f:
        for line in f:
            seg = json.loads(line)
            for s, v in zip(seg["loss_steps"], seg["losses"]):
                out[s] = v
    return out


def test_kill9_supervised_resume_bit_exact(tmp_path):
    """The ISSUE's pinned proof: train 2N straight == train N-ish +
    seeded kill -9 + SUPERVISED resume to 2N, params and per-step
    losses bit-identical, across a step-aligned mutation publish."""
    from euler_tpu.distributed.supervisor import TrainerSupervisor
    from euler_tpu.tools.train import main as train_main

    data = _write_graph_dir(tmp_path)
    spec_path = str(tmp_path / "mut.json")
    with open(spec_path, "w") as f:
        json.dump([{"step": 8, "upsert_edges": [[1, 5, 0, 3.5],
                                                [2, 9, 0, 1.25],
                                                [3, 20, 0, 2.5]]}], f)
    total, cadence = 24, 4

    # the uninterrupted reference, through the SAME CLI code path
    ref_losses = str(tmp_path / "ref_losses.jsonl")
    rc = train_main(_cli_args(
        data, tmp_path / "ref", total, cadence, ref_losses, spec_path
    ))
    assert rc == 0
    ref = _losses_by_step(ref_losses)
    assert sorted(ref) == list(range(1, total + 1))

    # the chaos run: supervised trainer, kill -9 right after the first
    # retained checkpoint commits
    model_dir = tmp_path / "chaos"
    chaos_losses = str(tmp_path / "chaos_losses.jsonl")
    sup = TrainerSupervisor(
        _cli_args(data, model_dir, total, cadence, chaos_losses, spec_path),
        log_path=str(tmp_path / "trainer.log"),
        backoff_s=0.1,
    ).start()
    try:
        store = CheckpointStore(str(model_dir))
        deadline = time.time() + 180
        while time.time() < deadline and not store.steps():
            time.sleep(0.005)
        assert store.steps(), "trainer never checkpointed"
        sup.kill(signal.SIGKILL)
        assert sup.wait(300), sup.stats()
        st = sup.stats()
        assert st["exit_code"] == 0 and not st["failed"], st
        assert st["restarts"] >= 1, (
            st, open(str(tmp_path / "trainer.log")).read()[-1000:],
        )
    finally:
        sup.stop()

    # params bit-identical to the uninterrupted run
    ref_ck = CheckpointStore(str(tmp_path / "ref")).load()
    chaos_ck = store.load()
    assert ref_ck["step"] == chaos_ck["step"] == total
    for a, b in zip(ref_ck["params"], chaos_ck["params"]):
        assert np.array_equal(a, b)
    for a, b in zip(ref_ck["opt_state"], chaos_ck["opt_state"]):
        assert np.array_equal(a, b)
    # per-step losses bit-identical wherever the chaos run recorded them
    # (the killed process's unfetched on-device tail died with it — by
    # design; the RESUMED segments must agree exactly)
    got = _losses_by_step(chaos_losses)
    assert got, "resumed trainer recorded no losses"
    assert max(got) == total
    for s, v in got.items():
        assert ref[s] == v, (s, v, ref[s])


def test_sigterm_drains_and_flushes_final_checkpoint(tmp_path):
    """SIGTERM = preemption: the trainer finishes the in-flight step,
    drains the loss history to the losses file, flushes a final
    checkpoint, and exits 3 (done-for-now, not a crash)."""
    data = _write_graph_dir(tmp_path)
    model_dir = tmp_path / "m"
    losses_out = str(tmp_path / "losses.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "euler_tpu.tools.train",
         *_cli_args(data, model_dir, 10**6, 3, losses_out)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        store = CheckpointStore(str(model_dir))
        deadline = time.time() + 180
        while time.time() < deadline and not store.steps():
            time.sleep(0.01)
        assert store.steps(), "trainer never checkpointed"
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    except BaseException:
        proc.kill()
        raise
    assert proc.returncode == 3, out[-1500:]
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["preempted"] is True and tail["done"] is False
    final_step = tail["step"]
    # the drain flushed a checkpoint AT the preempted step (not just the
    # last cadence point) and every fetched loss up to it
    assert store.latest_step() == final_step
    got = _losses_by_step(losses_out)
    assert sorted(got) == list(range(1, final_step + 1))


# ---------------------------------------------------------------------------
# anomaly guard + watchdog
# ---------------------------------------------------------------------------


class _PoisonSource(ResumableSource):
    """Resumable source that injects NaN features at chosen draws."""

    def __init__(self, draw_fn, seed=0, poison_at=()):
        super().__init__(draw_fn, seed=seed)
        self.poison_at = set(poison_at)

    def __call__(self):
        i = self._i
        batch = super().__call__()
        if i in self.poison_at:
            batch[0].feats[0][:] = np.nan
        return batch


def _poison_session(tmp_path, graph, poison_at, sub="p", **cfg_kw):
    flow = _flow(graph)

    def draw(rng):
        roots = graph.sample_node(8, -1, rng=rng)
        return (flow.query(roots),)

    src = _PoisonSource(draw, seed=3, poison_at=poison_at)
    return _session(graph, tmp_path / sub, source=src, **cfg_kw)


def test_anomaly_guard_skips_poisoned_step_bit_exactly(tmp_path):
    g = Graph.from_json(_graph_dict())
    # clean reference for the pre-anomaly trajectory
    s_ref, est_ref, _ = _poison_session(tmp_path, g, (), sub="clean")
    rep_ref = s_ref.run(5)

    s, est, src = _poison_session(tmp_path, g, {6}, sub="poison")
    rep = s.run(12)
    t = rep["telemetry"]
    assert t["anomalies"] == 1 and t["rollbacks"] == 0
    assert t["skipped_steps"] == [6]
    # step 6 produced no loss entry; everything recorded is finite
    assert rep["loss_steps"] == [s_ for s_ in range(1, 13) if s_ != 6]
    assert np.isfinite(rep["losses"]).all()
    # the validated prefix is untouched by the skip — bit-exact
    assert rep["losses"][:5] == rep_ref["losses"]
    # cursor parity held: the poisoned draw was consumed, not re-used
    assert src.cursor() == 13
    assert np.isfinite(
        np.concatenate([
            np.asarray(x).ravel()
            for x in jax.tree_util.tree_leaves(est.params)
        ])
    ).all()


def test_anomaly_strike_cap_raises_typed(tmp_path):
    g = Graph.from_json(_graph_dict())
    s, est, _ = _poison_session(
        tmp_path, g, set(range(5, 100)), sub="cap", max_strikes=3
    )
    with pytest.raises(AnomalyError, match="strike"):
        s.run(12)
    assert s.telemetry["anomalies"] == 4  # cap 3 + the raising strike
    # params were never poisoned: the last ACCEPTED state is what a
    # best-effort final checkpoint preserved (at the post-skip step —
    # steps 5/6 were consumed without updates before the cap tripped)
    assert np.isfinite(
        np.concatenate([
            np.asarray(x).ravel()
            for x in jax.tree_util.tree_leaves(est.params)
        ])
    ).all()
    assert CheckpointStore(str(tmp_path / "cap")).latest_step() == 7


def test_anomaly_rollback_policy_retries_transient_fault(tmp_path):
    """policy="rollback": revert to the last-good snapshot and RETRY —
    a transient anomaly (here: a batch poisoned only on its first draw)
    clears on replay and the run completes with every step applied."""
    g = Graph.from_json(_graph_dict())
    flow = _flow(g)
    poison_once = {6}

    def draw(rng):
        roots = g.sample_node(8, -1, rng=rng)
        return (flow.query(roots),)

    class _TransientPoison(ResumableSource):
        def __call__(self):
            i = self._i
            batch = super().__call__()
            if i in poison_once:
                poison_once.discard(i)  # transient: clean on the retry
                batch[0].feats[0][:] = np.nan
            return batch

    src = _TransientPoison(draw, seed=3)
    s, est, _ = _session(
        g, tmp_path / "rb", source=src, anomaly_policy="rollback"
    )
    rep = s.run(12)
    t = rep["telemetry"]
    assert t["anomalies"] == 1 and t["rollbacks"] == 1
    assert t["skipped_steps"] == []
    # the retry applied EVERY step: no hole in the loss trajectory
    assert rep["loss_steps"] == list(range(1, 13))
    assert np.isfinite(rep["losses"]).all()


def test_anomaly_abort_policy_raises_immediately(tmp_path):
    g = Graph.from_json(_graph_dict())
    s, _, _ = _poison_session(
        tmp_path, g, {2}, sub="abort", anomaly_policy="abort"
    )
    with pytest.raises(AnomalyError, match="policy=abort"):
        s.run(6)
    assert s.telemetry["rollbacks"] == 0


def test_hung_step_watchdog_dumps_and_aborts(tmp_path):
    g = Graph.from_json(_graph_dict())
    flow = _flow(g)
    hang_at = {5}

    def draw(rng):
        if draw.calls in hang_at:
            time.sleep(5.0)
        draw.calls += 1
        roots = g.sample_node(8, -1, rng=rng)
        return (flow.query(roots),)

    draw.calls = 0
    src = ResumableSource(draw, seed=3)
    s, est, _ = _session(g, tmp_path / "w", source=src)
    s.run(3)  # warm: compile outside the deadline window
    s.cfg.step_deadline_s = 0.75
    with pytest.raises(HungStepError, match="deadline"):
        s.run(4)  # step 4 passes, step 5's draw hangs
    assert s.telemetry["hung_aborts"] == 1
    diag = os.path.join(str(tmp_path / "w"), "hung_step_5.txt")
    assert os.path.exists(diag)
    body = open(diag, encoding="utf-8").read()
    assert "Thread" in body or "Current thread" in body  # stack dump
    # the best-effort final flush preserved the last accepted step
    assert CheckpointStore(str(tmp_path / "w")).latest_step() == 4


# ---------------------------------------------------------------------------
# serving: reload never swaps in an incomplete checkpoint
# ---------------------------------------------------------------------------


def test_reload_skips_torn_checkpoint(tmp_path):
    from euler_tpu.serving import InferenceRuntime
    from euler_tpu.tools.serve import _ckpt_signature

    g = Graph.from_json(_graph_dict())
    src = resumable_node_batches(g, _flow(g), 8, seed=2)
    cfg = EstimatorConfig(model_dir=str(tmp_path / "m"), log_steps=10**9)
    est = Estimator(MODEL, src, cfg)
    est.train(total_steps=2, log=False)  # → complete ckpt_2

    runtime = InferenceRuntime(MODEL, _flow(g), cfg, buckets=(8,))
    canary = np.arange(1, 9, dtype=np.uint64)
    before = runtime.predict(canary)
    sig0 = _ckpt_signature(cfg.model_dir)

    # a trainer dies mid-save: a newer torn dir (no COMMIT) + a tmp dir
    torn = os.path.join(cfg.model_dir, f"{ckptmod.PREFIX}{99:012d}")
    os.makedirs(torn)
    with open(os.path.join(torn, "tensors.bin"), "wb") as f:
        f.write(b"\x00garbage")
    os.makedirs(
        os.path.join(cfg.model_dir, f"{ckptmod.PREFIX}{100:012d}.tmp-1")
    )
    # the watcher's signature did not move → no reload triggers at all
    assert _ckpt_signature(cfg.model_dir) == sig0
    # a direct swap() still refuses the torn dir: it loads the newest
    # COMPLETE checkpoint, bit-identically
    report = runtime.swap()
    assert report["reloaded"] is True
    assert runtime._est.step == 2
    np.testing.assert_array_equal(runtime.predict(canary), before)

    # a model_dir holding ONLY torn state raises instead of swapping
    torn_only = str(tmp_path / "torn_only")
    os.makedirs(os.path.join(torn_only, f"{ckptmod.PREFIX}{7:012d}"))
    with pytest.raises(FileNotFoundError):
        InferenceRuntime(
            MODEL, _flow(g),
            EstimatorConfig(model_dir=torn_only, log_steps=10**9),
            buckets=(8,),
        )

    # and a NEW complete checkpoint does move the signature + swap
    est.train(total_steps=2, log=False)  # → complete ckpt_4
    assert _ckpt_signature(cfg.model_dir) != sig0
    runtime.swap()
    assert runtime._est.step == 4


# ---------------------------------------------------------------------------
# estimator train(): crash surfaces fetched losses + best-effort save
# ---------------------------------------------------------------------------


def test_estimator_train_crash_surfaces_losses_and_checkpoint(tmp_path):
    g = Graph.from_json(_graph_dict())
    flow = _flow(g)
    state = {"calls": 0}

    def bf():
        # _ensure_init's probe is call 0; step k is call k
        if state["calls"] == 5:
            raise RuntimeError("shard died mid-epoch")
        state["calls"] += 1
        roots = g.sample_node(
            8, rng=np.random.default_rng(state["calls"])
        )
        return (flow.query(roots),)

    cfg = EstimatorConfig(model_dir=str(tmp_path / "m"), log_steps=10**9)
    est = Estimator(MODEL, bf, cfg)
    with pytest.raises(RuntimeError, match="shard died"):
        est.train(total_steps=10)
    # the 4 completed steps' losses were drained and surfaced, and a
    # best-effort checkpoint preserved the progress — previously both
    # were silently dropped on the floor
    assert len(est.last_losses) == 4
    assert np.isfinite(est.last_losses).all()
    assert CheckpointStore(cfg.model_dir).latest_step() == 4


# ---------------------------------------------------------------------------
# chaos: trainer kill -9 under live traffic (PR 9 style)
# ---------------------------------------------------------------------------


def test_scenario_trainer_kill9_under_live_traffic(tmp_path):
    """Seeded kill -9 of the supervised trainer while a 2-shard remote
    cluster serves a 2-replica inference fleet, a hot reader, and a
    step-aligned mutation stream through the wire write path. The
    respawned trainer resumes bit-exactly: final params identical to an
    uninterrupted run over an identical cluster; zero typed errors leak
    to any reader; the fleet hot-loads the trainer's retained
    checkpoints and never observes a torn one."""
    from euler_tpu.distributed import connect
    from euler_tpu.distributed.service import serve_shard
    from euler_tpu.distributed.supervisor import TrainerSupervisor
    from euler_tpu.serving import InferenceRuntime, ModelServer, ServingClient
    from euler_tpu.tools.train import main as train_main

    total, cadence = 20, 4
    spec_path = str(tmp_path / "mut.json")
    with open(spec_path, "w") as f:
        json.dump([
            {"step": 6, "upsert_edges": [[1, 5, 0, 3.5], [2, 9, 0, 1.25]]},
            {"step": 14, "upsert_edges": [[3, 20, 0, 2.5],
                                          [4, 11, 0, 0.75]]},
        ], f)

    def boot_cluster(name):
        d = str(tmp_path / name)
        convert_json(_graph_dict(), d, num_partitions=2)
        # a registry per cluster: multi-shard fan-out (full-neighbor
        # queries through the service facade) discovers peers with it
        svcs = [
            serve_shard(
                d, s, native=False,
                registry_path=str(tmp_path / f"{name}_reg"),
            )
            for s in range(2)
        ]
        cluster = {s: [(svc.host, svc.port)] for s, svc in enumerate(svcs)}
        return svcs, json.dumps(
            {str(s): [[h, p] for h, p in v] for s, v in cluster.items()}
        )

    # uninterrupted reference over its own identical cluster
    svcs_a, cluster_a = boot_cluster("ga")
    try:
        rc = train_main([
            "--cluster", cluster_a, "--model-dir", str(tmp_path / "ref"),
            "--total-steps", str(total), "--checkpoint-every", str(cadence),
            "--batch-size", "8", "--dims", "8,8", "--max-degree", "4",
            "--mutate-spec", spec_path,
        ])
        assert rc == 0
    finally:
        for svc in svcs_a:
            svc.stop()

    # the chaos cluster: live reader + 2-replica fleet + supervised
    # trainer killed -9 mid-run
    svcs_b, cluster_b = boot_cluster("gb")
    model_dir = str(tmp_path / "chaos")
    store = CheckpointStore(model_dir)
    sup = TrainerSupervisor(
        ["--cluster", cluster_b, "--model-dir", model_dir,
         "--total-steps", str(total), "--checkpoint-every", str(cadence),
         "--batch-size", "8", "--dims", "8,8", "--max-degree", "4",
         "--mutate-spec", spec_path],
        log_path=str(tmp_path / "trainer.log"),
        backoff_s=0.1,
    ).start()
    rg = connect(cluster={
        int(k): [tuple(a) for a in v]
        for k, v in json.loads(cluster_b).items()
    })
    servers, client = [], None
    stop = threading.Event()
    leaks: list = []
    try:
        deadline = time.time() + 180
        while time.time() < deadline and not store.steps():
            time.sleep(0.005)
        assert store.steps(), "trainer never checkpointed"
        # the fleet boots FROM the trainer's retained checkpoints while
        # the trainer keeps committing new ones next to them
        for i in range(2):
            rt = InferenceRuntime(MODEL, _flow(rg), model_dir, buckets=(8,))
            rt.warmup()
            servers.append(ModelServer(rt, max_wait_us=200, shard=i).start())
        client = ServingClient(
            [(s.host, s.port) for s in servers], routing="consistent_hash"
        )
        watch_ids = np.asarray([2, 3, 7], np.uint64)
        serve_ids = np.arange(1, 9, dtype=np.uint64)

        def reader():
            try:
                while not stop.is_set():
                    rg.get_dense_feature(watch_ids, ["feat"])
            except Exception as e:  # noqa: BLE001
                leaks.append(f"reader: {e!r}")

        def predictor():
            try:
                while not stop.is_set():
                    client.predict(serve_ids)
            except Exception as e:  # noqa: BLE001
                leaks.append(f"predictor: {e!r}")

        threads = [threading.Thread(target=reader, daemon=True),
                   threading.Thread(target=predictor, daemon=True)]
        for t in threads:
            t.start()
        sup.kill(signal.SIGKILL)  # the seeded mid-run kill
        assert sup.wait(300), sup.stats()
        st = sup.stats()
        assert st["restarts"] >= 1 and st["exit_code"] == 0, (
            st, open(str(tmp_path / "trainer.log")).read()[-1000:],
        )
        # the fleet hot-reloads to the final checkpoint — only complete
        # ones are ever candidates (canary parity is expectedly False:
        # the swap moves from a mid-run checkpoint to the final one)
        reports = client.reload(canary_ids=serve_ids)
        assert all(r.get("reloaded") for r in reports.values()), reports
        for s in servers:
            assert s.runtime._est.step == total
        stop.set()
        for t in threads:
            t.join(timeout=60)
        assert not leaks, leaks[:5]
    finally:
        stop.set()
        if client is not None:
            client.close()
        for s in servers:
            s.stop()
        sup.stop()
        for svc in svcs_b:
            svc.stop()

    ref_ck = CheckpointStore(str(tmp_path / "ref")).load()
    chaos_ck = store.load()
    assert ref_ck["step"] == chaos_ck["step"] == total
    for a, b in zip(ref_ck["params"], chaos_ck["params"]):
        assert np.array_equal(a, b)
    for a, b in zip(ref_ck["opt_state"], chaos_ck["opt_state"]):
        assert np.array_equal(a, b)
