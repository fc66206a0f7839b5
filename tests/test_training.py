"""End-to-end slice: dataflow → convs → GNN → estimator train/eval/infer.

The synthetic task is 2-cluster classification where features are
cluster-separable, so a couple of GNN layers must drive the loss down —
the automated stand-in for the reference's manual example regression tables.
"""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu.dataflow import FullNeighborDataFlow, SageDataFlow
from euler_tpu.estimator import (
    Estimator,
    EstimatorConfig,
    id_batches,
    node_batches,
    unsupervised_batches,
)
from euler_tpu.graph import Graph
from euler_tpu.layers import CONVS, get_conv
from euler_tpu.nn import GNNNet, SuperviseModel, UnsuperviseModel


def make_cluster_graph(n_per=30, seed=0):
    """Two feature-separable clusters with intra-cluster ring edges."""
    rng = np.random.default_rng(seed)
    nodes, edges = [], []
    for c in range(2):
        base = c * n_per
        for i in range(n_per):
            nid = base + i + 1
            feat = (rng.normal(2.0 * (1 if c == 0 else -1), 1.0, 4)).tolist()
            label = [1.0, 0.0] if c == 0 else [0.0, 1.0]
            nodes.append(
                {
                    "id": nid,
                    "type": 0,
                    "weight": 1.0,
                    "features": [
                        {"name": "feat", "type": "dense", "value": feat},
                        {"name": "label", "type": "dense", "value": label},
                    ],
                }
            )
        for i in range(n_per):
            for d in (1, 2, 3):
                edges.append(
                    {
                        "src": base + i + 1,
                        "dst": base + (i + d) % n_per + 1,
                        "type": 0,
                        "weight": 1.0,
                        "features": [],
                    }
                )
    return Graph.from_json({"nodes": nodes, "edges": edges})


@pytest.fixture(scope="module")
def cluster_graph():
    return make_cluster_graph()


def test_sage_dataflow_shapes(cluster_graph):
    flow = SageDataFlow(
        cluster_graph,
        ["feat"],
        fanouts=[3, 2],
        label_feature="label",
        rng=np.random.default_rng(0),
    )
    roots = cluster_graph.sample_node(8, rng=np.random.default_rng(1))
    mb = flow.query(roots)
    assert mb.feats[0].shape == (8, 4)
    assert mb.feats[1].shape == (24, 4)
    assert mb.feats[2].shape == (48, 4)
    assert mb.labels.shape == (8, 2)
    assert mb.blocks[0].n_src == 24 and mb.blocks[0].n_dst == 8
    assert mb.blocks[1].n_src == 48 and mb.blocks[1].n_dst == 24
    assert mb.masks[0].all()


def test_full_neighbor_dataflow(cluster_graph):
    flow = FullNeighborDataFlow(
        cluster_graph, ["feat"], num_hops=2, max_degree=4
    )
    mb = flow.query(np.asarray([1, 2, 3], np.uint64))
    assert mb.feats[1].shape == (12, 4)
    # each node has exactly 3 out-edges → 3 valid slots of 4
    assert mb.blocks[0].mask.reshape(3, 4).sum(axis=1).tolist() == [3, 3, 3]


@pytest.mark.parametrize("conv", sorted(CONVS))
def test_conv_forward_shapes(cluster_graph, conv):
    flow = SageDataFlow(cluster_graph, ["feat"], fanouts=[3])
    mb = flow.query(np.asarray([1, 2, 3, 4], np.uint64))
    cls = get_conv(conv)
    layer = cls(out_dim=8)
    params = layer.init(
        jax.random.PRNGKey(0), mb.feats[0], mb.feats[1], mb.blocks[0]
    )
    out = layer.apply(params, mb.feats[0], mb.feats[1], mb.blocks[0])
    expected_dim = {
        "appnp": 4,
        "sgcn": 4,
        "agnn": 4,
    }.get(conv, 8)  # propagation-only convs keep input dim
    assert out.shape == (4, expected_dim)
    assert jnp.isfinite(out).all()


def test_gnn_net(cluster_graph):
    flow = SageDataFlow(cluster_graph, ["feat"], fanouts=[3, 2])
    mb = flow.query(np.asarray([1, 2], np.uint64))
    net = GNNNet(conv="gcn", dims=[8, 8])
    params = net.init(jax.random.PRNGKey(0), mb)
    out = net.apply(params, mb)
    assert out.shape == (2, 8)


def test_supervised_training(cluster_graph, tmp_path):
    rng = np.random.default_rng(0)
    flow = SageDataFlow(
        cluster_graph, ["feat"], fanouts=[3, 2], label_feature="label", rng=rng
    )
    model = SuperviseModel(conv="gcn", dims=[16, 16], label_dim=2)
    cfg = EstimatorConfig(
        model_dir=str(tmp_path / "m"),
        batch_size=16,
        total_steps=60,
        learning_rate=0.05,
        log_steps=1000,
    )
    est = Estimator(model, node_batches(cluster_graph, flow, 16, rng=rng), cfg)
    history = est.train()
    assert history[-1] < history[0] * 0.5, history[::10]

    # evaluate on all nodes
    all_ids = np.arange(1, 61, dtype=np.uint64)
    batches, _ = id_batches(flow, all_ids, 16)
    res = est.evaluate(batches)
    assert res["f1"] > 0.9, res

    # infer writes npy files
    batches, chunks = id_batches(flow, all_ids, 16)
    ids, emb = est.infer(batches, chunks)
    assert emb.shape == (60, 16)
    assert (ids == all_ids).all()
    import os

    assert os.path.exists(str(tmp_path / "m" / "embedding_0.npy"))


def test_checkpoint_roundtrip(cluster_graph, tmp_path):
    rng = np.random.default_rng(0)
    flow = SageDataFlow(
        cluster_graph, ["feat"], fanouts=[2], label_feature="label", rng=rng
    )
    model = SuperviseModel(conv="sage", dims=[8], label_dim=2)
    cfg = EstimatorConfig(
        model_dir=str(tmp_path / "ck"), total_steps=3, log_steps=1000
    )
    bf = node_batches(cluster_graph, flow, 8, rng=rng)
    est = Estimator(model, bf, cfg)
    est.train()
    est2 = Estimator(model, bf, cfg)
    assert est2.restore()
    assert est2.step == 3
    leaves1 = jax.tree.leaves(est.params)
    leaves2 = jax.tree.leaves(est2.params)
    for a, b in zip(leaves1, leaves2):
        np.testing.assert_allclose(a, b)


def test_unsupervised_training(cluster_graph, tmp_path):
    rng = np.random.default_rng(0)
    flow = SageDataFlow(cluster_graph, ["feat"], fanouts=[3], rng=rng)
    model = UnsuperviseModel(conv="sage", dims=[8])
    cfg = EstimatorConfig(
        model_dir=str(tmp_path / "u"),
        total_steps=40,
        learning_rate=0.05,
        log_steps=1000,
    )
    est = Estimator(
        model,
        unsupervised_batches(cluster_graph, flow, 16, num_negs=4, rng=rng),
        cfg,
    )
    history = est.train()
    assert history[-1] < history[0], (history[0], history[-1])


def test_remat_matches_exact(cluster_graph, tmp_path):
    """remat=True (jax.checkpoint around each conv layer — the TPU HBM
    lever for deep stacks) must change NOTHING numerically: identical
    loss trajectory and gradients, only the backward-pass memory/FLOP
    trade differs."""
    rng = np.random.default_rng(0)
    flow = SageDataFlow(
        cluster_graph, ["feat"], fanouts=[3, 2], label_feature="label",
        rng=rng,
    )
    batches = [
        (flow.query(cluster_graph.sample_node(8, rng=rng)),)
        for _ in range(6)  # one extra for _ensure_init's probe call
    ]

    def run(remat):
        it = iter(batches)
        model = SuperviseModel(
            conv="sage", dims=[8, 8], label_dim=2, remat=remat
        )
        cfg = EstimatorConfig(
            model_dir=str(tmp_path / f"r{remat}"), learning_rate=0.05,
            log_steps=10**9,
        )
        est = Estimator(model, lambda: next(it), cfg)
        return est.train(total_steps=4, save=False, log=False)

    np.testing.assert_allclose(run(False), run(True), rtol=1e-6, atol=1e-7)


def test_scan_training_matches_sequential(cluster_graph, tmp_path):
    """steps_per_call=K (lax.scan multi-step dispatch) must produce the same
    params as K sequential single-step dispatches over the same batches."""
    from euler_tpu.estimator import stack_batches

    rng = np.random.default_rng(0)
    flow = SageDataFlow(
        cluster_graph, ["feat"], fanouts=[3, 2], label_feature="label", rng=rng
    )
    # one fixed sequence of batches, replayed for both runs
    roots = [
        cluster_graph.sample_node(8, rng=np.random.default_rng(s))
        for s in range(8)
    ]
    batches = [(flow.query(r),) for r in roots]

    def replay(seq):
        it = iter(seq)
        return lambda: next(it)

    model = SuperviseModel(conv="gcn", dims=[8, 8], label_dim=2)
    cfg1 = EstimatorConfig(
        model_dir=str(tmp_path / "a"), learning_rate=0.05, log_steps=10**9
    )
    est1 = Estimator(model, lambda: batches[0], cfg1)
    est1._ensure_init()
    est1.batch_fn = replay(list(batches))
    h1 = est1.train(total_steps=8, save=False)

    cfg2 = EstimatorConfig(
        model_dir=str(tmp_path / "b"),
        learning_rate=0.05,
        log_steps=10**9,
        steps_per_call=4,
    )
    est2 = Estimator(model, stack_batches(lambda: batches[0], 4), cfg2)
    est2._ensure_init()
    est2.batch_fn = stack_batches(replay(list(batches)), 4)
    h2 = est2.train(total_steps=8, save=False)

    assert len(h2) == 8
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=2e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5),
        est1.params,
        est2.params,
    )


def test_scan_training_remainder_and_exact_steps(cluster_graph, tmp_path):
    """total_steps not a multiple of steps_per_call still applies exactly
    total_steps optimizer updates."""
    from euler_tpu.estimator import stack_batches

    rng = np.random.default_rng(0)
    flow = SageDataFlow(
        cluster_graph, ["feat"], fanouts=[2], label_feature="label", rng=rng
    )
    model = SuperviseModel(conv="gcn", dims=[8], label_dim=2)
    cfg = EstimatorConfig(
        model_dir=str(tmp_path / "r"),
        learning_rate=0.05,
        log_steps=10**9,
        steps_per_call=4,
    )
    est = Estimator(
        model, stack_batches(node_batches(cluster_graph, flow, 8, rng=rng), 4), cfg
    )
    h = est.train(total_steps=10, save=False)
    assert len(h) == 10
    assert est.step == 10


def test_scan_training_with_mesh(cluster_graph, tmp_path):
    """steps_per_call>1 under a data mesh shards axis 1 (batch), not the
    scan axis."""
    from euler_tpu.estimator import stack_batches
    from euler_tpu.parallel import make_mesh

    mesh = make_mesh(4)
    rng = np.random.default_rng(0)
    flow = SageDataFlow(
        cluster_graph, ["feat"], fanouts=[2], label_feature="label", rng=rng
    )
    model = SuperviseModel(conv="gcn", dims=[8], label_dim=2)
    cfg = EstimatorConfig(
        model_dir=str(tmp_path / "m"),
        learning_rate=0.05,
        log_steps=10**9,
        steps_per_call=2,
    )
    est = Estimator(
        model,
        stack_batches(node_batches(cluster_graph, flow, 8, rng=rng), 2),
        cfg,
        mesh=mesh,
    )
    h = est.train(total_steps=6, save=False)
    assert len(h) == 6 and np.isfinite(h).all()


def test_jit_step_cache_keying(tmp_path, monkeypatch):
    """Cross-instance jit sharing (estimator.py _jit_cache) must share
    EXACTLY when the traced program is identical: same (model config,
    optimizer cfg, flow, cache) shares; a differing learning rate or
    model width must NOT (a false hit silently trains with the wrong
    program)."""
    monkeypatch.setenv("EULER_TPU_STEP_CACHE", "1")  # the knob under test
    from euler_tpu.dataflow import DeviceSageFlow
    from euler_tpu.datasets.synthetic import random_graph
    from euler_tpu.estimator import DeviceFeatureCache
    from euler_tpu.models import GraphSAGESupervised

    g = random_graph(num_nodes=120, out_degree=5, feat_dim=4, seed=0)
    flow = DeviceSageFlow(g, fanouts=[3], batch_size=8, label_feature="label")
    fcache = DeviceFeatureCache(g, ["feat"])

    def est(lr=0.05, dims=(8,)):
        return Estimator(
            GraphSAGESupervised(dims=list(dims), label_dim=2),
            flow,
            EstimatorConfig(model_dir=str(tmp_path / "c"), learning_rate=lr,
                            log_steps=10**9, steps_per_call=2),
            feature_cache=fcache,
        )

    a, b = est(), est()
    assert a._train_step_scan() is b._train_step_scan(), (
        "identical config on shared flow/cache must reuse the program"
    )
    assert est(lr=0.2)._train_step_scan() is not a._train_step_scan(), (
        "learning rate is part of the traced optimizer — no sharing"
    )
    assert est(dims=(16,))._train_step_scan() is not a._train_step_scan(), (
        "model config is part of the trace — no sharing"
    )
    # the shared program still trains both instances to the same losses
    assert a.train(total_steps=4, log=False, save=False) == b.train(
        total_steps=4, log=False, save=False
    )
    # eviction never recycles the flow's init-shape probe
    from euler_tpu.estimator.estimator import (
        _JIT_CACHE_MAX,
        _flow_probe,
        _jit_cache,
    )

    probe = _flow_probe(flow)
    for i in range(_JIT_CACHE_MAX + 3):
        est(lr=0.3 + i / 100)._train_step_scan()
    assert _flow_probe(flow) is probe, "probe must survive FIFO eviction"
    assert len(_jit_cache(flow)) <= _JIT_CACHE_MAX + 1
    # the cache is a weak side table, NOT an attribute injected onto the
    # user's flow (ADVICE r5: injection broke deepcopy/pickle after use)
    assert not hasattr(flow, "_etpu_jit_cache")


def test_optimizer_key_derived_from_consumed_fields(tmp_path, monkeypatch):
    """_optimizer_key is derived mechanically from the SAME table
    make_optimizer consumes (_OPTIMIZER_CFG_FIELDS): perturbing each
    optimizer-relevant field yields a distinct cached program; a field
    the update program never reads (momentum under adam) shares."""
    import dataclasses as dc

    from euler_tpu.estimator.estimator import (
        _OPTIMIZER_CFG_FIELDS,
        _optimizer_key,
        make_optimizer,
    )

    # key level: every declared optimizer x every consumed field
    for opt, fields in _OPTIMIZER_CFG_FIELDS.items():
        base = EstimatorConfig(optimizer=opt)
        make_optimizer(base)  # the factory accepts every declared name
        for f in fields:
            bumped = dc.replace(base, **{f: getattr(base, f) + 0.123})
            assert _optimizer_key(bumped) != _optimizer_key(base), (opt, f)
    assert _optimizer_key(
        EstimatorConfig(optimizer="adam", momentum=0.9)
    ) == _optimizer_key(EstimatorConfig(optimizer="adam", momentum=0.5))

    # program level: the jit cache resolves the keys to distinct (or
    # shared) compiled update programs
    monkeypatch.setenv("EULER_TPU_STEP_CACHE", "1")
    from euler_tpu.dataflow import DeviceSageFlow
    from euler_tpu.datasets.synthetic import random_graph
    from euler_tpu.estimator import DeviceFeatureCache
    from euler_tpu.models import GraphSAGESupervised

    g = random_graph(num_nodes=60, out_degree=4, feat_dim=4, seed=0)
    flow = DeviceSageFlow(g, fanouts=[2], batch_size=4, label_feature="label")
    fcache = DeviceFeatureCache(g, ["feat"])

    def est(**kw):
        cfg = EstimatorConfig(model_dir=str(tmp_path / "ok"),
                              log_steps=10**9, **kw)
        return Estimator(
            GraphSAGESupervised(dims=[4], label_dim=2), flow, cfg,
            feature_cache=fcache,
        )

    base = est(optimizer="momentum", momentum=0.9)._train_step_scan()
    assert est(
        optimizer="momentum", momentum=0.5
    )._train_step_scan() is not base, "momentum feeds sgd(momentum=...)"
    adam = est(optimizer="adam", momentum=0.9)._train_step_scan()
    assert est(optimizer="adam", momentum=0.5)._train_step_scan() is adam, (
        "adam never reads momentum — same program must be shared"
    )


def test_model_key_structural_not_repr(tmp_path):
    """_model_key must not rely on repr(model): numpy summarizes large
    arrays, so two different big constants repr identically — a silent
    wrong-program share. The structural key distinguishes them, keys
    equal configs equally, and stays hashable."""
    from euler_tpu.estimator.estimator import _structural_key
    from euler_tpu.models import GraphSAGESupervised

    a = np.zeros(5000, np.float32)
    b = a.copy()
    b[2500] = 1.0
    assert repr(a) == repr(b), "precondition: repr collides when summarized"
    assert _structural_key(a) != _structural_key(b)

    m1 = GraphSAGESupervised(dims=[8, 8], label_dim=2)
    m2 = GraphSAGESupervised(dims=[8, 8], label_dim=2)
    m3 = GraphSAGESupervised(dims=[16], label_dim=2)
    k1, k2, k3 = map(_structural_key, (m1, m2, m3))
    assert k1 == k2 and k1 != k3
    hash(k1)  # cache keys must be hashable
    # dict-valued fields (conv_kwargs carrying a dtype) key structurally
    m4 = GraphSAGESupervised(
        dims=[8, 8], label_dim=2, conv_kwargs={"dtype": jnp.bfloat16}
    )
    assert _structural_key(m4) != k1
    hash(_structural_key(m4))


# -- the train loop's record (euler_tpu/utils/trace.py) ----------------------


def _recorded_train(cluster_graph, tmp_path, steps, steps_per_call=1, batch_hook=None):
    """`est.train(steps)` over host batches, the collector's own runs
    off; `batch_hook(est)` runs inside every `batch_fn()`. Returns the
    Estimator and the spans the call left."""
    from euler_tpu.estimator import stack_batches
    from euler_tpu.utils import trace

    rng = np.random.default_rng(0)
    flow = SageDataFlow(
        cluster_graph, ["feat"], fanouts=[2], label_feature="label", rng=rng
    )
    batches = node_batches(cluster_graph, flow, 8, rng=rng)
    held = []

    def batch_fn():
        if batch_hook is not None and held:
            batch_hook(held[0])
        return batches()

    cfg = EstimatorConfig(
        model_dir=str(tmp_path / "rec"), learning_rate=0.05, log_steps=10**9,
        steps_per_call=steps_per_call,
    )
    source = stack_batches(batch_fn, steps_per_call) if steps_per_call > 1 else batch_fn
    est = Estimator(SuperviseModel(conv="gcn", dims=[8], label_dim=2), source, cfg)
    held.append(est)
    was = gc.isenabled()
    gc.disable()
    try:
        since = time.perf_counter_ns()
        est.train(steps, log=False, save=False)
    finally:
        if was:
            gc.enable()
    return est, [s for s in trace.spans() if s.start_ns >= since]


def test_a_collector_run_in_batch_fn_is_a_gc_span_of_that_step(cluster_graph, tmp_path):
    def collect_at_step_two(est):
        if est.step == 2 and est.params is not None:
            gc.collect()

    _, got = _recorded_train(cluster_graph, tmp_path, 5, batch_hook=collect_at_step_two)
    by_id = {s.id: s for s in got}
    (run,) = [s for s in got if s.name == "gc"]  # and nowhere else
    batch = by_id[run.parent]
    assert (batch.name, batch.step) == ("train.next_batch", 2)
    assert (by_id[batch.parent].name, by_id[batch.parent].step) == ("train.step", 2)
    assert run.args == {"generation": 2}
    assert batch.start_ns <= run.start_ns <= run.end_ns <= batch.end_ns


@pytest.mark.parametrize("steps_per_call,steps", [(1, 5), (4, 8), (4, 10)])
def test_every_dispatch_has_a_model_metric_after_the_call(
    cluster_graph, tmp_path, steps_per_call, steps
):
    est, got = _recorded_train(cluster_graph, tmp_path, steps, steps_per_call)
    dispatches = [s for s in got if s.name == "train.dispatch"]
    whole, rest = divmod(steps, steps_per_call)
    assert len(dispatches) == whole + rest
    for s in dispatches:
        assert isinstance(s.args["model_metric"], float)
        assert 0.0 <= s.args["model_metric"] <= 1.0  # an f1
        assert "metric" not in s.args  # no profiler session was live
    assert len(est.last_losses) == steps
    assert all(isinstance(x, float) for x in est.last_losses)


def test_the_call_and_its_drain_count_what_moved_and_a_step_counts_nothing(
    cluster_graph, tmp_path, monkeypatch
):
    """The thread's counters are read where a stall was found, around
    the call and around its drain: twice a call, and the loop's body
    pays nothing for them."""
    from euler_tpu.utils import trace

    reads = []
    monkeypatch.setattr(trace, "INTERRUPTIONS_COUNTED", True)
    monkeypatch.setattr(
        trace, "_interruptions", lambda: reads.append(0) or (0, len(reads), 0, 0)
    )
    _, got = _recorded_train(cluster_graph, tmp_path, 3)
    named = {s.name: s for s in got}
    assert len(reads) == 4  # none of them a step's
    # train opens (1), the drain opens (2) and closes (3), train closes (4)
    assert named["train"].args == {"steps": 3, "nvcsw": 3}
    assert named["train.drain"].args == {"step": 3, "nvcsw": 1}
    steps = [s for s in got if s.name == "train.step"]
    assert [s.args for s in steps] == [{"step": 0}, {"step": 1}, {"step": 2}]
    # an undisturbed call's and drain's args are what they were given
    monkeypatch.setattr(trace, "_interruptions", lambda: (1, 2, 3, 4))
    _, got = _recorded_train(cluster_graph, tmp_path, 3)
    named = {s.name: s for s in got}
    assert named["train"].args == {"steps": 3}
    assert named["train.drain"].args == {"step": 3}


def test_the_drain_says_what_it_waited_for(cluster_graph, tmp_path):
    _, got = _recorded_train(cluster_graph, tmp_path, 5)
    (drain,) = [s for s in got if s.name == "train.drain"]
    assert drain.step == 5
    kids = sorted((s for s in got if s.parent == drain.id), key=lambda s: s.start_ns)
    # the join's own compile, the first time, is the drain's too
    kids = [s for s in kids if s.name != "late_compile"]
    assert [s.name for s in kids] == ["train.drain.wait", "train.drain.copy"]
    assert drain.start_ns <= kids[0].start_ns
    assert kids[0].end_ns <= kids[1].start_ns <= kids[1].end_ns <= drain.end_ns


def test_a_jit_first_called_in_batch_fn_is_a_late_compile(cluster_graph, tmp_path):
    late = jax.jit(lambda x: x * 2.0 + 1.0)

    def compile_at_step_three(est):
        if est.step == 3 and est.params is not None:
            late(jnp.ones(4))

    _, got = _recorded_train(cluster_graph, tmp_path, 5, batch_hook=compile_at_step_three)
    by_id = {s.id: s for s in got}
    found = [s for s in got if s.name == "late_compile"]
    in_batches = [s for s in found if by_id[s.parent].name == "train.next_batch"]
    assert {by_id[s.parent].step for s in in_batches} == {3}
    assert {"trace", "lower", "compile"} <= {s.args["event"] for s in in_batches}
    # the step program's own compile is its first call's, and no late one
    (first,) = [s for s in got if s.name == "step.first_call"]
    kids = [s for s in got if s.parent == first.id]
    assert kids and all(s.name.startswith("step.first_call.") for s in kids)
    assert not [s for s in found if by_id[s.parent].name == "train.dispatch"]


def test_both_drivers_leave_the_same_span_tree(cluster_graph, tmp_path):
    def tree(steps_per_call):
        _, got = _recorded_train(cluster_graph, tmp_path, 8, steps_per_call)
        by_id = {s.id: s for s in got}
        return {
            (s.name, by_id[s.parent].name if s.parent in by_id else None)
            for s in got
            if s.name != "late_compile" and not s.name.startswith("step.first_call")
        }

    single, scanned = tree(1), tree(4)
    assert single == scanned == {
        ("train", None),
        ("train.step", "train"),
        ("train.next_batch", "train.step"),
        ("train.dispatch", "train.step"),
        ("train.drain", "train"),
        ("train.drain.wait", "train.drain"),
        ("train.drain.copy", "train.drain"),
    }
