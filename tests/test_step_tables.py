"""The Estimator's programs take the staged graph, feature and label
tables as an argument, read from their owners at every dispatch: no
program compiles a table in as a constant, a `refresh_rows` reaches the
next step without a recompile, and under a mesh the tables sit
replicated and are not moved again."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu import dataflow as df
from euler_tpu import models
from euler_tpu.datasets.synthetic import random_graph
from euler_tpu.distributed.writer import GraphWriter
from euler_tpu.estimator import (
    DeviceFeatureCache,
    Estimator,
    EstimatorConfig,
    node_batches,
)
from euler_tpu.estimator.estimator import _flow_probe
from euler_tpu.graph import Graph
from euler_tpu.models.embedding_models import SkipGramModel
from euler_tpu.utils import trace

N = 3000  # every staged plane over N rows is then larger than the limit
CLOSED_OVER_LIMIT = 64 << 10


def closed_over(fn, *args) -> list:
    """Every array the traced `fn(*args)` closes over, at any depth."""
    seen: dict = {}

    def walk(jaxpr, consts=()):
        for const in consts:
            if hasattr(const, "nbytes"):
                seen[id(const)] = const
        for eqn in jaxpr.eqns:
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else (value,):
                    if hasattr(sub, "jaxpr") and hasattr(sub, "consts"):
                        walk(sub.jaxpr, sub.consts)
                    elif hasattr(sub, "eqns"):
                        walk(sub)

    traced = fn.trace(*args).jaxpr
    walk(traced.jaxpr, traced.consts)
    return list(seen.values())


def assert_tables_are_arguments(fn, args, owners) -> None:
    consts = closed_over(fn, *args)
    # every device array an owner holds, found without `tables()`, so a
    # table that `tables()` misses is named here however small it is
    for owner in owners:
        for name, value in vars(owner).items():
            for leaf in jax.tree_util.tree_leaves(value):
                assert not any(leaf is const for const in consts), (
                    f"{type(owner).__name__}.{name} is compiled into "
                    f"{fn.__name__} as a constant"
                )
    total = sum(const.nbytes for const in consts)
    assert total < CLOSED_OVER_LIMIT, (
        f"{fn.__name__} closes over {total} bytes: "
        f"{[(c.shape, str(c.dtype)) for c in consts]}"
    )


@pytest.fixture(scope="module")
def graph():
    return random_graph(num_nodes=N, out_degree=6, feat_dim=8, seed=3)


@pytest.fixture(scope="module")
def typed_graph():
    """A ring with three relations: i -> i + r + 1 has type r."""
    nodes = [
        {"id": i, "type": 0, "weight": 1.0,
         "features": [
             {"name": "feat", "type": "dense", "value": [float(i % 3), 1.0]},
             {"name": "label", "type": "dense",
              "value": [float(i % 2), float(1 - i % 2)]},
         ]}
        for i in range(N)
    ]
    edges = [
        {"src": i, "dst": (i + d) % N, "type": d - 1, "weight": 1.0,
         "features": []}
        for i in range(N)
        for d in (1, 2, 3)
    ]
    return Graph.from_json({"nodes": nodes, "edges": edges})


def _sage(graph, **kw):
    flow = df.DeviceSageFlow(
        graph, fanouts=[3, 2], batch_size=8, label_feature="label", **kw
    )
    return models.GraphSAGESupervised(dims=[8, 8], label_dim=2), flow, True


def _weighted_paged(graph):
    weighted = random_graph(
        num_nodes=N, out_degree=6, feat_dim=8, seed=4, weighted=True
    )
    flow = df.DeviceSageFlow(
        weighted, fanouts=[3, 2], batch_size=8, label_feature="label",
        layout="paged",
    )
    return models.GraphSAGESupervised(dims=[8, 8], label_dim=2), flow, weighted


def _unsup(graph):
    flow = df.DeviceUnsupSageFlow(graph, fanouts=[3], batch_size=8, num_negs=2)
    return models.GraphSAGEUnsupervised(dims=[8]), flow, True


def _walk(graph):
    flow = df.DeviceWalkFlow(graph, batch_size=4, walk_len=3, window=1)
    return SkipGramModel(num_nodes=N, dim=8), flow, False


def _edge(graph):
    flow = df.DeviceEdgeFlow(graph, batch_size=16, num_negs=2)
    return SkipGramModel(num_nodes=N, dim=8), flow, False


def _kg(graph):
    flow = df.DeviceKGFlow(graph, batch_size=16, num_negs=2)
    model = models.TransX(
        num_entities=N, num_relations=1, dim=8, variant="transe"
    )
    return model, flow, False


def _relation(typed_graph):
    flow = df.DeviceRelationFlow(
        typed_graph, ["feat"], num_relations=3, batch_size=4, fanout=2,
        num_hops=2, label_feature="label",
    )
    model = models.RGCNSupervised(
        dims=[8, 8], num_relations=3, label_dim=2, num_bases=2
    )
    return model, flow, False


def _layerwise(graph):
    flow = df.DeviceLayerwiseFlow(
        graph, ["feat"], batch_size=4, layer_sizes=[16, 16],
        label_feature="label",
    )
    return models.LayerwiseGCN(dims=[8, 8], label_dim=2), flow, False


def _gae(graph):
    flow = df.DeviceGaeFlow(graph, fanouts=[3], batch_size=8)
    return models.GAE(dims=[8]), flow, True


def _dgi(graph):
    flow = df.DeviceDgiFlow(graph, fanouts=[3], batch_size=8)
    return models.DGI(dims=[8]), flow, True


def _whole(graph):
    from euler_tpu.datasets.catalog import get_dataset

    mutag = get_dataset("mutag").load_graph(synthetic=True)
    flow = df.DeviceWholeGraphFlow(
        mutag, ["feature"], batch_size=4, max_nodes=16, max_degree=8
    )
    model = models.GraphClassifier(
        conv="gin", dims=(8, 8), num_classes=flow.num_classes, pool="mean"
    )
    return model, flow, False


def _host_lane(graph):
    flow = df.SageDataFlow(
        graph, ["feat"], fanouts=[3, 2], label_feature="label",
        feature_mode="rows", lean=True, rng=np.random.default_rng(0),
    )
    batch_fn = node_batches(graph, flow, 8, rng=np.random.default_rng(1))
    return models.GraphSAGESupervised(dims=[8, 8], label_dim=2), batch_fn, True


# per class: the builder, and the names of what it stages on the device.
# A table a subclass adds shows up here by name; one that the generic
# `tables()` missed would show among the closed-over constants.
DENSE = {"adj", "deg", "node_id"}
FLAT = {"eh", "et", "node_id"}
CASES = {
    "DeviceSageFlow": (_sage, DENSE | {"label_table"}),
    "DeviceSageFlow-weighted-paged": (
        _weighted_paged,
        {"pages2d", "page_start", "deg", "page_w2d", "page_q2d",
         "page_bound", "node_id", "label_table"},
    ),
    "DeviceUnsupSageFlow": (_unsup, DENSE),
    "DeviceWalkFlow": (_walk, DENSE),
    "DeviceEdgeFlow": (_edge, FLAT),
    "DeviceKGFlow": (_kg, FLAT | {"er"}),
    "DeviceRelationFlow": (
        _relation, DENSE | {"ttab", "feat_table", "label_table"},
    ),
    "DeviceLayerwiseFlow": (
        _layerwise, DENSE | {"feat_table", "label_table"},
    ),
    "DeviceGaeFlow": (_gae, DENSE | {"edge_src_cdf"}),
    "DeviceDgiFlow": (_dgi, DENSE),
    "DeviceWholeGraphFlow": (
        _whole,
        {"gfeats", "gmask", "gesrc", "gedst", "gew", "gemask", "glabels",
         "ghop"},
    ),
    "host-lane": (_host_lane, set()),
}


@pytest.mark.parametrize("case", list(CASES))
def test_train_steps_take_the_tables_as_arguments(
    case, graph, typed_graph, tmp_path
):
    build, staged = CASES[case]
    model, batch_fn, cached = build(
        typed_graph if build is _relation else graph
    )
    cache = None
    if cached:
        cache = DeviceFeatureCache(
            graph if cached is True else cached, ["feat"]
        )
    est = Estimator(
        model, batch_fn,
        EstimatorConfig(model_dir=str(tmp_path / "m"), log_steps=10**9),
        feature_cache=cache,
    )
    flow = est._device_flow
    owners = [o for o in (flow, cache) if o is not None]
    if flow is not None:
        assert set(flow.tables()) == staged
    if cache is not None:
        assert set(cache.tables()) == {"table"}
    est._ensure_init()
    state = (est.params, est.opt_state, est._tables())
    assert_tables_are_arguments(
        est._train_step(), (*state, est._rngs(0), *est._next_batch(1)), owners
    )
    if flow is not None:  # a host batch_fn stacks through `stack_batches`
        assert_tables_are_arguments(
            est._train_step_scan(),
            (*state, est._rngs_stacked(0, 2), *est._next_batch(2)),
            owners,
        )
    since = time.perf_counter_ns()
    losses = est.train(1, log=False, save=False)
    assert np.isfinite(losses).all()
    (call,) = [
        s for s in trace.spans()
        if s.name == "step.first_call" and s.start_ns >= since
    ]
    assert call.args["program"] == "train_step"
    assert call.args["table_arg_bytes"] == sum(
        table.nbytes for owner in owners for table in owner.tables().values()
    )
    assert call.args["table_arg_bytes"] > 0


def test_int8_cache_stages_its_scale_and_zero_point(graph):
    cache = DeviceFeatureCache(graph, ["feat"], quant="int8")
    assert set(cache.tables()) == {"table", "_scale", "_zero"}
    rows = jnp.arange(1, 9)
    bound = cache.bind(cache.tables())
    np.testing.assert_array_equal(bound.gather(rows), cache.gather(rows))
    assert_tables_are_arguments(
        jax.jit(lambda tables, r: cache.bind(tables).gather(r)),
        (cache.tables(), rows), [cache],
    )


@pytest.fixture(scope="module")
def served(graph, tmp_path_factory):
    """One trained Estimator on a device flow and a feature cache, and
    host batches for its eval and embed programs."""
    model, flow, _ = _sage(graph)
    cache = DeviceFeatureCache(graph, ["feat"])
    est = Estimator(
        model, flow,
        EstimatorConfig(
            model_dir=str(tmp_path_factory.mktemp("served")), log_steps=10**9
        ),
        feature_cache=cache,
    )
    est.train(1, log=False, save=False)
    host = df.SageDataFlow(
        graph, ["feat"], fanouts=[3, 2], label_feature="label",
        feature_mode="rows", rng=np.random.default_rng(0),
    )
    ids = np.concatenate([np.asarray(s.node_ids) for s in graph.shards])[:8]
    return est, flow, cache, host.query(ids), ids


@pytest.mark.parametrize("program", ["eval", "embed", "probe"])
def test_other_programs_take_the_tables_as_arguments(served, program):
    est, flow, cache, batch, ids = served
    if program == "eval":
        assert np.isfinite(est.evaluate([(batch,)])["loss"])
        fn, owners = est._jit_eval, [cache]
        args = (est.params, est._tables(flow=False), est._rngs(0), batch)
    elif program == "embed":
        _, emb = est.infer([(batch,)], [ids])
        assert emb.shape[0] == len(ids)
        fn, owners = est.embed_program().jitted, [cache]
        args = (est.params, est._tables(flow=False), batch)
    else:
        fn, owners = _flow_probe(flow), [flow]
        args = (flow.tables(), jax.random.PRNGKey(0))
    assert_tables_are_arguments(fn, args, owners)
    passed = jax.tree_util.tree_leaves(args[0] if program == "probe" else args[1])
    assert sum(t.nbytes for t in passed) == sum(
        t.nbytes for owner in owners for t in owner.tables().values()
    )


def test_a_caller_may_still_jit_the_unbound_flow(served):
    """`jax.jit(flow.sample)` closes over the tables, as it always did,
    and draws what the Estimator's probe draws from its argument."""
    _, flow, _, _, _ = served
    key = jax.random.PRNGKey(5)
    mine = jax.jit(flow.sample)
    assert sum(c.nbytes for c in closed_over(mine, key)) >= sum(
        t.nbytes for t in flow.tables().values()
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(mine(key)),
        jax.tree_util.tree_leaves(_flow_probe(flow)(flow.tables(), key)),
    ):
        np.testing.assert_array_equal(a, b)


# -- a refresh reaches the next step ------------------------------------


def _mutable_graph():
    n = 48
    rng = np.random.default_rng(0)
    nodes = [
        {"id": i, "type": 0, "weight": 1.0,
         "features": [
             {"name": "feat", "type": "dense",
              "value": rng.normal(size=4).tolist()},
             {"name": "label", "type": "dense",
              "value": [1.0, 0.0] if i % 2 else [0.0, 1.0]},
         ]}
        for i in range(1, n + 1)
    ]
    edges = [
        {"src": s, "dst": (s + off) % n + 1, "type": 0, "weight": 1.0,
         "features": []}
        for s in range(1, n + 1)
        for off in ((1, 3, 7, 11, 13) if s == 1 else (1, 3, 7))
    ]
    return Graph.from_json({"nodes": nodes, "edges": edges})


def _staged_estimator(graph, tmp_path, name):
    flow = df.DeviceSageFlow(
        graph, fanouts=[3, 2], batch_size=16, label_feature="label",
        layout="dense",
    )
    cache = DeviceFeatureCache(graph, ["feat"])
    est = Estimator(
        models.GraphSAGESupervised(dims=[8, 8], label_dim=2), flow,
        EstimatorConfig(model_dir=str(tmp_path / name), log_steps=10**9),
        feature_cache=cache,
    )
    return est, flow, cache


def _resume(est, params, opt_state):
    copy = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.array(x, copy=True), tree
    )
    est.params, est.opt_state, est.step = copy(params), copy(opt_state), 1
    return est.train(1, log=False, save=False)[0]


def test_a_refresh_reaches_the_next_step_without_a_recompile(tmp_path):
    g = _mutable_graph()
    est, flow, cache = _staged_estimator(g, tmp_path, "live")
    stale, _, _ = _staged_estimator(g, tmp_path, "stale")
    est.train(1, log=False, save=False)
    after_one = jax.tree_util.tree_map(np.asarray, (est.params, est.opt_state))

    ids = np.arange(1, 49, dtype=np.uint64)
    w = GraphWriter(g)
    w.upsert_nodes(
        ids, np.zeros(48, np.int32), np.ones(48, np.float32),
        dense={"feat": (np.arange(48 * 4).reshape(48, 4) % 7 - 3.0).tolist()},
    )
    w.upsert_edges([2, 3, 5], [30, 31, 32], [0, 0, 0], [1.0, 1.0, 1.0])
    w.delete_edges([4], [6], [0])
    rows = w.publish()["rows"]
    assert flow.refresh_rows(g, rows) > 0 and cache.refresh_rows(g, rows) > 0

    compiled = []

    def on_duration(event, seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        second = est.train(1, log=False, save=False)[0]
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert not compiled, "the refreshed tables recompiled the step"

    fresh, _, _ = _staged_estimator(g, tmp_path, "fresh")
    assert second == _resume(fresh, *after_one)
    assert second != _resume(stale, *after_one), (
        "the mutation does not show in the loss: the test proves nothing"
    )


def test_mesh_tables_are_replicated_once(graph, tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec

    from euler_tpu.parallel import make_mesh

    mesh = make_mesh(8)
    model, flow, _ = _sage(graph, mesh=mesh)
    cache = DeviceFeatureCache(graph, ["feat"])
    est = Estimator(
        model, flow,
        EstimatorConfig(model_dir=str(tmp_path / "mesh"), log_steps=10**9),
        mesh=mesh, feature_cache=cache,
    )
    step, seen = est._train_step(), []

    def train_step(params, opt_state, tables, *rest):
        seen.append(tables)
        return step(params, opt_state, tables, *rest)

    est._jit_train = train_step
    losses = est.train(2, log=False, save=False)
    assert np.isfinite(losses).all() and len(seen) == 2

    def buffers(tables):
        return [
            [shard.data.unsafe_buffer_pointer() for shard in t.addressable_shards]
            for t in jax.tree_util.tree_leaves(tables)
        ]

    replicated = NamedSharding(mesh, PartitionSpec())
    for table in jax.tree_util.tree_leaves(seen[0]):
        assert table.sharding == replicated and len(table.addressable_shards) == 8
    assert buffers(seen[0]) == buffers(seen[1])
    assert seen[1]["flow"]["adj"] is flow.adj
    assert seen[1]["features"]["table"] is cache.table
    # a refresh keeps the layout, so nothing is placed again
    cache.refresh_rows(graph, [0, 1])
    assert est._tables()["features"]["table"].sharding == replicated
