"""Edge-axis (subgraph) parallelism + bf16 mixed-precision convs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu.ops import scatter_add
from euler_tpu.parallel import make_mesh, sp_segment_mean, sp_segment_sum

from test_training import make_cluster_graph


def test_sp_segment_sum_matches_local():
    mesh = make_mesh(8, model=8)  # all devices on the edge axis
    rng = np.random.default_rng(0)
    E, F, n_dst = 64, 16, 10
    msgs = jnp.asarray(rng.normal(size=(E, F)).astype(np.float32))
    dst = jnp.asarray(rng.integers(0, n_dst, E).astype(np.int32))
    mask = jnp.asarray(rng.random(E) > 0.3)
    want = scatter_add(msgs, dst, n_dst, mask=mask)
    got = sp_segment_sum(msgs, dst, n_dst, mesh, axis="model", mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_sp_segment_mean_under_jit():
    mesh = make_mesh(8, model=4)
    rng = np.random.default_rng(1)
    E, F, n_dst = 32, 8, 6
    msgs = jnp.asarray(rng.normal(size=(E, F)).astype(np.float32))
    dst = jnp.asarray((np.arange(E) % n_dst).astype(np.int32))

    @jax.jit
    def f(m, d):
        return sp_segment_mean(m, d, n_dst, mesh, axis="model")

    got = f(msgs, dst)
    want = np.zeros((n_dst, F), np.float32)
    cnt = np.zeros(n_dst, np.float32)
    np.add.at(want, np.asarray(dst), np.asarray(msgs))
    np.add.at(cnt, np.asarray(dst), 1.0)
    np.testing.assert_allclose(
        np.asarray(got), want / cnt[:, None], atol=1e-5
    )


def test_sp_edge_count_must_divide():
    mesh = make_mesh(8, model=8)
    msgs = jnp.ones((10, 4))
    dst = jnp.zeros(10, jnp.int32)
    with pytest.raises(Exception):
        sp_segment_sum(msgs, dst, 4, mesh, axis="model")


@pytest.mark.parametrize("conv", ["gcn", "sage", "gat", "gin"])
def test_bf16_conv_forward(conv):
    from euler_tpu.dataflow import SageDataFlow
    from euler_tpu.layers import get_conv

    graph = make_cluster_graph()
    flow = SageDataFlow(graph, ["feat"], fanouts=[3])
    mb = flow.query(np.asarray([1, 2, 3, 4], np.uint64))
    layer = get_conv(conv)(out_dim=8, dtype=jnp.bfloat16)
    params = layer.init(
        jax.random.PRNGKey(0), mb.feats[0], mb.feats[1], mb.blocks[0]
    )
    # params stay f32 (mixed precision), compute runs bf16
    leaves = jax.tree.leaves(params)
    assert all(
        leaf.dtype == jnp.float32
        for leaf in leaves
        if jnp.issubdtype(leaf.dtype, jnp.floating)
    )
    out = layer.apply(params, mb.feats[0], mb.feats[1], mb.blocks[0])
    assert jnp.isfinite(out.astype(jnp.float32)).all()


def test_bf16_train_step_warning_clean():
    """Full bf16 train step under FutureWarning-as-error (VERDICT r2 #4)."""
    import warnings

    from euler_tpu.dataflow import SageDataFlow
    from euler_tpu.estimator import Estimator, EstimatorConfig, node_batches
    from euler_tpu.models import GraphSAGESupervised

    graph = make_cluster_graph()
    rng = np.random.default_rng(0)
    flow = SageDataFlow(
        graph, ["feat"], fanouts=[3, 2], label_feature="label", rng=rng
    )
    model = GraphSAGESupervised(
        dims=[16, 16], label_dim=2, conv_kwargs={"dtype": jnp.bfloat16}
    )
    est = Estimator(
        model,
        node_batches(graph, flow, 16, rng=rng),
        EstimatorConfig(model_dir="/tmp/bf16_warn_run", log_steps=10**9),
    )
    with warnings.catch_warnings():
        warnings.simplefilter("error", FutureWarning)
        hist = est.train(total_steps=3, log=False, save=False)
    assert np.isfinite(hist).all()


def test_bf16_gnn_training():
    from euler_tpu.dataflow import SageDataFlow
    from euler_tpu.estimator import Estimator, EstimatorConfig, node_batches
    from euler_tpu.models import GraphSAGESupervised

    graph = make_cluster_graph()
    rng = np.random.default_rng(0)
    flow = SageDataFlow(
        graph, ["feat"], fanouts=[3, 2], label_feature="label", rng=rng
    )
    model = GraphSAGESupervised(
        dims=[16, 16], label_dim=2, conv_kwargs={"dtype": jnp.bfloat16}
    )
    est = Estimator(
        model,
        node_batches(graph, flow, 16, rng=rng),
        EstimatorConfig(model_dir="/tmp/bf16_run", log_steps=10**9),
    )
    hist = est.train(total_steps=15, log=False, save=False)
    assert np.isfinite(hist).all()
    assert hist[-1] < hist[0]
