"""Paged device-resident sampling lane (dataflow/device.py layout="paged").

The standing contracts this file pins:

1. SEED CONTRACT — paged and dense lanes draw BIT-IDENTICAL batches from
   the same key on the same graph (shared quantized-CDF inversion), so
   the parity story stays one lane wide.
2. The power-law regime the lane exists for: a hub graph that FAILS the
   dense max_degree guard stages paged (layout="auto" auto-selects it,
   and the dense error names the fix) and trains end-to-end.
3. Remote staging — a 2-shard cluster stages the same tables bit-for-bit
   over the wire (ids_by_rows + get_full_neighbor sweeps) as a local
   load of the same data, trains, and serves residual fetches through
   the client ReadCache (hit-rate telemetry asserted via the
   double-buffered ResidualFetchRing).
"""

import os

import jax
import numpy as np
import pytest

from euler_tpu.dataflow import DeviceSageFlow, DeviceUnsupSageFlow
from euler_tpu.datasets.synthetic import random_graph
from euler_tpu.estimator import (
    DeviceFeatureCache,
    Estimator,
    EstimatorConfig,
    ResidualFetchRing,
)
from euler_tpu.graph import Graph
from euler_tpu.graph import format as tformat
from euler_tpu.models import GraphSAGESupervised


def _leaves_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _hub_graph(n: int = 60, hub_deg: int = 40, weighted: bool = True):
    """One hub with degree >> page size, everyone else on a ring — the
    shape the dense [N+1, Dmax] table cannot stage economically."""
    nodes = [
        {
            "id": i,
            "type": 0,
            "weight": 1.0,
            "features": [
                {"name": "feat", "type": "dense",
                 "value": [float(i % 3), 1.0]},
                {"name": "label", "type": "dense",
                 "value": [float(i % 2), float(1 - i % 2)]},
            ],
        }
        for i in range(n)
    ]
    edges = [
        {"src": 0, "dst": 1 + (j % (n - 1)), "type": 0,
         "weight": 1.0 + (j % 5 if weighted else 0), "features": []}
        for j in range(hub_deg)
    ]
    edges += [
        {"src": i, "dst": (i + 1) % n, "type": 0,
         "weight": 2.0 if weighted and i % 2 else 1.0, "features": []}
        for i in range(1, n)
    ]
    return Graph.from_json({"nodes": nodes, "edges": edges})


# ---------------------------------------------------------------------------
# 1. the seed contract: paged == dense, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("page_size", [8, 16])
def test_paged_draws_bit_identical_to_dense(weighted, page_size):
    """Property over keys: both layouts emit the same MiniBatch pytree
    leaf-for-leaf from the same key — roots, hops, weights, labels."""
    g = random_graph(
        num_nodes=300, out_degree=6, feat_dim=8, seed=3, weighted=weighted
    )
    dense = DeviceSageFlow(
        g, fanouts=[4, 3], batch_size=16, label_feature="label",
        layout="dense",
    )
    paged = DeviceSageFlow(
        g, fanouts=[4, 3], batch_size=16, label_feature="label",
        layout="paged", page_size=page_size,
    )
    assert dense.layout == "dense" and paged.layout == "paged"
    fd, fp = jax.jit(dense.sample), jax.jit(paged.sample)
    for t in range(8):
        assert _leaves_equal(fd(jax.random.PRNGKey(t)),
                             fp(jax.random.PRNGKey(t))), f"key {t} diverged"


def test_paged_bit_identical_on_hub_graph():
    """The skewed case: multi-page hub rows invert the same quantized
    CDF the dense row scan does (two-level search == full-row count)."""
    g = _hub_graph(n=60, hub_deg=40, weighted=True)
    dense = DeviceSageFlow(g, fanouts=[5], batch_size=32, max_degree=512,
                           layout="dense")
    paged = DeviceSageFlow(g, fanouts=[5], batch_size=32, layout="paged",
                           page_size=8)
    assert paged.max_pages >= 5, "fixture must exercise multi-page rows"
    fd, fp = jax.jit(dense.sample), jax.jit(paged.sample)
    for t in range(8):
        assert _leaves_equal(fd(jax.random.PRNGKey(t)),
                             fp(jax.random.PRNGKey(t)))


def test_unsup_triples_bit_identical():
    """The (src, pos, negs) triple flow rides the same draw primitives —
    the whole 3-batch pytree must match across layouts."""
    g = random_graph(num_nodes=200, out_degree=5, feat_dim=4, seed=9,
                     weighted=True)
    dense = DeviceUnsupSageFlow(g, fanouts=[3, 2], batch_size=8,
                                num_negs=3, layout="dense")
    paged = DeviceUnsupSageFlow(g, fanouts=[3, 2], batch_size=8,
                                num_negs=3, layout="paged")
    assert _leaves_equal(
        jax.jit(dense.sample)(jax.random.PRNGKey(5)),
        jax.jit(paged.sample)(jax.random.PRNGKey(5)),
    )


# ---------------------------------------------------------------------------
# 2. the power-law regime: dense fails loudly, paged stages and trains
# ---------------------------------------------------------------------------


def test_dense_guard_names_the_paged_lane():
    g = _hub_graph(n=50, hub_deg=40)
    with pytest.raises(ValueError, match="paged"):
        DeviceSageFlow(g, fanouts=[3], batch_size=8, max_degree=8,
                       layout="dense")


def test_auto_selects_paged_past_the_guard_and_trains(tmp_path):
    """layout='auto' on a hub graph that fails the dense guard stages
    paged instead of raising, samples true edges, and trains."""
    g = _hub_graph(n=60, hub_deg=40, weighted=True)
    flow = DeviceSageFlow(
        g, fanouts=[4, 3], batch_size=16, label_feature="label",
        max_degree=8,  # hub degree 40 >> guard: dense would raise
    )
    assert flow.layout == "paged"
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    ids = np.concatenate([np.asarray(s.node_ids) for s in g.shards])
    rows0 = np.asarray(mb.feats[0]) - 1
    rows1 = np.asarray(mb.feats[1]).reshape(16, 4) - 1
    nbr, _, _, m, _ = g.get_full_neighbor(ids[rows0])
    for i in range(16):
        true_set = set(nbr[i][m[i]].tolist())
        for r in rows1[i]:
            if r >= 0:
                assert int(ids[r]) in true_set
    est = Estimator(
        GraphSAGESupervised(dims=[8, 8], label_dim=2),
        flow,
        EstimatorConfig(model_dir=str(tmp_path / "pl"), learning_rate=0.05,
                        log_steps=10**9, steps_per_call=4),
        feature_cache=DeviceFeatureCache(g, ["feat"]),
    )
    losses = est.train(total_steps=8, log=False, save=False)
    assert np.isfinite(losses).all()


def test_paged_weighted_hub_distribution():
    """Hub draws follow edge weights through the paged two-level CDF:
    the hub's 1..5-weighted fan must be sampled ∝ weight."""
    g = _hub_graph(n=40, hub_deg=35, weighted=True)
    ids = np.concatenate([np.asarray(s.node_ids) for s in g.shards])
    hub_row = int(g.lookup_rows(np.array([0], np.uint64))[0])
    flow = DeviceSageFlow(
        g, fanouts=[64], batch_size=64, layout="paged", page_size=8,
        roots_pool=np.array([0], np.uint64),
    )
    nbr, w, _, m, _ = g.get_full_neighbor(np.array([0], np.uint64))
    w_of = {}
    for a, b in zip(nbr[0][m[0]], w[0][m[0]]):
        w_of[int(a)] = w_of.get(int(a), 0.0) + float(b)
    total_w = sum(w_of.values())
    counts = {}
    fn = jax.jit(flow.sample)
    for t in range(20):
        mb = fn(jax.random.PRNGKey(t))
        assert np.all(np.asarray(mb.feats[0]) == hub_row + 1)
        for x in np.asarray(mb.feats[1]):
            nid = int(ids[x - 1])
            counts[nid] = counts.get(nid, 0) + 1
    total = sum(counts.values())
    assert total == 20 * 64 * 64
    for nid, cnt in counts.items():
        expect = w_of[nid] / total_w
        assert abs(cnt / total - expect) < 0.05, (nid, cnt / total, expect)


def test_paged_trailing_isolated_node_pads():
    """A degree-0 node at the END of the row space (its page_start ==
    total pages) draws padding: the masked gather stays in-bounds."""
    n = 20
    nodes = [
        {"id": i, "type": 0, "weight": 1.0,
         "features": [{"name": "feat", "type": "dense", "value": [1.0]}]}
        for i in range(n)
    ]
    # every node but the LAST (by row order = id order) has out-edges
    edges = [
        {"src": i, "dst": (i + 1) % (n - 1), "type": 0,
         "weight": 1.0 + i % 3, "features": []}
        for i in range(n - 1)
    ]
    g = Graph.from_json({"nodes": nodes, "edges": edges})
    iso = np.array([n - 1], np.uint64)
    flow = DeviceSageFlow(
        g, fanouts=[3], batch_size=8, layout="paged", page_size=8,
        roots_pool=iso,
    )
    assert int(flow.deg[-1]) == 0
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    assert np.all(np.asarray(mb.feats[1]) == 0)


@pytest.mark.parametrize("page_size", [0, 12, 256])
def test_page_size_must_divide_the_staged_row(page_size):
    """The staged format keeps a page inside one PAGE_LANES-wide row."""
    g = random_graph(num_nodes=40, out_degree=3, feat_dim=4, seed=4)
    with pytest.raises(ValueError, match="page_size must divide 128"):
        DeviceSageFlow(g, fanouts=[2], batch_size=4, layout="paged",
                       page_size=page_size)


def test_paged_rejected_for_dense_plane_flows():
    """Flows that read the dense planes directly refuse the paged layout
    with a clear error instead of crashing mid-trace."""
    from euler_tpu.dataflow import DeviceWalkFlow

    g = random_graph(num_nodes=60, out_degree=4, feat_dim=4, seed=1)
    with pytest.raises(ValueError, match="SAGE-family"):
        DeviceWalkFlow(g, batch_size=8, walk_len=2, layout="paged")


# ---------------------------------------------------------------------------
# 3. remote staging + residual fetches through the ReadCache
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    from euler_tpu.distributed import connect, serve_shard

    base = tmp_path_factory.mktemp("paged_remote")
    data = str(base / "data")
    g = random_graph(
        num_nodes=240, out_degree=5, feat_dim=8, seed=7,
        num_partitions=2, weighted=True,
    )
    for p, sh in enumerate(g.shards):
        tformat.write_arrays(os.path.join(data, f"part_{p}"), sh.arrays)
    g.meta.save(data)
    services = [
        serve_shard(data, 0, native=False),
        serve_shard(data, 1, native=False),
    ]
    remote = connect(
        cluster={
            0: [("127.0.0.1", services[0].port)],
            1: [("127.0.0.1", services[1].port)],
        }
    )
    local = Graph.load(data, native=False)
    yield remote, local, services
    for s in services:
        s.stop()


def test_ids_by_rows_verb(cluster):
    remote, local, _ = cluster
    from euler_tpu.graph.store import DEFAULT_ID

    sh_r, sh_l = remote.shards[0], local.shards[0]
    rows = np.array([0, 1, 5, sh_l.num_nodes, -1], np.int64)
    ids, w, tt = sh_r.ids_by_rows(rows)
    np.testing.assert_array_equal(ids[:3], np.asarray(sh_l.node_ids)[rows[:3]])
    assert ids[3] == DEFAULT_ID and ids[4] == DEFAULT_ID
    np.testing.assert_allclose(
        w[:3], np.asarray(sh_l.node_weights, np.float64)[rows[:3]]
    )
    assert tt[3] == -1 and tt[4] == -1


def test_remote_paged_staging_bit_identical_to_local(cluster):
    """The tables staged over the wire must EQUAL a local load's, and so
    must the sampled batches — the remote seed-contract half."""
    remote, local, _ = cluster
    fr = DeviceSageFlow(remote, fanouts=[3, 2], batch_size=8,
                        label_feature="label", layout="paged")
    fl = DeviceSageFlow(local, fanouts=[3, 2], batch_size=8,
                        label_feature="label", layout="paged")
    for attr in ("pages2d", "page_start", "deg", "page_q2d", "page_w2d",
                 "page_bound", "node_id"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fr, attr)), np.asarray(getattr(fl, attr)),
            err_msg=attr,
        )
    for t in range(4):
        assert _leaves_equal(
            jax.jit(fr.sample)(jax.random.PRNGKey(t)),
            jax.jit(fl.sample)(jax.random.PRNGKey(t)),
        )


def test_remote_paged_trains_with_residual_ring(cluster, tmp_path):
    """The acceptance scenario: a 2-shard remote graph stages the paged
    lane, trains end-to-end, and residual row re-fetches ride the client
    ReadCache (hit-rate telemetry > 0) on the double-buffered ring."""
    remote, _, services = cluster
    flow = DeviceSageFlow(remote, fanouts=[3, 2], batch_size=8,
                          label_feature="label", layout="paged")
    cache = DeviceFeatureCache(remote, ["feat"])
    est = Estimator(
        GraphSAGESupervised(dims=[8, 8], label_dim=2),
        flow,
        EstimatorConfig(model_dir=str(tmp_path / "rp"), learning_rate=0.05,
                        log_steps=10**9, steps_per_call=2),
        feature_cache=cache,
    )
    losses = est.train(total_steps=4, log=False, save=False)
    assert np.isfinite(losses).all()
    ring = ResidualFetchRing(cache, remote)
    try:
        rows = np.arange(200, dtype=np.int64)
        for _ in range(2):  # pass 1 may miss; pass 2 must hit the cache
            assert ring.prefetch(rows)
            ring.flush()
        st = ring.stats()
        assert st["fetched_rows"] == 400
        assert st["residual_fetch_hit_rate"] > 0.4, st
        # the patched rows equal a direct fetch (the swap is lossless)
        direct = np.asarray(remote.get_dense_by_rows(rows, ["feat"]),
                            np.float32)
        np.testing.assert_allclose(
            np.asarray(cache.table)[rows + 1], direct, rtol=1e-6
        )
    finally:
        ring.close()


def test_ring_epoch_bump_restages(cluster):
    """bump_epoch on a shard → poll_epoch sees it (refresh_epoch flushes
    that shard's ReadCache) and schedules the residual refresh."""
    remote, _, services = cluster
    cache = DeviceFeatureCache(remote, ["feat"])
    ring = ResidualFetchRing(cache, remote)
    try:
        assert ring.poll_epoch() in (False, True)  # records baselines
        assert ring.poll_epoch() is False  # steady state: no bump
        services[0].store.bump_epoch()
        assert ring.poll_epoch(hot_rows=np.arange(64)) is True
        ring.flush()
        assert ring.stats()["fetched_rows"] >= 64
    finally:
        ring.close()
