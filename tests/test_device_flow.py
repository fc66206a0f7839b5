"""On-device sampling flow (dataflow/device.py): structure parity with the
host lean wire, sampling-distribution correctness, and Estimator
integration (train-from-keys, determinism, scan/step invariance).

This is the TPU-first replacement for the reference's host-side
sample_fanout feeding (euler/core/kernels/sample_fanout_op.cc): the
sampler runs as traced XLA ops against an HBM-resident adjacency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu.dataflow import DeviceSageFlow, SageDataFlow
from euler_tpu.datasets.synthetic import random_graph
from euler_tpu.estimator import DeviceFeatureCache, Estimator, EstimatorConfig
from euler_tpu.models import GraphSAGESupervised


@pytest.fixture(scope="module")
def graph():
    return random_graph(num_nodes=300, out_degree=6, feat_dim=8, seed=3)


@pytest.fixture(scope="module")
def flow(graph):
    return DeviceSageFlow(
        graph, fanouts=[4, 3], batch_size=16, label_feature="label"
    )


@pytest.fixture(scope="module")
def fcache(graph):
    # shared across tests: estimators keyed on the same (model, flow,
    # cache) objects reuse jitted train steps via the estimator's
    # cross-instance step cache instead of re-tracing per test
    return DeviceFeatureCache(graph, ["feat"])


def test_structure_matches_host_lean_wire(graph, flow):
    """The device batch must be pytree-identical to a device_put host lean
    batch: models, hydrate_blocks, and the feature cache are shared."""
    host = SageDataFlow(
        graph, ["feat"], fanouts=[4, 3], label_feature="label",
        feature_mode="rows", lean=True, rng=np.random.default_rng(0),
    )
    roots = graph.sample_node(16, rng=np.random.default_rng(0))
    host_mb = jax.device_put(host.query(roots))
    dev_mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    th = jax.tree_util.tree_structure(host_mb)
    td = jax.tree_util.tree_structure(dev_mb)
    assert th == td
    for a, b in zip(jax.tree_util.tree_leaves(host_mb),
                    jax.tree_util.tree_leaves(dev_mb)):
        assert a.shape == b.shape, (a.shape, b.shape)


def test_sampled_neighbors_are_real_edges(graph, flow):
    """Every sampled hop-1 node must be a true out-neighbor of its root."""
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(7))
    ids = np.concatenate([np.asarray(s.node_ids) for s in graph.shards])
    rows0 = np.asarray(mb.feats[0]) - 1  # row+1 encoding
    rows1 = np.asarray(mb.feats[1]).reshape(16, 4) - 1
    nbr, _, _, mask, _ = graph.get_full_neighbor(ids[rows0])
    for i in range(16):
        true_set = set(nbr[i][mask[i]].tolist())
        for r in rows1[i]:
            if r >= 0:
                assert int(ids[r]) in true_set


def test_uniform_sampling_distribution(graph):
    """Hop draws are uniform over each node's neighbor list."""
    flow = DeviceSageFlow(graph, fanouts=[64], batch_size=64)
    fn = jax.jit(flow.sample)
    counts = {}
    node = None
    for t in range(30):
        mb = fn(jax.random.PRNGKey(t))
        roots = np.asarray(mb.feats[0])
        hop = np.asarray(mb.feats[1]).reshape(64, 64)
        if node is None:
            node = int(roots[0])
        for r, row in zip(roots, hop):
            if int(r) == node:
                for x in row:
                    counts[int(x)] = counts.get(int(x), 0) + 1
    # the chosen node appears >=30 times x64 draws; each of its <=6
    # neighbors should get a roughly equal share
    total = sum(counts.values())
    assert total >= 64
    freqs = np.array(list(counts.values())) / total
    assert freqs.max() / freqs.min() < 3.0


def test_degree_zero_pads(graph):
    """An isolated root yields all-padding hop slots (rows 0)."""
    ids = np.concatenate([np.asarray(s.node_ids) for s in graph.shards])
    deg = graph.degree_sum(ids)
    flow = DeviceSageFlow(graph, fanouts=[4], batch_size=8)
    if (deg == 0).any():
        iso = ids[deg == 0][:1]
        pool_flow = DeviceSageFlow(
            graph, fanouts=[4], batch_size=8, roots_pool=iso
        )
        mb = jax.jit(pool_flow.sample)(jax.random.PRNGKey(0))
        assert np.all(np.asarray(mb.feats[1]) == 0)
    else:  # synthetic graph has no isolates: padding rows 0 do instead
        assert int(flow.deg[0]) == 0 and np.all(np.asarray(flow.adj[0]) == 0)


def test_roots_pool(graph):
    pool = np.array([5, 6, 7], dtype=np.uint64)
    flow = DeviceSageFlow(graph, fanouts=[3], batch_size=32, roots_pool=pool)
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(1))
    rows = graph.lookup_rows(pool) + 1
    assert set(np.asarray(mb.feats[0]).tolist()) <= set(rows.tolist())


def test_root_node_type_restricts_draws():
    """root_node_type draws roots only from that type (sample_node(t)
    parity on heterogeneous graphs)."""
    from euler_tpu.graph import Graph

    nodes = [
        {"id": i, "type": i % 2, "weight": 1.0,
         "features": [{"name": "feat", "type": "dense", "value": [1.0]}]}
        for i in range(20)
    ]
    edges = [
        {"src": i, "dst": (i + 1) % 20, "type": 0, "weight": 1.0,
         "features": []}
        for i in range(20)
    ]
    g = Graph.from_json({"nodes": nodes, "edges": edges})
    flow = DeviceSageFlow(g, fanouts=[2], batch_size=64, root_node_type=1)
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    ids = np.concatenate([np.asarray(s.node_ids) for s in g.shards])
    roots = ids[np.asarray(mb.feats[0]) - 1]
    assert np.all(roots % 2 == 1), "drew a type-0 root"


def test_weighted_structure_matches_host_weighted_lean():
    """Weighted graphs ship bf16 edge weights, leaf-for-leaf like the
    host weighted-lean wire (sage.py _lean_w)."""
    g = random_graph(num_nodes=100, out_degree=5, feat_dim=4, seed=1,
                     weighted=True)
    host = SageDataFlow(
        g, ["feat"], fanouts=[3, 2], label_feature="label",
        feature_mode="rows", lean=True, rng=np.random.default_rng(0),
    )
    roots = g.sample_node(8, rng=np.random.default_rng(0))
    host_mb = jax.device_put(host.query(roots))
    flow = DeviceSageFlow(g, fanouts=[3, 2], batch_size=8,
                          label_feature="label")
    dev_mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    assert host.lean and host._lean_w, "fixture must exercise weighted-lean"
    assert (jax.tree_util.tree_structure(host_mb)
            == jax.tree_util.tree_structure(dev_mb))
    assert dev_mb.blocks[0].edge_w.dtype == jnp.bfloat16
    for a, b in zip(jax.tree_util.tree_leaves(host_mb),
                    jax.tree_util.tree_leaves(dev_mb)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_weighted_edge_distribution():
    """Hop draws follow edge weights: a node whose out-edges carry weights
    1 and 3 must be sampled ~1:3."""
    g = random_graph(num_nodes=60, out_degree=2, feat_dim=4, seed=2)
    store = g.shards[0]
    # make every node's two out-edges carry weights 1 and 3
    w = np.asarray(store.arrays["edge_weights"], dtype=np.float32)
    w[0::2], w[1::2] = 1.0, 3.0
    store.arrays["edge_weights"][:] = w
    store.__init__(store.meta, store.arrays, store.part)  # rebuild samplers
    flow = DeviceSageFlow(g, fanouts=[64], batch_size=60)
    assert not flow.unit_w
    fn = jax.jit(flow.sample)
    hits = {}
    ids = np.asarray(store.node_ids)
    node = int(ids[0])
    nbr, wfull, _, mask, _ = g.get_full_neighbor(np.array([node], np.uint64))
    w_by_nbr = {int(a): float(b) for a, b in
                zip(nbr[0][mask[0]], wfull[0][mask[0]])}
    for t in range(20):
        mb = fn(jax.random.PRNGKey(t))
        roots = np.asarray(mb.feats[0])
        hop = np.asarray(mb.feats[1]).reshape(60, 64)
        for r, row in zip(roots, hop):
            if int(ids[r - 1]) == node:
                for x in row:
                    hits[int(ids[x - 1])] = hits.get(int(ids[x - 1]), 0) + 1
    total = sum(hits.values())
    assert total >= 64
    for nb, cnt in hits.items():
        expect = w_by_nbr[nb] / sum(w_by_nbr.values())
        assert abs(cnt / total - expect) < 0.15, (nb, cnt / total, expect)


def test_weighted_root_distribution():
    """Root draws follow node weights through the quantized CDF."""
    g = random_graph(num_nodes=40, out_degree=3, feat_dim=4, seed=4)
    store = g.shards[0]
    nw = np.ones(40, dtype=np.float32)
    nw[:4] = 10.0  # 4 hot nodes: 40/76 of the mass
    store.arrays["node_weights"][:] = nw
    store.node_weights = store.arrays["node_weights"]
    flow = DeviceSageFlow(g, fanouts=[2], batch_size=256)
    assert flow.node_cdf is not None
    fn = jax.jit(flow.sample)
    counts = np.zeros(41)
    for t in range(20):
        mb = fn(jax.random.PRNGKey(t))
        np.add.at(counts, np.asarray(mb.feats[0]), 1)
    hot = counts[1:5].sum() / counts.sum()
    assert abs(hot - 40 / 76) < 0.08, hot
    # a roots_pool restricting the draw keeps weight proportionality
    # within the pool (rows 0..7: weights 10,10,10,10,1,1,1,1 → hot 40/44)
    ids = np.asarray(store.node_ids)
    pool_flow = DeviceSageFlow(
        g, fanouts=[2], batch_size=256, roots_pool=ids[:8]
    )
    assert pool_flow.node_cdf is not None and len(pool_flow.node_cdf) == 8
    fn = jax.jit(pool_flow.sample)
    counts = np.zeros(41)
    for t in range(20):
        mb = fn(jax.random.PRNGKey(t))
        np.add.at(counts, np.asarray(mb.feats[0]), 1)
    assert counts[9:].sum() == 0, "draws escaped the pool"
    hot = counts[1:5].sum() / counts.sum()
    assert abs(hot - 40 / 44) < 0.05, hot


def test_estimator_trains_and_is_deterministic(graph, flow, fcache, tmp_path):
    # module-scoped flow/cache across runs: fresh Estimators on shared
    # objects exercise the cross-instance jitted-step cache rooted on the
    # flow (estimator.py _jit_cache / root._etpu_jit_cache)

    def run(steps_per_call):
        est = Estimator(
            GraphSAGESupervised(dims=[16, 16], label_dim=2),
            flow,
            EstimatorConfig(
                model_dir=str(tmp_path / f"k{steps_per_call}"),
                learning_rate=0.05,
                log_steps=10**9,
                steps_per_call=steps_per_call,
            ),
            feature_cache=fcache,
        )
        return est.train(total_steps=12, log=False, save=False)

    a = run(4)
    b = run(4)
    assert a == b, "same seed must reproduce the loss sequence bitwise"
    assert a[-1] < a[0], "loss should fall on the label-correlated graph"
    # flow keys fold per GLOBAL step: grouping steps into dispatches
    # differently must not change the batch stream (rtol covers the
    # scan-vs-unrolled program difference, not sampling jitter)
    c = run(1)
    np.testing.assert_allclose(np.array(a), np.array(c), rtol=1e-4)


def test_determinism_across_fresh_instances(graph, monkeypatch, tmp_path):
    """The cache-MISS path: freshly traced steps on fresh flow/cache
    objects must reproduce the same losses (the shared-fixture test above
    reuses one jitted program, which cannot catch a fresh-trace
    divergence)."""
    monkeypatch.setenv("EULER_TPU_STEP_CACHE", "0")

    def run():
        flow = DeviceSageFlow(
            graph, fanouts=[4, 3], batch_size=16, label_feature="label"
        )
        est = Estimator(
            GraphSAGESupervised(dims=[16, 16], label_dim=2),
            flow,
            EstimatorConfig(
                model_dir=str(tmp_path / "fresh"), learning_rate=0.05,
                log_steps=10**9, steps_per_call=4,
            ),
            feature_cache=DeviceFeatureCache(graph, ["feat"]),
        )
        return est.train(total_steps=8, log=False, save=False)

    assert run() == run(), "fresh traces must reproduce the loss sequence"


def test_mesh_data_parallel_loss_parity(graph, flow, fcache, tmp_path):
    """Device-flow training under an 8-device data mesh: sampled batches
    are sharding-constrained along the data axis, and the loss sequence
    is identical to the single-device run (same keys → same values)."""
    from euler_tpu.parallel import make_mesh

    base_flow = flow

    def run(mesh):
        flow = base_flow if mesh is None else DeviceSageFlow(
            graph, fanouts=[4, 3], batch_size=16, label_feature="label",
            mesh=mesh,
        )
        est = Estimator(
            GraphSAGESupervised(dims=[16, 16], label_dim=2),
            flow,
            EstimatorConfig(
                model_dir=str(tmp_path / f"mesh{mesh is not None}"),
                learning_rate=0.05, log_steps=10**9, steps_per_call=4,
            ),
            mesh=mesh,
            feature_cache=fcache,
        )
        return est.train(total_steps=8, log=False, save=False)

    sharded = run(make_mesh(8))
    single = run(None)
    np.testing.assert_allclose(np.array(sharded), np.array(single),
                               rtol=2e-4)


def test_mesh_mismatch_rejected(graph, tmp_path):
    from euler_tpu.parallel import make_mesh

    flow = DeviceSageFlow(graph, fanouts=[4], batch_size=16,
                          label_feature="label")
    with pytest.raises(ValueError, match="share one mesh"):
        Estimator(
            GraphSAGESupervised(dims=[16], label_dim=2), flow,
            EstimatorConfig(model_dir=str(tmp_path / "mm")),
            mesh=make_mesh(8),
        )
    # the reverse direction is guarded too: a mesh-built flow cannot feed
    # a meshless Estimator (its sharding constraints would misplace)
    mflow = DeviceSageFlow(graph, fanouts=[4], batch_size=16,
                           label_feature="label", mesh=make_mesh(8))
    with pytest.raises(ValueError, match="share one mesh"):
        Estimator(
            GraphSAGESupervised(dims=[16], label_dim=2), mflow,
            EstimatorConfig(model_dir=str(tmp_path / "mm2")),
        )
    # equal-but-distinct meshes are accepted (equality, not identity)
    Estimator(
        GraphSAGESupervised(dims=[16], label_dim=2),
        DeviceSageFlow(graph, fanouts=[4], batch_size=16,
                       label_feature="label", mesh=make_mesh(8)),
        EstimatorConfig(model_dir=str(tmp_path / "mm3")),
        mesh=make_mesh(8),
    )


def test_walk_flow_pairs_match_host_gen_pair(graph):
    """The static column gather reproduces walk.py gen_pair exactly: run
    both on the SAME walk matrix and compare pairs + mask."""
    from euler_tpu.dataflow import DeviceWalkFlow
    from euler_tpu.dataflow.walk import gen_pair
    from euler_tpu.graph.store import DEFAULT_ID

    flow = DeviceWalkFlow(graph, batch_size=6, walk_len=4, window=2)
    ids = np.concatenate([np.asarray(s.node_ids) for s in graph.shards])
    rng = np.random.default_rng(0)
    walk_rows = rng.integers(0, len(ids), (6, 5))
    walk_rows[2, 3:] = -1  # dead tail
    walks_ids = np.where(walk_rows >= 0, ids[np.maximum(walk_rows, 0)],
                         DEFAULT_ID)
    pairs, mask = gen_pair(walks_ids, 2, 2)
    dev_walks = np.where(walk_rows >= 0, walk_rows + 1, 0)
    src = dev_walks[:, flow._src_cols] * flow._col_valid
    ctx = dev_walks[:, flow._ctx_cols] * flow._col_valid
    dmask = ((src > 0) & (ctx > 0)).reshape(-1)
    np.testing.assert_array_equal(dmask, mask)
    sel = mask
    np.testing.assert_array_equal(
        ids[src.reshape(-1)[sel] - 1], pairs[sel, 0]
    )
    np.testing.assert_array_equal(
        ids[ctx.reshape(-1)[sel] - 1], pairs[sel, 1]
    )


def test_walk_flow_walks_follow_edges(graph):
    """Consecutive sampled walk hops must be true edges (or dead)."""
    from euler_tpu.dataflow import DeviceWalkFlow

    flow = DeviceWalkFlow(graph, batch_size=8, walk_len=3, window=1)
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    ids = np.concatenate([np.asarray(s.node_ids) for s in graph.shards])
    # reconstruct walks via src/pos of the window-1 offset blocks is
    # convoluted; instead re-trace the walk with the same key pieces via
    # membership: every (src, pos) pair at offset ±1 must be an edge
    src, pos, mask = (np.asarray(mb["src"]), np.asarray(mb["pos"]),
                      np.asarray(mb["mask"]))
    nbr_all, _, _, m_all, _ = graph.get_full_neighbor(ids)
    nbr_of = {
        int(nid): set(int(x) for x in nbr_all[i][m_all[i]])
        for i, nid in enumerate(ids)
    }
    checked = 0
    L = flow.walk_len + 1
    for pi in np.nonzero(mask)[0]:
        assert int(src[pi]) in nbr_of and int(pos[pi]) in nbr_of
        checked += 1
    assert checked > 0
    # strict adjacency on the off=+1 block (window=1 → offsets (-1, +1),
    # block 1 = off=+1): pairs are (walk[t], walk[t+1]), so pos must be a
    # sampled out-neighbor of src
    M = flow.pairs_per_walk
    per = L
    src2 = src.reshape(8, M)[:, per : 2 * per]
    pos2 = pos.reshape(8, M)[:, per : 2 * per]
    m2 = mask.reshape(8, M)[:, per : 2 * per]
    for w in range(8):
        for t in range(per):
            if m2[w, t]:
                assert int(pos2[w, t]) in nbr_of[int(src2[w, t])]


def test_walk_flow_trains_skipgram(graph, tmp_path):
    from euler_tpu.dataflow import DeviceWalkFlow
    from euler_tpu.models.embedding_models import SkipGramModel

    flow = DeviceWalkFlow(graph, batch_size=16, walk_len=3, window=1,
                          num_negs=3)
    est = Estimator(
        SkipGramModel(num_nodes=300, dim=16), flow,
        EstimatorConfig(model_dir=str(tmp_path / "dw"), learning_rate=0.05,
                        log_steps=10**9, steps_per_call=4),
    )
    losses = est.train(total_steps=32, log=False, save=False)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


def _ring_graph(n=40):
    """Bidirectional ring: every node has edges to both neighbors, so a
    return edge always exists and node2vec biases are fully observable."""
    from euler_tpu.graph import Graph

    nodes = [
        {"id": i, "type": 0, "weight": 1.0,
         "features": [{"name": "feat", "type": "dense", "value": [1.0]}]}
        for i in range(n)
    ]
    edges = [
        {"src": i, "dst": (i + d) % n, "type": 0, "weight": 1.0,
         "features": []}
        for i in range(n)
        for d in (1, n - 1)
    ]
    return Graph.from_json({"nodes": nodes, "edges": edges})


def test_walk_flow_node2vec_bias():
    """On a bidirectional ring, p→0 forces immediate backtracking
    (walk[2] == walk[0] for nearly every walk) and p→∞ forbids it."""
    from euler_tpu.dataflow import DeviceWalkFlow

    g = _ring_graph(40)

    def back_rate(p, q, key=3):
        flow = DeviceWalkFlow(g, batch_size=64, walk_len=2, window=1,
                              p=p, q=q)
        mb = jax.jit(flow.sample)(jax.random.PRNGKey(key))
        M, L = flow.pairs_per_walk, flow.walk_len + 1
        src = np.asarray(mb["src"]).reshape(64, M)
        pos = np.asarray(mb["pos"]).reshape(64, M)
        mask = np.asarray(mb["mask"]).reshape(64, M)
        # offsets (-1, +1): block 1 = off +1 → pairs (walk[t], walk[t+1])
        w0, w2 = src[:, L], pos[:, L + 1]
        ok = mask[:, L] & mask[:, L + 1]
        assert ok.sum() >= 32
        return float((w2[ok] == w0[ok]).mean())

    assert back_rate(1e-6, 1.0) > 0.95
    assert back_rate(1e6, 1.0) < 0.05
    # q→0 prefers prev-adjacent nodes: on the ring prev's neighbors are
    # {walk[0], cur's 2-hop-back node} — with p huge and q tiny, the walk
    # must still avoid exact backtracking but stay near prev, which on a
    # ring means w2 != w0 (already covered) — so just pin the unbiased
    # rate for contrast: ~50/50 on a 2-regular ring
    r = back_rate(1.0, 1.0)
    assert 0.3 < r < 0.7, r


def test_edge_flow_distribution_and_training(tmp_path):
    """DeviceEdgeFlow draws edges ∝ weight (LINE parity) and trains."""
    from euler_tpu.dataflow import DeviceEdgeFlow
    from euler_tpu.models.embedding_models import SkipGramModel

    g = random_graph(num_nodes=60, out_degree=2, feat_dim=4, seed=5)
    store = g.shards[0]
    w = np.asarray(store.arrays["edge_weights"], dtype=np.float32)
    w[0::2], w[1::2] = 1.0, 3.0
    store.arrays["edge_weights"][:] = w
    store.__init__(store.meta, store.arrays, store.part)
    flow = DeviceEdgeFlow(g, batch_size=256, num_negs=3)
    fn = jax.jit(flow.sample)
    ids = np.concatenate([np.asarray(s.node_ids) for s in g.shards])
    nbr_all, w_all, _, m_all, _ = g.get_full_neighbor(ids)
    wd_of = {
        int(nid): {int(a): float(b) for a, b in
                   zip(nbr_all[i][m_all[i]], w_all[i][m_all[i]])}
        for i, nid in enumerate(ids)
    }
    heavy = 0
    total = 0
    for t in range(3):  # 3×256 draws; tolerance below sized for ~768
        mb = fn(jax.random.PRNGKey(t))
        src, pos, mask = (np.asarray(mb["src"]), np.asarray(mb["pos"]),
                          np.asarray(mb["mask"]))
        assert mask.all()  # every node has out-edges in this graph
        for s, d in zip(src, pos):
            wd = wd_of[int(s)]
            assert int(d) in wd  # a real edge
            total += 1
            heavy += int(wd[int(d)] == 3.0)
    assert abs(heavy / total - 0.75) < 0.06, heavy / total
    est = Estimator(
        SkipGramModel(num_nodes=60, dim=8), flow,
        EstimatorConfig(model_dir=str(tmp_path / "line"),
                        learning_rate=0.05, log_steps=10**9,
                        steps_per_call=4),
    )
    losses = est.train(total_steps=16, log=False, save=False)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


def test_unsup_flow_triples_and_training(graph, fcache, tmp_path):
    """DeviceUnsupSageFlow: pos is a true neighbor of src (or src itself
    when src is isolated), and the triple trains GraphSAGEUnsupervised."""
    from euler_tpu.dataflow import DeviceUnsupSageFlow
    from euler_tpu.models import GraphSAGEUnsupervised

    flow = DeviceUnsupSageFlow(graph, fanouts=[4, 3], batch_size=16,
                               num_negs=3)
    src_mb, pos_mb, neg_mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    assert neg_mb.feats[0].shape == (48,)
    ids = np.concatenate([np.asarray(s.node_ids) for s in graph.shards])
    src = ids[np.asarray(src_mb.feats[0]) - 1]
    pos = ids[np.asarray(pos_mb.feats[0]) - 1]
    nbr, _, _, m, _ = graph.get_full_neighbor(src)
    for i, (s, p) in enumerate(zip(src, pos)):
        assert int(p) in set(int(x) for x in nbr[i][m[i]]) | {int(s)}
    est = Estimator(
        GraphSAGEUnsupervised(dims=[16, 16]), flow,
        EstimatorConfig(model_dir=str(tmp_path / "unsup"),
                        learning_rate=0.05, log_steps=10**9,
                        steps_per_call=4),
        feature_cache=fcache,
    )
    losses = est.train(total_steps=16, log=False, save=False)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4])
    # a roots_pool restricts src but NOT negatives (host neg_type=-1
    # parity): negs must escape a 3-node pool
    ids = np.concatenate([np.asarray(s.node_ids) for s in graph.shards])
    pflow = DeviceUnsupSageFlow(graph, fanouts=[4, 3], batch_size=16,
                                num_negs=3, roots_pool=ids[:3])
    s_mb, _, n_mb = jax.jit(pflow.sample)(jax.random.PRNGKey(1))
    assert set(np.asarray(s_mb.feats[0]).tolist()) <= {1, 2, 3}
    assert len(set(np.asarray(n_mb.feats[0]).tolist())) > 3


def test_kg_flow_triples_and_training(tmp_path):
    """DeviceKGFlow: (h, r, t) are true typed edges, negatives are global,
    and the triple dict trains TransE."""
    from euler_tpu.dataflow import DeviceKGFlow
    from euler_tpu.graph import Graph
    from euler_tpu.models import TransX

    n = 40
    nodes = [
        {"id": i, "type": 0, "weight": 1.0,
         "features": [{"name": "feat", "type": "dense", "value": [1.0]}]}
        for i in range(n)
    ]
    edges = [
        {"src": i, "dst": (i + d) % n, "type": d - 1, "weight": 1.0,
         "features": []}
        for i in range(n)
        for d in (1, 2)
    ]
    g = Graph.from_json({"nodes": nodes, "edges": edges})
    flow = DeviceKGFlow(g, batch_size=64, num_negs=4)
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    h = np.asarray(mb["h"])
    r = np.asarray(mb["r"])
    t = np.asarray(mb["t"])
    # every drawn triple must be a real typed edge of the ring
    np.testing.assert_array_equal(t, (h + r + 1) % n)
    assert set(np.unique(r).tolist()) == {0, 1}
    assert mb["neg_h"].shape == (64, 4) and mb["neg_t"].shape == (64, 4)
    est = Estimator(
        TransX(num_entities=n, num_relations=2, dim=8, variant="transe"),
        flow,
        EstimatorConfig(model_dir=str(tmp_path / "kg"), learning_rate=0.05,
                        log_steps=10**9, steps_per_call=4),
    )
    losses = est.train(total_steps=16, log=False, save=False)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


def test_relation_flow_typed_draws_and_training(tmp_path):
    """DeviceRelationFlow: every relation-r draw is a true type-r edge,
    the batch trains RGCNSupervised, and shapes match the host
    RelationDataFlow."""
    from euler_tpu.dataflow import DeviceRelationFlow, RelationDataFlow
    from euler_tpu.graph import Graph
    from euler_tpu.models import RGCNSupervised

    n = 60
    nodes = [
        {"id": i, "type": 0, "weight": 1.0,
         "features": [
             {"name": "feat", "type": "dense",
              "value": [float(i % 3), 1.0]},
             {"name": "label", "type": "dense",
              "value": [float(i % 2), float(1 - i % 2)]},
         ]}
        for i in range(n)
    ]
    edges = [
        {"src": i, "dst": (i + d) % n, "type": d - 1, "weight": 1.0,
         "features": []}
        for i in range(n)
        for d in (1, 2, 3)
    ]
    g = Graph.from_json({"nodes": nodes, "edges": edges})
    nr = 3
    flow = DeviceRelationFlow(
        g, ["feat"], num_relations=nr, batch_size=8, fanout=2,
        num_hops=2, label_feature="label",
    )
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    host = RelationDataFlow(
        g, ["feat"], num_relations=nr, fanout=2, num_hops=2,
        label_feature="label", rng=np.random.default_rng(0),
    ).query(g.sample_node(8, rng=np.random.default_rng(0)))
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_put(host)),
                    jax.tree_util.tree_leaves(mb)):
        assert a.shape == b.shape, (a.shape, b.shape)
    # type-r draws are true type-r edges: on this ring, relation r maps
    # i -> (i + r + 1) mod n
    ids = np.asarray(mb.hop_ids[0])
    hop1 = np.asarray(mb.hop_ids[1]).reshape(8, nr, 2)
    m1 = np.asarray(mb.masks[1]).reshape(8, nr, 2)
    for r in range(nr):
        assert m1[:, r, :].all()
        np.testing.assert_array_equal(
            hop1[:, r, :],
            np.broadcast_to((ids[:, None] + r + 1) % n, (8, 2)),
        )
    est = Estimator(
        RGCNSupervised(dims=[8, 8], num_relations=nr, label_dim=2,
                       num_bases=2),
        flow,
        EstimatorConfig(model_dir=str(tmp_path / "rgcn"),
                        learning_rate=0.05, log_steps=10**9,
                        steps_per_call=4),
    )
    losses = est.train(total_steps=12, log=False, save=False)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


def test_layerwise_flow_exact_when_frontier_fits(graph, tmp_path):
    """DeviceLayerwiseFlow: when the frontier fits in `count` the layer
    is EXACT (host layerwise_from_full contract) — every frontier node
    appears, the adjacency rows hold the true (normalized) weights, and
    the batch trains LayerwiseGCN."""
    from euler_tpu.dataflow import DeviceLayerwiseFlow
    from euler_tpu.models import LayerwiseGCN

    flow = DeviceLayerwiseFlow(
        g0 := graph, ["feat"], batch_size=4, layer_sizes=[64, 64],
        label_feature="label",
    )
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    roots = np.asarray(mb.hop_ids[0]).astype(np.uint64)  # already ids
    layer = np.asarray(mb.hop_ids[1])
    lmask = np.asarray(mb.masks[1])
    nbr, _, _, m, _ = g0.get_full_neighbor(roots)
    frontier = set(np.unique(nbr[m]).tolist())
    assert len(frontier) <= 64, "fixture must exercise the exact case"
    assert frontier == set(int(x) for x in layer[lmask])
    # adjacency rows: normalized true incident weights onto layer nodes
    adj = np.asarray(mb.adjs[0])
    for i in range(4):
        truth = np.zeros(64)
        for c, lid in enumerate(layer):
            if lmask[c]:
                truth[c] = (nbr[i][m[i]] == lid).sum()  # unit weights
        if truth.sum() > 0:
            truth = truth / truth.sum()
        np.testing.assert_allclose(adj[i], truth, rtol=1e-5, atol=1e-6)
    est = Estimator(
        LayerwiseGCN(dims=[16, 16], label_dim=2), flow,
        EstimatorConfig(model_dir=str(tmp_path / "lw"), learning_rate=0.05,
                        log_steps=10**9, steps_per_call=4),
    )
    losses = est.train(total_steps=12, log=False, save=False)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


def test_gae_and_dgi_flows(graph, fcache, tmp_path):
    """DeviceGaeFlow: (src, dst, neg) triples where dst is a true
    neighbor of src; DeviceDgiFlow: corrupted view is a permutation of
    the real batch's feature rows. Both train their models."""
    from euler_tpu.dataflow import DeviceDgiFlow, DeviceGaeFlow
    from euler_tpu.models import DGI, GAE

    gflow = DeviceGaeFlow(graph, fanouts=[4], batch_size=16)
    src_mb, dst_mb, neg_mb = jax.jit(gflow.sample)(jax.random.PRNGKey(0))
    ids = np.concatenate([np.asarray(s.node_ids) for s in graph.shards])
    src = ids[np.asarray(src_mb.feats[0]) - 1]
    dst = ids[np.asarray(dst_mb.feats[0]) - 1]
    nbr, _, _, m, _ = graph.get_full_neighbor(src)
    for i in range(16):
        assert int(dst[i]) in set(nbr[i][m[i]].tolist())
    est = Estimator(
        GAE(dims=[16]), gflow,
        EstimatorConfig(model_dir=str(tmp_path / "gae"),
                        learning_rate=0.05, log_steps=10**9,
                        steps_per_call=4),
        feature_cache=fcache,
    )
    losses = est.train(total_steps=8, log=False, save=False)
    assert np.isfinite(losses).all()

    dflow = DeviceDgiFlow(graph, fanouts=[4], batch_size=16)
    real, fake = jax.jit(dflow.sample)(jax.random.PRNGKey(1))
    for f_r, f_f in zip(real.feats, fake.feats):
        assert sorted(np.asarray(f_r).tolist()) == sorted(
            np.asarray(f_f).tolist()
        ), "corruption must be a permutation of the real rows"
    assert not all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(real.feats, fake.feats)
    ), "corruption must actually shuffle"
    # with_hop_ids: the id plane must ride the SAME permutation as the
    # rows, or pad slots land under valid-mask positions in the
    # corrupted view (ids of the permuted rows == permuted ids)
    iflow = DeviceDgiFlow(
        graph, fanouts=[4], batch_size=16, with_hop_ids=True
    )
    ireal, ifake = jax.jit(iflow.sample)(jax.random.PRNGKey(2))
    node_id = np.asarray(iflow.node_id)
    for mb in (ireal, ifake):
        for rows, ids in zip(mb.feats, mb.hop_ids):
            np.testing.assert_array_equal(
                np.asarray(ids), node_id[np.asarray(rows)]
            )
    est2 = Estimator(
        DGI(dims=[16]), dflow,
        EstimatorConfig(model_dir=str(tmp_path / "dgi"),
                        learning_rate=0.05, log_steps=10**9,
                        steps_per_call=4),
        feature_cache=fcache,
    )
    losses = est2.train(total_steps=8, log=False, save=False)
    assert np.isfinite(losses).all()


def test_whole_graph_flow_matches_host_batches(tmp_path):
    """DeviceWholeGraphFlow: a drawn graph's slice must EQUAL the host
    flow's query for the same label (same padding/slot logic), and the
    batch trains GraphClassifier."""
    from euler_tpu.dataflow import DeviceWholeGraphFlow, WholeGraphDataFlow
    from euler_tpu.datasets.catalog import get_dataset
    from euler_tpu.models import GraphClassifier

    g = get_dataset("mutag").load_graph(synthetic=True)
    host = WholeGraphDataFlow(g, ["feature"], max_nodes=16, max_degree=8)
    flow = DeviceWholeGraphFlow(g, ["feature"], batch_size=4,
                                max_nodes=16, max_degree=8)
    assert flow.num_classes == host.num_classes
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    assert mb.n_graphs == 4 and mb.feats.shape[0] == 64
    # reconstruct which labels were drawn via the staged label rows
    labels = np.asarray(mb.labels)
    hop = np.asarray(mb.hop_ids).reshape(4, 16)
    staged_hop = np.asarray(flow.ghop)
    for i in range(4):
        matches = np.nonzero((staged_hop == hop[i]).all(axis=1))[0]
        assert len(matches) >= 1
        gid = int(matches[0])
        ref = host.query(np.array([gid]))
        np.testing.assert_array_equal(hop[i], np.asarray(ref.hop_ids))
        np.testing.assert_allclose(
            labels[i], np.asarray(ref.labels[0]), rtol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(mb.feats).reshape(4, 16, -1)[i],
            np.asarray(ref.feats), rtol=1e-6,
        )
        # edge indices offset into the batch table by i*16
        e = 16 * int(flow.grid)
        np.testing.assert_array_equal(
            np.asarray(mb.block.edge_src).reshape(4, e)[i] - i * 16,
            np.asarray(ref.block.edge_src),
        )
    est = Estimator(
        GraphClassifier(conv="gin", dims=(16, 16),
                        num_classes=flow.num_classes, pool="mean"),
        flow,
        EstimatorConfig(model_dir=str(tmp_path / "wg"), learning_rate=0.05,
                        log_steps=10**9, steps_per_call=4),
    )
    losses = est.train(total_steps=8, log=False, save=False)
    assert np.isfinite(losses).all()


def test_partitioned_graph_staging(tmp_path):
    """Device flows stage from multi-shard local graphs: the shard-major
    row space must line up with DeviceFeatureCache's, and sampled
    neighbors must be true edges of the partitioned store."""
    g = random_graph(num_nodes=240, out_degree=5, feat_dim=8, seed=7,
                     num_partitions=4)
    assert g.num_shards == 4
    flow = DeviceSageFlow(g, fanouts=[3, 2], batch_size=16,
                          label_feature="label")
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    ids = np.concatenate([np.asarray(s.node_ids) for s in g.shards])
    rows0 = np.asarray(mb.feats[0]) - 1
    rows1 = np.asarray(mb.feats[1]).reshape(16, 3) - 1
    nbr, _, _, m, _ = g.get_full_neighbor(ids[rows0])
    for i in range(16):
        true_set = set(nbr[i][m[i]].tolist())
        for r in rows1[i]:
            if r >= 0:
                assert int(ids[r]) in true_set
    # feature rows resolve through the same shard-major space the cache
    # uses: hydrated root features must equal the store's dense features
    cache = DeviceFeatureCache(g, ["feat"])
    hydrated = np.asarray(cache.gather(np.asarray(mb.feats[0])))
    direct = g.get_dense_feature(ids[rows0], ["feat"])
    np.testing.assert_allclose(hydrated, direct, rtol=1e-6)
    # and training runs end-to-end on the partitioned graph
    est = Estimator(
        GraphSAGESupervised(dims=[16, 16], label_dim=2), flow,
        EstimatorConfig(model_dir=str(tmp_path / "part"),
                        learning_rate=0.05, log_steps=10**9,
                        steps_per_call=4),
        feature_cache=cache,
    )
    losses = est.train(total_steps=8, log=False, save=False)
    assert np.isfinite(losses).all()


def test_hop_ids_enable_id_embedding_models(graph, fcache, tmp_path):
    """with_hop_ids=True ships per-hop ids (free on device, unlike the
    host lean wire), and an id-embedding model (ShallowEncoder) trains."""
    from euler_tpu.dataflow.base import hydrate_blocks
    from euler_tpu.dataflow import DeviceUnsupSageFlow
    from euler_tpu.models import GraphSAGEUnsupervised

    flow = DeviceSageFlow(graph, fanouts=[4, 3], batch_size=16,
                          label_feature="label", with_hop_ids=True)
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(0))
    assert mb.hop_ids is not None and len(mb.hop_ids) == 3
    # pad-slot embeddings never reach the aggregation: hydration derives
    # hop masks from the rows-mode feats (False exactly on pad rows)
    hb = hydrate_blocks(mb)
    for h in range(1, 3):
        np.testing.assert_array_equal(
            np.asarray(hb.masks[h]), np.asarray(mb.feats[h]) > 0
        )
    # the unsupervised subclass forwards the flag (id-embedding models)
    uflow = DeviceUnsupSageFlow(graph, fanouts=[4], batch_size=8,
                                with_hop_ids=True)
    s_mb, _, _ = jax.jit(uflow.sample)(jax.random.PRNGKey(1))
    assert s_mb.hop_ids is not None
    uest = Estimator(
        GraphSAGEUnsupervised(dims=[16], encoder_dim=8, max_id=300),
        uflow,
        EstimatorConfig(model_dir=str(tmp_path / "unsup_ids"), learning_rate=0.05,
                        log_steps=10**9, steps_per_call=2),
        feature_cache=fcache,
    )
    ulosses = uest.train(total_steps=4, log=False, save=False)
    assert np.isfinite(ulosses).all()
    ids = np.concatenate([np.asarray(s.node_ids) for s in graph.shards])
    # hop_ids are the ids of the sampled rows (pad rows map to -1)
    rows = np.asarray(mb.feats[1])
    expect = np.where(rows > 0, ids[np.maximum(rows - 1, 0)].astype(np.int64),
                      -1).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(mb.hop_ids[1]), expect)
    est = Estimator(
        GraphSAGESupervised(dims=[16, 16], label_dim=2, encoder_dim=8,
                            max_id=300),
        flow,
        EstimatorConfig(model_dir=str(tmp_path / "ids"), learning_rate=0.05,
                        log_steps=10**9, steps_per_call=4),
        feature_cache=fcache,
    )
    losses = est.train(total_steps=8, log=False, save=False)
    assert np.isfinite(losses).all()


def test_remainder_steps(graph, flow, fcache, tmp_path):
    """total_steps not a multiple of steps_per_call exercises the
    single-step remainder path with sliced flow keys."""
    est = Estimator(
        GraphSAGESupervised(dims=[16, 16], label_dim=2),
        flow,
        EstimatorConfig(
            model_dir=str(tmp_path / "rem"), learning_rate=0.05,
            log_steps=10**9, steps_per_call=4,
        ),
        feature_cache=fcache,
    )
    losses = est.train(total_steps=10, log=False, save=False)
    assert len(losses) == 10 and np.isfinite(losses).all()


def _draw_tables(weighted: bool, dmax: int, seed: int = 5):
    """Dense-layout tables made by hand: row 0 the padding row, rows 1-3
    of degree 0, row 4 of full degree `dmax`, the rest of every degree in
    between."""
    from euler_tpu.dataflow.device import DeviceGraphTables, _quantize_rows

    rng = np.random.default_rng(seed)
    n = 64
    deg = rng.integers(1, dmax, n + 1).astype(np.int32)
    deg[:4], deg[4] = 0, dmax
    valid = np.arange(dmax)[None, :] < deg[:, None]
    adj = np.where(valid, rng.integers(1, n + 1, (n + 1, dmax)), 0)
    tables = object.__new__(DeviceGraphTables)
    tables.mesh, tables.layout = None, "dense"
    tables.adj = jnp.asarray(adj, jnp.int32)
    tables.deg = jnp.asarray(deg)
    tables.unit_w = not weighted
    if weighted:
        w = np.where(valid, rng.random((n + 1, dmax)) + 0.1, 0.0)
        tables.qtab = jnp.asarray(_quantize_rows(w, valid))
        tables.wtab = jnp.asarray(w, jnp.float32)
    return tables, adj, deg


@pytest.mark.parametrize("weighted", [False, True], ids=["unit", "weighted"])
@pytest.mark.parametrize("dmax", [120, 128, 256])
@pytest.mark.parametrize("k", [1, 2, 5, 15])
def test_draw_is_the_element_gather_it_replaces(k, dmax, weighted):
    """Whatever reads the plane — whole rows where a fan-out finds them
    contiguous (a width of whole lane tiles), single slots otherwise —
    the drawn neighbours are `adj[cur[:, None], idx]` under the same key,
    bit for bit."""
    from euler_tpu.utils import trace

    tables, adj, deg = _draw_tables(weighted, dmax)
    # padding row, degree-0 rows, the full row, then every row twice over
    cur = np.concatenate([[0, 0, 1, 2, 3, 4, 4], np.arange(65), np.arange(65)])
    key = jax.random.PRNGKey(7 + k)
    before = trace.counts()
    nbr, ew, idx = jax.jit(tables._draw_neighbors, static_argnums=2)(
        jnp.asarray(cur, jnp.int32), key, k
    )
    after = trace.counts()
    took = {
        form: after.get(form, 0) - before.get(form, 0)
        for form in ("draw_rows", "draw_elements")
    }
    rows = k > 1 and dmax % 128 == 0
    assert took == {"draw_rows": int(rows), "draw_elements": int(not rows)}
    idx = np.asarray(idx)
    d = deg[cur][:, None]
    assert idx.shape == (len(cur), k)
    assert ((idx >= 0) & (idx <= np.maximum(d - 1, 0))).all()
    if not weighted:
        u = np.asarray(jax.random.uniform(key, (len(cur), k)))
        want_idx = np.minimum((u * d).astype(np.int32), np.maximum(d - 1, 0))
        np.testing.assert_array_equal(idx, want_idx)
        assert idx[cur == 4].max() > 100  # the full row is drawn deep
    want = np.where(d > 0, adj[cur[:, None], idx], 0).reshape(-1)
    np.testing.assert_array_equal(np.asarray(nbr), want)
    assert (want.reshape(-1, k)[deg[cur] > 0] > 0).all()
    assert (want.reshape(-1, k)[deg[cur] == 0] == 0).all()
    if weighted:
        want_w = np.asarray(tables.wtab)[cur[:, None], idx].reshape(-1)
        np.testing.assert_array_equal(
            np.asarray(ew), np.asarray(jnp.asarray(want_w).astype(jnp.bfloat16))
        )
    else:
        assert ew is None


@pytest.mark.parametrize("dmax,k", [(120, 5), (7, 2), (128, 15), (300, 1)])
def test_pick_slots_is_take_along_axis(dmax, k):
    """Any width, any k, ids past 2**24 (no float could carry them)."""
    from euler_tpu.dataflow.device import _pick_slots

    rng = np.random.default_rng(dmax)
    rows = rng.integers(0, 2**31 - 1, (37, dmax)).astype(np.int32)
    idx = rng.integers(0, dmax, (37, k)).astype(np.int32)
    idx[0], idx[1] = 0, dmax - 1
    got = jax.jit(_pick_slots)(jnp.asarray(rows), jnp.asarray(idx))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(got), np.take_along_axis(rows, idx, axis=1)
    )


def test_fanout_flows_stage_whole_lane_tiles(graph, flow):
    """A `DeviceSageFlow`'s dense planes are a whole number of 128-lane
    tiles wide, the slots past the max degree padding; a walk flow's
    planes keep the max degree as their width."""
    from euler_tpu.dataflow import DeviceWalkFlow

    assert flow.layout == "dense" and flow.adj.shape == (301, 128)
    assert 0 < flow.max_deg < 128
    assert not np.asarray(flow.adj[:, flow.max_deg:]).any()
    assert int(flow.deg.max()) == flow.max_deg
    walk = DeviceWalkFlow(graph, batch_size=4, walk_len=3, window=1)
    assert walk.adj.shape == (301, walk.max_deg)


@pytest.mark.parametrize(
    "seed,digest,hop2_head,hop1_ids_head,root_head",
    [
        (0, "68f578d9653fd77e1b48b58cdbbe15edaa0775d9ac962c93fef9f45ba8dc9d3f",
         [44, 244, 234, 141, 209, 1], [249, 153, 233, 32], [92, 162, 248]),
        (31, "92068536e02f730ad07c873f390a73e1204b8647346153eea9b721305c2f7e3a",
         [207, 135, 125, 290, 290, 100], [105, 257, 51, 261], [299, 136, 208]),
        (2**31 + 7,
         "fe3620c2a9c56deb08f9691b14d8d00bc7fdf4a4e9747ce29ae40ebfa9136c40",
         [8, 105, 148, 141, 93, 241], [33, 184, 184, 125], [237, 165, 102]),
    ],
)
def test_sage_sample_is_the_parents_batch(
    graph, seed, digest, hop2_head, hop1_ids_head, root_head
):
    """`feats`, `hop_ids` and `root_idx` of `DeviceSageFlow.sample` as the
    element-gather draw gave them (commit 6c2b044, the same keys): the
    sha256 of their int32 bytes in that order, and a few values to read."""
    import hashlib

    flow = DeviceSageFlow(
        graph, fanouts=[4, 3], batch_size=16, label_feature="label",
        with_hop_ids=True,
    )
    mb = jax.jit(flow.sample)(jax.random.PRNGKey(seed))
    leaves = [*mb.feats, *mb.hop_ids, mb.root_idx]
    assert [x.shape for x in leaves] == [(16,), (64,), (192,)] * 2 + [(16,)]
    assert np.asarray(mb.feats[2][:6]).tolist() == hop2_head
    assert np.asarray(mb.hop_ids[1][:4]).tolist() == hop1_ids_head
    assert np.asarray(mb.root_idx[:3]).tolist() == root_head
    got = hashlib.sha256(
        b"".join(np.asarray(x, "<i4").tobytes() for x in leaves)
    ).hexdigest()
    assert got == digest
