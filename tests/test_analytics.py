"""Whole-graph offline analytics (ISSUE 12): bit-determinism, epoch
pinning, incremental replay, and the wire lane.

The load-bearing claims, each pinned here:
  * shard-count independence — 1/2/4-partition runs of every algorithm
    produce BIT-identical per-node values (canonical reduction order,
    never tolerance);
  * local/remote parity — the ``frontier_exchange`` wire path reduces
    through the same ``reduce_messages`` as the in-process path, and an
    old server (no analytics verbs) degrades per shard to the local
    path with identical bits;
  * incremental == from-scratch — ``rerun_incremental`` after a live
    ``GraphWriter`` publish converges to bit-exactly the from-scratch
    answer at the new epoch while touching only the mutated region;
  * durability — an interrupted run resumed from its last frontier
    checkpoint finishes bit-identical to an uninterrupted one.
"""

import numpy as np
import pytest

from euler_tpu.analytics import (
    connected_components,
    label_propagation,
    pagerank,
    reduce_messages,
    rerun_incremental,
    run_kg_sweep,
    WholeGraphEngine,
)
from euler_tpu.distributed.writer import GraphWriter
from euler_tpu.graph.builder import convert_json
from euler_tpu.graph.store import Graph

# ---------------------------------------------------------------------------
# fixtures / helpers
# ---------------------------------------------------------------------------


def _graph_dict(n=48):
    """Deterministic weighted digraph: 3 out-edges per node, 2 edge
    types, repeated weights (exercises the total-order tiebreaks)."""
    nodes = [
        {"id": i, "type": i % 2, "weight": 1.0, "features": []}
        for i in range(1, n + 1)
    ]
    edges = [
        {"src": s, "dst": (s + off) % n + 1, "type": off % 2,
         "weight": float(1 + (s + off) % 4), "features": []}
        for s in range(1, n + 1)
        for off in (1, 3, 7)
    ]
    return {"nodes": nodes, "edges": edges}


def _bits(v):
    return np.ascontiguousarray(np.asarray(v, np.float64)).view(np.uint64)


_ALGOS = {
    "pagerank": lambda g, **kw: pagerank(g, max_iters=60, tol=1e-10, **kw),
    "lp": lambda g, **kw: label_propagation(g, **kw),
    "cc": lambda g, **kw: connected_components(g, **kw),
}


# ---------------------------------------------------------------------------
# reduce_messages: the one reduction everybody shares
# ---------------------------------------------------------------------------


def test_reduce_messages_is_permutation_invariant():
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 5, 64)
    keys = rng.integers(0, 3, 64)
    vals = rng.normal(size=64)
    for mode in ("sum", "min", "vote"):
        ref = reduce_messages(rows, keys, vals, mode)
        for seed in range(3):
            p = np.random.default_rng(seed + 1).permutation(64)
            got = reduce_messages(rows[p], keys[p], vals[p], mode)
            for a, b in zip(ref, got):
                assert np.array_equal(_bits(a), _bits(b)) or np.array_equal(
                    a, b
                )


def test_reduce_messages_vote_ties_go_to_smallest_key():
    rows = np.array([0, 0, 0, 0])
    keys = np.array([7, 2, 7, 2])
    vals = np.array([1.0, 1.0, 1.0, 1.0])
    u, v, k = reduce_messages(rows, keys, vals, "vote")
    assert list(u) == [0] and list(k) == [2] and list(v) == [2.0]
    with pytest.raises(ValueError, match="unknown reduce mode"):
        reduce_messages(rows, keys, vals, "max")


# ---------------------------------------------------------------------------
# bit-identity across shard counts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("algo", sorted(_ALGOS))
def test_bit_identity_across_shard_counts(algo):
    data = _graph_dict()
    ref = None
    for parts in (1, 2, 4):
        res = _ALGOS[algo](Graph.from_json(data, num_partitions=parts))
        assert res.converged
        ids, vals = res.by_id()
        if ref is None:
            ref = (ids, _bits(vals), res.iterations)
        else:
            assert np.array_equal(ids, ref[0])
            assert np.array_equal(_bits(vals), ref[1]), (
                f"{algo}: {parts}-shard bits diverged from 1-shard"
            )
            assert res.iterations == ref[2]


def test_tolerance_stop_is_deterministic():
    data = _graph_dict()
    a = pagerank(Graph.from_json(data, num_partitions=2), tol=1e-10)
    b = pagerank(Graph.from_json(data, num_partitions=2), tol=1e-10)
    assert a.iterations == b.iterations and a.converged
    assert np.array_equal(_bits(a.values), _bits(b.values))


def test_device_frontier_parity():
    """The device lane's contract (dataflow/device.py): bit-equal to the
    host lane on the CPU backend these tests run on; on a TPU the bound is
    FRONTIER_F64_RTOL, which chip_smoke.py checks on the chip."""
    import jax

    assert jax.default_backend() == "cpu"
    data = _graph_dict()
    host = pagerank(Graph.from_json(data, num_partitions=2))
    dev = pagerank(Graph.from_json(data, num_partitions=2), device=True)
    assert np.array_equal(_bits(host.by_id()[1]), _bits(dev.by_id()[1]))


def test_stage_frontier_is_f64_device_array():
    """The device lane really stages on the device: under the installed
    JAX the scoped x64 context yields an f64 jax.Array, not a NumPy one."""
    import jax

    from euler_tpu.dataflow.device import frontier_contrib, stage_frontier

    arr = stage_frontier(np.arange(5, dtype=np.float32))
    assert isinstance(arr, jax.Array) and arr.dtype == np.float64
    out = frontier_contrib(
        np.array([0.1, 0.2]), np.array([3.0, 7.0, 11.0]), np.array([2, 0])
    )
    assert out.dtype == np.float64
    assert np.array_equal(out, np.array([0.1, 0.2]) * np.array([11.0, 3.0]))


# ---------------------------------------------------------------------------
# wire lane: remote parity + old-server degrade
# ---------------------------------------------------------------------------


@pytest.fixture
def cluster2(tmp_path):
    from euler_tpu.distributed import connect
    from euler_tpu.distributed.service import serve_shard

    data = _graph_dict(n=32)
    d = str(tmp_path / "graph")
    convert_json(data, d, num_partitions=2)
    reg = str(tmp_path / "reg")
    services = [
        serve_shard(d, p, registry_path=reg, native=False) for p in range(2)
    ]
    g = connect(registry_path=reg, num_shards=2)
    yield data, g, services
    for s in services:
        s.stop()


def test_local_vs_remote_parity(cluster2):
    data, rg, _ = cluster2
    local = pagerank(Graph.from_json(data, num_partitions=2))
    eng = WholeGraphEngine(rg, exchange="remote")
    remote = pagerank(rg, engine=eng)
    assert remote.stats["exchange_calls"] > 0, "never used the wire"
    assert np.array_equal(_bits(local.by_id()[1]), _bits(remote.by_id()[1]))
    # lp crosses the wire with vote reductions
    l_local = label_propagation(Graph.from_json(data, num_partitions=2))
    l_remote = label_propagation(rg, exchange="remote")
    assert np.array_equal(
        _bits(l_local.by_id()[1]), _bits(l_remote.by_id()[1])
    )


def test_old_server_degrades_to_local_bits(tmp_path, monkeypatch):
    """A server that predates the analytics verbs answers unknown-op;
    the engine must fall back (bulk fetch → per-row, remote exchange →
    in-process) and still produce the same bits."""
    from euler_tpu.distributed import connect
    from euler_tpu.distributed.service import GraphService, serve_shard

    monkeypatch.setattr(
        GraphService,
        "HANDLED_VERBS",
        frozenset(
            GraphService.HANDLED_VERBS
            - {"edges_by_rows", "frontier_exchange"}
        ),
    )
    data = _graph_dict(n=24)
    d = str(tmp_path / "graph")
    convert_json(data, d, num_partitions=2)
    reg = str(tmp_path / "reg")
    services = [
        serve_shard(d, p, registry_path=reg, native=False) for p in range(2)
    ]
    try:
        rg = connect(registry_path=reg, num_shards=2)
        eng = WholeGraphEngine(rg, exchange="remote")
        remote = pagerank(rg, engine=eng)
        assert not any(eng._exchange_wire), "degrade flag never tripped"
        assert not any(sh._edges_wire for sh in rg.shards)
        local = pagerank(Graph.from_json(data, num_partitions=2))
        assert np.array_equal(
            _bits(local.by_id()[1]), _bits(remote.by_id()[1])
        )
    finally:
        for s in services:
            s.stop()


# ---------------------------------------------------------------------------
# the E2E scenario: live writer + incremental recompute
# ---------------------------------------------------------------------------


def test_scenario_incremental_recompute_under_live_writer():
    """PageRank recomputed live while a writer streams edges: the rerun
    pins exactly one published epoch, matches the from-scratch answer
    bit-for-bit, and touches only the mutated region."""
    g = Graph.from_json(_graph_dict(), num_partitions=2)
    eng = WholeGraphEngine(g)
    r0 = pagerank(g, engine=eng, max_iters=60)
    assert r0.converged

    w = GraphWriter(g)
    w.upsert_edges([5, 9], [12, 30], [0, 1], [9.0, 3.5])
    w.publish()
    r_full = pagerank(g, max_iters=60)
    r_inc = rerun_incremental(g, r0, publish=None, engine=eng,
                              mutated_rows=_mutated_rows(eng, g, [5, 9]))
    assert np.array_equal(_bits(r_full.values), _bits(r_inc.values))
    assert r_inc.iterations == r_full.iterations
    assert r_inc.epoch_pin != r0.epoch_pin, "rerun did not re-pin"
    assert r_inc.stats["rows_recomputed"] < r_full.stats["rows_recomputed"]
    assert r_inc.stats["rows_refetched"] < r_inc.stats["num_rows"]

    # second round: another publish, rerun FROM the incremental result
    w.upsert_edges([17], [3], [1], [2.25])
    w.delete_edges([9], [30], [1])
    pub2 = w.publish()
    r_full2 = pagerank(g, max_iters=60)
    r_inc2 = rerun_incremental(g, r_inc, publish=pub2, engine=eng)
    assert np.array_equal(_bits(r_full2.values), _bits(r_inc2.values))
    assert (
        r_inc2.stats["rows_recomputed"] < r_full2.stats["rows_recomputed"]
    )


def _mutated_rows(eng, g, src_ids):
    """Global rows of the given source node ids in the engine's space."""
    order = np.argsort(eng.node_ids, kind="stable")
    pos = np.searchsorted(eng.node_ids[order], np.asarray(src_ids, np.uint64))
    return order[pos]


def test_incremental_label_propagation_matches_from_scratch():
    g = Graph.from_json(_graph_dict(), num_partitions=2)
    eng = WholeGraphEngine(g)
    l0 = label_propagation(g, engine=eng)
    w = GraphWriter(g)
    w.upsert_edges([5], [12], [0], [9.0])
    pub = w.publish()
    l_full = label_propagation(g)
    l_inc = rerun_incremental(g, l0, publish=pub, engine=eng)
    assert np.array_equal(_bits(l_full.values), _bits(l_inc.values))
    assert l_inc.stats["rows_recomputed"] < l_full.stats["rows_recomputed"]


def test_incremental_degrades_to_full_when_rows_unknown():
    g = Graph.from_json(_graph_dict(), num_partitions=2)
    r0 = pagerank(g, max_iters=60)
    w = GraphWriter(g)
    w.upsert_edges([5], [12], [0], [9.0])
    w.publish()
    r_inc = rerun_incremental(g, r0, publish=None, mutated_rows=None)
    r_full = pagerank(g, max_iters=60)
    assert np.array_equal(_bits(r_full.values), _bits(r_inc.values))
    assert r_inc.stats["rows_recomputed"] == r_full.stats["rows_recomputed"]


# ---------------------------------------------------------------------------
# durability: frontier checkpoints
# ---------------------------------------------------------------------------


def test_frontier_checkpoint_resume_is_bit_identical(tmp_path):
    data = _graph_dict()
    ref = pagerank(Graph.from_json(data, num_partitions=2), max_iters=60)
    assert ref.converged
    ck = str(tmp_path / "frontier")
    # interrupted run: dies (max_iters) after checkpointing iteration 6
    partial = pagerank(
        Graph.from_json(data, num_partitions=2),
        max_iters=8, checkpoint_dir=ck, checkpoint_every=3,
    )
    assert not partial.converged
    resumed = pagerank(
        Graph.from_json(data, num_partitions=2),
        max_iters=60, checkpoint_dir=ck, resume=True,
    )
    assert resumed.converged
    assert resumed.iterations == ref.iterations
    assert np.array_equal(_bits(ref.values), _bits(resumed.values))


def test_checkpoint_resume_rejects_other_algo_or_epoch(tmp_path):
    data = _graph_dict()
    ck = str(tmp_path / "frontier")
    pagerank(
        Graph.from_json(data, num_partitions=2),
        max_iters=8, checkpoint_dir=ck, checkpoint_every=3,
    )
    # a different algorithm must NOT adopt the pagerank frontier
    res = label_propagation(
        Graph.from_json(data, num_partitions=2),
        checkpoint_dir=ck, resume=True,
    )
    clean = label_propagation(Graph.from_json(data, num_partitions=2))
    assert np.array_equal(_bits(res.values), _bits(clean.values))


# ---------------------------------------------------------------------------
# KG sweeps
# ---------------------------------------------------------------------------


def test_kg_sweep_deterministic_and_resume_skip(tmp_path):
    g = Graph.from_json(_graph_dict(n=24), num_partitions=2)
    cfgs = [{"variant": "distmult", "dim": 8, "learning_rate": 0.05}]
    out = run_kg_sweep(
        g, str(tmp_path / "a"), configs=cfgs, steps=8, batch_size=16,
        eval_triples=32, seed=0,
    )
    assert out["num_triples"] == 72 and len(out["leaderboard"]) == 1
    entry = out["leaderboard"][0]
    assert not entry["resumed"] and 0.0 < entry["metrics"]["mrr"] <= 1.0
    # same seed, fresh dir → identical metrics (determinism)
    out2 = run_kg_sweep(
        g, str(tmp_path / "b"), configs=cfgs, steps=8, batch_size=16,
        eval_triples=32, seed=0,
    )
    assert out2["leaderboard"][0]["metrics"] == entry["metrics"]
    # same dir, same epoch → resume-skip (no retraining)
    out3 = run_kg_sweep(
        g, str(tmp_path / "a"), configs=cfgs, steps=8, batch_size=16,
        eval_triples=32, seed=0,
    )
    assert out3["leaderboard"][0]["resumed"]
    assert out3["leaderboard"][0]["metrics"] == entry["metrics"]


# ---------------------------------------------------------------------------
# the console (tools/analytics.py)
# ---------------------------------------------------------------------------


def test_cli_selftest_passes_the_oracle(capsys):
    from euler_tpu.tools import analytics as cli

    assert cli.main(["--selftest"]) == 0
    assert '"selftest": "ok"' in capsys.readouterr().out


def test_cli_state_and_incremental(tmp_path, capsys):
    import json

    from euler_tpu.tools import analytics as cli

    d1 = str(tmp_path / "g1")
    d2 = str(tmp_path / "g2")
    base = _graph_dict(n=24)
    convert_json(base, d1, 2)
    mutated = _graph_dict(n=24)
    mutated["edges"][0]["weight"] += 7.0
    convert_json(mutated, d2, 2)
    state = str(tmp_path / "state")
    assert cli.main([
        "--algo", "pagerank", "--data", d1, "--state-dir", state,
        "--epoch-pin", "0,0",
    ]) == 0
    first = json.loads(capsys.readouterr().out)
    assert first["converged"] and not first["incremental"]
    # epoch-pin guard: wrong pin → exit 3
    assert cli.main([
        "--algo", "pagerank", "--data", d1, "--epoch-pin", "9,9",
    ]) == 3
    capsys.readouterr()
    # incremental against the mutated build: signature diff seeds the
    # dirty set; digest must equal a from-scratch run on the same data
    assert cli.main([
        "--algo", "pagerank", "--data", d2, "--state-dir", state,
        "--incremental",
    ]) == 0
    inc = json.loads(capsys.readouterr().out)
    assert cli.main(["--algo", "pagerank", "--data", d2]) == 0
    scratch = json.loads(capsys.readouterr().out)
    assert inc["incremental"]
    assert inc["value_digest"] == scratch["value_digest"]
    assert inc["rows_recomputed"] < scratch["rows_recomputed"]
