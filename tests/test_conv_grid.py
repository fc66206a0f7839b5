"""Grid blocks aggregate by a reduce over each dst row's slots; every
other block by gather + segment_sum, which is the oracle here: the same
block with `grid=0` must give the same output and gradients. Also: what
the conv stack lowers to, and the tally `step.first_call` carries."""

import re
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu.dataflow import DeviceSageFlow, DeviceWalkFlow, RelationDataFlow
from euler_tpu.dataflow.base import (
    Block,
    MiniBatch,
    fanout_block,
    hydrate_blocks,
)
from euler_tpu.datasets.synthetic import random_graph
from euler_tpu.estimator import DeviceFeatureCache, Estimator, EstimatorConfig
from euler_tpu.layers import get_conv
from euler_tpu.layers.conv import RelationConv, edge_count
from euler_tpu.models import GraphSAGESupervised
from euler_tpu.models.embedding_models import SkipGramModel
from euler_tpu.nn.base_gnn import GNNNet
from euler_tpu.utils import trace

N_DST, K, F = 6, 5, 8
CONVS = (
    "sage", "gcn", "gin", "graph", "appnp", "sgcn", "tagcn", "arma",
    "gated", "gat",
)
BLOCKS = ("lazy_fanout", "shipped_fanout", "whole_graph", "empty_row")

# the benchmark's rehearsal sizes of `sage-products-id`
REHEARSE = {"dims": [32, 32, 32], "fanouts": [4, 3, 2], "batch": 64, "enc": 16}


def _mask(rng, n_dst, empty_row=False):
    mask = rng.random((n_dst, K)) > 0.3
    mask[0, :2] = True  # no row empties by chance
    if empty_row:
        mask[2] = False
    return mask


def _block(kind, rng, n_dst=N_DST):
    """One grid block of each kind the repo makes, and its src width."""
    e = n_dst * K
    w = rng.random((n_dst, K)).astype(np.float32)
    if kind == "lazy_fanout":
        # as the lean wire ships it: no ids, no mask; hydrated on device
        mask = _mask(rng, n_dst)
        lazy = fanout_block(n_dst, K, w, None, lazy=True, ship_mask=False)
        batch = MiniBatch(
            feats=(np.zeros((n_dst, F), np.float32), np.zeros((e, F), np.float32)),
            masks=(np.ones(n_dst, bool), mask.reshape(-1)),
            blocks=(lazy,),
            root_idx=np.zeros(n_dst, np.int32),
        )
        return hydrate_blocks(batch).blocks[0], e
    if kind == "shipped_fanout":
        return fanout_block(n_dst, K, w, _mask(rng, n_dst)), e
    # whole-graph style (dataflow/whole.py): the src table is the dst
    # table, slots hold real neighbour rows, missing neighbours are masked
    mask = _mask(rng, n_dst, empty_row=kind == "empty_row").reshape(-1)
    deg = rng.integers(1, 9, n_dst).astype(np.float32)
    block = Block(
        edge_src=np.where(mask, rng.integers(0, n_dst, e), 0).astype(np.int32),
        edge_dst=np.repeat(np.arange(n_dst, dtype=np.int32), K),
        edge_w=np.where(mask, w.reshape(-1), 0.0).astype(np.float32),
        mask=mask,
        n_src=n_dst,
        n_dst=n_dst,
        grid=K,
        src_deg=deg,  # GCNConv's exact-normalisation branch
        dst_deg=deg,
    )
    return block, n_dst


def _scatter_oracle(block):
    return block.replace(grid=0, src_in_order=False)


def _close(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-30)
    err = float(np.abs(got - want).max()) / scale
    assert err <= 1e-6, f"{what}: {err:.3g} relative"


def _vjp_on(layer, params, block, x_dst, x_src, cot):
    def f(params, x_dst, x_src):
        return layer.apply(params, x_dst, x_src, block)

    out, vjp = jax.vjp(f, params, x_dst, x_src)
    return out, vjp(cot)


def _check_grid_equals_scatter(conv, kind, rng, n_dst=N_DST, f=F):
    block, n_src = _block(kind, rng, n_dst)
    assert block.grid == K
    assert block.src_in_order == (kind in ("lazy_fanout", "shipped_fanout"))
    x_dst = jnp.asarray(rng.normal(size=(n_dst, f)), jnp.float32)
    x_src = jnp.asarray(rng.normal(size=(n_src, f)), jnp.float32)
    layer = get_conv(conv)(out_dim=f)
    params = layer.init(jax.random.PRNGKey(1), x_dst, x_src, block)
    cot = jnp.asarray(rng.normal(size=(n_dst, f)), jnp.float32)
    out, grads = _vjp_on(layer, params, block, x_dst, x_src, cot)
    want_out, want_grads = _vjp_on(
        layer, params, _scatter_oracle(block), x_dst, x_src, cot
    )
    _close(out, want_out, "output")
    got_leaves, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(got_leaves, jax.tree_util.tree_leaves(want_grads)):
        _close(g, w, "gradient " + jax.tree_util.keystr(path))


@pytest.mark.parametrize("kind", BLOCKS)
@pytest.mark.parametrize("conv", CONVS)
def test_grid_form_equals_scatter_oracle(conv, kind):
    rng = np.random.default_rng(100 * CONVS.index(conv) + BLOCKS.index(kind))
    _check_grid_equals_scatter(conv, kind, rng)


@pytest.mark.parametrize("f", [64, 200, 256])
def test_grid_form_equals_scatter_oracle_at_real_widths(f):
    """Below, at twice and off the 128-lane width, on 13 dst rows (no
    whole 8-row tile, so the transpose takes `jnp.repeat`) and on 24
    (whole tiles: the 0/1 product)."""
    for n_dst in (13, 24):
        _check_grid_equals_scatter(
            "sage", "shipped_fanout", np.random.default_rng(f + n_dst),
            n_dst=n_dst, f=f,
        )


@pytest.mark.parametrize(
    "conv,kind,form",
    [
        ("sage", "shipped_fanout", "grid"),  # messages are x_src itself
        ("sage", "whole_graph", "grid"),  # gathered by real edge_src
        ("sage", "whole_graph", "scatter"),
        ("gat", "whole_graph", "grid"),
        ("gat", "whole_graph", "scatter"),
    ],
)
def test_bf16_inputs_get_bf16_cotangents_close_to_float32(conv, kind, form):
    """bfloat16 rows through both aggregation forms: no float32 update
    scattered into a bfloat16 buffer (the FutureWarning, as an error),
    each cotangent in its primal's dtype, values near the float32 run."""
    rng = np.random.default_rng(7)
    block, n_src = _block(kind, rng)
    if form == "scatter":
        block = _scatter_oracle(block)
    x_dst = jnp.asarray(rng.normal(size=(N_DST, F)), jnp.bfloat16)
    x_src = jnp.asarray(rng.normal(size=(n_src, F)), jnp.bfloat16)
    layer = get_conv(conv)(out_dim=F)
    params = layer.init(jax.random.PRNGKey(1), x_dst, x_src, block)
    cot32 = rng.normal(size=(N_DST, F)).astype(np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error", FutureWarning)
        out = layer.apply(params, x_dst, x_src, block)
        _, grads = _vjp_on(
            layer, params, block, x_dst, x_src, jnp.asarray(cot32, out.dtype)
        )
    primals = (params, x_dst, x_src)
    assert jax.tree.map(lambda g: g.dtype, grads) == jax.tree.map(
        lambda p: p.dtype, primals
    )
    _, want = _vjp_on(
        layer, params, block, x_dst.astype(jnp.float32),
        x_src.astype(jnp.float32), jnp.asarray(cot32),
    )
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w), rtol=0.05, atol=0.05
        )


def test_a_row_with_no_valid_slot_has_mean_zero_and_count_clamped():
    rng = np.random.default_rng(3)
    block, n_src = _block("empty_row", rng)
    count = np.asarray(edge_count(block))
    np.testing.assert_array_equal(
        count, np.asarray(block.mask).reshape(N_DST, K).sum(1)
    )
    assert count[2] == 0
    x_src = jnp.asarray(rng.normal(size=(n_src, F)), jnp.float32)
    layer = get_conv("sage")(out_dim=F, use_bias=False)
    params = layer.init(jax.random.PRNGKey(0), x_src, x_src, block)
    # W . [x_dst | mean]: with x_dst = 0 the empty row's output is W . 0
    out = layer.apply(params, jnp.zeros_like(x_src), x_src, block)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out[2]), 0.0)
    assert np.abs(np.asarray(out[0])).max() > 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [16, 12])  # whole 8-row tiles, and not
def test_the_transpose_repeats_rows_bit_for_bit(n, dtype):
    """`grid_add`'s own vjp: repeat each row `grid` times — as a product
    with a 0/1 matrix where the rows come in whole tiles — exactly."""
    from euler_tpu.ops import grid_add
    from euler_tpu.ops.mp_ops import _repeat_rows

    rng = np.random.default_rng(n)
    g = jnp.asarray(rng.normal(size=(n, 5)) * 1e3, dtype)
    want = np.asarray(jnp.repeat(g, 3, axis=0))
    np.testing.assert_array_equal(np.asarray(_repeat_rows(g, 3)), want)
    for other in (g[:, 0], g.reshape(n, 5, 1)):  # any rank
        np.testing.assert_array_equal(
            np.asarray(_repeat_rows(other, 3)),
            np.asarray(jnp.repeat(other, 3, axis=0)),
        )
    mask = jnp.asarray(rng.random(3 * n) > 0.3)
    x = jnp.asarray(rng.normal(size=(3 * n, 5)), dtype)
    _, vjp = jax.vjp(lambda x: grid_add(x, 3, mask=mask), x)
    np.testing.assert_array_equal(
        np.asarray(vjp(g)[0]), np.where(np.asarray(mask)[:, None], want, 0)
    )


def _tally(fn):
    before = trace.counts()
    out = fn()
    after = trace.counts()
    return out, {
        k: after.get(k, 0) - before.get(k, 0)
        for k in ("agg_grid", "agg_scatter")
    }


def _ops(lowered):
    return set(re.findall(r"stablehlo\.(\w+)", lowered.as_text()))


def test_relation_blocks_keep_gather_and_scatter():
    """`grid == 0`: the only path that runs on them, as before."""
    from test_training import make_cluster_graph

    g = make_cluster_graph()
    rng = np.random.default_rng(0)
    flow = RelationDataFlow(
        g, ["feat"], num_relations=1, fanout=3, num_hops=1, rng=rng
    )
    mb = flow.query(g.sample_node(4, rng=rng))
    blocks = mb.rel_blocks[0]
    assert all(b.grid == 0 and not b.src_in_order for b in blocks)
    layer = RelationConv(out_dim=8, num_relations=1)
    x_dst, x_src = jnp.asarray(mb.feats[0]), jnp.asarray(mb.feats[1])
    params = layer.init(jax.random.PRNGKey(0), x_dst, x_src, blocks)
    lowered, tally = _tally(
        lambda: jax.jit(layer.apply).lower(params, x_dst, x_src, blocks)
    )
    assert tally == {"agg_grid": 0, "agg_scatter": 1}
    assert {"gather", "scatter"} <= _ops(lowered)
    assert "reduce_window" not in _ops(lowered)


def _lazy_sage_batch(feats, masks):
    """What `DeviceSageFlow._fanout_batch` hands the conv stack, hydrated:
    lazy grid blocks with sources in order, hop tables already encoded."""
    blocks = []
    width = REHEARSE["batch"]
    for k in REHEARSE["fanouts"]:
        blocks.append(
            Block(
                edge_src=None, edge_dst=None, edge_w=None, mask=None,
                n_src=width * k, n_dst=width, grid=k, src_in_order=True,
            )
        )
        width *= k
    return hydrate_blocks(
        MiniBatch(
            feats=tuple(feats), masks=tuple(masks), blocks=tuple(blocks),
            root_idx=jnp.zeros(REHEARSE["batch"], jnp.int32),
        )
    )


def test_sage_stack_lowers_without_gather_or_scatter():
    """The conv stack alone (no encoder, no table), forward and gradient:
    a change that brings index traffic back fails here, on the CPU."""
    widths = [REHEARSE["batch"]]
    for k in REHEARSE["fanouts"]:
        widths.append(widths[-1] * k)
    rng = np.random.default_rng(0)
    feats = [
        jnp.asarray(rng.normal(size=(w, REHEARSE["enc"])), jnp.float32)
        for w in widths
    ]
    masks = [jnp.asarray(rng.random(w) > 0.2) for w in widths]
    net = GNNNet("sage", dims=REHEARSE["dims"])
    params = net.init(jax.random.PRNGKey(0), _lazy_sage_batch(feats, masks))

    def loss(params, feats, masks):
        return jnp.sum(net.apply(params, _lazy_sage_batch(feats, masks)) ** 2)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1)))
    lowered, tally = _tally(lambda: grad.lower(params, feats, masks))
    assert tally == {"agg_grid": 6, "agg_scatter": 0}
    ops = _ops(lowered)
    assert not ops & {"gather", "scatter", "dynamic_gather"}, sorted(ops)
    assert "reduce_window" in ops
    # and the numbers are the oracle's
    def oracle_loss(params, feats):
        batch = _lazy_sage_batch(feats, masks)
        batch = batch.replace(
            blocks=tuple(_scatter_oracle(b) for b in batch.blocks)
        )
        return jnp.sum(net.apply(params, batch) ** 2)

    got = grad(params, feats, masks)
    want = jax.grad(oracle_loss, argnums=(0, 1))(params, feats)
    for g, w in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        _close(g, w, "stack gradient")


def _first_calls(tmp_path, kind, steps_per_call=1):
    """The `step.first_call` spans of a rehearsal-size Estimator over a
    `DeviceSageFlow` ("sage") or a `DeviceWalkFlow`, by program."""
    graph = random_graph(num_nodes=300, out_degree=5, feat_dim=8, seed=7)
    since = time.perf_counter_ns()
    if kind == "sage":
        flow = DeviceSageFlow(
            graph, fanouts=REHEARSE["fanouts"], batch_size=REHEARSE["batch"],
            label_feature="label", with_hop_ids=True,
        )
        model = GraphSAGESupervised(
            dims=REHEARSE["dims"], label_dim=2,
            encoder_dim=REHEARSE["enc"], max_id=300,
        )
        cache = DeviceFeatureCache(graph, ["feat"])
    else:
        flow = DeviceWalkFlow(graph, batch_size=4, walk_len=3, window=1)
        model = SkipGramModel(num_nodes=300, dim=8)
        cache = None
    cfg = EstimatorConfig(
        model_dir=str(tmp_path / "m"), log_steps=10**9,
        steps_per_call=steps_per_call,
    )
    est = Estimator(model, flow, cfg, feature_cache=cache)
    est.train(2 * steps_per_call, log=False, save=False)
    return {
        s.args["program"]: s.args
        for s in trace.spans()
        if s.name == "step.first_call" and s.start_ns >= since
    }


@pytest.mark.parametrize(
    "kind,steps_per_call,want",
    [
        ("sage", 1, {"train_step": (6, 0)}),
        ("sage", 2, {"multi_step": (6, 0)}),
        ("skipgram", 1, {"train_step": (0, 0)}),
    ],
)
def test_first_call_span_carries_the_tally(tmp_path, kind, steps_per_call, want):
    firsts = _first_calls(tmp_path, kind, steps_per_call)
    assert {
        program: (args.get("agg_grid", 0), args.get("agg_scatter", 0))
        for program, args in firsts.items()
    } == want


@pytest.mark.parametrize(
    "kind,want",
    [
        ("sage", (3, 0)),
        # a walk is single draws: the plane is read slot by slot, as before
        ("skipgram", (0, 3)),
    ],
)
def test_first_call_span_carries_the_draw_forms(tmp_path, kind, want):
    (args,) = _first_calls(tmp_path, kind).values()
    assert args["program"] == "train_step"
    assert (args.get("draw_rows", 0), args.get("draw_elements", 0)) == want


@pytest.mark.parametrize("kind", ["sage", "skipgram"])
def test_first_call_span_of_a_graph_model_keeps_no_mixer_core(tmp_path, kind):
    """`mixer_core_kept` is the sequence mixers' (`layers/sequence.py`):
    a graph model's step program has none."""
    (args,) = _first_calls(tmp_path, kind).values()
    assert (args.get("mixer_core_kept", 0), args.get("dsa_layers", 0)) == (0, 0)


def test_conv_scope_has_no_scope_nested_in_it():
    """`benchmarks/scoped.py` names an op by its innermost `euler.*`
    scope and `conv_ms` reads `conv.forward` / `conv.backward` exactly."""
    feats = [jnp.ones((8, 4)), jnp.ones((16, 4))]
    masks = [jnp.ones(8, bool), jnp.ones(16, bool)]
    block = fanout_block(8, 2, np.ones((8, 2), np.float32), np.ones((8, 2), bool))
    batch = MiniBatch(
        feats=tuple(feats), masks=tuple(masks), blocks=(block,),
        root_idx=jnp.zeros(8, jnp.int32),
    )
    net = GNNNet("sage", dims=[4])
    params = net.init(jax.random.PRNGKey(0), batch)
    text = jax.jit(net.apply).lower(params, batch).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    scoped = [n for n in names if "euler.conv" in n]
    assert scoped
    assert all(n.count("euler.") == 1 for n in scoped), scoped
