"""Trinity-Mini's decoder on the CPU at a small size, against the
benchmark's plain reference (`benchmarks/reference/trinity.py`, loaded by
path): the two kinds of gated attention (window with rotary, full with
none) against the reference's full-row mask, the share test of the
sigmoid-routed expert layer, what a decoder layer adds with sandwich
norms and a dense feed-forward, three `Estimator.train` steps against the
reference's loop, what a window layer keeps of its forward, and the
counts in the step's set-up span."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_sequence_lm import (
    BENCH,
    _assert_keeping_the_core_changes_no_bit,
    _built,
    _highest,
    _load,
    _named,
    _one_layer_both_ways,
    _program_first_steps,
    _rehearsal,
    _value_and_grads,
)

LOCAL, FULL = "sliding_attention", "full_attention"


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        import graphs
        import weights

        yield {
            "ref": _load(os.path.join(BENCH, "reference", "trinity.py"), "ref_trinity"),
            "train": _load(os.path.join(BENCH, "reference", "train.py"), "ref_train"),
            "family": _load(os.path.join(BENCH, "families", "afmoe.py"), "fam_afmoe"),
            "graphs": graphs,
            "weights": weights,
        }
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def config():
    return _rehearsal("trinity-mini-ep8")


# -- (a) the two kinds of attention layer ------------------------------------


@pytest.mark.parametrize("length,block", [(64, 16), (40, 16), (24, 64)])
@pytest.mark.parametrize("local", [True, False])
def test_gated_attention_of_either_kind_matches_the_reference(bench, config, local, length, block):
    """A window of 24 over blocks of 16 (no multiple), a last block that
    is not whole, and one block in all; the full layer has no rotary."""
    model = _built(bench, config)[1]["model"].clone(attention_block=block)
    layer = model.mixer(0 if local else 2)
    assert (layer.window, layer.rotary_dim) == ((24, 16) if local else (None, 0))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, length, config["hidden_size"]))
    params = layer.init(jax.random.PRNGKey(1), x)
    # norm weights off zero, so that (1 + w) is tested
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(2), p.shape), params
    )

    def program(params, x):
        return layer.apply(params, x)[0]

    def reference(params, x):
        flat = bench["weights"].flatten(params["params"])
        return bench["ref"].gated_attention(flat, x, config, local, 8)

    (_, got), g_got = _value_and_grads(program, jnp.sin, (0, 1))(params, x)
    (_, want), g_want = _value_and_grads(reference, jnp.sin, (0, 1))(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    for a, b in zip(*map(jax.tree_util.tree_leaves, (g_got, g_want))):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


# -- (b) the share test --------------------------------------------------------


def _moe(config, first, count):
    from euler_tpu.layers.moe import SparseMoE

    return SparseMoE(
        num_experts=config["model"]["router_experts"], top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"], shared_dim=config["moe_intermediate_size"],
        held=(first, count), norm_topk=config["route_norm"], score=config["score_func"],
        route_scale=config["route_scale"], shared_gated=False,
    )


@pytest.mark.parametrize("bias", [0.0, 0.3])
@pytest.mark.parametrize("router_scale", [1.0, 40.0])
def test_sigmoid_routed_shares_add_up_to_the_whole_layer(bench, config, router_scale, bias):
    """The parts all `router_experts / held` chips compute, the ungated
    shared expert counted once, are the uncut layer: under an even router
    and under one far from even; with the expert bias at zero and off it
    (the pick moves, the weights do not take the bias in)."""
    ref = bench["ref"]
    experts, top_k = config["model"]["router_experts"], config["num_experts_per_tok"]
    hidden, count = config["hidden_size"], config["model"]["experts_here"][1]
    x = jax.random.normal(jax.random.PRNGKey(0), (96, hidden))
    params = _moe(config, 0, experts).init(jax.random.PRNGKey(1), x)["params"]
    assert "shared_gate" in params and "shared_mix" not in params and "expert_bias" in params
    params["router"] = params["router"] * router_scale
    params["expert_bias"] = bias * jax.random.normal(jax.random.PRNGKey(2), (experts,))

    uncut = dict(config, model=dict(config["model"], experts_here=[0, experts]))
    want = _highest(ref.mixture)(params, x, uncut, "")
    shared_only = _highest(ref.mixture)(params, x, uncut, "no_routed")
    if bias:  # the bias is in the pick: without it the layer is another
        unbiased = dict(params, expert_bias=jnp.zeros(experts))
        assert float(jnp.max(jnp.abs(want - _highest(ref.mixture)(unbiased, x, uncut, "")))) > 1e-4

    total, rows = jnp.zeros_like(x), 0
    for first in range(0, experts, count):
        mine = dict(params)
        for name in ("experts_gate", "experts_up", "experts_down"):
            mine[name] = params[name][first : first + count]
        y, routed = _highest(_moe(config, first, count).apply)({"params": mine}, x)
        cut = dict(config, model=dict(config["model"], experts_here=[first, count]))
        np.testing.assert_allclose(
            y, _highest(ref.mixture)(mine, x, cut, ""), rtol=1e-4, atol=1e-6
        )
        total, rows = total + (y - shared_only), rows + int(routed)
    assert rows == x.shape[0] * top_k  # every assignment landed on one chip
    np.testing.assert_allclose(total + shared_only, want, rtol=1e-4, atol=1e-6)
    assert float(jnp.max(jnp.abs(want - shared_only))) > 1e-4  # the experts matter


def test_the_kept_weights_add_up_to_route_scale_and_the_bias_takes_no_gradient(config):
    layer = _moe(config, 0, config["model"]["router_experts"])
    x = jax.random.normal(jax.random.PRNGKey(0), (32, config["hidden_size"]))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]

    def total(params):
        return jnp.sum(jnp.sin(layer.apply({"params": params}, x)[0]))

    grads = jax.grad(total)(params)
    np.testing.assert_array_equal(grads["expert_bias"], 0.0)
    assert float(jnp.max(jnp.abs(grads["router"]))) > 0
    # experts that return their input's first coordinates would need other
    # weights; the sum of a token's kept weights is read off the router
    scores = jax.nn.sigmoid(x @ params["router"])
    top, _ = jax.lax.top_k(scores, config["num_experts_per_tok"])
    kept = config["route_scale"] * top / jnp.sum(top, axis=-1, keepdims=True)
    np.testing.assert_allclose(jnp.sum(kept, axis=-1), config["route_scale"], rtol=1e-6)


# -- (c) three Estimator.train steps against the reference's loop -----------


@pytest.fixture(scope="module")
def three_steps(bench, config):
    """Three `Estimator.train` steps from seeded weights, once for the
    tests below: what `benchmarks/run.py` compares, and the set-up span
    of the step program."""
    from euler_tpu.utils import trace

    since = time.perf_counter_ns()  # not a count of spans: the record is bounded
    got, reference = _program_first_steps(bench, config, 3000000034)
    spans = [s for s in trace.spans() if s.start_ns >= since]
    return {"got": got, "spans": spans, "reference": reference}


def test_three_train_steps_match_the_reference(bench, three_steps):
    got, want = three_steps["got"], three_steps["reference"]()
    assert set(got["grad_norm"]) == set(want["grad_norm"])  # one tree, leaf for leaf
    compared = bench["train"].compare(got, want)
    assert all(v < 1e-4 for v in compared.values()), compared
    bias = [k for k in got["grad_norm"] if k.endswith("expert_bias")]
    assert len(bias) == 2 and all(got["grad_norm"][k] == got["change_norm"][k] == 0.0 for k in bias)
    # each of the mechanism's own faults is another model: the comparison sees it
    for fault in ("no_window", "rotary_everywhere", "softmax_router"):
        broken = three_steps["reference"](fault)
        assert max(bench["train"].compare(broken, want).values()) > 1e-2, fault


def test_first_call_span_carries_the_layers_forms(config, three_steps):
    args = next(
        s.args for s in three_steps["spans"]
        if s.name == "step.first_call" and s.args["program"] == "train_step"
    )
    kinds = config["model"]["layer_types_here"]
    assert kinds == [LOCAL, LOCAL, FULL]
    assert (args.get("swa_layers", 0), args.get("swa_window", 0), args.get("attn_full_layers", 0)) == (2, 2 * 24, 1)
    assert (args.get("dense_layers", 0), args.get("router_sigmoid", 0)) == (1, 2)
    assert args.get("mlp_grads_by_hand", 0) == 1
    assert args.get("mixer_core_kept", 0) == 3  # every layer's mixer is a softmax attention
    # heads of 16 in blocks of 16 at the rehearsal's size: no whole tile
    assert (args.get("attn_core_dense", 0), args.get("attn_core_kernel", 0)) == (3, 0)
    assert (args.get("dsa_layers", 0), args.get("dsa_index_vjp", 0)) == (0, 0)  # no indexer here
    assert (args.get("agg_grid", 0), args.get("draw_elements", 0)) == (0, 1)


def test_the_model_is_its_embedding_scale_and_its_sandwich_norms(bench, config):
    """`routed_share` counts the expert layers alone, the embedding enters
    times sqrt(hidden), and the tree holds four norms a layer, a dense
    `mlp` in the first layer and no `shared_mix`."""
    graph, built = _built(bench, config)
    weights = bench["weights"]
    flat = weights.make_params(bench["ref"].param_spec(config, graph), 5)
    ids = jax.jit(built["flow"].sample)(bench["train"].step_key(5, 0))
    model = built["model"]
    assert model.embed_scale == config["hidden_size"] ** 0.5
    loss, share = jax.jit(lambda p: model.apply(p, ids)[1::2])(weights.nest(flat))
    held = config["model"]["experts_here"][1] / config["model"]["router_experts"]
    assert 0.5 * held < float(share) < 2.0 * held  # over 2 expert layers of the 3
    want = _highest(jax.jit(lambda p: bench["ref"].forward_loss(p, ids, config, config["reference_blocks"], "")))(flat)
    np.testing.assert_allclose(_highest(jax.jit(lambda p: model.apply(p, ids)[1]))(weights.nest(flat)), want, rtol=1e-5)
    plain = model.clone(embed_scale=1.0)
    assert abs(float(plain.apply(weights.nest(flat), ids)[1]) - float(loss)) > 1e-6
    names = {k.split("/", 2)[2] for k in flat if k.startswith("params/layer_0/")}
    assert {"input_norm/w", "mixer_out_norm/w", "post_norm/w", "ffn_out_norm/w", "mlp/gate", "mlp/up", "mlp/down"} <= names
    assert not [k for k in flat if "layer_0/moe" in k or "shared_mix" in k or "layer_1/mlp" in k]


# -- (d) what a rematerialised window layer keeps -------------------------------


def test_keeping_the_attention_core_changes_no_bit(bench, config, monkeypatch):
    _assert_keeping_the_core_changes_no_bit(bench, config, monkeypatch, no_gradient=("expert_bias",))


@pytest.mark.parametrize("dense", [0, 1])
def test_a_window_layer_keeps_its_core_output_and_no_scores(bench, config, monkeypatch, dense):
    """One window layer (window 24, blocks of 16 over 64 positions: two
    growing stretches, a loop of two blocks): the backward keeps the
    blocks' output [B, G, R, T, d] and nothing else of five axes (a
    block's scores are [B, G, R, block, keys]), and the layer's second
    forward runs no block; so with a dense feed-forward as with experts."""
    model = _built(bench, config)[1]["model"].clone(
        num_layers=1, layer_types=(LOCAL,), num_dense_layers=dense
    )
    (kept, program), (whole, whole_program), core = _one_layer_both_ways(model, monkeypatch)
    assert _named(kept) == [core] and _named(whole) == [] and len(kept) == len(whole) + 1
    assert not [shape for shape, _ in kept if len(shape) > 3 and shape != core]
    program, whole_program = program.compile().as_text(), whole_program.compile().as_text()
    assert whole_program.count(" dot(") > program.count(" dot(")
    assert whole_program.count(" while(") > program.count(" while(")  # the run's loop, once more
