"""SmallThinker's decoder on the CPU at a small size, against the
benchmark's plain reference (`benchmarks/reference/smallthinker.py`,
loaded by path): a full and a window layer of plain attention (no gate,
no head norms, 7 query heads a key head) against the reference's
full-row masks, the router on the layer's input against the same layer
routed on the experts' input, ReLU against SiLU experts, the share test
of the expert layer, three `Estimator.train` steps against the
reference's loop and each of its faults, the counts in the step's set-up
span, and that what became the model's choice lowers, at its default,
to the program it was."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_sequence_lm import (
    BENCH,
    _built,
    _highest,
    _load,
    _program_first_steps,
    _rehearsal,
    _value_and_grads,
)


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        import graphs
        import weights

        yield {
            "ref": _load(os.path.join(BENCH, "reference", "smallthinker.py"), "ref_smallthinker"),
            "train": _load(os.path.join(BENCH, "reference", "train.py"), "ref_train"),
            "family": _load(os.path.join(BENCH, "families", "smallthinker.py"), "fam_smallthinker"),
            "graphs": graphs,
            "weights": weights,
        }
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def config():
    return _rehearsal("smallthinker-21b-a3b-ep4")


# -- (a) the two kinds of attention layer ------------------------------------


@pytest.mark.parametrize("length,block", [(64, 16), (40, 16), (24, 64)])
@pytest.mark.parametrize("local", [True, False])
def test_plain_attention_of_either_kind_matches_the_reference(bench, config, local, length, block):
    """A window of 24 over blocks of 16 (no multiple), a last block that
    is not whole, and one block in all; the full layer has no rotary. The
    layer's tree is four matrices: no gate's columns, no head norm."""
    model = _built(bench, config)[1]["model"].clone(attention_block=block)
    layer = model.mixer(1 if local else 0)
    assert (layer.window, layer.rotary_dim) == ((24, 16) if local else (None, 0))
    assert (layer.gated, layer.head_norms) == (False, False)
    assert layer.num_heads // layer.num_kv_heads == 7
    x = jax.random.normal(jax.random.PRNGKey(0), (2, length, config["hidden_size"]))
    params = layer.init(jax.random.PRNGKey(1), x)
    nq, d = config["num_attention_heads"], config["head_dim"]
    assert {k: v.shape for k, v in params["params"].items()} == {
        "q_proj": (config["hidden_size"], nq * d), "k_proj": (config["hidden_size"], d),
        "v_proj": (config["hidden_size"], d), "o_proj": (nq * d, config["hidden_size"]),
    }

    def program(params, x):
        return layer.apply(params, x)[0]

    def reference(params, x):
        return bench["ref"].attention(params["params"], x, config, local, local, 8)

    (_, got), g_got = _value_and_grads(program, jnp.sin, (0, 1))(params, x)
    (_, want), g_want = _value_and_grads(reference, jnp.sin, (0, 1))(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    for a, b in zip(*map(jax.tree_util.tree_leaves, (g_got, g_want))):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


def test_the_two_layouts_plan_each_layer_on_their_own(bench, config):
    """Published, the layouts agree; the class takes them apart: a window
    layer without rotary and a full layer with it are planned as said."""
    model = _built(bench, config)[1]["model"]
    kinds = [(mixer.window, mixer.rotary_dim) for mixer in map(model.mixer, range(4))]
    assert kinds == [(None, 0), (24, 16), (24, 16), (24, 16)]
    apart = model.clone(sliding_window_layout=(1, 0, 1, 0), rope_layout=(0, 0, 1, 1))
    kinds = [(mixer.window, mixer.rotary_dim) for mixer in map(apart.mixer, range(4))]
    assert kinds == [(24, 0), (None, 0), (24, 16), (None, 16)]


# -- (b) the expert layer: where the router reads, the gate, the shares ------


def _moe(config, first, count, activation="relu"):
    from euler_tpu.layers.moe import SparseMoE

    return SparseMoE(
        num_experts=config["model"]["router_experts"],
        top_k=config["moe_num_active_primary_experts"],
        expert_dim=config["moe_ffn_hidden_size"], shared_dim=0, held=(first, count),
        activation=activation,
    )


def _layer_inputs(config, tokens=96):
    """The experts' input, the tensor the router reads (another one), and
    a whole layer's parameters with the router far enough from zero that
    the two tensors pick differently."""
    hidden, experts = config["hidden_size"], config["model"]["router_experts"]
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, hidden))
    entered = jax.random.normal(jax.random.PRNGKey(3), (tokens, hidden))
    params = _moe(config, 0, experts).init(jax.random.PRNGKey(1), x, entered)["params"]
    assert set(params) == {"router", "experts_gate", "experts_up", "experts_down"}
    return x, entered, params


def test_the_router_reads_what_it_is_handed_and_the_experts_do_not(bench, config):
    """`route_on` moves the pick and the weights and nothing else: the
    layer routed on the layer's input is the reference's, the same layer
    routed on `x` is the reference's fault, and the two differ; the
    router's gradient reaches the tensor it read."""
    experts = config["model"]["router_experts"]
    uncut = dict(config, model=dict(config["model"], experts_here=[0, experts]))
    x, entered, params = _layer_inputs(config)
    layer = _moe(config, 0, experts)
    ahead, rows = _highest(layer.apply)({"params": params}, x, entered)
    usual, _ = _highest(layer.apply)({"params": params}, x)
    same, _ = _highest(layer.apply)({"params": params}, x, x)
    assert int(rows) == x.shape[0] * config["moe_num_active_primary_experts"]
    ref = _highest(bench["ref"].mixture)
    np.testing.assert_allclose(ahead, ref(params, x, entered, uncut, ""), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        usual, ref(params, x, entered, uncut, "router_after_attention"), rtol=1e-4, atol=1e-6
    )
    np.testing.assert_array_equal(usual, same)
    assert float(jnp.max(jnp.abs(ahead - usual))) > 1e-3

    def total(x, entered):
        return jnp.sum(jnp.sin(layer.apply({"params": params}, x, entered)[0]))

    d_x, d_entered = jax.grad(total, (0, 1))(x, entered)
    assert float(jnp.max(jnp.abs(d_x))) > 0 and float(jnp.max(jnp.abs(d_entered))) > 0


def test_relu_experts_are_not_silu_experts(bench, config):
    """Value and every gradient of the ReLU-gated layer against the
    reference, through the tiles' own backward; the SiLU-gated layer on
    the same weights is the reference's `silu_experts` fault, another
    function."""
    experts = config["model"]["router_experts"]
    uncut = dict(config, model=dict(config["model"], experts_here=[0, experts]))
    x, entered, params = _layer_inputs(config)

    def program(activation):
        layer = _moe(config, 0, experts, activation)
        return lambda params, x, entered: layer.apply({"params": params}, x, entered)[0]

    def reference(fault):
        return lambda params, x, entered: bench["ref"].mixture(params, x, entered, uncut, fault)

    values = {}
    for activation, fault in [("relu", ""), ("silu", "silu_experts")]:
        (_, got), g_got = _value_and_grads(program(activation), jnp.sin, (0, 1, 2))(params, x, entered)
        (_, want), g_want = _value_and_grads(reference(fault), jnp.sin, (0, 1, 2))(params, x, entered)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
        for a, b in zip(*map(jax.tree_util.tree_leaves, (g_got, g_want))):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-6)
        values[activation] = got
    assert float(jnp.max(jnp.abs(values["relu"] - values["silu"]))) > 1e-4


@pytest.mark.parametrize("router_scale", [1.0, 40.0])
def test_the_shares_of_the_held_experts_add_up_to_the_whole_layer(bench, config, router_scale):
    """The parts all `router_experts / held` chips compute are the uncut
    reference layer (there is no shared expert to count once): under an
    even router and under one far from even, whose shares see unequal
    loads; every chip routes on the same layer input."""
    ref = _highest(bench["ref"].mixture)
    experts, top_k = config["model"]["router_experts"], config["moe_num_active_primary_experts"]
    count = config["model"]["experts_here"][1]
    x, entered, params = _layer_inputs(config)
    params["router"] = params["router"] * router_scale
    uncut = dict(config, model=dict(config["model"], experts_here=[0, experts]))
    want = ref(params, x, entered, uncut, "")

    total, rows, loads = jnp.zeros_like(x), 0, []
    for first in range(0, experts, count):
        mine = dict(params)
        for name in ("experts_gate", "experts_up", "experts_down"):
            mine[name] = params[name][first : first + count]
        y, routed = _highest(_moe(config, first, count).apply)({"params": mine}, x, entered)
        cut = dict(config, model=dict(config["model"], experts_here=[first, count]))
        np.testing.assert_allclose(y, ref(mine, x, entered, cut, ""), rtol=1e-4, atol=1e-6)
        total, rows, loads = total + y, rows + int(routed), loads + [int(routed)]
    assert rows == x.shape[0] * top_k  # every assignment landed on one chip
    assert len(loads) == experts // count and (router_scale == 1.0 or max(loads) > min(loads))
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-6)
    assert float(jnp.max(jnp.abs(want))) > 1e-4  # the experts matter


def test_softmax_over_the_kept_logits_is_the_softmax_router_renormalised(config):
    """The model states its router as a softmax over the 6 kept logits;
    the program's `score="softmax"`, `norm_topk=True` is that function."""
    _, entered, params = _layer_inputs(config)
    logits = entered @ params["router"]
    k = config["moe_num_active_primary_experts"]
    kept, picked = jax.lax.top_k(logits, k)
    stated = jax.nn.softmax(kept, axis=-1)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    np.testing.assert_array_equal(picked, top_e)
    np.testing.assert_allclose(top_p / jnp.sum(top_p, -1, keepdims=True), stated, rtol=1e-5)


# -- (c) three Estimator.train steps against the reference's loop -----------


@pytest.fixture(scope="module")
def three_steps(bench, config):
    """Three `Estimator.train` steps from seeded weights, once for the
    tests below: what `benchmarks/run.py` compares, and the set-up span
    of the step program."""
    from euler_tpu.utils import trace

    since = time.perf_counter_ns()  # not a count of spans: the record is bounded
    got, reference = _program_first_steps(bench, config, 3000000040)
    spans = [s for s in trace.spans() if s.start_ns >= since]
    return {"got": got, "spans": spans, "reference": reference}


def test_three_train_steps_match_the_reference(bench, three_steps):
    got, want = three_steps["got"], three_steps["reference"]()
    assert set(got["grad_norm"]) == set(want["grad_norm"])  # one tree, leaf for leaf
    assert not [k for k in got["grad_norm"] if "q_norm" in k or "k_norm" in k or "shared" in k]
    compared = bench["train"].compare(got, want)
    assert all(v < 1e-4 for v in compared.values()), compared


@pytest.mark.parametrize(
    "fault",
    ["half_batch", "router_after_attention", "silu_experts", "no_window", "rotary_everywhere"],
)
def test_each_fault_of_the_reference_is_another_model(bench, three_steps, fault):
    """The reference names its faults, and the comparison sees each."""
    assert bench["ref"].FAULTS[0] == "" and fault in bench["ref"].FAULTS
    assert len(bench["ref"].FAULTS) == 6
    want, broken = three_steps["reference"](), three_steps["reference"](fault)
    assert max(bench["train"].compare(broken, want).values()) > 1e-2, fault


def test_first_call_span_carries_the_layers_forms(config, three_steps):
    args = next(
        s.args for s in three_steps["spans"]
        if s.name == "step.first_call" and s.args["program"] == "train_step"
    )
    assert config["model"]["layouts_here"]["sliding_window_layout"] == [0, 1, 1, 1]
    assert (args.get("router_on_input", 0), args.get("experts_relu", 0), args.get("attn_ungated", 0)) == (4, 4, 4)
    assert (args.get("swa_layers", 0), args.get("swa_window", 0), args.get("attn_full_layers", 0)) == (3, 3 * 24, 1)
    assert (args.get("dense_layers", 0), args.get("router_sigmoid", 0)) == (0, 0)
    assert args.get("mixer_core_kept", 0) == 4  # every layer's mixer is a softmax attention
    # heads of 16 in blocks of 16 at the rehearsal's size: no whole tile
    assert (args.get("attn_core_dense", 0), args.get("attn_core_kernel", 0)) == (4, 0)
    assert (args.get("dsa_layers", 0), args.get("agg_grid", 0), args.get("draw_elements", 0)) == (0, 0, 1)


def test_the_model_is_its_reference_and_routes_ahead_of_the_attention(bench, config):
    """One drawn batch: the model's loss is the reference's, the share of
    the assignments that landed here is near the even router's, and the
    same model routed on the experts' input is another."""
    graph, built = _built(bench, config)
    weights = bench["weights"]
    flat = weights.make_params(bench["ref"].param_spec(config, graph), 5)
    ids = jax.jit(built["flow"].sample)(bench["train"].step_key(5, 0))
    model = built["model"]
    assert (model.route_on_input, model.expert_activation, model.embed_scale) == (True, "relu", 1.0)
    loss, share = _highest(jax.jit(lambda p: model.apply(p, ids)[1::2]))(weights.nest(flat))
    held = config["model"]["experts_here"][1] / config["model"]["router_experts"]
    assert 0.5 * held < float(share) < 2.0 * held
    blocks = config["reference_blocks"]
    want = _highest(jax.jit(lambda p: bench["ref"].forward_loss(p, ids, config, blocks, "")))(flat)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    usual = model.clone(route_on_input=False)
    assert abs(float(usual.apply(weights.nest(flat), ids)[1]) - float(loss)) > 1e-6
    names = {k.split("/", 2)[2] for k in flat if k.startswith("params/layer_0/")}
    assert names == {
        "input_norm/w", "post_norm/w", "mixer/q_proj", "mixer/k_proj", "mixer/v_proj",
        "mixer/o_proj", "moe/router", "moe/experts_gate", "moe/experts_up", "moe/experts_down",
    }


# -- (d) what became the model's choice lowers, at its default, as it did -----


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


def test_the_defaults_lower_to_the_program_they_were():
    """`route_on=None` and `activation="silu"`; `gated=True` and
    `head_norms=True`: the same text as the layer given neither word,
    value and gradient; and the other choice is another program."""
    from euler_tpu.layers.moe import SparseMoE
    from euler_tpu.layers.sequence import GatedAttention

    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    common = dict(num_experts=8, top_k=2, expert_dim=8, shared_dim=8, held=(0, 4))
    plain, said = SparseMoE(**common), SparseMoE(activation="silu", **common)
    params = plain.init(jax.random.PRNGKey(1), x)

    def step(layer, *more):
        return jax.value_and_grad(lambda p, x: jnp.sum(layer.apply(p, x, *more)[0]), (0, 1))

    want = _lowered(step(plain), params, x)
    assert _lowered(step(said, None), params, x) == want
    assert _lowered(step(SparseMoE(activation="relu", **common)), params, x) != want
    assert _lowered(step(plain, x + 1.0), params, x) != want

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 16))
    common = dict(num_heads=4, num_kv_heads=2, head_dim=8, rotary_dim=4, block=16, window=24)
    plain, said = GatedAttention(**common), GatedAttention(gated=True, head_norms=True, **common)
    params = plain.init(jax.random.PRNGKey(1), x)
    want = _lowered(step(plain), params, x)
    assert _lowered(step(said), params, x) == want
    bare = GatedAttention(gated=False, head_norms=False, **common)
    bare_params = bare.init(jax.random.PRNGKey(1), x)
    assert set(bare_params["params"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    assert bare_params["params"]["q_proj"].shape == (16, 4 * 8)
    assert params["params"]["q_proj"].shape == (16, 4 * 8 * 2)
    assert _lowered(step(bare), bare_params, x) != want


def test_no_op_of_the_step_lies_under_two_scopes(bench, config):
    """The router's work stands ahead of the attention in the program and
    keeps its name: every op of the model's loss-and-gradient program
    bears one `euler.*` scope at most, `moe.route` and `moe.dispatch`
    among those found."""
    import re

    graph, built = _built(bench, config)
    weights = bench["weights"]
    params = weights.nest(weights.make_params(bench["ref"].param_spec(config, graph), 5))
    ids = jax.jit(built["flow"].sample)(bench["train"].step_key(5, 0))
    step = jax.jit(jax.grad(lambda p: built["model"].apply(p, ids)[1]))
    names = set(re.findall(r'op_name="([^"]*)"', step.lower(params).compile().as_text()))
    # an op XLA merged from several bears their names joined by ";"
    scoped = [n for name in names for n in name.split(";") if "euler." in n]
    found = {m for n in scoped for m in re.findall(r"euler\.([a-z_.]+)", n)}
    assert {"moe.route", "moe.dispatch", "moe.experts", "moe.combine"} <= found
    assert {"swa.proj", "swa.core", "swa.out", "attn.proj", "attn.core", "attn.out"} <= found
    assert not [n for n in scoped if len(set(re.findall(r"euler\.[a-z_.]+", n))) > 1]
