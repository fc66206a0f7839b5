"""LFM2's decoder on the CPU at a small size (the head of 64 kept),
against the benchmark's plain reference (`benchmarks/reference/lfm2_moe.py`,
loaded by path): the doubly gated short convolution against three
shifted products and that it sees no later step, grouped-query attention
at a head of 64 against the reference's full-row masks, the tied table's
gradient as the embedding's scatter-add plus the head's dense product,
the published router against `SparseMoE(score="sigmoid", norm_eps=1e-6)`,
the share test of the expert layer, three `Estimator.train` steps against
the reference's loop and each of its faults, the counts in the step's
set-up span, and that what became the model's choice lowers, at its
default, to the program it was."""

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

FAULTS = ["half_batch", "conv_no_out_gate", "conv_two_taps", "router_softmax", "no_head_norms"]


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        import graphs
        import weights

        yield {
            "ref": _load(os.path.join(BENCH, "reference", "lfm2_moe.py"), "ref_lfm2_moe"),
            "train": _load(os.path.join(BENCH, "reference", "train.py"), "ref_train"),
            "family": _load(os.path.join(BENCH, "families", "lfm2_moe.py"), "fam_lfm2_moe"),
            "graphs": graphs,
            "weights": weights,
        }
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def config():
    return _rehearsal("lfm2-24b-a2b-ep8")


# -- (a) the short convolution ---------------------------------------------------


@pytest.mark.parametrize("taps", [3, 4, 1])
@pytest.mark.parametrize("length", [64, 5, 2])
def test_the_short_convolution_is_three_shifted_products_between_two_gates(
    bench, config, length, taps
):
    """Value and every gradient against the reference's shifted
    products, at the published 3 taps, at another count, at one (no
    mixing over time at all) and at a sequence shorter than the taps."""
    from euler_tpu.layers.sequence import GatedShortConv

    hidden = config["hidden_size"]
    layer = GatedShortConv(taps=taps)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, length, hidden))
    params = layer.init(jax.random.PRNGKey(1), x)
    assert {k: v.shape for k, v in params["params"].items()} == {
        "in_proj": (hidden, 3 * hidden), "conv": (hidden, taps), "out_proj": (hidden, hidden),
    }
    cut = dict(config, conv_L_cache=taps)

    def program(params, x):
        y, own = layer.apply(params, x)
        assert own is None  # no loss of its own
        return y

    def reference(params, x):
        return bench["ref"].short_conv(params["params"], x, cut)

    (_, got), g_got = _value_and_grads(program, jnp.sin, (0, 1))(params, x)
    (_, want), g_want = _value_and_grads(reference, jnp.sin, (0, 1))(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)
    for a, b in zip(*map(jax.tree_util.tree_leaves, (g_got, g_want))):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-7)
    assert float(jnp.max(jnp.abs(want))) > 1e-4


def test_a_change_at_a_step_moves_nothing_before_it_and_two_steps_after(config):
    """Causal, and of three taps' reach: step t's input moves the outputs
    t, t + 1 and t + 2 and no other."""
    from euler_tpu.layers.sequence import GatedShortConv

    layer = GatedShortConv(taps=3)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, config["hidden_size"]))
    params = layer.init(jax.random.PRNGKey(1), x)
    y = layer.apply(params, x)[0]
    moved = layer.apply(params, x.at[0, 7].add(1.0))[0]
    changed = np.flatnonzero(np.asarray(jnp.max(jnp.abs(moved - y), axis=-1)[0]) > 0)
    assert changed.tolist() == [7, 8, 9]


# -- (b) attention at a head of 64 -----------------------------------------------


@pytest.mark.parametrize("length,block", [(64, 16), (40, 16), (24, 64)])
def test_attention_at_a_head_of_64_matches_the_reference(bench, config, length, block):
    """No gate, head norms, rotary over the whole head of 64, every
    earlier key: blocks of 16, a last block that is not whole, one block
    in all. The layer's tree is four matrices and two head norms."""
    model = _built(bench, config)[1]["model"].clone(attention_block=block)
    layer = model.mixer(1)
    assert (layer.window, layer.rotary_dim, layer.head_dim) == (None, 64, 64)
    assert (layer.gated, layer.head_norms) == (False, True)
    hidden = config["hidden_size"]
    x = jax.random.normal(jax.random.PRNGKey(0), (2, length, hidden))
    params = layer.init(jax.random.PRNGKey(1), x)
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    assert {k: getattr(v, "shape", None) for k, v in params["params"].items()} == {
        "q_proj": (hidden, nq * 64), "k_proj": (hidden, nkv * 64),
        "v_proj": (hidden, nkv * 64), "o_proj": (nq * 64, hidden),
        "q_norm": None, "k_norm": None,
    }
    # far from zero, so that the head norms' weights matter
    for name in ("q_norm", "k_norm"):
        params["params"][name]["w"] = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (64,))
    flat = bench["weights"].flatten(params["params"])

    def program(params, x):
        return layer.apply(params, x)[0]

    def reference(flat, x):
        return bench["ref"].attention(flat, x, config, config["norm_eps"], 8)

    (_, got), g_got = _value_and_grads(program, jnp.sin, (0, 1))(params, x)
    (_, want), g_want = _value_and_grads(reference, jnp.sin, (0, 1))(flat, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    g_got = (bench["weights"].flatten(g_got[0]["params"]), g_got[1])
    assert set(g_got[0]) == set(g_want[0])
    for name in g_want[0]:
        np.testing.assert_allclose(g_got[0][name], g_want[0][name], rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(g_got[1], g_want[1], rtol=1e-3, atol=1e-5)


def test_layer_types_plan_the_mixers_and_an_unknown_kind_raises(bench, config):
    from euler_tpu.layers.sequence import GatedAttention, GatedShortConv

    model = _built(bench, config)[1]["model"]
    assert model.layer_types == ("conv", "full_attention", "conv", "conv", "conv")
    kinds = [type(model.mixer(i)) for i in range(5)]
    assert kinds == [GatedShortConv, GatedAttention] + [GatedShortConv] * 3
    assert model.mixer(0).taps == config["conv_L_cache"] == 3
    with pytest.raises(ValueError, match="no known kind"):
        model.clone(layer_types=("conv", "sliding_attention")).mixer(1)


# -- (c) the tied head -----------------------------------------------------------


def test_the_tied_tables_gradient_is_the_scatter_add_plus_the_heads_product(bench, config):
    """The tied model has no `head` leaf; its table's gradient is the
    untied model's table gradient (the embedding's scatter-add) plus the
    untied head's dense gradient transposed, where the untied head is the
    table; loss and every other gradient are the untied model's."""
    graph, built = _built(bench, config)
    weights = bench["weights"]
    flat = weights.make_params(bench["ref"].param_spec(config, graph), 5)
    assert "params/head" not in flat
    ids = jax.jit(built["flow"].sample)(bench["train"].step_key(5, 0))
    tied = built["model"]
    assert tied.tie_embeddings
    untied = tied.clone(tie_embeddings=False)
    table = flat["params/embed/table"]
    vocab = config["vocab_size"]

    def loss_of(model):
        return _highest(jax.jit(jax.value_and_grad(lambda p: model.apply(p, ids)[1])))

    loss, grads = loss_of(tied)(weights.nest(flat))
    with_head = weights.nest({**flat, "params/head": table[:vocab].T})
    loss_u, grads_u = loss_of(untied)(with_head)
    np.testing.assert_allclose(loss, loss_u, rtol=1e-6)
    grads, grads_u = weights.flatten(grads), weights.flatten(grads_u)
    assert set(grads_u) - set(grads) == {"params/head"}
    scatter, dense = grads_u["params/embed/table"], grads_u["params/head"].T
    assert float(jnp.linalg.norm(scatter)) > 1e-4 and float(jnp.linalg.norm(dense)) > 1e-4
    np.testing.assert_allclose(
        grads["params/embed/table"][:vocab], scatter[:vocab] + dense, rtol=1e-4, atol=1e-7
    )
    for name in set(grads) - {"params/embed/table"}:
        np.testing.assert_allclose(grads[name], grads_u[name], rtol=1e-4, atol=1e-7)


# -- (d) the expert layer: the published router, the shares ----------------------


def _moe(config, first, count, experts=None, norm_eps=1e-6, score="sigmoid"):
    from euler_tpu.layers.moe import SparseMoE

    return SparseMoE(
        num_experts=experts or config["model"]["router_experts"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"], shared_dim=0, held=(first, count),
        score=score, norm_eps=norm_eps,
    )


def _layer_inputs(config, experts, tokens=96):
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, config["hidden_size"]))
    params = _moe(config, 0, experts, experts).init(jax.random.PRNGKey(1), x)["params"]
    assert set(params) == {
        "router", "expert_bias", "experts_gate", "experts_up", "experts_down"
    }
    # a bias that moves the pick and is in no weight
    params["expert_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(2), (experts,))
    return x, params


def _whole(config, experts):
    return dict(config, model=dict(config["model"], router_experts=experts, experts_here=[0, experts]))


@pytest.mark.parametrize("faint", [False, True])
def test_the_published_router_is_the_sigmoid_score_with_its_divisor(bench, config, faint):
    """Sigmoid, the pick on `s + b`, the `s` over their sum + 1e-6: the
    layer at `norm_eps` 1e-6 is the reference's, value and gradients; the
    pick follows the bias and the weights do not carry it. Where the
    scores are faint (logits near -13: four of them sum to a few 1e-6)
    the divisor shows, and the layer at the default 1e-20 is another."""
    experts = config["model"]["router_experts"]
    x, params = _layer_inputs(config, experts)
    if faint:
        x = jnp.abs(x)
        params["router"] = params["router"] - 13.0 / (0.8 * config["hidden_size"])
    weight = _highest(bench["ref"].router_weights)(params, x, config)
    assert int(jnp.sum(weight > 0)) == x.shape[0] * config["num_experts_per_tok"]
    if faint:
        assert 0.05 < float(jnp.mean(jnp.sum(weight, axis=-1))) < 0.95
    else:
        np.testing.assert_allclose(jnp.sum(weight, axis=-1), 1.0, atol=1e-5)
    unbiased = _highest(bench["ref"].router_weights)(
        {**params, "expert_bias": jnp.zeros(experts)}, x, config
    )
    assert bool(jnp.any((weight > 0) != (unbiased > 0)))  # the bias moves the pick

    def program(norm_eps):
        layer = _moe(config, 0, experts, norm_eps=norm_eps)
        return lambda params, x: layer.apply({"params": params}, x)[0]

    def reference(params, x):
        return bench["ref"].mixture(params, x, _whole(config, experts))

    (_, got), g_got = _value_and_grads(program(1e-6), jnp.sin, (0, 1))(params, x)
    (_, want), g_want = _value_and_grads(reference, jnp.sin, (0, 1))(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)
    for name in g_want[0]:
        np.testing.assert_allclose(g_got[0][name], g_want[0][name], rtol=1e-3, atol=1e-7)
    np.testing.assert_allclose(g_got[1], g_want[1], rtol=1e-3, atol=1e-7)
    assert float(jnp.max(jnp.abs(g_got[0]["expert_bias"]))) == 0.0  # no gradient reaches it
    bare = _highest(program(1e-20))(params, x)
    gap = float(jnp.max(jnp.abs(bare - want))) / float(jnp.max(jnp.abs(want)))
    assert gap > 0.05 if faint else gap < 1e-5


@pytest.mark.parametrize("router_scale", [1.0, 40.0])
def test_the_eight_shares_of_the_held_experts_add_up_to_the_whole_layer(
    bench, config, router_scale
):
    """The deployment's own counts: 64 experts, top-4, 8 held a chip. The
    parts the 8 chips compute (experts 8i..8i+7; there is no shared
    expert to count once) are the uncut reference layer: under an even
    router and under one far from even, whose shares see unequal loads."""
    ref = _highest(bench["ref"].mixture)
    experts, count, top_k = 64, 8, config["num_experts_per_tok"]
    assert top_k == 4
    x, params = _layer_inputs(config, experts)
    params["router"] = params["router"] * router_scale
    want = ref(params, x, _whole(config, experts))

    total, rows, loads = jnp.zeros_like(x), 0, []
    for first in range(0, experts, count):
        mine = dict(params)
        for name in ("experts_gate", "experts_up", "experts_down"):
            mine[name] = params[name][first : first + count]
        layer = _moe(config, first, count, experts)
        y, routed = _highest(layer.apply)({"params": mine}, x)
        np.testing.assert_allclose(
            y, ref(mine, x, _whole(config, experts), "", (first, count)), rtol=1e-4, atol=1e-6
        )
        total, rows, loads = total + y, rows + int(routed), loads + [int(routed)]
    assert rows == x.shape[0] * top_k  # every assignment landed on one chip
    assert len(loads) == 8 and (router_scale == 1.0 or max(loads) > 2 * min(loads))
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-6)
    assert float(jnp.max(jnp.abs(want))) > 1e-4  # the experts matter


# -- (e) three Estimator.train steps against the reference's loop -----------------


@pytest.fixture(scope="module")
def three_steps(bench, config):
    """Three `Estimator.train` steps from seeded weights, once for the
    tests below: what `benchmarks/run.py` compares, and the set-up span
    of the step program."""
    from euler_tpu.utils import trace

    since = time.perf_counter_ns()  # not a count of spans: the record is bounded
    got, reference = _program_first_steps(bench, config, 3000000042)
    spans = [s for s in trace.spans() if s.start_ns >= since]
    return {"got": got, "spans": spans, "reference": reference}


def test_three_train_steps_match_the_reference(bench, three_steps):
    """Loss, every leaf's gradient norm, every leaf's change after three
    Adam steps; the two trees are one, leaf for leaf, with no head."""
    got, want = three_steps["got"], three_steps["reference"]()
    assert set(got["grad_norm"]) == set(want["grad_norm"])
    assert not [k for k in got["grad_norm"] if "head" in k or "shared" in k]
    assert sum("mixer/conv" in k for k in got["grad_norm"]) == 4
    assert got["grad_norm"]["params/layer_2/moe/expert_bias"] == 0.0
    compared = bench["train"].compare(got, want)
    assert all(v < 1e-4 for v in compared.values()), compared


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_of_the_reference_is_another_model(bench, three_steps, fault):
    """The reference names its faults, and the comparison sees each."""
    assert bench["ref"].FAULTS == ("",) + tuple(FAULTS)
    want, broken = three_steps["reference"](), three_steps["reference"](fault)
    assert max(bench["train"].compare(broken, want).values()) > 1e-2, fault


def test_first_call_span_carries_the_layers_forms(config, three_steps):
    args = next(
        s.args for s in three_steps["spans"]
        if s.name == "step.first_call" and s.args["program"] == "train_step"
    )
    assert config["model"]["layer_types_here"] == config["layer_types"][1:6]
    assert (args.get("sconv_layers", 0), args.get("sconv_taps", 0), args.get("head_tied", 0)) == (4, 12, 1)
    assert (args.get("attn_full_layers", 0), args.get("attn_ungated", 0), args.get("swa_layers", 0)) == (1, 1, 0)
    assert (args.get("dense_layers", 0), args.get("router_sigmoid", 0)) == (1, 4)
    assert (args.get("router_on_input", 0), args.get("experts_relu", 0)) == (0, 0)
    assert args.get("mixer_core_kept", 0) == 1  # the one softmax layer; a convolution keeps nothing
    # 64 tokens in blocks of 16 at the rehearsal's size: no whole tile
    assert (args.get("attn_core_dense", 0), args.get("attn_core_kernel", 0), args.get("attn_head_64", 0)) == (1, 0, 0)
    assert (args.get("dsa_layers", 0), args.get("agg_grid", 0), args.get("draw_elements", 0)) == (0, 0, 1)


def test_a_layer_of_whole_tiles_at_a_head_of_64_counts_itself(config):
    """128 tokens in a block of 128: the shapes send the layer to the
    kernels (the Pallas interpreter here) and it says so."""
    from euler_tpu.layers.sequence import GatedAttention
    from euler_tpu.utils import trace

    layer = GatedAttention(
        num_heads=4, num_kv_heads=2, head_dim=64, rotary_dim=64, block=128, gated=False
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 32))
    before = trace.counts()
    params = layer.init(jax.random.PRNGKey(1), x)
    after = trace.counts()
    moved = {
        k: after.get(k, 0) - before.get(k, 0)
        for k in ("attn_core_kernel", "attn_head_64", "attn_core_dense")
    }
    assert moved == {"attn_core_kernel": 1, "attn_head_64": 1, "attn_core_dense": 0}
    dense = layer.clone(block=16)
    np.testing.assert_allclose(
        layer.apply(params, x)[0], dense.apply(params, x)[0], rtol=2e-2, atol=2e-3
    )


def test_the_model_is_its_reference_on_one_batch(bench, config):
    """One drawn batch: the model's loss is the reference's and the share
    of the assignments that landed here is near the even router's; the
    first layer's tree is a convolution and a dense SwiGLU, the others'
    feed-forwards are routed."""
    graph, built = _built(bench, config)
    weights = bench["weights"]
    flat = weights.make_params(bench["ref"].param_spec(config, graph), 5)
    ids = jax.jit(built["flow"].sample)(bench["train"].step_key(5, 0))
    model = built["model"]
    assert (model.router_score, model.router_norm_eps, model.route_scale) == ("sigmoid", 1e-6, 1.0)
    assert (model.shared_expert_intermediate_size, model.sandwich_norms, model.embed_scale) == (0, False, 1.0)
    loss, share = _highest(jax.jit(lambda p: model.apply(p, ids)[1::2]))(weights.nest(flat))
    held = config["model"]["experts_here"][1] / config["model"]["router_experts"]
    assert 0.5 * held < float(share) < 2.0 * held
    blocks = config["reference_blocks"]
    want = _highest(jax.jit(lambda p: bench["ref"].forward_loss(p, ids, config, blocks, "")))(flat)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    names = lambda i: {k.split("/", 2)[2] for k in flat if k.startswith(f"params/layer_{i}/")}  # noqa: E731
    norms = {"input_norm/w", "post_norm/w"}
    conv = {"mixer/in_proj", "mixer/conv", "mixer/out_proj"}
    moe = {"moe/router", "moe/expert_bias", "moe/experts_gate", "moe/experts_up", "moe/experts_down"}
    assert names(0) == norms | conv | {"mlp/gate", "mlp/up", "mlp/down"}
    assert names(1) == norms | moe | {
        "mixer/q_proj", "mixer/k_proj", "mixer/v_proj", "mixer/o_proj",
        "mixer/q_norm/w", "mixer/k_norm/w",
    }
    assert names(2) == names(3) == names(4) == norms | conv | moe


# -- (f) what became the model's choice lowers, at its default, as it did ---------


def test_the_defaults_lower_to_the_program_they_were():
    """`norm_eps` at its default is the literal it replaced: the sigmoid
    router lowers to the same text with and without the word, and to
    another at 1e-6."""
    from euler_tpu.layers.moe import SparseMoE

    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    common = dict(num_experts=8, top_k=2, expert_dim=8, shared_dim=8, held=(0, 4), score="sigmoid")
    plain = SparseMoE(**common)
    params = plain.init(jax.random.PRNGKey(1), x)

    def text(layer):
        step = jax.value_and_grad(lambda p, x: jnp.sum(layer.apply(p, x)[0]), (0, 1))
        return jax.jit(step).lower(params, x).as_text()

    assert text(SparseMoE(norm_eps=1e-20, **common)) == text(plain)
    assert text(SparseMoE(norm_eps=1e-6, **common)) != text(plain)


def test_no_op_of_the_step_lies_under_two_scopes(bench, config):
    """Every op of the model's loss-and-gradient program bears one
    `euler.*` scope at most; the convolution's three are among those
    found, and the tied head's product is under `euler.head`."""
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
    assert {"sconv.proj", "sconv.mix", "sconv.out"} <= found
    assert {"attn.proj", "attn.core", "attn.out", "mlp", "head", "loss", "embed"} <= found
    assert {"moe.route", "moe.dispatch", "moe.experts", "moe.combine"} <= found
    assert not [n for n in scoped if len(set(re.findall(r"euler\.[a-z_.]+", n))) > 1]
