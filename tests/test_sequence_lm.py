"""Qwen3-Next on the CPU at a small size, against the benchmark's plain
reference (`benchmarks/reference/qwen3_next.py`, loaded by path — there is
no second copy): the chunked delta rule against the token-by-token
recurrence, gated attention and its partial rotary, the share test of the
expert layer, three `Estimator.train` steps against the reference's loop,
and the device flow's sequences against the reference's walks."""

import contextlib
import importlib.util
import inspect
import io
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, importable as its own files import one
    another; taken off the path again afterwards."""
    sys.path.insert(0, BENCH)
    try:
        import graphs
        import program_graph
        import weights

        yield {
            "ref": _load(os.path.join(BENCH, "reference", "qwen3_next.py"), "ref_qwen3_next"),
            "ref_keye_vl2": _load(os.path.join(BENCH, "reference", "keye_vl2.py"), "ref_keye_vl2"),
            "train": _load(os.path.join(BENCH, "reference", "train.py"), "ref_train"),
            "family": _load(os.path.join(BENCH, "families", "qwen3_next.py"), "fam_qwen3_next"),
            "graphs": graphs,
            "program_graph": program_graph.program_graph,
            "weights": weights,
        }
    finally:
        sys.path.remove(BENCH)


def _rehearsal(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        full = json.load(f)

    def merge(base, over):
        out = dict(base)
        for k, v in over.items():
            out[k] = merge(base[k], v) if isinstance(v, dict) and isinstance(base.get(k), dict) else v
        return out

    return merge(full, full["rehearse"])


@pytest.fixture(scope="module")
def config():
    return _rehearsal("qwen3-next-80b-a3b-ep16")


def _highest(fn):
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    return run


def _value_and_grads(fn, weigh, argnums):
    """One compiled call: fn's result and the gradients of a scalar of it."""

    def scalar(*args):
        out = fn(*args)
        return jnp.sum(weigh(out)), out

    return _highest(jax.jit(jax.value_and_grad(scalar, argnums=argnums, has_aux=True)))


# -- (a) the chunked delta rule -------------------------------------------


def _rule_inputs(length, seed=0, batch=2, heads=3, dk=8, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (batch, heads, length, dk))
    k = jax.random.normal(ks[1], (batch, heads, length, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (batch, heads, length, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (batch, heads, length)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, heads, length)))
    return q, k, v, g, beta


@pytest.mark.parametrize("length,chunk", [(32, 8), (30, 8), (19, 16), (7, 16)])
def test_chunked_delta_rule_matches_the_recurrence(bench, length, chunk):
    from euler_tpu.ops import seq_ops

    args = _rule_inputs(length)
    to_ref = lambda a: jnp.moveaxis(a, 1, 2)  # noqa: E731  [B,H,T,..] -> [B,T,H,..]

    def program(q, k, v, g, beta):
        return seq_ops.chunk_gated_delta_rule(q, k, v, g, beta, chunk=chunk, group=2)

    def reference(q, k, v, g, beta):
        o = bench["ref"].delta_rule(
            to_ref(q), to_ref(k), to_ref(v), to_ref(jnp.exp(g)), to_ref(beta), 4
        )
        return jnp.moveaxis(o, 2, 1)

    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    (_, got), g_got = _value_and_grads(program, lambda o: o * weight, (0, 1, 2, 3, 4))(*args)
    (_, want), g_want = _value_and_grads(reference, lambda o: o * weight, (0, 1, 2, 3, 4))(*args)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize(
    "length,chunk,group",
    [(64, 8, 2), (50, 8, 2), (24, 8, 4), (32, 8, 1)],
    ids=["whole_groups", "padded", "one_group", "group_of_one"],
)
def test_the_rules_own_backward_under_a_rematerialised_caller(bench, length, chunk, group):
    """`chunk_gated_delta_rule` inside a caller that is rematerialised
    and saves what `keep` names, as a decoder layer is: the gradients of
    all five inputs against `jax.grad` through the reference's
    token-by-token recurrence, and against the same rule with `keep` the
    identity under a caller rematerialised whole, equal to the bit; the
    kept values are the output and the groups' start states alone."""
    from jax.ad_checkpoint import checkpoint_name

    from euler_tpu.ops import seq_ops

    args = _rule_inputs(length)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    to_ref = lambda a: jnp.moveaxis(a, 1, 2)  # noqa: E731  [B,H,T,..] -> [B,T,H,..]

    def caller(policy, keep):
        def layer(q, k, v, g, beta):  # its own arithmetic before and after the rule
            o = seq_ops.chunk_gated_delta_rule(
                q, 0.5 * k, v, g, beta, chunk=chunk, group=group, keep=keep
            )
            return jnp.tanh(o)

        return jax.checkpoint(layer, policy=policy)

    def reference(q, k, v, g, beta):
        o = bench["ref"].delta_rule(
            to_ref(q), to_ref(0.5 * k), to_ref(v), to_ref(jnp.exp(g)), to_ref(beta), 4
        )
        return jnp.tanh(jnp.moveaxis(o, 2, 1))

    kept = caller(
        jax.checkpoint_policies.save_only_these_names("rule"),
        lambda a: checkpoint_name(a, "rule"),
    )
    both = lambda fn: _value_and_grads(fn, lambda o: o * weight, (0, 1, 2, 3, 4))(*args)  # noqa: E731
    (_, got), g_got = both(kept)
    (_, whole), g_whole = both(caller(None, lambda a: a))
    (_, want), g_want = both(reference)
    np.testing.assert_array_equal(got, whole)
    for a, b, c in zip(g_got, g_whole, g_want):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(a, c, rtol=2e-4, atol=2e-5)
    named = sorted(shape for shape, _ in _kept_of_the_forward(lambda *a: jnp.sum(kept(*a)), *args))
    group = min(group, -(-length // chunk))
    groups = -(-length // (chunk * group))
    batch, heads, _, dk = args[0].shape
    dv = args[2].shape[-1]
    assert named == sorted(
        [(groups, batch, heads, dk, dv), (groups, group, batch, heads, chunk, dv)]
    )


def _both_forms(fn, args, weight, monkeypatch):
    """Value and the five gradients of `fn` with the rule's groups in
    the kernels (interpreted here) and in XLA (`_one_group`, `jax.vjp`)."""
    from euler_tpu.ops import seq_ops

    run = lambda: _value_and_grads(fn, lambda o: o * weight, (0, 1, 2, 3, 4))(*args)  # noqa: E731
    kernels = run()
    monkeypatch.setattr(seq_ops, "delta_rule_by_kernels", lambda *_: False)
    return kernels, run()


def _close_in_float32(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * float(np.abs(b).max()))


@pytest.mark.parametrize(
    "length,group",
    [(256, 2), (200, 2), (512, 4), (300, 4)],
    ids=["group_2_whole", "group_2_padded", "group_4_whole", "group_4_padded"],
)
def test_the_rules_kernels_match_the_xla_group(length, group, monkeypatch):
    """Where dk and dv are whole 128-lane tiles the rule's groups are the
    grid steps of two Pallas kernels (`ops/delta_rule_kernels.py`, the
    interpreter here, whose products are float32 as XLA's are on the
    CPU): the output and all five gradients are the XLA group's to
    float32's rounding, at a length of whole groups and one padded."""
    from euler_tpu.ops import seq_ops

    args = _rule_inputs(length, batch=1, heads=2, dk=128, dv=128)
    assert seq_ops.delta_rule_by_kernels(args[0], args[2], 64)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def rule(q, k, v, g, beta):
        return seq_ops.chunk_gated_delta_rule(q, k, v, g, beta, chunk=64, group=group)

    kernels, xla = _both_forms(rule, args, weight, monkeypatch)
    _close_in_float32(kernels, xla)


def test_the_rules_kernels_under_a_rematerialised_caller(monkeypatch):
    """The kernels inside a caller rematerialised as a decoder layer is,
    saving what `_keep_core` names: gradients as the XLA group's under the
    same caller, and the kept values are still the output and the groups'
    start states alone — the output as the kernels leave it, [B, H, T, dv]
    padded to whole groups, not split into them."""
    from euler_tpu.layers.sequence import CORE_OUTPUT, _keep_core
    from euler_tpu.ops import seq_ops

    args = _rule_inputs(300, batch=1, heads=2, dk=128, dv=128)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def layer(q, k, v, g, beta):
        o = seq_ops.chunk_gated_delta_rule(q, 0.5 * k, v, g, beta, chunk=64, keep=_keep_core)
        return jnp.tanh(o)

    kept = jax.checkpoint(layer, policy=jax.checkpoint_policies.save_only_these_names(CORE_OUTPUT))
    named = sorted(shape for shape, _ in _kept_of_the_forward(lambda *a: jnp.sum(kept(*a)), *args))
    assert named == sorted([(2, 1, 2, 128, 128), (1, 2, 512, 128)])
    kernels, xla = _both_forms(kept, args, weight, monkeypatch)
    _close_in_float32(kernels, xla)


# -- (b) gated attention and the partial rotary ----------------------------


@pytest.mark.parametrize("length,block", [(16, 4), (12, 8), (8, 64)])
def test_gated_attention_matches_the_reference(bench, config, length, block):
    from euler_tpu.layers.sequence import GatedAttention

    layer = GatedAttention(
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        rotary_dim=int(config["head_dim"] * config["partial_rotary_factor"]),
        block=block,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, length, config["hidden_size"]))
    params = layer.init(jax.random.PRNGKey(1), x)
    # norm weights off zero, so that (1 + w) is tested
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(2), p.shape), params
    )
    flat = bench["weights"].flatten(params["params"])

    def program(params, x):
        y, own = layer.apply(params, x)
        assert own is None  # called as every mixer is: no loss of its own
        return y

    def reference(params, x):
        flat = bench["weights"].flatten(params["params"])
        return bench["ref"].gated_attention(flat, x, config, 4)

    assert set(flat) == {"q_proj", "k_proj", "v_proj", "o_proj", "q_norm/w", "k_norm/w"}
    (_, got), g_got = _value_and_grads(program, jnp.sin, 0)(params, x)
    (_, want), g_want = _value_and_grads(reference, jnp.sin, 0)(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    for a, b in zip(*map(jax.tree_util.tree_leaves, (g_got, g_want))):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-6)


def test_rotary_turns_only_the_first_part_of_the_head(bench):
    from euler_tpu.layers.sequence import rotary

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 16))
    out = rotary(x, 1e7, 4)
    np.testing.assert_array_equal(out[..., 4:], x[..., 4:])
    np.testing.assert_allclose(out[:, 0], x[:, 0], atol=1e-6)  # position 0
    assert not np.allclose(out[:, 1:, :, :4], x[:, 1:, :, :4])
    np.testing.assert_allclose(out, bench["ref"].rotate(x, 1e7, 4), atol=1e-6)


# -- (c) the share test -----------------------------------------------------


def _moe_layers(config):
    from euler_tpu.layers.moe import SparseMoE

    experts, top_k = config["model"]["router_experts"], config["num_experts_per_tok"]
    common = dict(
        num_experts=experts, top_k=top_k,
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config.get("shared_expert_intermediate_size", 0),
    )
    return experts, top_k, lambda first, count: SparseMoE(held=(first, count), **common)


@pytest.mark.parametrize("shared_expert", [True, False])
@pytest.mark.parametrize("router_scale", [1.0, 40.0])
def test_expert_shares_add_up_to_the_whole_layer(bench, config, router_scale, shared_expert):
    """The parts all `num_experts / count` chips compute, the shared
    expert counted once, are the uncut layer: under an even router and
    under one far from even, whose shares see unequal loads; with a
    shared expert, and in a layer that has none (`shared_dim` 0: the
    other reference, whose configuration has no such key)."""
    ref = bench["ref"]
    if not shared_expert:
        ref, config = bench["ref_keye_vl2"], _rehearsal("keye-vl2-30b-a3b-ep8")
    experts, top_k, layer = _moe_layers(config)
    hidden, count = config["hidden_size"], config["model"]["experts_here"][1]
    x = jax.random.normal(jax.random.PRNGKey(0), (96, hidden))
    params = layer(0, experts).init(jax.random.PRNGKey(1), x)["params"]
    params["router"] = params["router"] * router_scale

    uncut = dict(config, model=dict(config["model"], experts_here=[0, experts]))
    want = _highest(ref.mixture)(params, x, uncut, "")
    assert shared_expert == ("shared_gate" in params)
    shared_only = jnp.zeros_like(x)
    if shared_expert:
        shared_only = _highest(ref.mixture)(params, x, uncut, "no_routed")

    total, rows = jnp.zeros_like(x), 0
    for first in range(0, experts, count):
        mine = dict(params)
        for name in ("experts_gate", "experts_up", "experts_down"):
            mine[name] = params[name][first : first + count]
        y, routed = _highest(layer(first, count).apply)({"params": mine}, x)
        # this chip's result against the reference given the same share
        cut = dict(config, model=dict(config["model"], experts_here=[first, count]))
        np.testing.assert_allclose(
            y, _highest(ref.mixture)(mine, x, cut, ""), rtol=1e-4, atol=1e-6
        )
        total, rows = total + (y - shared_only), rows + int(routed)
    assert rows == x.shape[0] * top_k  # every assignment landed on one chip
    np.testing.assert_allclose(total + shared_only, want, rtol=1e-4, atol=1e-6)
    assert float(jnp.max(jnp.abs(want - shared_only))) > 1e-4  # the experts matter


@pytest.mark.parametrize("held", [4, 8])
@pytest.mark.parametrize("prefers_held", [0.0, 8.0])
def test_expert_layer_drops_nothing_however_the_router_leans(bench, config, prefers_held, held):
    """Result and gradients of one chip's share against the reference,
    under an even router (less than one tile of rows lands here) and
    under one that sends every token's whole top-k here (all the tiles):
    the loop over tiles, forward and backward, leaves no row out. With 4
    of 16 experts held a tile is as many assignments as there are tokens;
    with 8 an even router would fill that, and a tile is twice as many
    (`tile_rows`)."""
    from euler_tpu.layers.moe import tile_rows

    experts, top_k, layer = _moe_layers(config)
    first, count = 0, held
    config = dict(config, model=dict(config["model"], experts_here=[first, count]))
    part = layer(first, count)
    x = jax.random.normal(jax.random.PRNGKey(2), (96, config["hidden_size"]))
    params = part.init(jax.random.PRNGKey(3), x)["params"]
    params["router"] = params["router"].at[:, first : first + count].add(prefers_held)
    x = jnp.abs(x)  # so that the lean has one sign for every token

    def program(params, x):
        y, routed = part.apply({"params": params}, x)
        return jnp.sum(jnp.sin(y)), routed

    def reference(params, x):
        return jnp.sum(jnp.sin(bench["ref"].mixture(params, x, config, ""))), None

    with jax.default_matmul_precision("highest"):
        (got, routed), g_got = jax.value_and_grad(program, (0, 1), has_aux=True)(params, x)
        (want, _), g_want = jax.value_and_grad(reference, (0, 1), has_aux=True)(params, x)
    step = tile_rows(x.shape[0], top_k, count, experts)
    assert step == x.shape[0] * (1 if held == 4 else 2)
    tiles = -(-int(routed) // step)
    assert tiles == (x.shape[0] * min(top_k, count) // step if prefers_held else 1), int(routed)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_got), jax.tree_util.tree_leaves(g_want)
    ):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-6, err_msg=str(path))


def test_tile_rows_leave_an_even_router_a_quarter_to_spare():
    """The two cells' expert layers: 10 picks over 32 of 512 experts fill
    five eighths of a tile of as many assignments as tokens, which stays;
    8 picks over 16 of 128 would fill it, and the tile is twice that. A
    layer that holds every expert takes its assignments as one tile."""
    from euler_tpu.layers.moe import tile_rows

    assert tile_rows(16384, 10, 32, 512) == 16384
    assert tile_rows(16384, 8, 16, 128) == 2 * 16384
    assert tile_rows(96, 6, 8, 8) == 6 * 96
    for tokens, top_k, count, experts in [(16384, 10, 32, 512), (16384, 8, 16, 128), (100, 6, 5, 8)]:
        step = tile_rows(tokens, top_k, count, experts)
        assert (tokens * top_k) % step == 0  # whole tiles cover the assignments
        assert step >= 1.25 * tokens * top_k * count / experts or step == tokens * top_k


def test_grouped_matmul_keeps_rows_past_the_groups_out():
    """Whatever lies in the rows that belong to no group (the TPU's kernel
    leaves them unwritten; here they are NaN on the way in) reaches no
    result and no gradient."""
    from euler_tpu.ops import seq_ops

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    rows = jax.random.normal(k1, (12, 8)).at[7:].set(jnp.nan)
    weights = jax.random.normal(k2, (3, 8, 5))
    sizes = jnp.array([4, 0, 3], jnp.int32)
    seen = jax.random.normal(k3, (12, 5))  # a cotangent that is not zero anywhere

    def total(rows, weights):
        return jnp.sum(seq_ops.grouped_matmul(rows, weights, sizes) * seen)

    out = seq_ops.grouped_matmul(rows, weights, sizes)
    want = jnp.concatenate([rows[:4] @ weights[0], rows[4:7] @ weights[2]])
    np.testing.assert_allclose(out[:7], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(out[7:], 0.0)
    d_rows, d_weights = jax.grad(total, (0, 1))(rows, weights)
    np.testing.assert_array_equal(d_rows[7:], 0.0)
    assert bool(jnp.all(jnp.isfinite(d_rows))) and bool(jnp.all(jnp.isfinite(d_weights)))
    np.testing.assert_allclose(d_weights[0], rows[:4].T @ seen[:4], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(d_weights[1], 0.0)


# -- (d) three Estimator.train steps against the reference's loop -----------


def _built(bench, config):
    graph = bench["graphs"].build(config["graph"])
    built = bench["family"].build(config, {}, graph)
    return graph, built


def _program_first_steps(bench, config, seed, later_steps=contextlib.nullcontext):
    """Three `Estimator.train` steps from seeded weights at float32
    `highest`, the second and third inside `later_steps()`: the numbers
    `benchmarks/run.py` compares (losses, the first gradient's norm per
    leaf from Adam's first moment, each leaf's change), and the
    reference's three steps as a function of a planted fault."""
    from euler_tpu.estimator import Estimator, EstimatorConfig

    weights = bench["weights"]
    graph, built = _built(bench, config)
    spec = bench["ref"].param_spec(config, graph)
    lr = config["optimizer"]["learning_rate"]
    cfg = EstimatorConfig(
        model_dir="/tmp/never_saved", learning_rate=lr, optimizer="adam",
        log_steps=10**9, seed=weights.key_seed(seed), steps_per_call=1,
    )
    est = Estimator(built["model"], built["flow"], cfg)
    est.params = weights.nest(weights.make_params(spec, seed))
    with jax.default_matmul_precision("highest"):
        losses = est.train(1, log=False, save=False)
        adam = next(s for s in est.opt_state if hasattr(s, "mu"))
        grad = {
            k: float(v) / 0.1
            for k, v in weights.leaf_norms(weights.flatten(adam.mu)).items()
        }
        with later_steps():
            losses += est.train(2, log=False, save=False)
    change = weights.change_norms(
        weights.flatten(est.params), weights.make_params(spec, seed)
    )
    got = {
        "loss": losses, "grad_norm": grad,
        "change_norm": {k: float(v) for k, v in change.items()},
    }
    tables, loss_fn = bench["ref"].make(config, {}, graph)

    def reference(fault=""):
        return bench["train"].first_steps(loss_fn, tables, spec, seed, lr, fault=fault)

    return got, reference


@pytest.mark.parametrize("seed", [3000000019])
def test_three_train_steps_match_the_reference(bench, config, seed, tmp_path):
    from euler_tpu.utils import trace

    # a profiler session, whoever started it: each step dispatched
    # under it keeps the model's metric, on the device, in its span
    got, reference = _program_first_steps(
        bench, config, seed, lambda: jax.profiler.trace(str(tmp_path))
    )
    mine = [s for s in trace.spans() if s.name == "train.dispatch"][-3:]
    assert "metric" not in mine[0].args  # step 0 ran under no session
    kept = [s for s in mine if "metric" in s.args]
    assert [(s.name, s.step) for s in kept] == [("train.dispatch", 1), ("train.dispatch", 2)]
    held = config["model"]["experts_here"][1] / config["model"]["router_experts"]
    for s in kept:
        assert 0.5 * held < float(s.args["metric"]) < 2.0 * held
    want = reference()
    assert set(got["grad_norm"]) == set(want["grad_norm"])  # one tree, leaf for leaf
    compared = bench["train"].compare(got, want)
    assert all(v < 1e-4 for v in compared.values()), compared
    # the experts' sum left out is another model: the comparison sees it
    broken = reference("no_routed")
    assert max(bench["train"].compare(broken, want).values()) > 1e-2


# -- (e) what a rematerialised layer keeps ------------------------------------


def _loss_and_grads(bench, config, seed=11):
    """The rehearsal model's loss and gradients on one drawn batch, as
    one compiled program."""
    graph, built = _built(bench, config)
    weights = bench["weights"]
    params = weights.nest(weights.make_params(bench["ref"].param_spec(config, graph), seed))
    ids = jax.jit(built["flow"].sample)(bench["train"].step_key(seed, 0))
    return jax.jit(jax.value_and_grad(lambda p: built["model"].apply(p, ids)[1]))(params)


def _assert_keeping_the_core_changes_no_bit(
    bench, config, monkeypatch, no_gradient=(), rounding=0.0, step=None
):
    """Loss and every gradient leaf under `_KEEP_CORE` against the same
    model with each layer rematerialised whole; leaves named in
    `no_gradient` are those the model gives none. With `rounding` the
    leaves may differ by that share of the leaf's largest entry (the loss
    by no bit still). `step` makes (loss, gradients) where it is not the
    rehearsal model's own."""
    from euler_tpu.models import sequence_lm

    step = step or (lambda: _loss_and_grads(bench, config))
    loss, grads = step()
    monkeypatch.setattr(sequence_lm, "_KEEP_CORE", None)
    loss_whole, grads_whole = step()
    assert float(loss) == float(loss_whole) and np.isfinite(float(loss))
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(grads_whole)
    ):
        np.testing.assert_allclose(
            a, b, rtol=0, atol=rounding * float(jnp.max(jnp.abs(b))), err_msg=str(path)
        )
        taken = not any(name in str(path) for name in no_gradient)
        assert (float(jnp.max(jnp.abs(a))) > 0) == taken, path


def _kept_of_the_forward(loss, *args):
    """[(shape, where from)] of what the backward pass of `loss(*args)`
    keeps of its forward that is no argument, by
    `jax.ad_checkpoint.print_saved_residuals`."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        jax.ad_checkpoint.print_saved_residuals(loss, *args)
    kept = []
    for line in out.getvalue().splitlines():
        shape, origin = re.match(r"\w+\[([\d,]*)\] (.*)", line).groups()
        if not origin.startswith("from the argument"):
            kept.append((tuple(int(n) for n in shape.split(",") if n), origin))
    return kept


def _one_layer_both_ways(model, monkeypatch, length=64):
    """A one-layer model's loss-and-gradient step on `length` tokens
    under `_KEEP_CORE` and with the layer rematerialised whole: for each
    `(what the backward keeps of the forward, the lowered program)`, and
    the shape [B, G, R, T, d] of a softmax mixer's core output."""
    from euler_tpu.models import sequence_lm

    ids = jax.random.randint(jax.random.PRNGKey(1), (2, length + 1), 0, model.vocab_size)
    params = model.init(jax.random.PRNGKey(0), ids)

    def look():  # a jit of its own each time: the policy is no argument of the step
        step = jax.jit(jax.value_and_grad(lambda p: model.apply(p, ids)[1]))
        return _kept_of_the_forward(lambda p: model.apply(p, ids)[1], params), step.lower(params)

    kept = look()
    monkeypatch.setattr(sequence_lm, "_KEEP_CORE", None)
    core = (2, model.num_kv_heads, model.num_heads // model.num_kv_heads, length, model.head_dim)
    return kept, look(), core


def _named(kept):
    return [shape for shape, origin in kept if "_keep_core" in origin]


def test_keeping_the_attention_core_changes_no_bit(bench, config, monkeypatch):
    """The rehearsal model with a `GatedAttention` in every layer."""
    model = _built(bench, config)[1]["model"].clone(full_attention_interval=1)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, model.vocab_size)
    params = model.init(jax.random.PRNGKey(0), ids)

    def step():  # a jit of its own each time: the policy is no argument of the step
        return jax.jit(jax.value_and_grad(lambda p: model.apply(p, ids)[1]))(params)

    _assert_keeping_the_core_changes_no_bit(bench, config, monkeypatch, step=step)


def test_keeping_the_four_cores_changes_the_gradients_by_rounding_alone(bench, config, monkeypatch):
    """The period as it is, three `GatedDeltaNet` layers and a
    `GatedAttention`: the loss to the bit, the gradients to float32
    rounding. The rule itself is equal to the bit kept or not
    (`test_the_rules_own_backward_under_a_rematerialised_caller`); what
    moves the last bits is the CPU compiler fusing the layer's own norms
    and gates otherwise around a value that is kept (a layer whose rule
    is replaced by one product of its inputs moves as much)."""
    _assert_keeping_the_core_changes_no_bit(bench, config, monkeypatch, rounding=1e-5)


def test_an_attention_layer_keeps_its_blocks_output_and_no_scores(bench, config, monkeypatch):
    """One `GatedAttention` layer: the backward keeps the blocks' output
    [B, G, R, T, d] and nothing else of five axes (a block's scores are
    [B, G, R, block, keys]), and the layer's second forward runs no
    block."""
    model = _built(bench, config)[1]["model"].clone(num_layers=1, full_attention_interval=1)
    (kept, program), (whole, whole_program), core = _one_layer_both_ways(model, monkeypatch)
    assert _named(kept) == [core] and _named(whole) == [] and len(kept) == len(whole) + 1
    assert not [shape for shape, _ in kept if len(shape) > 3 and shape != core]
    assert whole_program.compile().as_text().count(" dot(") > program.compile().as_text().count(" dot(")


def test_a_deltanet_layer_keeps_its_output_and_its_groups_states(bench, config, monkeypatch):
    """One `GatedDeltaNet` layer: the backward keeps the rule's output
    [groups, group, B, H, C, dv] and the state at each group's start
    [groups, B, H, dk, dv], nothing chunk-local ([.., C, C]), and the
    layer's second forward runs no scan over the chunks."""
    from euler_tpu.ops import seq_ops

    # a chunk of 8 under heads of 16: a [C, C] matrix is told by its shape
    model = _built(bench, config)[1]["model"].clone(num_layers=1, chunk=8)
    batch, heads, dk, dv = 2, model.linear_num_value_heads, model.linear_key_head_dim, model.linear_value_head_dim
    chunk = model.chunk
    group = inspect.signature(seq_ops.chunk_gated_delta_rule).parameters["group"].default
    length = 2 * group * chunk  # two groups: with one the compiler merges the runs itself
    (kept, program), (whole, whole_program), _ = _one_layer_both_ways(model, monkeypatch, length)
    out, starts = (2, group, batch, heads, chunk, dv), (2, batch, heads, dk, dv)
    # a value kept by the rule's own backward is reported at the rule's call
    of_the_rule = lambda kept: sorted(  # noqa: E731
        shape for shape, origin in kept if "chunk_gated_delta_rule" in origin
    )
    assert of_the_rule(kept) == sorted([out, starts]) and of_the_rule(whole) == []
    assert len(kept) == len(whole) + 2
    assert not [shape for shape, _ in kept if shape[-2:] == (chunk, chunk)]
    assert whole_program.compile().as_text().count(" while(") > program.compile().as_text().count(" while(")


def test_first_call_span_counts_the_four_cores_kept(bench, config):
    """Each of the period's four mixers keeps its core: the
    `GatedAttention` its blocks' output, the three `GatedDeltaNet`
    layers the rule's output and its groups' start states."""
    from euler_tpu.estimator import Estimator, EstimatorConfig
    from euler_tpu.utils import trace

    _, built = _built(bench, config)
    assert built["model"].num_layers == built["model"].full_attention_interval == 4
    cfg = EstimatorConfig(model_dir="/tmp/never_saved", log_steps=10**9, steps_per_call=1)
    since = time.perf_counter_ns()  # not a count of spans: the record is bounded
    Estimator(built["model"], built["flow"], cfg).train(1, log=False, save=False)
    (args,) = [
        s.args for s in trace.spans() if s.name == "step.first_call" and s.start_ns >= since
    ]
    assert args["program"] == "train_step" and args.get("mixer_core_kept", 0) == 4
    assert (args.get("attn_core_dense", 0), args.get("attn_core_kernel", 0)) == (1, 0)  # a head of 16
    assert (args.get("gdn_rule_xla", 0), args.get("gdn_rule_kernel", 0)) == (3, 0)  # dk 16: XLA's groups
    assert (args.get("dsa_layers", 0), args.get("draw_elements", 0)) == (0, 1)


# -- (f) the device flow ------------------------------------------------------


def test_device_sequence_flow_draws_the_reference_walks(bench, config):
    m = config["model"]
    graph, built = _built(bench, config)
    flow = built["flow"]
    tables, _ = bench["ref"].make(config, {}, graph)
    sample = jax.jit(flow.sample)
    drawn = []
    for step in (0, 1):
        key = bench["train"].step_key(7, step)
        ids = np.asarray(sample(key))
        want = bench["ref"].sequences(
            tables, key, graph["num_nodes"], m["batch_size"], m["seq_len"], m["doc_len"]
        )
        assert ids.shape == (m["batch_size"], m["seq_len"] + 1) and ids.dtype == np.int32
        np.testing.assert_array_equal(ids, np.asarray(want))
        assert ids.min() >= 0 and ids.max() < config["vocab_size"]
        drawn.append(ids)
    assert not np.array_equal(drawn[0], drawn[1])
    # a document is a walk: each token is an out-neighbour of the one before
    ids, doc = drawn[0], m["doc_len"]
    for t in range(1, doc):
        nbrs = graph["dst"][graph["indptr"][ids[0, t - 1]] : graph["indptr"][ids[0, t - 1] + 1]]
        assert ids[0, t] in nbrs


def test_sequence_length_must_hold_whole_documents(bench, config):
    from euler_tpu.dataflow import DeviceSequenceFlow

    graph = bench["graphs"].build(config["graph"])
    with pytest.raises(ValueError, match="whole number of documents"):
        DeviceSequenceFlow(
            bench["program_graph"](graph, {}), batch_size=2, seq_len=60, doc_len=16
        )
