"""Granite-4.0-H's decoder on the CPU at a small size, against the
benchmark's plain reference (`benchmarks/reference/granite_hybrid.py`,
loaded by path): the chunked state-space scan against the per-token
recurrence that defines it, forward and gradients, at decays as strong
as A = -64, across chunk boundaries and at a length that is no whole
chunk; the scan's Pallas kernels, interpreted here, against its XLA
chunked form; the Mamba-2 mixer and the position-free attention at a
scale of 1/64 against the reference; three `Estimator.train` steps
against the reference's loop, and each of its faults another model; the
tallies in the step's set-up span; a model with no expert layer reports
its loss, not 0/0; and that what became the model's choice lowers, at
its default, to the program it was."""

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

FAULTS = [
    "half_batch", "no_D_skip", "ungated_norm", "no_residual_multiplier", "conv_no_bias",
    "attn_sqrt_scale",
]


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        import graphs
        import weights

        yield {
            "ref": _load(os.path.join(BENCH, "reference", "granite_hybrid.py"), "ref_granite"),
            "train": _load(os.path.join(BENCH, "reference", "train.py"), "ref_train"),
            "family": _load(os.path.join(BENCH, "families", "granite_hybrid.py"), "fam_granite"),
            "graphs": graphs,
            "weights": weights,
        }
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def config():
    """The rehearsal's widths on a stage of two published layers, 4 and
    5 (mamba, attention): both kinds, a fifth of the compile."""
    cut = _rehearsal("granite-4.0-h-micro-vp8")
    cut["num_hidden_layers"] = 2
    cut["model"] = dict(
        cut["model"], first_published_layer=4, layer_types_here=cut["layer_types"][4:6]
    )
    return cut


# -- (a) the chunked scan against its definition ---------------------------------


def _scan_inputs(length, seed=0, batch=2, heads=4, head=16, state=8, strongest=64.0):
    """u = dt x, a = dt A with A from -1 to `strongest` by head and dt
    up to ~1: in-chunk sums of a reach the thousands."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (batch, heads, length, head))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, heads, length)) - 1.0)
    big_a = -jnp.linspace(1.0, strongest, heads)
    b = 0.5 * jax.random.normal(ks[2], (batch, length, state))
    c = 0.5 * jax.random.normal(ks[3], (batch, length, state))
    return x, dt, big_a, b, c


def _by_recurrence(ref, x, dt, big_a, b, c):
    """The reference's per-token scan from a zero state, in the program's
    layout."""
    state = jnp.zeros(x.shape[:2] + (x.shape[-1], b.shape[-1]))
    _, y = ref.recurrence(
        state, jnp.moveaxis(x, 1, 2), jnp.moveaxis(dt, 1, 2), big_a, b, c
    )
    return jnp.moveaxis(y, 2, 1)


@pytest.mark.parametrize("length,chunk", [(64, 16), (40, 16), (7, 16), (48, 48)])
def test_the_chunked_scan_is_the_recurrence(bench, length, chunk):
    """Value and the gradients of u, a, B and C at float32 `highest`:
    the chunked form reorders sums of up to `chunk` terms whose
    exponents are differences of running sums in the thousands, so the
    gaps are rounding of those (under 1e-4 of the largest value); no
    NaN, no inf, where a product of decays taken as exp(G_i) exp(-G_j)
    would overflow at the first chunk."""
    from euler_tpu.ops import seq_ops

    x, dt, big_a, b, c = _scan_inputs(length)
    assert not seq_ops.ssd_by_kernels(x, b, chunk)  # the XLA form, here and on the chip

    def program(x, dt, b, c):
        return seq_ops.chunked_ssd(x * dt[..., None], dt * big_a[:, None], b, c, chunk)

    def reference(x, dt, b, c):
        return _by_recurrence(bench["ref"], x, dt, big_a, b, c)

    weigh = jnp.sin
    (_, got), g_got = _value_and_grads(program, weigh, (0, 1, 2, 3))(x, dt, b, c)
    (_, want), g_want = _value_and_grads(reference, weigh, (0, 1, 2, 3))(x, dt, b, c)
    assert bool(jnp.all(jnp.isfinite(got)))
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=1e-5 * scale, rtol=0)
    for a, w in zip(g_got, g_want):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, w, atol=1e-4 * float(jnp.max(jnp.abs(w))), rtol=0)


def test_a_step_moves_nothing_before_it(bench):
    """Causal: the input at step 20 moves the outputs from 20 on and no
    earlier one, across the chunk boundary at 16."""
    from euler_tpu.ops import seq_ops

    x, dt, big_a, b, c = _scan_inputs(40, strongest=4.0)
    run = lambda x: seq_ops.chunked_ssd(x * dt[..., None], dt * big_a[:, None], b, c, 16)  # noqa: E731
    moved = jnp.max(jnp.abs(run(x.at[:, :, 20].add(1.0)) - run(x)), axis=(0, 1, 3))
    assert np.flatnonzero(np.asarray(moved) > 0).tolist() == list(range(20, 40))


# -- (b) the kernels, interpreted, against the XLA chunked form ----------------


@pytest.mark.parametrize("length", [256, 200])
def test_the_scan_kernels_are_the_xla_chunks(bench, monkeypatch, length):
    """At the cell's head of 64 and state of 128, chunks of 128, two
    heads: the Pallas kernels (the interpreter here, float32 products)
    against `_ssd_chunk` in XLA, value and the four gradients. The gap
    is the order of float32 sums (masked sums in the kernel, cumsum and
    einsum in XLA) of running sums that reach the thousands, whose
    rounding (1.2e-4 at 2,000) is a relative error of a decay: under
    1e-5 of the largest value, 1e-4 in the gradients."""
    from euler_tpu.ops import seq_ops

    x, dt, big_a, b, c = _scan_inputs(length, batch=1, heads=2, head=64, state=128)
    args = (x * dt[..., None], dt * big_a[:, None], b, c)
    assert seq_ops.ssd_by_kernels(args[0], b, 128)

    def both(*args):
        return seq_ops.chunked_ssd(*args, chunk=128)

    got, g_got = _value_and_grads(both, jnp.sin, (0, 1, 2, 3))(*args)
    monkeypatch.setattr(seq_ops, "ssd_by_kernels", lambda *a: False)
    want, g_want = _value_and_grads(both, jnp.sin, (0, 1, 2, 3))(*args)
    scale = float(jnp.max(jnp.abs(want[1])))
    np.testing.assert_allclose(got[1], want[1], atol=1e-5 * scale, rtol=0)
    for a, w in zip(g_got, g_want):
        np.testing.assert_allclose(a, w, atol=1e-4 * float(jnp.max(jnp.abs(w))), rtol=0)


# -- (c) the mixers against the reference ----------------------------------------


def test_the_mamba_mixer_is_the_reference(bench, config):
    """The whole mixer — projection, conv with its bias, softplus, the
    scan, the skip, the gated norm — and every parameter's gradient,
    with the parameters moved off their zeros so that A_log, dt_bias, D
    and the norm all matter."""
    from euler_tpu.layers.sequence import Mamba2Mixer

    layer = Mamba2Mixer(
        num_heads=config["mamba_n_heads"], head_dim=config["mamba_d_head"],
        state_size=config["mamba_d_state"], conv_kernel=config["mamba_d_conv"],
        chunk=config["mamba_chunk_size"], eps=config["rms_norm_eps"],
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, config["hidden_size"]))
    params = layer.init(jax.random.PRNGKey(1), x)
    params = jax.tree_util.tree_map(
        lambda p: p + 0.3 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape), params
    )

    def program(params, x):
        y, own = layer.apply(params, x)
        assert own is None
        return y

    def reference(params, x):
        return bench["ref"].mamba(params["params"], x, config, 8)

    (_, got), g_got = _value_and_grads(program, jnp.sin, (0, 1))(params, x)
    (_, want), g_want = _value_and_grads(reference, jnp.sin, (0, 1))(params, x)
    # float32 sums of values of the largest's size: gaps of its rounding
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(want))))
    for a, w in zip(*map(jax.tree_util.tree_leaves, (g_got, g_want))):
        np.testing.assert_allclose(a, w, rtol=1e-3, atol=1e-5 * float(jnp.max(jnp.abs(w))))


@pytest.mark.parametrize("length,block", [(64, 16), (40, 16)])
def test_position_free_attention_at_a_scale_of_one_64th(bench, config, length, block):
    """No rotary, no head norms, no gate, the scores times 1/64 at a head
    of 64: the program's blocks against the reference's full rows."""
    from euler_tpu.layers.sequence import GatedAttention

    cut = dict(config, model=dict(config["model"], head_dim=64))
    layer = GatedAttention(
        num_heads=4, num_kv_heads=2, head_dim=64, rotary_dim=0, block=block,
        gated=False, head_norms=False, scale=cut["attention_multiplier"],
    )
    cut.update(num_attention_heads=4, num_key_value_heads=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, length, config["hidden_size"]))
    params = layer.init(jax.random.PRNGKey(1), x)
    params = jax.tree_util.tree_map(lambda p: p * 20.0, params)  # scores of some size
    got = _highest(jax.jit(lambda p, x: layer.apply(p, x)[0]))(params, x)
    want = _highest(jax.jit(
        lambda p, x: bench["ref"].attention(p["params"], x, cut, 8)
    ))(params, x)
    # float32 sums of values of the largest's size: gaps of its rounding
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(want))))
    plain = _highest(jax.jit(lambda p, x: layer.clone(scale=None).apply(p, x)[0]))(params, x)
    assert float(jnp.max(jnp.abs(plain - want))) > 1e-3  # 1/8 is another layer


# -- (d) three Estimator.train steps against the reference's loop -----------------


@pytest.fixture(scope="module")
def three_steps(bench, config):
    from euler_tpu.utils import trace

    since = time.perf_counter_ns()
    got, reference = _program_first_steps(bench, config, 3000000050)
    spans = [s for s in trace.spans() if s.start_ns >= since]
    return {"got": got, "spans": spans, "reference": reference}


def test_three_train_steps_match_the_reference(bench, three_steps):
    """Loss, every leaf's gradient norm, every leaf's change after three
    Adam steps; the two trees are one, leaf for leaf, with no head and
    no router."""
    got, want = three_steps["got"], three_steps["reference"]()
    assert set(got["grad_norm"]) == set(want["grad_norm"])
    assert not [k for k in got["grad_norm"] if "head" in k or "moe" in k]
    assert sum(k.endswith("mixer/conv_bias") for k in got["grad_norm"]) == 1
    compared = bench["train"].compare(got, want)
    assert all(v < 1e-4 for v in compared.values()), compared


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_of_the_reference_is_another_model(bench, three_steps, fault):
    assert bench["ref"].FAULTS == ("",) + tuple(FAULTS)
    want, broken = three_steps["reference"](), three_steps["reference"](fault)
    assert max(bench["train"].compare(broken, want).values()) > 1e-2, fault


def test_first_call_span_carries_the_layers_forms(config, three_steps):
    args = next(
        s.args for s in three_steps["spans"]
        if s.name == "step.first_call" and s.args["program"] == "train_step"
    )
    n = config["mamba_d_state"]
    assert (args.get("ssd_layers", 0), args.get("ssd_state", 0)) == (1, n)
    # 64 tokens, a state of 8: no whole tile
    assert (args.get("ssd_scan_xla", 0), args.get("ssd_scan_kernel", 0)) == (1, 0)
    assert (args.get("attn_full_layers", 0), args.get("attn_ungated", 0), args.get("head_tied", 0)) == (1, 1, 1)
    assert (args.get("dense_layers", 0), args.get("mixer_core_kept", 0)) == (2, 1)
    assert args.get("mlp_grads_by_hand", 0) == 2
    assert (args.get("sconv_layers", 0), args.get("router_sigmoid", 0), args.get("swa_layers", 0)) == (0, 0, 0)


def test_the_model_is_its_reference_on_one_batch(bench, config):
    """One drawn batch: the model's loss is the reference's, and a model
    with no expert layer hands back its loss as its metric, where the
    routed share would be 0 / 0."""
    graph, built = _built(bench, config)
    weights = bench["weights"]
    flat = weights.make_params(bench["ref"].param_spec(config, graph), 5)
    ids = jax.jit(built["flow"].sample)(bench["train"].step_key(5, 0))
    model = built["model"]
    assert (model.embed_scale, model.residual_multiplier, model.logits_divisor) == (12.0, 0.22, 8.0)
    assert model.tie_embeddings and model.num_dense_layers >= model.num_layers
    out = _highest(jax.jit(lambda p: model.apply(p, ids)[1::2]))(weights.nest(flat))
    assert model.apply(weights.nest(flat), ids)[2] == "loss"
    assert float(out[0]) == float(out[1])
    blocks = config["reference_blocks"]
    want = _highest(jax.jit(lambda p: bench["ref"].forward_loss(p, ids, config, blocks, "")))(flat)
    np.testing.assert_allclose(out[0], want, rtol=1e-5)


def test_an_unknown_layer_kind_raises():
    from euler_tpu.models.sequence_lm import GraniteHybridLM

    model = GraniteHybridLM(vocab_size=128, hidden_size=64, num_layers=1, layer_types=("conv",))
    with pytest.raises(ValueError, match="no known kind"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 9), jnp.int32))


# -- (e) what became the model's choice lowers, at its default, as it did ---------


def test_the_defaults_lower_to_the_program_they_were():
    """A convolution handed no bias, and an attention at scale None, are
    the programs they were before the words existed: the same text as
    the bias None spelt out and the scale d^-0.5 spelt out."""
    from euler_tpu.layers.sequence import GatedAttention
    from euler_tpu.ops import seq_ops

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 4))
    text = lambda f: jax.jit(f).lower(x, w).as_text()  # noqa: E731
    assert text(lambda x, w: seq_ops.causal_conv1d(x, w)) == text(
        lambda x, w: seq_ops.causal_conv1d(x, w, None)
    )
    layer = GatedAttention(num_heads=2, num_kv_heads=1, head_dim=8, rotary_dim=8, block=16)
    params = layer.init(jax.random.PRNGKey(2), x)

    def lowered(layer):
        step = jax.value_and_grad(lambda p, x: jnp.sum(layer.apply(p, x)[0]), (0, 1))
        return jax.jit(step).lower(params, x).as_text()

    assert lowered(layer) == lowered(layer.clone(scale=8**-0.5))
    assert lowered(layer) != lowered(layer.clone(scale=1 / 64))
