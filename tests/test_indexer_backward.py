"""The lightning indexer's hand-written backward
(`ops/seq_ops.py:indexer_scores`, a `jax.custom_vjp`) on the CPU at small
sizes: dq, dk and dw against `jax.grad` of the dense three-line
definition kept here, the forward's bits against the same definition,
what the backward's jaxpr never holds (the [B, J, R, S] products, or
anything of those three extents at once), and which rehearsal layers
count `dsa_index_vjp`."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_indexed_sparse_attention import _float32_shapes
from test_sequence_lm import BENCH, _built, _load, _rehearsal


def _definition(q, k, w, first):
    """`I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])`, -inf past row
    `first + r`: what `indexer_scores` was before it had a backward of
    its own, and what `jax.grad` transposes as it stands."""
    dots = jnp.einsum("brjd,bsd->bjrs", q, k).astype(jnp.float32)
    weight = jnp.moveaxis(w.astype(jnp.float32), 2, 1)[..., None]
    scores = jnp.sum(weight * jax.nn.relu(dots), axis=1)
    rows = first + jnp.arange(q.shape[1])[:, None]
    return jnp.where(jnp.arange(k.shape[1])[None, :] <= rows, scores, -jnp.inf)


# batch, rows, index heads, index dim, keys, first
SHAPES = {
    "a_block_of_8": (2, 8, 2, 8, 24, 16),
    "a_last_block_that_is_not_whole": (2, 5, 2, 8, 21, 16),
    "the_first_block": (1, 8, 2, 4, 8, 0),  # first = 0: half of the square is -inf
    "a_stretch_past_the_block": (2, 8, 2, 8, 40, 8),  # first > 0 and keys past its last row
    "sixteen_heads": (1, 8, 16, 8, 24, 16),
}


def _inputs(shape, picked):
    batch, rows, heads, dim, keys, first = shape
    ks = jax.random.split(jax.random.PRNGKey(sum(shape)), 5)
    q = jax.random.normal(ks[0], (batch, rows, heads, dim))
    k = jax.random.normal(ks[1], (batch, keys, dim))
    w = jax.random.normal(ks[2], (batch, rows, heads))
    d_scores = jax.random.normal(ks[3], (batch, rows, keys))
    if picked:  # zero off a random pick, as `index_kl` leaves it
        d_scores = d_scores * (jax.random.uniform(ks[4], d_scores.shape) < 0.25)
    return q, k, w, first, d_scores


def _cotangents(scores_of, q, k, w, first, d_scores):
    """dq, dk, dw of sum(I dI) over the entries of I that are not -inf;
    `first` traced, as the layer's `lax.map` hands it over."""

    def scalar(q, k, w, first):
        scores = scores_of(q, k, w, first)
        return jnp.sum(jnp.where(scores > -jnp.inf, scores * d_scores, 0.0))

    return jax.jit(jax.grad(scalar, argnums=(0, 1, 2)))(q, k, w, jnp.int32(first))


@pytest.mark.parametrize("picked", [True, False], ids=["picked", "dense"])
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_the_backward_is_jax_grads_of_the_definition(shape, picked):
    from euler_tpu.ops import seq_ops

    q, k, w, first, d_scores = _inputs(shape, picked)
    got = _cotangents(seq_ops.indexer_scores, q, k, w, first, d_scores)
    want = _cotangents(_definition, q, k, w, first, d_scores)
    for name, a, b in zip(("dq", "dk", "dw"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert float(jnp.max(jnp.abs(b))) > 0, name
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5 * float(jnp.max(jnp.abs(b))), err_msg=name)


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_the_forward_is_the_definitions_bit_for_bit(shape):
    """The pick reads the forward's scores: alone, inside `jax.vjp` (the
    rule's forward), and with `first` traced."""
    from euler_tpu.ops import seq_ops

    q, k, w, first, _ = _inputs(shape, False)
    want = np.asarray(jax.jit(_definition)(q, k, w, jnp.int32(first)))
    seen = np.arange(shape[4])[None, :] <= first + np.arange(shape[1])[:, None]
    assert np.all(want[:, ~seen] == -np.inf) and np.all(np.isfinite(want[:, seen]))
    np.testing.assert_array_equal(jax.jit(seq_ops.indexer_scores)(q, k, w, jnp.int32(first)), want)
    np.testing.assert_array_equal(jax.jit(seq_ops.indexer_scores, static_argnums=3)(q, k, w, first), want)
    under_vjp = jax.jit(lambda q, k, w: jax.vjp(lambda *a: seq_ops.indexer_scores(*a, first), q, k, w)[0])
    np.testing.assert_array_equal(under_vjp(q, k, w), want)


@pytest.mark.parametrize("heads", [2, 16])
def test_the_backward_holds_no_products_of_all_the_heads(heads):
    """Extents told apart (rows 8, keys 24, heads 2 or 16, dim 4): the
    backward's jaxpr makes no float32 value that has heads, rows and keys
    at once, in any order; its largest is a head's [B, rows, keys]. What
    `jax.grad` makes of the definition holds [B, J, rows, keys]."""
    from euler_tpu.ops import seq_ops

    q, k, w, first, d_scores = _inputs((1, 8, heads, 4, 24, 16), True)

    def made_backward(scores_of):
        pull = jax.vjp(lambda q, k, w: scores_of(q, k, w, first), q, k, w)[1]
        return _float32_shapes(jax.make_jaxpr(pull)(d_scores).jaxpr, set())

    def of_all_heads(shapes):
        return sorted(s for s in shapes if {heads, 8, 24} <= set(s))

    mine = made_backward(seq_ops.indexer_scores)
    assert of_all_heads(mine) == [] and (1, 8, 24) in mine
    assert max(int(np.prod(s)) for s in mine) == max(8 * 24, 8 * heads * 4)
    assert (1, heads, 8, 24) in of_all_heads(made_backward(_definition))


@pytest.mark.parametrize(
    "name,family,counted",
    [("keye-vl2-30b-a3b-ep8", "keye_vl2", 1), ("trinity-mini-ep8", "afmoe", 0)],
)
def test_a_rehearsal_layer_with_an_indexer_counts_its_backward(name, family, counted):
    """`dsa_index_vjp` is tallied where an `IndexedSparseAttention` is
    built, once a layer; a layer without an indexer tallies nothing."""
    from euler_tpu.utils import trace

    config = _rehearsal(name)
    sys.path.insert(0, BENCH)
    try:
        import graphs

        bench = {"graphs": graphs, "family": _load(os.path.join(BENCH, "families", f"{family}.py"), f"fam_{family}")}
        layer = _built(bench, config)[1]["model"].mixer(0)
    finally:
        sys.path.remove(BENCH)
    x = jnp.zeros((1, 32, config["hidden_size"]))
    args = (x, jnp.zeros((3, 1, 32), jnp.int32)) if counted else (x,)
    before = trace.counts().get("dsa_index_vjp", 0)
    jax.eval_shape(lambda: layer.init_with_output(jax.random.PRNGKey(0), *args)[0])
    assert trace.counts().get("dsa_index_vjp", 0) - before == counted
