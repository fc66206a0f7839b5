"""`seq_ops.blockwise_causal_attention(window=W)` on the CPU against a
dense masked softmax, values and gradients: windows that are whole
blocks and not, a window that holds the whole sequence (the program
without a window, bit for bit), a last block that is not whole; keys
outside every window of the queries that got a cotangent get none; the
window layers' `euler.swa.*` scopes are named and none nests."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu.ops import seq_ops


def _inputs(length, batch=2, groups=2, per_group=3, d=8, seed=0):
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (batch, groups, per_group, length, d))
    k = jax.random.normal(kk, (batch, groups, length, d))
    v = jax.random.normal(kv, (batch, groups, length, d))
    return q, k, v, jax.random.normal(kw, q.shape)


def _dense(q, k, v, scale, window):
    """Every query against every key under the mask `t - window < s <= t`."""
    length = q.shape[3]
    t, s = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
    seen = (s <= t) & (s > t - window)
    scores = jnp.einsum("bgrtd,bgsd->bgrts", q, k) * scale
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bgrts,bgsd->bgrtd", probs, v)


def _value_and_grads(fn, weight):
    def total(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out * weight), out

    def run(*args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(total, (0, 1, 2), has_aux=True))(*args)

    return run


# (T, block, W): W whole blocks; W no multiple of the block; W under a
# block; a last block that is not whole, behind a run and with no run; one
# block in all
CASES = [
    (64, 16, 32), (64, 16, 16), (64, 16, 24), (64, 16, 7), (64, 16, 1),
    (72, 16, 32), (72, 16, 24), (40, 16, 30), (70, 32, 33), (24, 64, 5),
    (256, 128, 128), (320, 128, 100),
]


@pytest.mark.parametrize("length,block,window", CASES)
def test_windowed_attention_matches_the_dense_mask(length, block, window):
    q, k, v, weight = _inputs(length)
    scale = q.shape[-1] ** -0.5
    (_, got), g_got = _value_and_grads(
        lambda q, k, v: seq_ops.blockwise_causal_attention(q, k, v, scale, block, window),
        weight,
    )(q, k, v)
    (_, want), g_want = _value_and_grads(
        lambda q, k, v: _dense(q, k, v, scale, window), weight
    )(q, k, v)
    assert got.shape == q.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for name, a, b in zip("qkv", g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("length,block,window", [(64, 16, 64), (64, 16, 1000), (40, 16, 40)])
def test_a_window_that_holds_the_sequence_is_no_window(length, block, window):
    """The same program, to the letter, and so the same bits."""
    q, k, v, weight = _inputs(length)

    def step(window):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(
                seq_ops.blockwise_causal_attention(q, k, v, 0.3, block, window) * weight
            ),
            (0, 1, 2),
        ))

    assert step(window).lower(q, k, v).as_text() == step(None).lower(q, k, v).as_text()
    (got, g_got), (want, g_want) = step(window)(q, k, v), step(None)(q, k, v)
    assert float(got) == float(want)
    for a, b in zip(g_got, g_want):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("length,block,window", [(128, 16, 32), (136, 16, 24)])
def test_keys_outside_every_window_get_a_zero_cotangent(length, block, window):
    """Only the rows from `since` on are weighed: no key more than
    `window - 1` before them is differentiated at all, not by a small
    number but by none."""
    q, k, v, _ = _inputs(length)
    since = length - 20

    def total(q, k, v):
        out = seq_ops.blockwise_causal_attention(q, k, v, 0.3, block, window)
        return jnp.sum(jnp.sin(out[:, :, :, since:]))

    dq, dk, dv = jax.jit(jax.grad(total, (0, 1, 2)))(q, k, v)
    first_seen = since - window + 1
    for grad in (dk, dv):
        np.testing.assert_array_equal(grad[:, :, :first_seen], 0.0)
        assert float(jnp.min(jnp.max(jnp.abs(grad[:, :, first_seen:]), axis=-1))) > 0
    np.testing.assert_array_equal(dq[:, :, :, :since], 0.0)


def test_the_blocks_past_the_window_are_one_program():
    """At 16 blocks under a window of 4 the first 4 stretches grow from
    key 0 and the other 12 blocks are one `while` loop over one
    stretch, where 16 blocks without a window are 16 programs: the
    lowered module holds one block's dot pair a program."""
    q, k, v, _ = _inputs(256, batch=1, groups=1, per_group=1)

    def lower(window):
        return jax.jit(
            lambda q, k, v: seq_ops.blockwise_causal_attention(q, k, v, 0.3, 16, window)
        ).lower(q, k, v).as_text()

    full, windowed = lower(None), lower(64)
    assert full.count("stablehlo.dot_general") == 2 * 16 and "stablehlo.while" not in full
    assert windowed.count("stablehlo.dot_general") == 2 * (4 + 1)
    assert windowed.count("stablehlo.while") == 1
    # the looped blocks see window + block keys: 64 + 16
    assert re.search(r"dynamic_slice.*tensor<1x1x80x8xf32>", windowed)
    # a last block that is not whole is one more program
    q, k, v, _ = _inputs(250, batch=1, groups=1, per_group=1)
    assert lower(64).count("stablehlo.dot_general") == 2 * (4 + 1 + 1)


def _swa_layer(window=24, rotary_dim=8):
    from euler_tpu.layers.sequence import GatedAttention

    return GatedAttention(
        num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=1e4,
        rotary_dim=rotary_dim, block=16, window=window,
    )


def test_window_layers_name_their_scopes_and_none_nests():
    """`euler.swa.{proj,core,out}` and no `euler.attn.*` in a window
    layer's program, the other way round in a full layer's; an op's
    innermost `euler.*` scope (`benchmarks/scoped.py:scope_of` names it
    by that) is its only one."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32))
    for window, mine, other in [(24, "swa", "attn"), (None, "attn", "swa")]:
        layer = _swa_layer(window)
        params = layer.init(jax.random.PRNGKey(1), x)
        step = jax.jit(jax.grad(lambda p: jnp.sum(layer.apply(p, x)[0])))
        names = set(re.findall(r'op_name="([^"]*)"', step.lower(params).compile().as_text()))
        scoped = [n for n in names if "euler." in n]
        found = {m for n in scoped for m in re.findall(r"euler\.([a-z_.]+)", n)}
        assert found == {f"{mine}.proj", f"{mine}.core", f"{mine}.out"}, found
        # the loop's own backward repeats the name it runs under; no op
        # lies under two different ones
        assert not [n for n in scoped if len(set(re.findall(r"euler\.[a-z_.]+", n))) > 1]


def test_a_layer_without_rotary_knows_no_position():
    """`rotary_dim` 0: a full layer's output for a query depends on the
    set of keys before it and not on their order; with rotary it does."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 32))
    swapped = x.at[:, [3, 11]].set(x[:, [11, 3]])
    for rotary_dim, same in [(0, True), (8, False)]:
        layer = _swa_layer(window=None, rotary_dim=rotary_dim)
        params = layer.init(jax.random.PRNGKey(1), x)
        a, b = layer.apply(params, x)[0], layer.apply(params, swapped)[0]
        close = np.allclose(a[:, 12:], b[:, 12:], rtol=1e-4, atol=1e-6)
        assert close == same
