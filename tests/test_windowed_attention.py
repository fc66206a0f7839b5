"""`seq_ops.blockwise_causal_attention(window=W)` on the CPU against a
dense masked softmax, values and gradients: windows that are whole
blocks and not, a window that holds the whole sequence (the program
without a window, bit for bit), a last block that is not whole; keys
outside every window of the queries that got a cotangent get none; the
window layers' `euler.swa.*` scopes are named and none nests. And the
same function where the shapes are whole tiles of the chip, as Pallas
kernels through the interpreter: against a plain oracle, the exact zeros,
which form which shapes take, a layer by tiles against the layer by dense
blocks under the decoder's rematerialisation, and what the kernel form
never makes."""

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


# -- by tiles: the causal kernels (ops/masked_flash.py), interpreted here --------


def _whole_tile_inputs(length, head_dim, per_group, groups=1, seed=0):
    """q, k, v in whole bf16 numbers, so that the scores of the kernels
    (bf16 operands) and of the oracle (float32) agree to float32
    rounding, and a weight for the output."""
    ks = jax.random.split(jax.random.PRNGKey(seed + length + head_dim + per_group), 4)
    whole = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    q = whole(jax.random.normal(ks[0], (1, groups, per_group, length, head_dim)))
    k = whole(jax.random.normal(ks[1], (1, groups, length, head_dim)))
    v = whole(jax.random.normal(ks[2], (1, groups, length, head_dim)))
    return q, k, v, jax.random.normal(ks[3], q.shape)


@pytest.mark.parametrize("per_group", [1, 8])
@pytest.mark.parametrize("head_dim", [128, 256])
@pytest.mark.parametrize(
    "window",
    # causal; a window of whole tiles; one cut inside a tile; one under a
    # tile; one that holds the sequence
    [None, 256, 200, 100, 512],
)
def test_causal_kernels_match_a_plain_oracle(window, head_dim, per_group):
    """Output and the cotangents of q, k, v, four tiles of 128 so that
    every kind of tile occurs (wholly seen, cut by the causal line, cut
    by the window's far edge, before key 0). The probabilities and the
    score cotangents enter the MXU as bf16 in the kernels and as float32
    in the oracle: that is the tolerance."""
    q, k, v, weight = _whole_tile_inputs(512, head_dim, per_group)
    scale = head_dim**-0.5
    assert seq_ops.causal_tile(q, 128) == 128
    (_, got), g_got = _value_and_grads(
        lambda q, k, v: seq_ops.blockwise_causal_attention(q, k, v, scale, 128, window), weight
    )(q, k, v)
    (_, want), g_want = _value_and_grads(
        lambda q, k, v: _dense(q, k, v, scale, window or 512), weight
    )(q, k, v)
    assert got.shape == q.shape
    for name, a, b in zip("oqkv", (got, *g_got), (want, *g_want)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-2 * float(jnp.max(jnp.abs(b))), name


@pytest.mark.parametrize("window", [1024, None])
def test_causal_kernels_at_seven_query_heads_and_a_reach_of_nine(window):
    """SmallThinker's group and its window in tiles: 7 query heads a key
    head (no power of two) and a window of 8 tiles, so that a tile of
    queries is given 9 tiles of keys, its own among them (`_reach`; the
    cell's 4,096 over tiles of 512) — here 10 tiles of 128, so that the
    last query tile's window starts past key 0 —, and the same group
    over every earlier key."""
    from euler_tpu.ops import masked_flash

    q, k, v, weight = _whole_tile_inputs(1280, 128, 7)
    scale = 128**-0.5
    assert seq_ops.causal_tile(q, 128) == 128
    assert masked_flash._reach(1280, 128, window or 1280) == (9 if window else 10)
    assert masked_flash._reach(16384, 512, 4096) == 9
    (_, got), g_got = _value_and_grads(
        lambda q, k, v: seq_ops.blockwise_causal_attention(q, k, v, scale, 128, window), weight
    )(q, k, v)
    (_, want), g_want = _value_and_grads(
        lambda q, k, v: _dense(q, k, v, scale, window or 1280), weight
    )(q, k, v)
    assert got.shape == q.shape == (1, 1, 7, 1280, 128)
    for name, a, b in zip("oqkv", (got, *g_got), (want, *g_want)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-2 * float(jnp.max(jnp.abs(b))), name


@pytest.mark.parametrize("window", [None, 200, 256])
def test_causal_kernels_at_a_head_of_64_and_four_query_heads(window):
    """LFM2's group: 4 query heads a key head at a head of 64, half a
    tile of lanes, so that the kernels' blocks end in an extent of 64 and
    a row's max and sum are cut to it (`masked_flash._across`): every
    earlier key, a window cut inside a tile and one of whole tiles,
    output and all three cotangents against the plain oracle and against
    the dense blocks the same shapes took before."""
    q, k, v, weight = _whole_tile_inputs(512, 64, 4, groups=2)
    scale = 64**-0.5
    assert seq_ops.causal_tile(q, 128) == 128

    def by_blocks(q, k, v):
        # a block of 16 is no whole tile: the dense float32 blocks
        assert seq_ops.causal_tile(q, 16) == 0
        return seq_ops.blockwise_causal_attention(q, k, v, scale, 16, window)

    (_, got), g_got = _value_and_grads(
        lambda q, k, v: seq_ops.blockwise_causal_attention(q, k, v, scale, 128, window), weight
    )(q, k, v)
    (_, want), g_want = _value_and_grads(
        lambda q, k, v: _dense(q, k, v, scale, window or 512), weight
    )(q, k, v)
    (_, blocks), g_blocks = _value_and_grads(by_blocks, weight)(q, k, v)
    assert got.shape == q.shape == (1, 2, 4, 512, 64)
    for name, a, b, c in zip("oqkv", (got, *g_got), (want, *g_want), (blocks, *g_blocks)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-2 * float(jnp.max(jnp.abs(b))), name
        assert float(jnp.max(jnp.abs(a - c))) < 1e-2 * float(jnp.max(jnp.abs(c))), name


def test_by_tiles_keys_outside_every_window_get_a_zero_cotangent():
    """As by dense blocks: not a small number but none, through tiles
    that are run (a cut tile's dropped pairs) and tiles that are not."""
    q, k, v, _ = _whole_tile_inputs(1024, 128, 2)
    window, since = 200, 1024 - 20
    assert seq_ops.causal_tile(q, 128) == 128

    def total(q, k, v):
        out = seq_ops.blockwise_causal_attention(q, k, v, 0.1, 128, window)
        return jnp.sum(jnp.sin(out[:, :, :, since:]))

    dq, dk, dv = jax.jit(jax.grad(total, (0, 1, 2)))(q, k, v)
    first_seen = since - window + 1
    for grad in (dk, dv):
        np.testing.assert_array_equal(grad[:, :, :first_seen], 0.0)
        assert float(jnp.min(jnp.max(jnp.abs(grad[:, :, first_seen:]), axis=-1))) > 0
    np.testing.assert_array_equal(dq[:, :, :, :since], 0.0)


def _tiled_layer(length=512, head_dim=128, block=128, window=200):
    from euler_tpu.layers.sequence import GatedAttention

    layer = GatedAttention(
        num_heads=4, num_kv_heads=2, head_dim=head_dim, rope_theta=1e4,
        rotary_dim=head_dim // 2, block=block, window=window,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (1, length, 32))
    params = layer.init(jax.random.PRNGKey(1), x)
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(2), p.shape), params
    )
    return layer, params, x


@pytest.mark.parametrize(
    "length,head_dim,block,kernel,dense",
    # whole tiles; a head that is half a tile, which the kernels take
    # too; a head of a quarter; blocks of 16; a length that is no whole tile
    [
        (512, 128, 128, 1, 0), (512, 64, 128, 1, 0), (512, 32, 128, 0, 1),
        (64, 128, 16, 0, 1), (576, 128, 128, 0, 1),
    ],
)
@pytest.mark.parametrize("window", [None, 200])
def test_the_form_follows_the_shapes_and_the_counter_says_so(length, head_dim, block, kernel, dense, window):
    from euler_tpu.utils import trace

    layer, params, x = _tiled_layer(length, head_dim, block, window)
    before = trace.counts()
    text = str(jax.make_jaxpr(lambda p, x: layer.apply(p, x))(params, x))
    after = trace.counts()
    counted = {
        name: after.get(name, 0) - before.get(name, 0)
        for name in ("attn_core_kernel", "attn_core_dense")
    }
    assert counted == {"attn_core_kernel": kernel, "attn_core_dense": dense}
    assert ("pallas_call" in text) == bool(kernel)


def _through_the_decoders_remat(layer, policy):
    import flax.linen as nn

    kept = nn.remat(type(layer), policy=policy)(
        **{f: getattr(layer, f) for f in layer.__dataclass_fields__ if f not in ("parent", "name")}
    )

    def scalar(params, x):
        y, _ = kept.apply(params, x)
        return jnp.sum(jnp.sin(y)), y

    return jax.value_and_grad(scalar, argnums=(0, 1), has_aux=True)


@pytest.mark.parametrize("window", [None, 200])
def test_the_layer_by_tiles_is_the_layer_by_dense_blocks(monkeypatch, window):
    """Loss and every gradient through `nn.remat(policy=_KEEP_CORE)`, as
    a decoder layer runs its mixer, at the tolerance of bf16 operands
    (the dense form's products are whole float32 here on the CPU); and
    the forward kernel is traced once where a layer rematerialised whole
    traces it twice."""
    from euler_tpu.models.sequence_lm import _KEEP_CORE

    layer, params, x = _tiled_layer(window=window)
    step = _through_the_decoders_remat(layer, _KEEP_CORE)
    forwards = lambda step: str(jax.make_jaxpr(step)(params, x)).count("name=causal_core_forward")  # noqa: E731
    once, twice = forwards(step), forwards(_through_the_decoders_remat(layer, None))
    assert once > 0 and twice == 2 * once
    (loss, y), grads = jax.jit(step)(params, x)
    monkeypatch.setattr(seq_ops, "causal_tile", lambda q, block: 0)
    step = _through_the_decoders_remat(layer, _KEEP_CORE)  # traced anew: a trace is kept by function
    assert forwards(step) == 0
    (loss_want, y_want), grads_want = jax.jit(step)(params, x)
    # a sum of sines cancels: against the sum of their sizes
    assert abs(float(loss - loss_want)) < 2e-3 * float(jnp.sum(jnp.abs(jnp.sin(y_want))))
    np.testing.assert_allclose(y, y_want, atol=2e-2 * float(jnp.max(jnp.abs(y_want))))
    for (path, got), want in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(grads_want)
    ):
        assert float(jnp.max(jnp.abs(want))) > 0, path
        assert float(jnp.max(jnp.abs(got - want))) < 2e-2 * float(jnp.max(jnp.abs(want))), path


@pytest.mark.parametrize("window", [None, 200])
def test_by_tiles_no_blocks_scores_are_made(monkeypatch, window):
    """The kernel form makes no float32 value of five axes with a
    block's 128 rows, forward or backward (a tile's scores are
    [128, 128], inside a kernel); the dense form, at the same shapes,
    makes a block's [B, G, R, 128, keys]."""
    from test_indexed_sparse_attention import _float32_shapes

    layer, params, x = _tiled_layer(window=window)

    def scores_made():
        grad = jax.grad(lambda p, x: jnp.sum(layer.apply(p, x)[0]))
        made = _float32_shapes(jax.make_jaxpr(grad)(params, x).jaxpr, set())
        return sorted(s for s in made if len(s) == 5 and s[-2] == 128)

    assert scores_made() == []
    monkeypatch.setattr(seq_ops, "causal_tile", lambda q, block: 0)
    assert (1, 2, 2, 128, 128) in scores_made()
