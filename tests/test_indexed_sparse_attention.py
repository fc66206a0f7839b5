"""Indexed sparse attention and the language model built on it, on the
CPU at a small size, against the benchmark's plain reference
(`benchmarks/reference/keye_vl2.py`, loaded by path): the mixer forward
and backward at lengths below, at and above `topk`, the selection
against a stable sort, the rotary by three position axes, where the
indexer's gradient comes from, the scopes' names, three
`Estimator.train` steps against the reference's loop, and the attention
under the pick's mask as Pallas kernels (through the interpreter here):
against a plain oracle, against the dense form inside the layer, which
form which shapes take, and what the kernel form never makes."""

import contextlib
import io
import os
import re
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
)

INDEXER = ("index_q", "index_k", "index_w", "index_k_norm_w", "index_k_norm_b")


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        import graphs
        import weights

        yield {
            "ref": _load(os.path.join(BENCH, "reference", "keye_vl2.py"), "ref_keye_vl2"),
            "train": _load(os.path.join(BENCH, "reference", "train.py"), "ref_train"),
            "family": _load(os.path.join(BENCH, "families", "keye_vl2.py"), "fam_keye_vl2"),
            "graphs": graphs,
            "weights": weights,
        }
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def config():
    return _rehearsal("keye-vl2-30b-a3b-ep8")


def _mixer(config, block):
    from euler_tpu.layers.sequence import IndexedSparseAttention

    sa = config["sa_config"]
    return IndexedSparseAttention(
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"],
        topk=sa["topk"],
        rope_theta=float(config["rope_theta"]),
        sections=tuple(config["rope_scaling"]["mrope_section"]),
        block=block,
    )


def _mixer_inputs(config, length, block, batch=2):
    """A mixer with every leaf off its initial value (so that `1 + w`,
    the LayerNorm's bias and an indexer that disagrees with the recency
    order are all tested), its input, and positions whose three axes
    differ."""
    layer = _mixer(config, block)
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, length, config["hidden_size"]))
    time = jnp.broadcast_to(jnp.arange(length), (batch, length))
    positions = jnp.stack([time, time // 3, time % 3 + jnp.arange(batch)[:, None]])
    params = layer.init(jax.random.PRNGKey(1), x, positions)["params"]
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(2), p.shape), params
    )
    return layer, params, x, positions


# -- (a) the mixer against the reference -----------------------------------


@pytest.mark.parametrize(
    "length,block",
    # topk is 8: every query sees all its keys; the last one sees exactly
    # topk; most queries pick, blocks that are not whole; one block
    [(6, 4), (8, 4), (13, 4), (20, 64)],
)
def test_indexed_sparse_attention_matches_the_reference(bench, config, length, block):
    layer, params, x, positions = _mixer_inputs(config, length, block)
    assert config["sa_config"]["topk"] == 8

    def program(params, x):
        return layer.apply({"params": params}, x, positions)

    def reference(params, x):
        flat = bench["weights"].flatten(params)
        y, kl = bench["ref"].sparse_attention(flat, x, positions, config, length)
        return y, kl / (x.shape[0] * length)

    def value_and_grads(fn):
        def scalar(params, x):
            y, kl = fn(params, x)
            return jnp.sum(jnp.sin(y)) + kl, (y, kl)

        return _highest(jax.jit(jax.value_and_grad(scalar, argnums=(0, 1), has_aux=True)))

    (_, (got, kl_got)), g_got = value_and_grads(program)(params, x)
    (_, (want, kl_want)), g_want = value_and_grads(reference)(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(kl_got, kl_want, rtol=1e-4)
    assert float(kl_want) > 1e-3  # the indexer and the attention do disagree
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_got), jax.tree_util.tree_leaves(g_want)
    ):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-6, err_msg=str(path))


def test_selection_follows_the_indexer_not_the_recency(bench, config):
    """The reference with the last `topk` keys in the picked set's place
    is another function wherever a query picks."""
    layer, params, x, positions = _mixer_inputs(config, 24, 8)
    flat = bench["weights"].flatten(params)
    got, _ = _highest(jax.jit(layer.apply))({"params": params}, x, positions)
    recent, _ = _highest(
        jax.jit(lambda p, x: bench["ref"].sparse_attention(p, x, positions, config, 24, "recent_keys"))
    )(flat, x)
    topk = config["sa_config"]["topk"]
    np.testing.assert_allclose(got[:, :topk], recent[:, :topk], rtol=1e-4, atol=1e-6)
    assert float(jnp.max(jnp.abs(got[:, topk:] - recent[:, topk:]))) > 1e-3


def test_indexer_learns_from_its_own_loss_alone(config):
    """The output passes no gradient to the indexer (a pick passes none,
    its input is stopped), and the indexer's loss none to anything else
    (its target is a constant)."""
    layer, params, x, positions = _mixer_inputs(config, 20, 8)

    def output(params, x):
        return jnp.sum(jnp.sin(layer.apply({"params": params}, x, positions)[0]))

    def own(params, x):
        return layer.apply({"params": params}, x, positions)[1]

    from_output, x_from_output = jax.jit(jax.grad(output, (0, 1)))(params, x)
    from_own, x_from_own = jax.jit(jax.grad(own, (0, 1)))(params, x)
    flat_output = {"/".join(k.key for k in p): v for p, v in jax.tree_util.tree_leaves_with_path(from_output)}
    flat_own = {"/".join(k.key for k in p): v for p, v in jax.tree_util.tree_leaves_with_path(from_own)}
    assert set(INDEXER) < set(flat_own)
    for name in flat_own:
        if name in INDEXER:
            np.testing.assert_array_equal(flat_output[name], 0.0, err_msg=name)
            assert float(jnp.max(jnp.abs(flat_own[name]))) > 0, name
        else:
            np.testing.assert_array_equal(flat_own[name], 0.0, err_msg=name)
            assert float(jnp.max(jnp.abs(flat_output[name]))) > 0, name
    np.testing.assert_array_equal(x_from_own, 0.0)
    assert float(jnp.max(jnp.abs(x_from_output))) > 0


def test_query_runs_cover_every_query_once_with_its_keys_in_reach():
    from euler_tpu.layers.sequence import query_runs

    # the cell: 4 blocks that pick nothing, then stretches that double
    assert query_runs(16384, 512, 2048) == [
        (0, 4, 512, 2048), (2048, 4, 512, 4096), (4096, 8, 512, 8192), (8192, 16, 512, 16384),
    ]
    assert query_runs(13, 4, 8) == [(0, 2, 4, 8), (8, 1, 4, 13), (12, 1, 1, 13)]
    assert query_runs(6, 64, 8) == [(0, 1, 6, 6)]
    for length, block, topk in [(16384, 512, 2048), (100, 8, 20), (64, 16, 8), (7, 2, 1), (40, 16, 64)]:
        runs = query_runs(length, block, topk)
        at = 0
        for first, count, rows, keys in runs:
            assert first == at and first + count * rows <= keys <= length
            at += count * rows
        assert at == length
        scored = sum(count * rows * keys for _, count, rows, keys in runs)
        assert scored <= max(0.75 * length * length, length * min(length, max(topk, block) + block))


# -- (b) the selection ------------------------------------------------------


def _stable_topk(scores, k):
    """True at the k largest finite entries of each row by a stable
    descending sort: ties to the lower index."""
    scores = np.asarray(scores, np.float64)
    keep = np.zeros(scores.shape, bool)
    for row, out in zip(scores.reshape(-1, scores.shape[-1]), keep.reshape(-1, scores.shape[-1])):
        order = np.argsort(-row, kind="stable")
        order = [i for i in order if row[i] > -np.inf][:k]
        out[order] = True
    return keep


@pytest.mark.parametrize("k", [1, 5, 8, 64])
def test_topk_mask_is_a_stable_sort(k):
    from euler_tpu.ops import seq_ops

    rng = np.random.default_rng(k)
    scores = rng.normal(size=(2, 12, 40)).astype(np.float32)
    scores[0, 0, :] = 0.25  # a row of one value: the first k
    scores[0, 1, ::3] = scores[0, 1, 0]  # ties that straddle the k-th place
    scores[0, 2, :] = np.where(rng.random(40) < 0.5, 0.0, -0.0)  # the two zeros are one value
    scores[0, 3, :] = rng.integers(-2, 3, 40)  # few values, many ties, both signs
    scores[0, 4, 20:] = -np.inf  # 20 keys
    scores[0, 5, 3:] = -np.inf  # fewer keys than any k but 1
    scores[0, 6, :] = -np.inf
    scores[0, 6, 7] = -1e30  # one key, very low
    scores[0, 7, :] = rng.normal(size=40) * 1e-30  # tiny, both signs
    scores[1, :, :] = np.where(
        np.arange(40)[None, :] <= np.arange(12)[:, None] * 3, scores[1], -np.inf
    )  # a causal staircase: rows of 1, 4, 7, ... keys
    got = np.asarray(jax.jit(seq_ops.topk_mask, static_argnums=1)(jnp.asarray(scores), k))
    np.testing.assert_array_equal(got, _stable_topk(scores, k))
    assert got.sum(-1).max() <= k


def test_indexer_scores_are_causal_by_the_blocks_first_row():
    from euler_tpu.ops import seq_ops

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 4, 3, 8))
    k = jax.random.normal(ks[1], (2, 10, 8))
    w = jax.random.normal(ks[2], (2, 4, 3))
    got = seq_ops.indexer_scores(q, k, w, 6)  # rows are positions 6..9
    want = jnp.einsum("brj,brjs->brs", w, jax.nn.relu(jnp.einsum("brjd,bsd->brjs", q, k)))
    seen = np.arange(10)[None, :] <= (6 + np.arange(4))[:, None]
    np.testing.assert_allclose(np.where(seen, got, 0.0), np.where(seen, want, 0.0), rtol=1e-5, atol=1e-6)
    assert np.all(np.asarray(got)[:, ~seen] == -np.inf)


# -- (c) the rotary by three axes -------------------------------------------


def test_rotary_by_three_unequal_axes_matches_the_reference(bench):
    from euler_tpu.layers.sequence import rotary

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16))
    positions = jax.random.randint(jax.random.PRNGKey(1), (3, 2, 9), 0, 50)
    got = rotary(x, 1e7, 16, positions, (2, 3, 3))
    np.testing.assert_allclose(got, bench["ref"].rotate(x, 1e7, positions, [2, 3, 3]), atol=1e-6)
    # pair j turns by its own axis alone: moving the width moves pairs 5..7
    moved = rotary(x, 1e7, 16, positions.at[2].add(7), (2, 3, 3))
    same = np.r_[0:5, 8:13]
    np.testing.assert_array_equal(moved[..., same], got[..., same])
    assert not np.allclose(moved[..., 5:8], got[..., 5:8])
    # the time axis alone, over the whole head: the indexer's rotary
    np.testing.assert_allclose(
        rotary(x, 1e7, 16, positions[:1]), bench["ref"].rotate(x, 1e7, positions[:1], [8]), atol=1e-6
    )


@pytest.mark.parametrize("rotary_dim", [16, 4])
def test_rotary_with_equal_axes_is_the_rotary_by_position(rotary_dim):
    """Bit for bit: text under three axes is text under one."""
    from euler_tpu.layers.sequence import rotary

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 3, 16))
    positions = jnp.broadcast_to(jnp.arange(11), (3, 2, 11))
    half = rotary_dim // 2
    sections = (half - 2 * (half // 3), half // 3, half // 3)
    np.testing.assert_array_equal(
        rotary(x, 1e7, rotary_dim, positions, sections), rotary(x, 1e7, rotary_dim)
    )


# -- (d) the scopes ------------------------------------------------------------


@pytest.mark.parametrize("form", ["masked", "kernel"])
def test_dsa_scopes_are_named_and_none_nests_in_another(config, form):
    """`benchmarks/scoped.py` names an op by its innermost `euler.*`
    scope, and the readers sum `dsa.*` by prefix: every op of the mixer
    lies under exactly one of the six names, forward and backward — the
    ops of the two kernels too, as the interpreter lowers them."""
    if form == "masked":
        layer, params, x, positions = _mixer_inputs(config, 24, 8)
    else:
        layer, params, x, positions = _tiled_mixer_inputs(length=256)

    def scalar(params, x):
        y, kl = layer.apply({"params": params}, x, positions)
        return jnp.sum(y) + kl

    text = jax.jit(jax.grad(scalar)).lower(params, x).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    scoped = [n for n in names if "euler." in n]
    found = {m for n in scoped for m in re.findall(r"euler\.([a-z_.]+)", n)}
    assert found == {"dsa.proj", "dsa.index", "dsa.select", "dsa.core", "dsa.aux", "dsa.out"}
    # the interpreter, which runs the kernels here, names a kernel's ops by
    # the whole stack twice over: the same scope again, never another
    nested = [n for n in scoped if len(set(re.findall(r"euler\.([a-z_.]+)", n))) > 1]
    assert not nested, nested
    if form == "masked":
        assert all(n.count("euler.") == 1 for n in scoped), [n for n in scoped if n.count("euler.") > 1]


# -- (d2) the attention under the mask, tile by tile ---------------------------


def _plain_attention(q, k, v, keep, scale):
    """(o, logsumexp, the heads' probabilities averaged) by whole
    tensors, float32 throughout."""
    s = jnp.einsum("bgrtd,bgsd->bgrts", q, k, precision="highest") * scale
    s = jnp.where(keep[:, None, None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    return jnp.einsum("bgrts,bgsd->bgrtd", p, v, precision="highest"), lse, jnp.mean(p, axis=(1, 2))


def _masks(case, rows, keys, key):
    """keep [2, rows, keys] and the block's first row for each case."""
    first = {"first_not_zero": keys - rows}.get(case, 0)
    at = first + jnp.arange(rows)[:, None]
    cols = jnp.arange(keys)[None, :]
    causal = jnp.broadcast_to(cols <= at, (2, rows, keys))
    if case == "all_causal":  # a run whose queries pick every key they see
        return causal
    picked = jax.random.uniform(key, (2, rows, keys)) < 0.25
    keep = (picked & causal) | (cols == at)  # its own key, so no row is empty
    if case == "empty_tiles":  # keys 128..255 picked by no row: a whole tile of every row tile
        keep = keep & ~((cols >= 128) & (cols < 256)) | (cols == 0)
    if case == "one_key_row":
        keep = keep.at[:, 5].set(cols[0] == 3).at[1, 77].set(cols[0] == 0)
    if case == "first_not_zero":  # a row with nothing in the two live tiles before its one key's
        keep = keep.at[:, 7].set(cols[0] == 300)
    return keep


@pytest.mark.parametrize(
    "case,groups,keys",
    [("empty_tiles", 2, 512), ("one_key_row", 1, 256), ("all_causal", 1, 256), ("first_not_zero", 2, 512)],
)
def test_kernels_match_a_plain_oracle(case, groups, keys):
    """Output, logsumexp, the heads' shares and the gradients to q, k, v
    of the kernel pair, tiles of 128 so that every case spans several.
    q, k, v are whole bf16 numbers, so the scores agree to float32
    rounding; the probabilities enter the MXU as bf16 in the kernels and
    as float32 in the oracle: that is the output's and the gradients'
    tolerance."""
    from euler_tpu.ops import masked_flash

    rows, heads, d = 128, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(keys + groups), 5)
    whole = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    q = whole(jax.random.normal(ks[0], (2, groups, heads, rows, d)))
    k = whole(jax.random.normal(ks[1], (2, groups, keys, d)))
    v = whole(jax.random.normal(ks[2], (2, groups, keys, d)))
    weigh = jax.random.normal(ks[3], q.shape)
    keep = _masks(case, rows, keys, ks[4])
    assert int(keep.sum(-1).min()) >= 1
    if case == "empty_tiles":
        assert not bool(keep[:, :, 128:256].any())
    scale = d**-0.5

    def run(attend):
        def scalar(q, k, v):
            o, lse = attend(q, k, v)
            return jnp.sum(o * weigh), (o, lse)

        return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    (_, (o, lse)), grads = run(lambda q, k, v: masked_flash.attention(q, k, v, keep, scale, 128, 128))
    (_, (o_want, lse_want)), grads_want = run(lambda q, k, v: _plain_attention(q, k, v, keep, scale)[:2])
    np.testing.assert_allclose(lse, lse_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o, o_want, atol=2e-2)
    assert float(jnp.max(jnp.abs(o - o_want))) < 1e-2 * float(jnp.max(jnp.abs(o_want)))
    for name, got, want in zip("qkv", grads, grads_want):
        assert float(jnp.max(jnp.abs(got - want))) < 1e-2 * float(jnp.max(jnp.abs(want))), name
    share = jax.jit(lambda: masked_flash.share(q, k, keep, lse, scale, 128, 128))()
    share_want = _plain_attention(q, k, v, keep, scale)[2]
    np.testing.assert_allclose(share, share_want, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(jnp.sum(share, axis=-1), 1.0, rtol=1e-5)
    assert not bool(jnp.any(jnp.where(keep, 0.0, share) != 0))
    # the tiles chosen from the shapes are another cut of the same sums
    o_auto, lse_auto = jax.jit(lambda: masked_flash.attention(q, k, v, keep, scale))()
    np.testing.assert_allclose(lse_auto, lse, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o_auto, o, atol=2e-2)


def _tiled_mixer_inputs(length=512, head_dim=128, block=128):
    """A mixer whose blocks are whole tiles (rows 128, head 128, keys
    256 and 512) at a width small enough for the interpreter: 2 key/value
    heads of 2 query heads, 3 indexer heads, and what `_mixer_inputs`
    gives."""
    from euler_tpu.layers.sequence import IndexedSparseAttention

    layer = IndexedSparseAttention(
        num_heads=4, num_kv_heads=2, head_dim=head_dim, index_heads=3, index_dim=16,
        topk=256, rope_theta=1e4, sections=(head_dim // 4, head_dim // 8, head_dim // 8),
        block=block,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (1, length, 32))
    time = jnp.broadcast_to(jnp.arange(length), (1, length))
    positions = jnp.stack([time, time // 3, time % 3])
    params = layer.init(jax.random.PRNGKey(1), x, positions)["params"]
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(2), p.shape), params
    )
    return layer, params, x, positions


def _counted(fn, *args):
    """How often each `dsa_core_*` form was traced inside `fn(*args)`."""
    from euler_tpu.utils import trace

    before = trace.counts()
    jax.eval_shape(fn, *args)
    after = trace.counts()
    return {
        name: after.get(name, 0) - before.get(name, 0)
        for name in ("dsa_core_kernel", "dsa_core_masked")
    }


@pytest.mark.parametrize(
    "length,head_dim,block,kernel,masked",
    # whole tiles; a block of 8; a head that is half a tile; a last block
    # that is not whole beside runs that are
    [(512, 128, 128, 1, 0), (64, 128, 8, 0, 1), (512, 64, 128, 0, 1), (576, 128, 128, 1, 1)],
)
def test_the_form_follows_the_shapes_and_the_counter_says_so(length, head_dim, block, kernel, masked):
    layer, params, x, positions = _tiled_mixer_inputs(length, head_dim, block)
    text = str(jax.make_jaxpr(lambda p, x: layer.apply({"params": p}, x, positions))(params, x))
    assert ("pallas_call" in text) == bool(kernel)
    counted = _counted(lambda p, x: layer.apply({"params": p}, x, positions), params, x)
    assert counted == {"dsa_core_kernel": kernel, "dsa_core_masked": masked}


def test_the_layer_by_tiles_is_the_layer_by_dense_blocks(monkeypatch):
    """Loss, KL and every gradient, through `checkpoint` + `lax.map` +
    `custom_vjp`, at the tolerance of bf16 operands (the dense form's
    products are whole float32 here on the CPU)."""
    from euler_tpu.ops import seq_ops

    layer, params, x, positions = _tiled_mixer_inputs()

    def run():
        def scalar(params, x):
            y, kl = layer.apply({"params": params}, x, positions)
            return jnp.sum(jnp.sin(y)) + kl, (y, kl)

        return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1), has_aux=True))(params, x)

    (loss, (y, kl)), grads = run()
    monkeypatch.setattr(seq_ops, "attends_by_tiles", lambda q, k: False)
    (loss_want, (y_want, kl_want)), grads_want = run()
    assert float(kl_want) > 1e-3
    np.testing.assert_allclose(loss, loss_want, rtol=2e-3)
    np.testing.assert_allclose(kl, kl_want, rtol=5e-3)
    np.testing.assert_allclose(y, y_want, atol=2e-2 * float(jnp.max(jnp.abs(y_want))))
    for (path, got), want in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(grads_want)
    ):
        assert float(jnp.max(jnp.abs(want))) > 0, path
        assert float(jnp.max(jnp.abs(got - want))) < 2e-2 * float(jnp.max(jnp.abs(want))), path


def _float32_shapes(jaxpr, found):
    """Every float32 value's shape in `jaxpr` and in what its equations
    hold (loops, checkpoints, custom rules, kernels)."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            if getattr(var.aval, "dtype", None) == jnp.float32:
                found.add(tuple(var.aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _float32_shapes(sub, found)
    return found


def test_by_tiles_no_blocks_scores_are_made_or_kept(monkeypatch):
    """The kernel form makes no float32 value of rank >= 4 whose last two
    axes are a block's rows and a run's keys but the indexer's products
    [B, 3, rows, keys] — forward, backward, or kept between them; the
    dense form, at the same shapes, does."""
    from euler_tpu.ops import seq_ops

    layer, params, x, positions = _tiled_mixer_inputs()

    def scalar(params, x):
        y, kl = layer.apply({"params": params}, x, positions)
        return jnp.sum(y) + kl

    def scores_of(shapes):  # rows 128; the runs' keys are 256 and 512, the head 128
        return sorted(
            s for s in shapes
            if len(s) >= 4 and s[-2] == 128 and s[-1] in (256, 512) and s[:-2] != (1, 3)
        )

    def look():
        made = _float32_shapes(jax.make_jaxpr(jax.grad(scalar))(params, x).jaxpr, set())
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            jax.ad_checkpoint.print_saved_residuals(scalar, params, x)
        kept = {
            tuple(int(n) for n in shape.split(",") if n)
            for shape in re.findall(r"^f32\[([\d,]*)\]", out.getvalue(), re.M)
        }
        text = jax.jit(jax.grad(scalar)).lower(params, x).compile().as_text()
        compiled = {tuple(int(n) for n in s.split(",")) for s in re.findall(r"f32\[([\d,]+)\]", text)}
        return scores_of(made), scores_of(kept), scores_of(compiled)

    assert look() == ([], [], [])
    monkeypatch.setattr(seq_ops, "attends_by_tiles", lambda q, k: False)
    made, kept, compiled = look()
    assert (1, 2, 2, 128, 256) in made and (1, 2, 2, 128, 512) in made and kept == []
    assert (1, 2, 2, 128, 512) in compiled


# -- (e) the model ------------------------------------------------------------


def test_model_gradients_split_between_the_two_losses(bench, config):
    """Whole model, one batch: the indexers' leaves get the gradient the
    reference gives them, which is zero once the indexer's loss is left
    out; every other leaf gets the gradient of the cross-entropy alone."""
    graph, built = _built(bench, config)
    weights = bench["weights"]
    spec = bench["ref"].param_spec(config, graph)
    flat = weights.make_params(spec, 7)
    # an indexer off its initial value and far from the attention
    flat = {
        k: v + (0.5 * jax.random.normal(jax.random.PRNGKey(i), v.shape) if k.rsplit("/", 1)[-1] in INDEXER else 0.0)
        for i, (k, v) in enumerate(flat.items())
    }
    tables, loss_fn = bench["ref"].make(config, {}, graph)
    key = bench["train"].step_key(7, 0)
    ids = jax.jit(built["flow"].sample)(key)

    def program(flat):
        return built["model"].apply(weights.nest(flat), ids)[1]

    got = _highest(jax.jit(jax.grad(program)))(flat)
    whole = _highest(jax.jit(jax.grad(lambda p: loss_fn(p, tables, key, jnp.float32, ""))))(flat)
    alone = _highest(jax.jit(jax.grad(lambda p: loss_fn(p, tables, key, jnp.float32, "no_index_loss"))))(flat)
    indexer = [k for k in flat if k.rsplit("/", 1)[-1] in INDEXER]
    assert len(indexer) == len(INDEXER) * config["num_hidden_layers"]
    for k in flat:
        np.testing.assert_allclose(got[k], whole[k], rtol=2e-3, atol=1e-7, err_msg=k)
        if k in indexer:
            np.testing.assert_array_equal(alone[k], 0.0, err_msg=k)
            assert float(jnp.max(jnp.abs(whole[k]))) > 0, k
        else:
            np.testing.assert_allclose(got[k], alone[k], rtol=2e-3, atol=1e-7, err_msg=k)


def test_model_takes_positions_and_text_needs_none(bench, config):
    graph, built = _built(bench, config)
    spec = bench["ref"].param_spec(config, graph)
    params = bench["weights"].nest(bench["weights"].make_params(spec, 3))
    ids = jax.jit(built["flow"].sample)(bench["train"].step_key(3, 0))
    length = ids.shape[1] - 1
    text = jnp.broadcast_to(jnp.arange(length), (3, ids.shape[0], length))
    loss, share = jax.jit(lambda: built["model"].apply(params, ids)[1::2])()
    assert 0.0 < float(share) < 1.0
    loss_at = jax.jit(lambda positions: built["model"].apply(params, ids, positions)[1])
    np.testing.assert_allclose(loss, loss_at(text), rtol=1e-6)
    image = text.at[1:, :, 8:24].set(jnp.arange(16) % 4)  # a patch of 4 columns
    assert abs(float(loss_at(image)) - float(loss)) > 1e-6


def test_keeping_the_attention_core_changes_no_bit(bench, config, monkeypatch):
    _assert_keeping_the_core_changes_no_bit(bench, config, monkeypatch)


def test_a_layer_keeps_its_core_output_and_no_blocks_scores(bench, config, monkeypatch):
    """One decoder layer: of the mixer's loops over query blocks the
    backward keeps their output [B, G, R, T, d] and nothing with a
    block's rows (index scores [B, block, keys], probabilities
    [B, G, R, block, keys]); rematerialised whole it would keep neither,
    and run every loop once more."""
    model = _built(bench, config)[1]["model"].clone(num_layers=1, attention_block=8)
    (kept, program), (whole, whole_program), core = _one_layer_both_ways(model, monkeypatch)
    assert _named(kept) == [core] and _named(whole) == [] and len(kept) == len(whole) + 1
    assert not [shape for shape, _ in kept if len(shape) > 1 and shape[-2] == 8]
    assert max(np.prod(shape) for shape, _ in kept) <= 2 * 64 * max(model.hidden_size, core[1] * core[2] * core[4])
    program, whole_program = program.compile().as_text(), whole_program.compile().as_text()
    # keys 8, 16, 32, 64: four runs, each one loop fewer (and the pick's inside it)
    assert whole_program.count(" while(") - program.count(" while(") >= 4
    assert whole_program.count(" dot(") > program.count(" dot(")


@pytest.fixture(scope="module")
def three_steps(bench, config):
    """Three `Estimator.train` steps from seeded weights, once for the
    tests below: what `benchmarks/run.py` compares, and the set-up span
    of the step program."""
    from euler_tpu.utils import trace

    since = time.perf_counter_ns()  # not a count of spans: the record is bounded
    got, reference = _program_first_steps(bench, config, 3000000023)
    spans = [s for s in trace.spans() if s.start_ns >= since]
    return {"got": got, "spans": spans, "reference": reference}


def test_first_call_span_carries_the_mixers_forms(config, three_steps):
    args = next(
        s.args for s in three_steps["spans"]
        if s.name == "step.first_call" and s.args["program"] == "train_step"
    )
    layers, topk = config["num_hidden_layers"], config["sa_config"]["topk"]
    assert (args["dsa_layers"], args["dsa_topk"], args["dsa_core_masked"]) == (layers, layers * topk, layers)
    assert args["dsa_core_kernel"] == 0  # blocks of 8 at the rehearsal's size: no whole tile
    assert args["dsa_index_vjp"] == layers  # every layer's indexer brings its own backward
    assert args["mixer_core_kept"] == layers  # every layer's mixer is a softmax attention
    assert (args["attn_core_dense"], args["attn_core_kernel"]) == (0, 0)  # no `GatedAttention` here
    assert (args["agg_grid"], args["draw_rows"], args["draw_elements"]) == (0, 0, 1)


def test_three_train_steps_match_the_reference(bench, three_steps):
    got, want = three_steps["got"], three_steps["reference"]()
    assert set(got["grad_norm"]) == set(want["grad_norm"])  # one tree, leaf for leaf
    compared = bench["train"].compare(got, want)
    assert all(v < 1e-4 for v in compared.values()), compared
    # each of the mechanism's own faults is another model: the comparison sees it
    for fault in ("recent_keys", "no_index_loss"):
        broken = three_steps["reference"](fault)
        assert max(bench["train"].compare(broken, want).values()) > 1e-2, fault
