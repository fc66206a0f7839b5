"""Pallas kernel semantics (interpret mode on CPU) vs XLA reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu.ops.pallas_kernels import (
    _reference_forward,
    gather_weighted_sum,
)


@pytest.fixture
def data(rng):
    n_src, n_dst, d, f = 20, 12, 4, 128
    x = jnp.asarray(rng.normal(size=(n_src, f)), jnp.float32)
    slots = jnp.asarray(rng.integers(0, n_src, size=(n_dst, d)), jnp.int32)
    w = jnp.asarray(rng.random((n_dst, d)), jnp.float32)
    return x, slots, w


def test_xla_impl_matches_einsum(data):
    x, slots, w = data
    out = gather_weighted_sum(x, slots, w, "xla")
    np.testing.assert_allclose(out, _reference_forward(x, slots, w), rtol=1e-5)


def test_interpret_matches_xla(data):
    x, slots, w = data
    out_i = gather_weighted_sum(x, slots, w, "interpret")
    out_x = gather_weighted_sum(x, slots, w, "xla")
    np.testing.assert_allclose(out_i, out_x, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("f", [64, 256, 200])
def test_wide_features_chunked_gather(rng, f):
    """f > 128 rides the two-level 128-lane chunk gather: 256 covers the
    k=2 chunk loop (size-generic — wider k re-runs the same copies), 200
    the pad-to-lane-tile path. Sizes are the minimum that still cover a
    non-tile-aligned n_dst — interpret-mode DMA emulation costs ~0.15s
    per copy, so row counts directly set the gate's wall clock."""
    n_src, n_dst, d = 18, 6, 3
    x = jnp.asarray(rng.normal(size=(n_src, f)), jnp.float32)
    slots = jnp.asarray(rng.integers(0, n_src, size=(n_dst, d)), jnp.int32)
    w = jnp.asarray(rng.random((n_dst, d)), jnp.float32)
    out_i = gather_weighted_sum(x, slots, w, "interpret")
    out_x = gather_weighted_sum(x, slots, w, "xla")
    np.testing.assert_allclose(out_i, out_x, rtol=1e-4, atol=1e-5)


def test_non_tile_multiple(rng):
    # n_dst not divisible by TILE exercises the pad path
    x = jnp.asarray(rng.normal(size=(9, 128)), jnp.float32)
    slots = jnp.asarray(rng.integers(0, 9, size=(5, 3)), jnp.int32)
    w = jnp.ones((5, 3), jnp.float32)
    out = gather_weighted_sum(x, slots, w, "interpret")
    np.testing.assert_allclose(
        out, gather_weighted_sum(x, slots, w, "xla"), rtol=1e-4, atol=1e-5
    )


def test_gradients(data):
    x, slots, w = data

    def loss(x, w):
        return jnp.sum(gather_weighted_sum(x, slots, w, "xla") ** 2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    # numeric check on a few coordinates
    eps = 1e-2
    for idx in [(0, 0), (3, 17)]:
        xp = x.at[idx].add(eps)
        xm = x.at[idx].add(-eps)
        num = (loss(xp, w) - loss(xm, w)) / (2 * eps)
        np.testing.assert_allclose(gx[idx], num, rtol=2e-2, atol=1e-2)
    for idx in [(0, 0), (7, 2)]:
        wp = w.at[idx].add(eps)
        wm = w.at[idx].add(-eps)
        num = (loss(x, wp) - loss(x, wm)) / (2 * eps)
        np.testing.assert_allclose(gw[idx], num, rtol=2e-2, atol=1e-2)


def test_jit(data):
    x, slots, w = data
    f = jax.jit(lambda x, s, w: gather_weighted_sum(x, s, w, "xla"))
    np.testing.assert_allclose(
        f(x, slots, w), gather_weighted_sum(x, slots, w, "xla"), rtol=1e-6
    )


def test_sage_conv_pallas_path_matches(rng):
    """SAGEConv with the fused grid path (interpret) == segment-op path."""
    import sys
    sys.path.insert(0, "tests")
    import euler_tpu.ops as ops
    from euler_tpu.dataflow import SageDataFlow
    from euler_tpu.layers import SAGEConv
    from test_training import make_cluster_graph

    g = make_cluster_graph()
    flow = SageDataFlow(g, ["feat"], fanouts=[3], rng=np.random.default_rng(0))
    mb = flow.query(np.asarray([1, 2, 3, 4], np.uint64))
    layer = SAGEConv(out_dim=8)
    params = layer.init(
        jax.random.PRNGKey(0), mb.feats[0], mb.feats[1], mb.blocks[0]
    )
    ops.set_pallas("off")
    ref = layer.apply(params, mb.feats[0], mb.feats[1], mb.blocks[0])
    try:
        ops.set_pallas("interpret")
        out = layer.apply(params, mb.feats[0], mb.feats[1], mb.blocks[0])
    finally:
        ops.set_pallas("off")
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_paged_gather_interpret_matches_reference(rng):
    """The paged ragged-gather kernel (interpret) == the jnp reference,
    for both the int32 neighbor plane and the f32 weight plane."""
    from euler_tpu.ops.pallas_kernels import _as_lane_rows, paged_gather

    for dtype in (np.int32, np.float32):
        flat = jnp.asarray(
            rng.integers(0, 1000, 700).astype(dtype)
        )
        t2d = _as_lane_rows(flat)
        fidx = jnp.asarray(rng.integers(0, 700, (11, 3)), jnp.int32)
        ref = paged_gather(t2d, fidx, "xla")
        out = paged_gather(t2d, fidx, "interpret")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
        np.testing.assert_array_equal(
            np.asarray(ref), np.asarray(flat)[np.asarray(fidx)]
        )
    # the packed-bf16 twin: two values per 32-bit word, unpacked in-kernel
    from euler_tpu.ops.pallas_kernels import (
        pack_bf16_words,
        paged_gather_dequant,
    )

    vals = jnp.asarray(rng.normal(size=700), jnp.float32)
    packed = _as_lane_rows(pack_bf16_words(vals))
    ref = paged_gather_dequant(packed, fidx, "xla")
    out = paged_gather_dequant(packed, fidx, "interpret")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    np.testing.assert_array_equal(
        np.asarray(ref),
        np.asarray(vals.astype(jnp.bfloat16).astype(jnp.float32))[
            np.asarray(fidx)
        ],
    )


def test_paged_cdf_count_interpret_matches_reference(rng):
    """In-page CDF inversion kernel (interpret) == jnp reference, and
    composed with the page-boundary search it reproduces the dense
    full-row count — the bit-identity the device lanes rely on."""
    from euler_tpu.ops.pallas_kernels import (
        _as_lane_rows,
        paged_cdf_count,
        paged_page_search,
    )

    P = 8
    deg = np.array([5, 21, 0, 8])
    npages = -(-deg // P)
    ps = np.concatenate([[0], np.cumsum(npages)]).astype(np.int64)
    total = max(int(ps[-1]), 1)
    flat_q = np.full(total * P, 0xFFFFFFFF, np.uint32)
    qrows = {}
    for n in range(len(deg)):
        if deg[n] == 0:
            continue
        w = rng.random(deg[n])
        cum = np.cumsum(w)
        q = np.floor(cum / cum[-1] * (2**32 - 1)).astype(np.uint64)
        flat_q[ps[n] * P : ps[n] * P + deg[n]] = q.astype(np.uint32)
        qrows[n] = q.astype(np.uint32)
    bound = flat_q.reshape(total, P).max(axis=1)
    q2d = _as_lane_rows(jnp.asarray(flat_q))
    r = jnp.asarray(
        rng.integers(0, 2**32, (len(deg), 6), dtype=np.uint64
                     ).astype(np.uint32)
    )
    pg = paged_page_search(
        jnp.asarray(bound), jnp.asarray(ps[:-1], jnp.int32),
        jnp.asarray(npages, jnp.int32), r, 6,
    )
    pgc = jnp.minimum(
        pg, jnp.maximum(jnp.asarray(npages, jnp.int32)[:, None] - 1, 0)
    )
    page = jnp.asarray(ps[:-1], jnp.int32)[:, None] + pgc
    cnt_x = paged_cdf_count(q2d, page, r, P, "xla")
    cnt_i = paged_cdf_count(q2d, page, r, P, "interpret")
    np.testing.assert_array_equal(np.asarray(cnt_x), np.asarray(cnt_i))
    idx = np.minimum(
        np.asarray(pgc) * P + np.asarray(cnt_x),
        np.maximum(deg[:, None] - 1, 0),
    )
    for n, q in qrows.items():  # dense full-row oracle
        pad = np.full(int(npages[n]) * P - deg[n], 0xFFFFFFFF, np.uint32)
        row = np.concatenate([q, pad])
        for j in range(6):
            want = min(int((row <= np.asarray(r)[n, j]).sum()), deg[n] - 1)
            assert want == idx[n, j], (n, j, want, idx[n, j])


def test_gat_fused_grid_matches_scatter_path(rng):
    """GATConv's fused segment-softmax path (grid blocks through
    gather_weighted_sum) must match the generic scatter_softmax path."""
    import jax
    import jax.numpy as jnp

    from euler_tpu.layers.conv import GATConv

    n_dst, d, f = 6, 4, 16
    x_dst = jnp.asarray(rng.normal(size=(n_dst, f)), jnp.float32)
    x_src = jnp.asarray(rng.normal(size=(n_dst * d, f)), jnp.float32)
    from euler_tpu.dataflow.base import Block

    mask = rng.random((n_dst * d,)) > 0.3
    mask[:d] = False  # one fully-masked row
    grid_block = Block(
        edge_src=jnp.arange(n_dst * d, dtype=jnp.int32),
        edge_dst=jnp.repeat(jnp.arange(n_dst, dtype=jnp.int32), d),
        edge_w=jnp.ones(n_dst * d, jnp.float32),
        mask=jnp.asarray(mask),
        n_src=n_dst * d,
        n_dst=n_dst,
        grid=d,
    )
    flat_block = grid_block.replace(grid=0)
    layer = GATConv(out_dim=8)
    params = layer.init(jax.random.PRNGKey(0), x_dst, x_src, grid_block)
    from euler_tpu.ops import pallas_mode, set_pallas

    prev = pallas_mode()
    set_pallas("interpret")  # force the fused path through the kernel
    try:
        out_grid = layer.apply(params, x_dst, x_src, grid_block)
    finally:
        set_pallas(prev)
    out_flat = layer.apply(params, x_dst, x_src, flat_block)
    np.testing.assert_allclose(
        np.asarray(out_grid), np.asarray(out_flat), rtol=2e-5, atol=2e-6
    )


def test_paged_topk_score_matches_left_to_right_oracle_bitwise(rng):
    """The paged retrieval scorer == a strict left-to-right NumPy
    accumulation, BITWISE.  Operands carry 12-bit-truncated significands
    (retrieval quantize_sig12 canon) so every product is exact in f32
    and LLVM's FMA contraction is a semantic no-op — without that,
    parity is at the compiler's mercy."""
    import jax.numpy as jnp

    from euler_tpu.ops.pallas_kernels import PAGE_LANES, paged_topk_score
    from euler_tpu.retrieval.corpus import quantize_sig12

    nrows, dp, B = 257, 32, 5  # non-tile-multiple row count, dp | 128
    x = quantize_sig12(
        rng.standard_normal((nrows, dp)).astype(np.float32)
    )
    q = quantize_sig12(rng.standard_normal((B, dp)).astype(np.float32))
    flat = x.reshape(-1)
    flat = np.pad(flat, (0, (-flat.size) % PAGE_LANES))
    t2d = jnp.asarray(flat.reshape(-1, PAGE_LANES))
    ref = np.asarray(paged_topk_score(t2d, jnp.asarray(q), nrows, dp))
    assert ref.shape == (B, nrows)
    acc = np.zeros((B, nrows), np.float32)  # left-to-right f32 oracle
    for d in range(dp):
        acc = acc + q[:, d][:, None] * x[:, d][None, :]
    assert np.array_equal(ref, acc)  # bitwise, not allclose
