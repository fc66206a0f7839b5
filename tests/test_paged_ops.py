"""The paged readers (ops/paged_ops.py) against NumPy."""

import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu.ops.paged_ops import (
    PAGE_LANES,
    _as_lane_rows,
    pack_bf16_words,
    paged_cdf_count,
    paged_gather,
    paged_gather_dequant,
    paged_page_search,
    paged_topk_score,
)


@pytest.mark.parametrize("plane", ["int32", "float32", "bf16_words"])
def test_paged_gather_matches_numpy(rng, plane):
    """The int32 neighbour plane, the f32 weight plane and the packed
    bf16 weight plane (two values a word, odd and even logical indices,
    exact bf16 round trip), each through its reader."""
    n = 701  # odd: the last word holds one value
    fidx = rng.integers(0, n, (11, 3))
    fidx[0] = [0, 1, n - 1]  # even, odd, and the half-filled last word
    if plane == "bf16_words":
        vals = rng.normal(size=n).astype(np.float32)
        table = _as_lane_rows(pack_bf16_words(jnp.asarray(vals)))
        assert table.dtype == jnp.uint32 and table.shape[1] == PAGE_LANES
        out = paged_gather_dequant(table, jnp.asarray(fidx, jnp.int32))
        want = np.asarray(
            jnp.asarray(vals).astype(jnp.bfloat16).astype(jnp.float32)
        )[fidx]
    else:
        flat = rng.integers(0, 1000, n).astype(plane)
        table = _as_lane_rows(jnp.asarray(flat))
        out = paged_gather(table, jnp.asarray(fidx, jnp.int32))
        want = flat[fidx]
    assert out.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(out), want)


@pytest.mark.parametrize("page_size", [8, 16])
def test_paged_cdf_count_with_page_search_equals_dense_row_count(
    rng, page_size
):
    """In-page CDF inversion == NumPy, and composed with the
    page-boundary search it reproduces the dense full-row count — the
    bit-identity the device lanes rely on. Column 0 draws
    rbits == 0xFFFFFFFF, where the padding lanes count too and the
    caller's clamp by degree decides."""
    P, draws = page_size, 6
    deg = np.array([5, 21, 0, 8, 16])
    npages = -(-deg // P)
    ps = np.concatenate([[0], np.cumsum(npages)]).astype(np.int64)
    total = int(ps[-1])
    flat_q = np.full(total * P, 0xFFFFFFFF, np.uint32)
    qrows = {}
    for n in range(len(deg)):
        if deg[n] == 0:
            continue
        cum = np.cumsum(rng.random(deg[n]))
        q = np.floor(cum / cum[-1] * (2**32 - 1)).astype(np.uint64)
        flat_q[ps[n] * P : ps[n] * P + deg[n]] = q.astype(np.uint32)
        qrows[n] = q.astype(np.uint32)
    bound = flat_q.reshape(total, P).max(axis=1)
    q2d = _as_lane_rows(jnp.asarray(flat_q))
    r_np = rng.integers(0, 2**32, (len(deg), draws), dtype=np.uint64).astype(
        np.uint32
    )
    r_np[:, 0] = 0xFFFFFFFF
    r = jnp.asarray(r_np)
    npg = jnp.asarray(npages, jnp.int32)
    pstart = jnp.asarray(ps[:-1], jnp.int32)
    pg = paged_page_search(jnp.asarray(bound), pstart, npg, r, 6)
    pgc = jnp.minimum(pg, jnp.maximum(npg[:, None] - 1, 0))
    page = jnp.minimum(pstart[:, None] + pgc, total - 1)
    cnt = np.asarray(paged_cdf_count(q2d, page, r, P))
    lanes = np.asarray(page)[..., None] * P + np.arange(P)
    np.testing.assert_array_equal(
        cnt, (flat_q[lanes] <= r_np[..., None]).sum(-1)
    )
    idx = np.minimum(
        np.asarray(pgc) * P + cnt, np.maximum(deg[:, None] - 1, 0)
    )
    for n, q in qrows.items():  # dense full-row oracle
        pad = np.full(int(npages[n]) * P - deg[n], 0xFFFFFFFF, np.uint32)
        row = np.concatenate([q, pad])
        assert cnt[n, 0] == P and idx[n, 0] == deg[n] - 1
        for j in range(draws):
            want = min(int((row <= r_np[n, j]).sum()), deg[n] - 1)
            assert want == idx[n, j], (n, j, want, idx[n, j])


def test_paged_topk_score_matches_left_to_right_oracle_bitwise(rng):
    """The paged retrieval scorer == a strict left-to-right NumPy
    accumulation, BITWISE.  Operands carry 12-bit-truncated significands
    (retrieval quantize_sig12 canon) so every product is exact in f32
    and LLVM's FMA contraction is a semantic no-op — without that,
    parity is at the compiler's mercy."""
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
