"""Reads through the paged layouts, in plain `jax.numpy`.

Two callers keep a flat buffer of fixed-size pages on the device and
read it through these functions under their own `jit`:

- the paged sampling lane (dataflow/device.py, layout="paged"): ragged
  neighbour and weight gathers through the page indirection
  (`paged_gather`, `paged_gather_dequant` over `pack_bf16_words` pages)
  and the two-level quantized-CDF neighbour draw (`paged_page_search`
  over the page boundaries, then `paged_cdf_count` inside the page);
- the retrieval scan (retrieval/topk.py): `paged_topk_score`.

Every buffer is viewed `[M, PAGE_LANES]` (`_as_lane_rows`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the flat page buffers are staged as [M, PAGE_LANES] rows. Logical
# page_size must divide PAGE_LANES, so one page never straddles a row.
PAGE_LANES = 128


def _as_lane_rows(flat):
    """Flat 4-byte-dtype buffer → [M, PAGE_LANES] lane-row view (padded)."""
    flat = flat.reshape(-1)
    pad = (-flat.shape[0]) % PAGE_LANES
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, PAGE_LANES)


def paged_gather(table2d, fidx):
    """out[i, j] = flat(table2d)[fidx[i, j]] — ragged gather through the
    paged indirection. `table2d` is a [M, 128] lane-row view of a flat
    page buffer (`_as_lane_rows`); `fidx` int32 [W, k] flat element
    indices (page*page_size + slot)."""
    return table2d.reshape(-1)[fidx]


def pack_bf16_words(flat):
    """f32 1-D buffer → uint32 words, two bf16 values per word (low half
    = even index, high half = odd). This keeps quantized weight pages in
    the same 4-byte lane-row shape as every other page buffer. bf16 here
    is truncation-free f32 prefixes, so unpack (<< 16 + bitcast) is exact
    bf16 → f32."""
    flat = jnp.asarray(flat).reshape(-1)
    u16 = jax.lax.bitcast_convert_type(
        flat.astype(jnp.bfloat16), jnp.uint16
    ).astype(jnp.uint32)
    if u16.shape[0] % 2:
        u16 = jnp.pad(u16, (0, 1))
    pair = u16.reshape(-1, 2)
    return pair[:, 0] | (pair[:, 1] << 16)


def _unpack_bf16_word(word, odd):
    # select the half, re-widen to f32 by shifting into the high bits —
    # bf16 is a truncated f32, so this is the exact inverse of the pack.
    # Works on uint32 and int32 words alike: the mask drops whatever an
    # arithmetic >> smeared into the high half.
    half = jnp.where(odd, word >> 16, word) & 0xFFFF
    return jax.lax.bitcast_convert_type(half << 16, jnp.float32)


def paged_gather_dequant(table2d, fidx):
    """out[i, j] = bf16_unpack(flat(table2d))[fidx[i, j]] as f32 — the
    quantized-page twin of `paged_gather`. `table2d` is a [M, 128]
    lane-row view of a `pack_bf16_words` buffer (uint32, two bf16 per
    word); `fidx` indexes LOGICAL bf16 elements. Dequantize happens at
    the gather, so the pages hold half the bytes of the f32 plane."""
    fidx = fidx.astype(jnp.int32)
    word = table2d.reshape(-1)[fidx // 2]
    return _unpack_bf16_word(word, fidx % 2 == 1)


def paged_cdf_count(q2d, page, rbits, page_size: int):
    """In-page quantized-CDF inversion: out[i, j] = |{l < page_size :
    flat(q2d)[page[i, j]*page_size + l] <= rbits[i, j]}| — the slot count
    within the already-selected page. Padding lanes hold 0xFFFFFFFF so
    they count only at rbits == MAX (callers clamp by degree)."""
    flat = q2d.reshape(-1)
    base = page.astype(jnp.int32) * page_size
    lanes = base[..., None] + jnp.arange(page_size, dtype=jnp.int32)
    q = flat[lanes]  # [W, k, page_size]
    return (q <= rbits[..., None]).sum(axis=-1).astype(jnp.int32)


def paged_page_search(bound, pstart, npages, rbits, iters: int):
    """Per-node upper-bound search over the flat page-boundary array:
    returns [W, k] counts of the node's pages whose boundary (last valid
    quantized-CDF value) is <= rbits — i.e. the pages the draw skips
    entirely. Branchless binary search with a static iteration count
    (`iters` >= bit_length(max pages per node) + 1); pure integer math."""
    lo = jnp.broadcast_to(pstart[:, None].astype(jnp.int32), rbits.shape)
    hi = lo + jnp.broadcast_to(npages[:, None].astype(jnp.int32), rbits.shape)
    cap = bound.shape[0] - 1
    for _ in range(max(int(iters), 1)):
        active = lo < hi
        mid = (lo + hi) // 2
        le = bound[jnp.minimum(mid, cap)] <= rbits
        lo = jnp.where(active & le, mid + 1, lo)
        hi = jnp.where(active & ~le, mid, hi)
    return lo - pstart[:, None].astype(jnp.int32)


# ---------------------------------------------------------------------------
# Retrieval scoring (embedding top-K serving lane)
# ---------------------------------------------------------------------------


def paged_topk_score(table2d, q, nrows: int, dp: int):
    """scores[b, i] = <flat(table2d)[i*dp : (i+1)*dp], q[b, :dp]> — the
    brute-force retrieval scorer over a paged corpus.

    `table2d` is the [M, 128] lane-row view (`_as_lane_rows`) of a flat
    f32 buffer holding `nrows` packed dp-wide vectors; `q` is [B, dp]
    f32 queries. Returns [B, nrows] f32 scores.

    Bit-reproducibility contract (the retrieval parity oracle leans on
    it): the dot product accumulates STRICTLY left-to-right in f32 —
    acc = f32(acc + x[d] * q[d]) for d = 0..dp-1 — here and in the
    NumPy oracle (retrieval/topk.py), so scores are bit-identical to
    NumPy rather than at the mercy of a reduction order XLA is free to
    pick. The contract additionally REQUIRES operands with
    12-bit-truncated significands (retrieval/corpus.py quantize_sig12):
    LLVM contracts the mul+add into FMA non-uniformly on CPU (no HLO
    barrier or XLA flag stops it), and only exact products — which
    12x12-bit significands guarantee — make fma(x, q, acc) ==
    f32(x*q) + acc identically.

    ROADMAP S7 replaces this rank-1-update scan with one matmul.
    """
    q = q.astype(jnp.float32)
    flat = table2d.reshape(-1)[: nrows * dp]
    x = flat.astype(jnp.float32).reshape(nrows, dp)

    def body(d, acc):
        xcol = jax.lax.dynamic_index_in_dim(x, d, 1, keepdims=False)
        qcol = jax.lax.dynamic_index_in_dim(q, d, 1, keepdims=False)
        return acc + qcol[:, None] * xcol[None, :]

    acc = jnp.zeros((q.shape[0], nrows), jnp.float32)
    return jax.lax.fori_loop(0, dp, body, acc)
