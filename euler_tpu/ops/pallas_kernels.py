"""Pallas TPU kernels for the message-passing hot path.

`gather_weighted_sum(x, slots, w)` fuses the neighbor gather with the
weighted segment reduction: out[i] = Σ_j w[i, j] · x[slots[i, j]].

Every euler_tpu dataflow emits *grid-structured* blocks (each dst row owns a
fixed strip of D neighbor slots), so the aggregation is this one primitive —
it subsumes SAGE-mean (w = mask/deg), GCN (w = norm products), and weighted
sums, without materializing the [E, F] message tensor in HBM. The kernel
keeps the feature table in HBM, DMA-gathers each row's D neighbor vectors
into VMEM scratch, and reduces them with a (1×D)·(D×F) matmul on the MXU.

Backward is pure JAX (scatter-add of w·g, and g·x for the weights) via
custom_vjp — gradient layout matches mp_ops (reference mp_ops.py:39-62).

CPU/interpret fallback makes the same entry point usable in tests.

The paged device-sampling lane (dataflow/device.py, layout="paged") adds
three more entry points with the same impl discipline — `paged_gather`
and `paged_gather_dequant` (ragged neighbor/weight gather through a
fixed-size-page indirection, the Ragged-Paged-Attention access shape) and
`paged_cdf_count` (the in-page step of the two-level quantized-CDF
neighbor draw). Each carries a jitted jnp reference (`impl="xla"`) that is
the `auto` choice and the A/B oracle; the Pallas forms are exposed via
`impl='pallas'`. chip_smoke.py compiles every Pallas form on the chip and
compares it with its reference — that run, not the interpreter tests in
tests/test_pallas.py (which only pin the semantics on CPU), is the
evidence that Mosaic accepts them. The page-table binary search
(`paged_page_search`) is scalar log-depth work that stays plain XLA in
every impl — only the bandwidth-bound page reads are kernel territory.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 8  # dst rows per grid step


def _kernel(k, x_ref, slot_ref, w_ref, out_ref, scratch, sems):
    # scratch [2, d, k, 128] double buffer: row i+1's neighbor-row DMAs
    # are in flight while row i reduces on the MXU. Statically unrolled
    # (TILE, d, k are compile-time), so buffer indices are constants.
    #
    # Wide features (f > 128) ride the SAME one-lane-tile DMA shape that
    # Mosaic accepts at f <= 128: the caller reshapes the table to
    # [n_src*k, 128] (k column chunks per logical row) and each neighbor
    # issues k row copies from slot*k+c — a two-level gather instead of
    # an unaligned (1, k*128) HBM slice, which Mosaic rejects.
    d = scratch.shape[1]

    def copies(i, buf):
        for j in range(d):
            for c in range(k):
                yield pltpu.make_async_copy(
                    x_ref.at[slot_ref[i, j] * k + c],
                    scratch.at[buf, j, c],
                    sems.at[buf, j, c],
                )

    start = lambda i, buf: [cp.start() for cp in copies(i, buf)]
    wait = lambda i, buf: [cp.wait() for cp in copies(i, buf)]

    start(0, 0)
    for i in range(TILE):
        if i + 1 < TILE:
            start(i + 1, (i + 1) % 2)
        wait(i, i % 2)
        out_ref[i, :] = jnp.dot(
            w_ref[i, :].reshape(1, d),
            scratch[i % 2].reshape(d, k * 128),
            preferred_element_type=jnp.float32,
        )[0]


def _pallas_forward(x, slots, w, interpret: bool):
    n_dst, d = slots.shape
    f = x.shape[1]
    # feature width padded to the 128-lane register width — narrower or
    # non-multiple rows fail Mosaic's tiling, and the DMA copies stay
    # row-aligned
    padf = (-f) % 128
    if padf:
        x = jnp.pad(x, ((0, 0), (0, padf)))
    pad = (-n_dst) % TILE
    if pad:
        slots = jnp.pad(slots, ((0, pad), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
    n = slots.shape[0]
    fp = f + padf
    k = fp // 128
    x = x.astype(jnp.float32).reshape(-1, 128)  # [n_src*k, 128]
    out = pl.pallas_call(
        functools.partial(_kernel, k),
        grid=(n // TILE,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # x stays in HBM
            pl.BlockSpec((TILE, d), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((TILE, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (TILE, fp), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((n, fp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, d, k, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2, d, k)),
        ],
        interpret=interpret,
    )(x, slots, w.astype(jnp.float32))
    return out[:n_dst, :f]


def _reference_forward(x, slots, w):
    gathered = jnp.take(x, slots, axis=0)  # [N, D, F]
    return jnp.einsum("nd,ndf->nf", w, gathered)


# Where `auto` picks the DMA kernel over XLA's gather+einsum on TPU.
# Neither side of the boundary has a timing on the chip (PERF.md); choosing
# it from a measurement is ROADMAP S5. f > 128 rides the chunked gather
# (k-fold DMA descriptors per neighbor) via impl='pallas'.
_PALLAS_AUTO_MAX_F = 128
_PALLAS_MIN_DST = 4096


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gather_weighted_sum(x, slots, w, impl: str = "auto"):
    """out[i] = Σ_j w[i,j] · x[slots[i,j]].

    impl: 'pallas' | 'interpret' | 'xla' | 'auto'. 'auto' picks the DMA
    kernel inside the region above on TPU and XLA elsewhere; an explicit
    'pallas' never silently falls back.
    """
    return _forward(x, slots, w, impl)


def _forward(x, slots, w, impl):
    f = x.shape[1]
    if impl == "auto":
        on_tpu = jax.devices()[0].platform == "tpu"
        impl = (
            "pallas"
            if on_tpu
            and 64 < f <= _PALLAS_AUTO_MAX_F
            and slots.shape[0] >= _PALLAS_MIN_DST
            else "xla"
        )
    if impl == "xla":
        return _reference_forward(x, slots, w)
    return _pallas_forward(x, slots, w, interpret=(impl == "interpret"))


def _fwd(x, slots, w, impl):
    return _forward(x, slots, w, impl), (x, slots, w)


def _bwd(impl, res, g):
    x, slots, w = res
    # dL/dx: scatter-add of w·g into the gathered rows. Accumulate in f32
    # (w is f32, and bf16 scatter-add both loses precision and is a dtype
    # mismatch JAX will reject), then cast the cotangent back to x.dtype.
    contrib = (
        w[:, :, None].astype(jnp.float32) * g[:, None, :].astype(jnp.float32)
    )  # [N, D, F]
    dx = (
        jnp.zeros(x.shape, jnp.float32)
        .at[slots.reshape(-1)]
        .add(contrib.reshape(-1, x.shape[1]))
        .astype(x.dtype)
    )
    # dL/dw: per-slot inner product with g
    gathered = jnp.take(x, slots, axis=0)
    dw = jnp.einsum(
        "nf,ndf->nd",
        g.astype(jnp.float32),
        gathered.astype(jnp.float32),
    ).astype(w.dtype)
    return dx, None, dw


gather_weighted_sum.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# Paged ragged-indirection kernels (device-resident sampling lane)
# ---------------------------------------------------------------------------

# the flat page buffers are viewed [M, PAGE_LANES] so every DMA is a
# one-row, lane-aligned copy — the exact shape Mosaic already accepts in
# the gather_weighted_sum chunked path above. Logical page_size must
# divide PAGE_LANES, so one page never straddles a lane row.
PAGE_LANES = 128


def _as_lane_rows(flat):
    """Flat 4-byte-dtype buffer → [M, PAGE_LANES] lane-row view (padded)."""
    flat = flat.reshape(-1)
    pad = (-flat.shape[0]) % PAGE_LANES
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, PAGE_LANES)


def _paged_gather_kernel(k, table_ref, fidx_ref, out_ref, scratch, sems):
    # per (row i, draw j): DMA the lane row holding flat element
    # fidx[i, j] into double-buffered scratch, then select its lane with
    # an iota compare-sum (vector select — no dynamic lane extract).
    def copies(i, buf):
        for j in range(k):
            yield pltpu.make_async_copy(
                table_ref.at[fidx_ref[i, j] // PAGE_LANES],
                scratch.at[buf, j],
                sems.at[buf, j],
            )

    start = lambda i, buf: [cp.start() for cp in copies(i, buf)]  # noqa: E731
    wait = lambda i, buf: [cp.wait() for cp in copies(i, buf)]  # noqa: E731

    start(0, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, PAGE_LANES), 1)
    for i in range(TILE):
        if i + 1 < TILE:
            start(i + 1, (i + 1) % 2)
        wait(i, i % 2)
        vals = []
        for j in range(k):
            lane = fidx_ref[i, j] % PAGE_LANES
            row = scratch[i % 2, j].reshape(1, PAGE_LANES)
            vals.append(jnp.sum(jnp.where(lanes == lane, row, 0)))
        out_ref[i, :] = jnp.stack(vals)


def _paged_gather_pallas(table2d, fidx, interpret: bool):
    n, k = fidx.shape
    pad = (-n) % TILE
    if pad:
        fidx = jnp.pad(fidx, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_paged_gather_kernel, k),
        grid=(fidx.shape[0] // TILE,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # pages stay in HBM
            pl.BlockSpec(
                (TILE, k), lambda i: (i, 0), memory_space=pltpu.SMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (TILE, k), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((fidx.shape[0], k), table2d.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, k, PAGE_LANES), table2d.dtype),
            pltpu.SemaphoreType.DMA((2, k)),
        ],
        interpret=interpret,
    )(table2d, fidx.astype(jnp.int32))
    return out[:n]


def _paged_impl(impl: str) -> str:
    # the paged kernels compile on the chip and match the reference
    # (chip_smoke.py) but have no timing there yet, so `auto` routes
    # everywhere to the jitted jnp reference. 'pallas'/'interpret' stay
    # explicit; choosing between them is ROADMAP S5.
    if impl == "auto":
        return "xla"
    if impl not in ("xla", "pallas", "interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    return impl


def paged_gather(table2d, fidx, impl: str = "auto"):
    """out[i, j] = flat(table2d)[fidx[i, j]] — ragged gather through the
    paged indirection. `table2d` is a [M, 128] lane-row view of a flat
    page buffer (`_as_lane_rows`); `fidx` int32 [W, k] flat element
    indices (page*page_size + slot). 4-byte dtypes only."""
    impl = _paged_impl(impl)
    if impl == "xla":
        flat = table2d.reshape(-1)
        return flat[fidx]
    return _paged_gather_pallas(table2d, fidx, interpret=(impl == "interpret"))


def pack_bf16_words(flat):
    """f32 1-D buffer → uint32 words, two bf16 values per word (low half
    = even index, high half = odd). This keeps quantized feature pages in
    the SAME 4-byte lane-row shape the validated DMA path uses — bf16's
    native (16, 128) min tile never enters the kernel; the u32 word is
    split after the lane select. bf16 here is truncation-free f32
    prefixes, so unpack (<< 16 + bitcast) is exact bf16 → f32."""
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


def _paged_gather_dequant_kernel(k, table_ref, fidx_ref, out_ref, scratch,
                                 sems):
    # same DMA/iota-select shape as _paged_gather_kernel, but fidx is a
    # logical bf16 element index: the holding 32-bit word sits at
    # fidx // 2, and the word is unpacked in-kernel (the RPA playbook:
    # compact pages in HBM, pay decode next to the gather, not on host).
    # Two Mosaic limits (v5e, jaxlib 0.9.0) shape the body: it has no
    # reduction over unsigned integers, so the words arrive as int32, and
    # tpu.bitcast takes vectors only, so the whole lane row is unpacked to
    # f32 BEFORE the select-sum picks one lane (exact: one non-zero term).
    def copies(i, buf):
        for j in range(k):
            yield pltpu.make_async_copy(
                table_ref.at[(fidx_ref[i, j] // 2) // PAGE_LANES],
                scratch.at[buf, j],
                sems.at[buf, j],
            )

    start = lambda i, buf: [cp.start() for cp in copies(i, buf)]  # noqa: E731
    wait = lambda i, buf: [cp.wait() for cp in copies(i, buf)]  # noqa: E731

    start(0, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, PAGE_LANES), 1)
    for i in range(TILE):
        if i + 1 < TILE:
            start(i + 1, (i + 1) % 2)
        wait(i, i % 2)
        vals = []
        for j in range(k):
            lane = (fidx_ref[i, j] // 2) % PAGE_LANES
            row = scratch[i % 2, j].reshape(1, PAGE_LANES)
            vals_f32 = _unpack_bf16_word(row, fidx_ref[i, j] % 2 == 1)
            vals.append(jnp.sum(jnp.where(lanes == lane, vals_f32, 0.0)))
        out_ref[i, :] = jnp.stack(vals)


def _paged_gather_dequant_pallas(table2d, fidx, interpret: bool):
    n, k = fidx.shape
    pad = (-n) % TILE
    if pad:
        fidx = jnp.pad(fidx, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_paged_gather_dequant_kernel, k),
        grid=(fidx.shape[0] // TILE,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # packed pages stay in HBM
            pl.BlockSpec(
                (TILE, k), lambda i: (i, 0), memory_space=pltpu.SMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (TILE, k), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((fidx.shape[0], k), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, k, PAGE_LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2, k)),
        ],
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(table2d, jnp.int32), fidx.astype(jnp.int32))
    return out[:n]


def paged_gather_dequant(table2d, fidx, impl: str = "auto"):
    """out[i, j] = bf16_unpack(flat(table2d))[fidx[i, j]] as f32 — the
    quantized-page twin of `paged_gather`. `table2d` is a [M, 128]
    lane-row view of a `pack_bf16_words` buffer (uint32, two bf16 per
    word); `fidx` indexes LOGICAL bf16 elements. Dequantize happens at
    the gather (in-kernel for 'pallas'), so HBM and DMA bytes are half
    the f32 path. Same impl discipline as paged_gather: 'auto' → the
    jitted jnp reference."""
    impl = _paged_impl(impl)
    fidx = fidx.astype(jnp.int32)
    if impl == "xla":
        flat = table2d.reshape(-1)
        word = flat[fidx // 2]
        return _unpack_bf16_word(word, fidx % 2 == 1)
    return _paged_gather_dequant_pallas(
        table2d, fidx, interpret=(impl == "interpret")
    )


def _paged_count_kernel(k, page_size, q_ref, page_ref, r_ref, out_ref,
                        scratch, sems):
    # per (row i, draw j): DMA the lane row holding page page_ref[i, j]
    # (pages are page_size-aligned, page_size | PAGE_LANES, so a page
    # never straddles rows), then count the page's lanes with q <= r.
    def copies(i, buf):
        for j in range(k):
            yield pltpu.make_async_copy(
                q_ref.at[(page_ref[i, j] * page_size) // PAGE_LANES],
                scratch.at[buf, j],
                sems.at[buf, j],
            )

    start = lambda i, buf: [cp.start() for cp in copies(i, buf)]  # noqa: E731
    wait = lambda i, buf: [cp.wait() for cp in copies(i, buf)]  # noqa: E731

    start(0, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, PAGE_LANES), 1)
    for i in range(TILE):
        if i + 1 < TILE:
            start(i + 1, (i + 1) % 2)
        wait(i, i % 2)
        vals = []
        for j in range(k):
            lane0 = (page_ref[i, j] * page_size) % PAGE_LANES
            row = scratch[i % 2, j].reshape(1, PAGE_LANES)
            sel = (lanes >= lane0) & (lanes < lane0 + page_size)
            vals.append(
                jnp.sum(jnp.where(sel & (row <= r_ref[i, j]), 1, 0))
            )
        out_ref[i, :] = jnp.stack(vals).astype(jnp.int32)


def _paged_count_pallas(q2d, page, rbits, page_size: int, interpret: bool):
    n, k = page.shape
    pad = (-n) % TILE
    if pad:
        page = jnp.pad(page, ((0, pad), (0, 0)))
        rbits = jnp.pad(rbits, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_paged_count_kernel, k, page_size),
        grid=(page.shape[0] // TILE,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # quantized CDF in HBM
            pl.BlockSpec(
                (TILE, k), lambda i: (i, 0), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec(
                (TILE, k), lambda i: (i, 0), memory_space=pltpu.SMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (TILE, k), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((page.shape[0], k), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((2, k, PAGE_LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((2, k)),
        ],
        interpret=interpret,
    )(q2d, page.astype(jnp.int32), rbits)
    return out[:n]


def paged_cdf_count(q2d, page, rbits, page_size: int, impl: str = "auto"):
    """In-page quantized-CDF inversion: out[i, j] = |{l < page_size :
    flat(q2d)[page[i, j]*page_size + l] <= rbits[i, j]}| — the slot count
    within the already-selected page. Padding lanes hold 0xFFFFFFFF so
    they count only at rbits == MAX (callers clamp by degree)."""
    impl = _paged_impl(impl)
    if impl == "xla":
        flat = q2d.reshape(-1)
        base = page.astype(jnp.int32) * page_size
        lanes = base[..., None] + jnp.arange(page_size, dtype=jnp.int32)
        q = flat[lanes]  # [W, k, page_size]
        return (q <= rbits[..., None]).sum(axis=-1).astype(jnp.int32)
    return _paged_count_pallas(
        q2d, page, rbits, page_size, interpret=(impl == "interpret")
    )


def paged_page_search(bound, pstart, npages, rbits, iters: int):
    """Per-node upper-bound search over the flat page-boundary array:
    returns [W, k] counts of the node's pages whose boundary (last valid
    quantized-CDF value) is <= rbits — i.e. the pages the draw skips
    entirely. Branchless binary search with a static iteration count
    (`iters` >= bit_length(max pages per node) + 1); pure integer math,
    so it is bit-identical across impls by construction and stays plain
    XLA (log-depth scalar work — not kernel territory)."""
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

    Plain XLA: a Pallas kernel over this layout needs a (B, 8*128/dp)
    output block, below Mosaic's (8, 128) tile, and a lane-splitting
    reshape inside the kernel. ROADMAP S4 replaces this rank-1-update
    scan with one matmul.
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
