"""Softmax attention of a block of queries under a mask of picked keys,
tile by tile on the chip (Pallas TPU): `seq_ops.masked_attention` and
`seq_ops.attention_share` where every extent is whole tiles.

A block's scores [B, G, R, t, S] never exist outside a tile: the forward
keeps a running max, sum and output per row and head (online softmax)
and leaves the output and the logsumexp; the backward makes a tile's
probabilities again from the logsumexp (flash attention), once, for all
three cotangents; a third, forward-only kernel sums a tile's
probabilities over the heads, which is the target of the indexer's KL.
One grid step holds one tile of keys and the R query heads of one
key/value head, so the mask's tile (int8, one for all heads) is read
once a group and becomes the additive float32 tile the heads share; a
tile the mask empties (`live` 0: at the cell, a run's tiles beyond the
block's own diagonal) runs nothing.

Arithmetic: q, k, v, the probabilities and the cotangents enter the MXU
as bfloat16 and accumulate in float32 — what XLA's default precision
makes of a float32 `einsum` on the TPU; max, sum, logsumexp, the output
accumulator, the shares and every cotangent are float32.

Off the TPU the same kernels run through the Pallas interpreter: which
lowering is used follows the platform the computation is placed on
(`jax.lax.platform_dependent`), nothing a caller sets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

LANES = 128
TILES = (512, 256, 128)  # a tile's rows or keys: the largest that divides
# what a dropped key's score becomes: exp(it - any max) is 0, and it minus
# itself is 0 where -inf would make a NaN
_DROPPED = -1e30
# resident at the cell's shape (8 heads, 512 rows, 512 keys, head 128):
# ~20 MB forward, ~24 MB backward; a v5e core has 128 MiB
_VMEM_LIMIT = 64 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _tile(extent: int, asked: int | None) -> int:
    return asked or next(t for t in TILES if extent % t == 0)


def _call(kernel, *, name, grid, in_specs, out_specs, out_shape, scratch=(), reduced=1):
    """`kernel` over `grid` with the `live` table prefetched into scalar
    memory: compiled by Mosaic on a TPU, interpreted anywhere else. The
    last `reduced` axes of the grid carry an accumulator."""

    def build(interpret):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=list(scratch),
            ),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * (len(grid) - reduced)
                + ("arbitrary",) * reduced,
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=interpret,
            name=name,
        )

    def run(*args):
        return jax.lax.platform_dependent(
            *args, tpu=build(False), default=build(True)
        )

    return run


def _traced_once(fn):
    """`fn(*arrays, scale, bq, bk)` under a `jax.jit` of its own: a model's
    layers, a `custom_vjp`'s primal and forward rule and a block's
    rematerialisation call a kernel at the same shapes many times (the
    cell: 128 calls of 12 kernels), and a jitted function's trace — and
    its lowering, inside one program — is made once for them all; a bare
    `pallas_call` traces its kernel at every call."""
    return jax.jit(fn, static_argnames=("scale", "bq", "bk"))


def _live(keep: Array, bq: int, bk: int) -> Array:
    """int32 [B * t/bq * S/bk]: whether a tile of the mask keeps any key."""
    batch, rows, keys = keep.shape
    tiles = keep.reshape(batch, rows // bq, bq, keys // bk, bk)
    return jnp.any(tiles, axis=(2, 4)).astype(jnp.int32).reshape(-1)


def _bias(keep_ref):
    """A mask tile (int8) as what is added to a score: 0 or `_DROPPED`."""
    return (1.0 - keep_ref[...].astype(jnp.float32)) * _DROPPED


def _across(column, width: int):
    """[rows, LANES], every lane the row's value -> [rows, width]."""
    return column if width == LANES else jnp.tile(column, (1, width // LANES))


# -- forward ---------------------------------------------------------------


def _forward_kernel(
    live_ref, q_ref, k_ref, v_ref, keep_ref, o_ref, lse_ref,
    m_ref, l_ref, acc_ref, bias_ref, *, scale: float,
):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nq, nk = pl.num_programs(2), pl.num_programs(3)
    heads, _, head_dim = q_ref.shape
    keys = k_ref.shape[0]

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _DROPPED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live_ref[(b * nq + qi) * nk + ki] != 0)
    def _():
        bias_ref[...] = _bias(keep_ref)
        k, v = k_ref[...], v_ref[...]
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[h], k, _NT, preferred_element_type=jnp.float32
            ) * scale + bias_ref[...]
            m_prev = m_ref[h]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _across(m_next, keys))
            alpha = jnp.exp(m_prev - m_next)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[h] = m_next
            acc_ref[h] = _across(alpha, head_dim) * acc_ref[h] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )

    @pl.when(ki == nk - 1)
    def _():
        for h in range(heads):
            total = l_ref[h]
            o_ref[h] = acc_ref[h] / _across(total, head_dim)
            lse_ref[h] = m_ref[h] + jnp.log(total)


@_traced_once
def _forward(live, q, k, v, keep, scale, bq, bk):
    """q [B, G, R, t, d], k, v [B, G, S, d] bfloat16, keep [B, t, S] int8
    and its `_live` -> o [B, G, R, t, d], the logsumexp [B, G, R, t],
    float32."""
    batch, groups, heads, rows, head_dim = q.shape
    keys = k.shape[2]
    per_rows = lambda b, g, qi, ki, live: (b, g, 0, qi, 0)  # noqa: E731
    per_keys = lambda b, g, qi, ki, live: (b, g, ki, 0)  # noqa: E731
    o, lse = _call(
        functools.partial(_forward_kernel, scale=scale),
        name="dsa_core_forward",
        grid=(batch, groups, rows // bq, keys // bk),
        in_specs=[
            pl.BlockSpec((None, None, heads, bq, head_dim), per_rows),
            pl.BlockSpec((None, None, bk, head_dim), per_keys),
            pl.BlockSpec((None, None, bk, head_dim), per_keys),
            pl.BlockSpec((None, bq, bk), lambda b, g, qi, ki, live: (b, qi, ki)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, heads, bq, head_dim), per_rows),
            pl.BlockSpec((None, None, heads, bq, LANES), per_rows),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(q.shape[:4] + (LANES,), jnp.float32),
        ],
        scratch=[
            pltpu.VMEM((heads, bq, LANES), jnp.float32),  # running max
            pltpu.VMEM((heads, bq, LANES), jnp.float32),  # running sum
            pltpu.VMEM((heads, bq, head_dim), jnp.float32),  # running output
            pltpu.VMEM((bq, bk), jnp.float32),  # the mask's tile, to add
        ],
    )(live, q, k, v, keep)
    return o, lse[..., 0]


# -- backward --------------------------------------------------------------


def _backward_kernel(
    live_ref, q_ref, k_ref, v_ref, keep_t_ref, do_ref, lse_ref, di_ref,
    dq_ref, dk_ref, dv_ref, bias_ref, *, scale: float,
):
    """A tile of keys (rows of every tile here) against a tile of queries
    (lanes): scores and probabilities are made transposed, so that the
    logsumexp and `di`, one a query, lie along the lanes as they are
    stored, and dk, dv are plain products."""
    b, ki, qi = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk, nq = pl.num_programs(2), pl.num_programs(3)
    heads, bq, _ = q_ref.shape

    @pl.when((ki == 0) & (qi == 0))
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(qi == 0)
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(live_ref[(b * nq + qi) * nk + ki] != 0)
    def _():
        bias_ref[...] = _bias(keep_t_ref)
        k, v = k_ref[...], v_ref[...]
        rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)
        dk = jnp.zeros(dk_ref.shape, jnp.float32)
        dv = jnp.zeros(dv_ref.shape, jnp.float32)
        for h in range(heads):
            q, do = q_ref[h], do_ref[h]
            s_t = jax.lax.dot_general(
                k, q, _NT, preferred_element_type=jnp.float32
            ) * scale + bias_ref[...]
            p_t = jnp.exp(s_t - lse_ref[h])
            dv += jnp.dot(p_t.astype(do.dtype), do, preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
            ds_t = p_t * (dp_t - di_ref[h])
            dk += jnp.dot(ds_t.astype(q.dtype), q, preferred_element_type=jnp.float32)
            dq_ref[h, rows, :] += jnp.dot(
                ds_t.T.astype(k.dtype), k, preferred_element_type=jnp.float32
            )
        dk_ref[...] += dk * scale
        dv_ref[...] += dv

    @pl.when((ki == nk - 1) & (qi == nq - 1))
    def _():
        dq_ref[...] *= scale


@_traced_once
def _backward(live, q, k, v, keep, do, lse, di, scale, bq, bk):
    """The cotangents of q, k, v (float32) from the forward's inputs, the
    output's cotangent (bfloat16), the logsumexp and `di = sum(o * do)`
    [B, G, R, t]."""
    batch, groups, heads, rows, head_dim = q.shape
    keys = k.shape[2]
    per_rows = lambda b, g, ki, qi, live: (b, g, 0, qi, 0)  # noqa: E731
    per_row = lambda b, g, ki, qi, live: (b, g, 0, 0, qi)  # noqa: E731
    per_keys = lambda b, g, ki, qi, live: (b, g, ki, 0)  # noqa: E731
    return _call(
        functools.partial(_backward_kernel, scale=scale),
        name="dsa_core_backward",
        grid=(batch, groups, keys // bk, rows // bq),
        reduced=2,
        in_specs=[
            pl.BlockSpec((None, None, heads, bq, head_dim), per_rows),
            pl.BlockSpec((None, None, bk, head_dim), per_keys),
            pl.BlockSpec((None, None, bk, head_dim), per_keys),
            pl.BlockSpec((None, bk, bq), lambda b, g, ki, qi, live: (b, ki, qi)),
            pl.BlockSpec((None, None, heads, bq, head_dim), per_rows),
            pl.BlockSpec((None, None, heads, 1, bq), per_row),
            pl.BlockSpec((None, None, heads, 1, bq), per_row),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, None, heads, rows, head_dim),
                lambda b, g, ki, qi, live: (b, g, 0, 0, 0),
            ),
            pl.BlockSpec((None, None, bk, head_dim), per_keys),
            pl.BlockSpec((None, None, bk, head_dim), per_keys),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(k.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        scratch=[pltpu.VMEM((bk, bq), jnp.float32)],
    )(
        live, q, k, v, jnp.swapaxes(keep, 1, 2), do,
        lse[..., None, :], di[..., None, :],
    )


# -- the pair --------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attention(q, k, v, keep, scale, bq, bk):
    return _attention_fwd(q, k, v, keep, scale, bq, bk)[0]


def _attention_fwd(q, k, v, keep, scale, bq, bk):
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    live, keep = _live(keep, bq, bk), keep.astype(jnp.int8)
    o, lse = _forward(live, q, k, v, keep, scale, bq, bk)
    return (o, lse), (live, q, k, v, keep, o, lse)


def _attention_bwd(scale, bq, bk, kept, cotangents):
    live, q, k, v, keep, o, lse = kept
    do, _ = cotangents  # the logsumexp feeds a constant: its cotangent is no one's
    di = jnp.sum(o * do, axis=-1)
    dq, dk, dv = _backward(
        live, q, k, v, keep, do.astype(jnp.bfloat16), lse, di, scale, bq, bk
    )
    return dq, dk, dv, None


_attention.defvjp(_attention_fwd, _attention_bwd)


def attention(
    q: Array, k: Array, v: Array, keep: Array, scale: float,
    block_q: int | None = None, block_k: int | None = None,
):
    """softmax(`scale` q k^T under `keep`) v and the logsumexp of the
    kept scores; differentiable in q, k, v through the output (the
    logsumexp's cotangent is dropped: take it as a constant).

    q [B, G, R, t, d], k, v [B, G, S, d] float32, keep [B, t, S] bool
    with at least one key a row; t, S and d whole tiles
    (`seq_ops.attends_by_tiles`). Returns
    (o [B, G, R, t, d], lse [B, G, R, t]) float32. The tiles are the
    largest of `TILES` that divide t and S unless given.
    """
    bq, bk = _tile(q.shape[3], block_q), _tile(k.shape[2], block_k)
    o, lse = _attention(q, k, v, keep, float(scale), bq, bk)
    return o, jax.lax.stop_gradient(lse)


# -- the heads' shares, summed ------------------------------------------------


def _share_kernel(live_ref, q_ref, k_ref, keep_ref, lse_ref, p_ref, *, scale: float, total: int):
    b, qi, ki, g = (pl.program_id(i) for i in range(4))
    nq, nk, groups = (pl.num_programs(i) for i in range(1, 4))
    heads = q_ref.shape[0]
    keys = k_ref.shape[0]
    live = live_ref[(b * nq + qi) * nk + ki] != 0

    @pl.when(g == 0)
    def _():
        p_ref[...] = jnp.zeros_like(p_ref)

    @pl.when(live)
    def _():
        k = k_ref[...]
        share = jnp.zeros(p_ref.shape, jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[h], k, _NT, preferred_element_type=jnp.float32
            ) * scale
            share += jnp.exp(s - _across(lse_ref[h], keys))
        p_ref[...] += share

    @pl.when(live & (g == groups - 1))
    def _():
        p_ref[...] = jnp.where(keep_ref[...] != 0, p_ref[...] * (1.0 / total), 0.0)


@_traced_once
def _share(live, q, k, keep, lse, scale, bq, bk):
    """q [B, G, R, t, d], k [B, G, S, d] bfloat16, keep [B, t, S] int8 and
    its `_live`, lse [B, G, R, t, LANES] -> float32 [B, t, S]."""
    batch, groups, heads, rows, head_dim = q.shape
    keys = k.shape[2]
    per_rows = lambda b, qi, ki, g, live: (b, g, 0, qi, 0)  # noqa: E731
    per_tile = lambda b, qi, ki, g, live: (b, qi, ki)  # noqa: E731
    return _call(
        functools.partial(_share_kernel, scale=scale, total=groups * heads),
        name="dsa_aux_share",
        grid=(batch, rows // bq, keys // bk, groups),
        in_specs=[
            pl.BlockSpec((None, None, heads, bq, head_dim), per_rows),
            pl.BlockSpec((None, None, bk, head_dim), lambda b, qi, ki, g, live: (b, g, ki, 0)),
            pl.BlockSpec((None, bq, bk), per_tile),
            pl.BlockSpec((None, None, heads, bq, LANES), per_rows),
        ],
        out_specs=pl.BlockSpec((None, bq, bk), per_tile),
        out_shape=jax.ShapeDtypeStruct(keep.shape, jnp.float32),
    )(live, q, k, keep, lse)


def share(
    q: Array, k: Array, keep: Array, lse: Array, scale: float,
    block_q: int | None = None, block_k: int | None = None,
) -> Array:
    """The heads' attention probabilities on the kept keys, averaged:
    `p[t, s] = mean over the G R heads of exp(scale q_h[t] . k[s] -
    lse_h[t])`, 0 off `keep`. No gradient passes (a constant).

    q [B, G, R, t, d], k [B, G, S, d], keep [B, t, S] bool, lse
    [B, G, R, t] as `attention` left it. Returns float32 [B, t, S].
    """
    bq, bk = _tile(q.shape[3], block_q), _tile(k.shape[2], block_k)
    q, k, keep, lse = jax.lax.stop_gradient((q, k, keep, lse))
    return _share(
        _live(keep, bq, bk), q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        keep.astype(jnp.int8),
        jnp.broadcast_to(lse[..., None], lse.shape + (LANES,)),
        float(scale), bq, bk,
    )
