"""Softmax attention tile by tile on the chip (Pallas TPU), where every
extent is whole tiles: of a block of queries under a mask of picked keys
(`seq_ops.masked_attention`, `seq_ops.attention_share`), and of a whole
sequence under the causal line and a sliding window
(`seq_ops.blockwise_causal_attention`; there the head may also be the
half tile, 64).

Scores [B, G, R, t, S] never exist outside a tile: a forward kernel
keeps a running max, sum and output per row and head (online softmax)
and leaves the output and the logsumexp; a backward kernel makes a tile's
probabilities again from the logsumexp (flash attention). One grid step
holds one tile of keys and the R query heads of one key/value head, so
what the heads share of a tile (its keys, its values, what its mask adds
to a score) is fetched or made once a group.

Under a mask of picked keys the mask's tile (int8, one for all heads) is
read once a group and becomes the additive float32 tile the heads share;
a tile the mask empties (`live` 0: at the cell, a run's tiles beyond the
block's own diagonal) runs nothing; one backward kernel makes all three
cotangents and holds a head's dq [R, t, d] meanwhile; a third,
forward-only kernel sums a tile's probabilities over the heads, which is
the target of the indexer's KL.

Under the causal line and a window no mask is read and no `live` table
made: which tiles of keys a tile of queries is given, which of them are
run bare and which get an additive tile made from two iotas follows from
the tiles' places in the grid (`_reach`, `_by_case`); one call covers the
whole sequence, and the backward is two kernels (dk and dv; dq), so that
nothing sequence-long is held on the chip.

Arithmetic, everywhere: q, k, v, the probabilities and the cotangents
enter the MXU as bfloat16 and accumulate in float32 — what XLA's default
precision makes of a float32 `einsum` on the TPU; max, sum, logsumexp,
the output accumulator, the shares and every cotangent are float32.

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


def _call(
    kernel, *, name, grid, in_specs, out_specs, out_shape, scratch=(), reduced=1, prefetch=1,
    interpreted=None,
):
    """`kernel` over `grid` with its first `prefetch` arguments (the `live` table) prefetched
    into scalar memory: compiled by Mosaic on a TPU, interpreted anywhere else (`interpreted`
    there, where given). The last `reduced` axes of the grid carry an accumulator."""

    def build(interpret):
        return pl.pallas_call(
            interpreted if interpret and interpreted else kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=prefetch, grid=grid, in_specs=in_specs,
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


def _traced_once(*static: str):
    """`fn(*arrays, *static)` under a `jax.jit` of its own: a model's
    layers, a `custom_vjp`'s primal and forward rule and a block's
    rematerialisation call a kernel at the same shapes many times (the
    cell: 128 calls of 12 kernels), and a jitted function's trace — and
    its lowering, inside one program — is made once for them all; a bare
    `pallas_call` traces its kernel at every call."""
    return functools.partial(jax.jit, static_argnames=static)


def _live(keep: Array, bq: int, bk: int) -> Array:
    """int32 [B * t/bq * S/bk]: whether a tile of the mask keeps any key."""
    batch, rows, keys = keep.shape
    tiles = keep.reshape(batch, rows // bq, bq, keys // bk, bk)
    return jnp.any(tiles, axis=(2, 4)).astype(jnp.int32).reshape(-1)


def _bias(keep_ref):
    """A mask tile (int8) as what is added to a score: 0 or `_DROPPED`."""
    return (1.0 - keep_ref[...].astype(jnp.float32)) * _DROPPED


def _across(column, width: int):
    """[rows, LANES], every lane the row's value -> [rows, width]: whole
    tiles of lanes, or the half tile a head of 64 is."""
    if width <= LANES:
        return column if width == LANES else column[:, :width]
    return jnp.tile(column, (1, width // LANES))


# -- forward ---------------------------------------------------------------


def _start(m_ref, l_ref, acc_ref):
    """Before a tile of queries' first tile of keys."""
    m_ref[...] = jnp.full_like(m_ref, _DROPPED)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _attend(q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref, acc_ref, scale: float):
    """One tile of keys into every head's running max, sum and output;
    `bias_ref` is added to each head's scores, None where the tile drops
    no key."""
    heads, _, head_dim = q_ref.shape
    keys = k_ref.shape[0]
    k, v = k_ref[...], v_ref[...]
    for h in range(heads):
        s = jax.lax.dot_general(
            q_ref[h], k, _NT, preferred_element_type=jnp.float32
        ) * scale
        if bias_ref is not None:
            s = s + bias_ref[...]
        m_prev = m_ref[h]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _across(m_next, keys))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[h] = m_next
        acc_ref[h] = _across(alpha, head_dim) * acc_ref[h] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )


def _leave(o_ref, lse_ref, m_ref, l_ref, acc_ref, lse_along_lanes: bool = False):
    """After a tile of queries' last tile of keys. The logsumexp is left
    as it was kept, every lane a row's value [rows, LANES], or one row a
    head [1, rows], along the lanes as a backward kernel reads it."""
    heads, _, head_dim = o_ref.shape
    for h in range(heads):
        total = l_ref[h]
        o_ref[h] = acc_ref[h] / _across(total, head_dim)
        lse = m_ref[h] + jnp.log(total)
        lse_ref[h] = lse.T[:1] if lse_along_lanes else lse


def _forward_kernel(
    live_ref, q_ref, k_ref, v_ref, keep_ref, o_ref, lse_ref,
    m_ref, l_ref, acc_ref, bias_ref, *, scale: float,
):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nq, nk = pl.num_programs(2), pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        _start(m_ref, l_ref, acc_ref)

    @pl.when(live_ref[(b * nq + qi) * nk + ki] != 0)
    def _():
        bias_ref[...] = _bias(keep_ref)
        _attend(q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref, acc_ref, scale)

    @pl.when(ki == nk - 1)
    def _():
        _leave(o_ref, lse_ref, m_ref, l_ref, acc_ref)


@_traced_once("scale", "bq", "bk")
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


@_traced_once("scale", "bq", "bk")
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


@_traced_once("scale", "bq", "bk")
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


# -- the mask from positions ---------------------------------------------------
#
# Causal attention of a whole sequence, with or without a window: query t
# sees key s iff `t - window < s <= t`. Queries and keys are cut into
# tiles of one size; a tile of queries sees the `_reach` tiles of keys
# that end at its own, and how a tile of keys lies to it follows from
# `delta`, the query tile's index minus the key tile's: before key 0 (not
# run, nothing fetched: the index map stays on the tile it held), cut by
# the causal line (`delta` 0) or by the window's far edge (`delta >=
# window // tile`): an additive tile made from two iotas, or wholly seen:
# nothing added at all (`_by_case`). A row's own key is in the last tile it is
# given, so a row a cut tile leaves with no key is put right there.


def _reach(length: int, tile: int, window: int) -> int:
    """Tiles of keys a tile of queries sees, its own among them."""
    return min(length // tile, -(-(window - 1) // tile) + 1)


def _position_bias(bias_ref, delta, window: int, keys_by_rows: bool):
    """What a tile adds to its scores, 0 or `_DROPPED`: [queries, keys],
    or [keys, queries] with `keys_by_rows`."""
    tile = bias_ref.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, bias_ref.shape, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, bias_ref.shape, 1)
    # the key's position minus the query's
    ahead = (rows - lanes if keys_by_rows else lanes - rows) - delta * tile
    bias_ref[...] = jnp.where((ahead <= 0) & (ahead > -window), 0.0, _DROPPED)


def _by_case(live, delta, window: int, bias_ref, keys_by_rows: bool, tile_of):
    """`tile_of(bias_ref or None)` for a tile that is run (`live`), the
    one `delta` before the queries' own: under the bias of its positions
    where it is cut, bare where it is wholly seen."""
    cut = (delta == 0) | (delta >= window // bias_ref.shape[0])

    @pl.when(live & cut)
    def _():
        _position_bias(bias_ref, delta, window, keys_by_rows)
        tile_of(bias_ref)

    @pl.when(live & jnp.logical_not(cut))
    def _():
        tile_of(None)


def _causal_forward_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, bias_ref,
    *, scale: float, window: int,
):
    qi, j, reach = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    delta = reach - 1 - j  # the farthest tile first, the queries' own last

    @pl.when(j == 0)
    def _():
        _start(m_ref, l_ref, acc_ref)

    _by_case(
        delta <= qi, delta, window, bias_ref, False,
        lambda bias: _attend(q_ref, k_ref, v_ref, bias, m_ref, l_ref, acc_ref, scale),
    )

    @pl.when(j == reach - 1)
    def _():
        _leave(o_ref, lse_ref, m_ref, l_ref, acc_ref, lse_along_lanes=True)


_causal_once = _traced_once("scale", "tile", "window")


@_causal_once
def _causal_forward(q, k, v, scale, tile, window):
    """q [B, G, R, T, d], k, v [B, G, T, d] bfloat16 -> o [B, G, R, T, d],
    the logsumexp [B, G, R, 1, T] (as the backward kernels cut it),
    float32."""
    batch, groups, heads, length, head_dim = q.shape
    reach = _reach(length, tile, window)
    per_rows = lambda b, g, qi, j: (b, g, 0, qi, 0)  # noqa: E731
    per_keys = lambda b, g, qi, j: (b, g, jnp.maximum(qi - (reach - 1) + j, 0), 0)  # noqa: E731
    return _call(
        functools.partial(_causal_forward_kernel, scale=scale, window=window),
        name="causal_core_forward",
        grid=(batch, groups, length // tile, reach),
        prefetch=0,
        in_specs=[
            pl.BlockSpec((None, None, heads, tile, head_dim), per_rows),
            pl.BlockSpec((None, None, tile, head_dim), per_keys),
            pl.BlockSpec((None, None, tile, head_dim), per_keys),
        ],
        out_specs=[
            pl.BlockSpec((None, None, heads, tile, head_dim), per_rows),
            pl.BlockSpec((None, None, heads, 1, tile), lambda b, g, qi, j: (b, g, 0, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(q.shape[:3] + (1, length), jnp.float32),
        ],
        scratch=[
            pltpu.VMEM((heads, tile, LANES), jnp.float32),  # running max
            pltpu.VMEM((heads, tile, LANES), jnp.float32),  # running sum
            pltpu.VMEM((heads, tile, head_dim), jnp.float32),  # running output
            pltpu.VMEM((tile, tile), jnp.float32),  # a cut tile's bias
        ],
    )(q, k, v)


def _score_cotangents(q, do, k, v, bias_ref, lse, di, scale: float):
    """A head's probabilities and the cotangent of its scores on one
    tile, both [keys, queries] (`_backward_kernel` says why)."""
    s_t = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s_t = s_t + bias_ref[...]
    p_t = jnp.exp(s_t - lse)
    dp_t = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    return p_t, p_t * (dp_t - di)


def _causal_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref, bias_ref,
    *, scale: float, window: int,
):
    """A tile of keys against the tiles of queries that see it, its own
    first: dk, dv add up where they are written."""
    ki, delta, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(2)

    @pl.when(delta == 0)
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    def tile_of(bias):
        k, v = k_ref[...], v_ref[...]
        dk = jnp.zeros(dk_ref.shape, jnp.float32)
        dv = jnp.zeros(dv_ref.shape, jnp.float32)
        for h in range(q_ref.shape[0]):
            q, do = q_ref[h], do_ref[h]
            p_t, ds_t = _score_cotangents(q, do, k, v, bias, lse_ref[h], di_ref[h], scale)
            dv += jnp.dot(p_t.astype(do.dtype), do, preferred_element_type=jnp.float32)
            dk += jnp.dot(ds_t.astype(q.dtype), q, preferred_element_type=jnp.float32)
        dk_ref[...] += dk * scale
        dv_ref[...] += dv

    _by_case(ki + delta < nk, delta, window, bias_ref, True, tile_of)


def _causal_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, bias_ref,
    *, scale: float, window: int,
):
    """A tile of queries against the tiles of keys it sees: dq adds up
    where it is written."""
    qi, j, reach = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    delta = reach - 1 - j

    @pl.when(j == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def tile_of(bias):
        k, v = k_ref[...], v_ref[...]
        for h in range(q_ref.shape[0]):
            _, ds_t = _score_cotangents(
                q_ref[h], do_ref[h], k, v, bias, lse_ref[h], di_ref[h], scale
            )
            dq_ref[h] += jnp.dot(
                ds_t.T.astype(k.dtype), k, preferred_element_type=jnp.float32
            )

    _by_case(delta <= qi, delta, window, bias_ref, True, tile_of)

    @pl.when(j == reach - 1)
    def _():
        dq_ref[...] *= scale


def _cotangent_call(kernel, name, q, tile, reach, query_tile, key_tile, out):
    """One of the two backward kernels over the grid [B, G, tiles, reach]
    whose step (i, j) holds the tiles `query_tile(i, j)` of q, do, the
    logsumexp and di and `key_tile(i, j)` of k and v; each of `out` is
    a result cut as "rows" (q is) or as "keys", which adds up over j."""
    batch, groups, heads, length, head_dim = q.shape
    rows = pl.BlockSpec(
        (None, None, heads, tile, head_dim), lambda b, g, i, j: (b, g, 0, query_tile(i, j), 0)
    )
    row = pl.BlockSpec(
        (None, None, heads, 1, tile), lambda b, g, i, j: (b, g, 0, 0, query_tile(i, j))
    )
    keys = pl.BlockSpec(
        (None, None, tile, head_dim), lambda b, g, i, j: (b, g, key_tile(i, j), 0)
    )
    cut = {"rows": (rows, q.shape), "keys": (keys, (batch, groups, length, head_dim))}
    return _call(
        kernel, name=name, prefetch=0,
        grid=(batch, groups, length // tile, reach),
        in_specs=[rows, keys, keys, rows, row, row],
        out_specs=[cut[kind][0] for kind in out],
        out_shape=[jax.ShapeDtypeStruct(cut[kind][1], jnp.float32) for kind in out],
        scratch=[pltpu.VMEM((tile, tile), jnp.float32)],  # a cut tile's bias
    )


@_causal_once
def _causal_dkv(q, k, v, do, lse, di, scale, tile, window):
    """dk, dv [B, G, T, d] float32: a tile of keys against the query
    tiles from its own on (past the last one, the last again, not run)."""
    tiles, reach = q.shape[3] // tile, _reach(q.shape[3], tile, window)
    return _cotangent_call(
        functools.partial(_causal_dkv_kernel, scale=scale, window=window),
        "causal_core_dkv", q, tile, reach,
        lambda ki, delta: jnp.minimum(ki + delta, tiles - 1), lambda ki, delta: ki,
        ("keys", "keys"),
    )(q, k, v, do, lse, di)


@_causal_once
def _causal_dq(q, k, v, do, lse, di, scale, tile, window):
    """dq [B, G, R, T, d] float32: a tile of queries against the key
    tiles up to its own (before key 0, tile 0, not run)."""
    reach = _reach(q.shape[3], tile, window)
    (dq,) = _cotangent_call(
        functools.partial(_causal_dq_kernel, scale=scale, window=window),
        "causal_core_dq", q, tile, reach,
        lambda qi, j: qi, lambda qi, j: jnp.maximum(qi - (reach - 1) + j, 0),
        ("rows",),
    )(q, k, v, do, lse, di)
    return dq


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _causal(q, k, v, scale, tile, window, keep):
    return _causal_fwd(q, k, v, scale, tile, window, keep)[0]


def _causal_fwd(q, k, v, scale, tile, window, keep):
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    o, lse = (keep(a) for a in _causal_forward(q, k, v, scale, tile, window))
    return o, (q, k, v, o, lse)


def _causal_bwd(scale, tile, window, keep, residuals, do):
    """Two kernels, so that what adds up over a grid's last axis is one
    tile: dk, dv over the queries that see a tile of keys, dq over the
    keys a tile of queries sees. Each makes the probabilities again from
    the logsumexp: 7 products a tile, where `_backward_kernel` makes 5
    and holds a head's whole dq (64 MiB at 16,384 rows of 8 heads)."""
    q, k, v, o, lse = residuals
    di = jnp.sum(o * do, axis=-1)[..., None, :]
    args = (q, k, v, do.astype(jnp.bfloat16), lse, di)
    dk, dv = _causal_dkv(*args, scale, tile, window)
    return _causal_dq(*args, scale, tile, window), dk, dv


_causal.defvjp(_causal_fwd, _causal_bwd)


def causal_attention(
    q: Array, k: Array, v: Array, scale: float, tile: int,
    window: int | None = None, keep=lambda a: a,
) -> Array:
    """softmax(`scale` q k^T over the keys s with `t - window < s <= t`)
    v for a whole sequence, in one forward and two backward kernels whose
    grids are the tiles; differentiable in q, k, v.

    q [B, G, R, T, d], k, v [B, G, T, d] float32, T whole `tile`s, `tile`
    whole 128-lane tiles and d such tiles or the half tile, 64
    (`seq_ops.causal_tile`); no window, or one that holds the sequence,
    is every earlier key. Returns float32 [B, G, R, T, d].

    Of the forward the backward reads the output and the logsumexp
    [B, G, R, 1, T]; both pass through `keep` first. A caller rematerialised
    under a policy that saves what `keep` names has them when its
    backward starts, and its second forward runs no kernel.
    """
    length = q.shape[3]
    window = length if window is None else min(window, length)
    return _causal(q, k, v, float(scale), tile, window, keep)
