"""Device-side message-passing primitives.

The TPU equivalent of the reference's MPGather/MPScatter* TF custom ops
(tf_euler/python/euler_ops/mp_ops.py:27-79, tf_euler/kernels/scatter_op.cc).
Everything is expressed over *static-shape* operations: segment ops driven by
index vectors for irregular edge sets, and `grid_add` — a strided window
reduce, no indices at all — where every segment is a run of equally many
consecutive rows (fixed-fanout blocks).

Padding convention: dataflows route padded edges to valid-looking indices and
pass `mask`; masked lanes contribute the reduction identity (0 for add/mean,
-inf for max, zero probability for softmax).

Gradient parity with the reference:
  - gather ↔ scatter_add adjoints (mp_ops.py:39-49)
  - scatter_max splits the subgradient equally among argmax ties
    (mp_ops.py:52-62)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def gather(params: Array, indices: Array) -> Array:
    """params[indices] along axis 0 (MPGather)."""
    return jnp.take(params, indices, axis=0)


def _masked(data: Array, mask: Array | None, fill) -> Array:
    if mask is None:
        return data
    shape = mask.shape + (1,) * (data.ndim - mask.ndim)
    return jnp.where(mask.reshape(shape), data, fill)


def scatter_add(
    data: Array,
    segment_ids: Array,
    num_segments: int,
    mask: Array | None = None,
) -> Array:
    """Sum `data` rows into `num_segments` rows (MPScatterAdd)."""
    return jax.ops.segment_sum(
        _masked(data, mask, 0), segment_ids, num_segments=num_segments
    )


_SUBLANES = 8  # rows of a float32 tile on the TPU


def _repeat_rows(x: Array, k: int) -> Array:
    """`jnp.repeat(x, k, axis=0)`, bit for bit. Where the rows come in
    whole 8-row tiles it is written as a product with a 0/1 matrix over
    blocks of 8 rows -> 8k rows: both reshapes are then free on the TPU
    and the copy runs on the MXU (each output row has one term, and at
    `highest` a float32 times 1.0 is exact), where broadcast + reshape
    goes through a padded relayout that takes twice as long (PERF.md,
    PR 29)."""
    n = x.shape[0]
    if n % _SUBLANES:
        return jnp.repeat(x, k, axis=0)
    rows = np.arange(_SUBLANES * k)
    spread = np.zeros((_SUBLANES * k, _SUBLANES), x.dtype)
    spread[rows, rows // k] = 1
    out = jnp.einsum(
        "rs,bsf->brf",
        spread,
        x.reshape(n // _SUBLANES, _SUBLANES, -1),
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.reshape((n * k,) + x.shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _run_sum(data: Array, grid: int) -> Array:
    window = (grid,) + (1,) * (data.ndim - 1)
    zero = np.zeros((), data.dtype)
    return jax.lax.reduce_window(data, zero, jax.lax.add, window, window, "VALID")


def _run_sum_fwd(data, grid):
    return _run_sum(data, grid), None


def _run_sum_bwd(grid, _, g):
    return (_repeat_rows(g, grid),)


# The transpose JAX derives for this window is a base-dilated reduce-window,
# which XLA's TPU backend gets wrong at f32[153600 x 5, 128] (gradients off
# by 1.5 times their largest entry against segment_sum; right on the CPU and
# at the smaller blocks: my chip run, PR 29). The transpose of "sum each run
# of k rows" is "repeat each row k times", so say that.
_run_sum.defvjp(_run_sum_fwd, _run_sum_bwd)


def grid_add(data: Array, grid: int, mask: Array | None = None) -> Array:
    """`scatter_add` for segment ids `arange(len(data)) // grid`: row i of
    the result sums rows [i*grid, (i+1)*grid) of `data`, in `data`'s dtype
    like the scatter it stands in for. A strided window over the row axis
    rather than `reshape(-1, grid, F).sum(1)`: the same sum, but the
    reshape puts `grid` (5, 10, 15) on the TPU's 8-row tiles and XLA then
    copies the whole operand into a padded layout first."""
    return _run_sum(_masked(data, mask, 0), grid)


def scatter_mean(
    data: Array,
    segment_ids: Array,
    num_segments: int,
    mask: Array | None = None,
) -> Array:
    """Segment mean; empty segments yield 0 (scatter_mean, mp_ops.py:65-69)."""
    total = scatter_add(data, segment_ids, num_segments, mask)
    ones = jnp.ones(data.shape[:1], dtype=data.dtype)
    if mask is not None:
        ones = jnp.where(mask, ones, 0)
    count = jax.ops.segment_sum(ones, segment_ids, num_segments=num_segments)
    count = jnp.maximum(count, 1)
    return total / count.reshape((num_segments,) + (1,) * (data.ndim - 1))


@jax.custom_vjp
def _segment_max(data, segment_ids, num_segments):
    return jax.ops.segment_max(data, segment_ids, num_segments=num_segments)


def _segment_max_fwd(data, segment_ids, num_segments):
    out = jax.ops.segment_max(data, segment_ids, num_segments=num_segments)
    return out, (data, segment_ids, num_segments, out)


def _segment_max_bwd(res, g):
    data, segment_ids, num_segments, out = res
    picked = gather(out, segment_ids)
    ties = (data == picked).astype(data.dtype)
    counts = jax.ops.segment_sum(ties, segment_ids, num_segments=num_segments)
    counts = jnp.maximum(counts, 1)
    # equal split among argmax ties (scatter_op.cc:66-78 / mp_ops.py:52-62)
    dd = ties * gather(g / counts, segment_ids)
    return dd, None, None


_segment_max.defvjp(_segment_max_fwd, _segment_max_bwd)


def scatter_max(
    data: Array,
    segment_ids: Array,
    num_segments: int,
    mask: Array | None = None,
    empty_value: float = 0.0,
) -> Array:
    """Segment max; ties split the gradient equally (MPScatterMax).

    Empty segments produce `empty_value` (the reference fills a large
    negative then replaces; we expose the fill directly).
    """
    neg = jnp.finfo(data.dtype).min
    filled = _masked(data, mask, neg)
    out = _segment_max(filled, segment_ids, num_segments)
    # empty segments surface as -inf (segment_max identity) or as the mask
    # fill; both are <= finfo.min
    return jnp.where(out <= neg, jnp.asarray(empty_value, out.dtype), out)


def scatter_softmax(
    data: Array,
    segment_ids: Array,
    num_segments: int,
    mask: Array | None = None,
) -> Array:
    """Per-segment softmax over rows (scatter_softmax, mp_ops.py:71-79).

    Returns an array shaped like `data`: each row's probability within its
    segment. Masked rows get probability 0.
    """
    neg = jnp.finfo(data.dtype).min
    filled = _masked(data, mask, neg)
    seg_max = jax.ops.segment_max(filled, segment_ids, num_segments=num_segments)
    seg_max = jnp.where(seg_max <= neg, 0.0, seg_max)
    shifted = filled - gather(seg_max, segment_ids)
    expd = jnp.exp(shifted)
    if mask is not None:
        shape = mask.shape + (1,) * (data.ndim - mask.ndim)
        expd = jnp.where(mask.reshape(shape), expd, 0.0)
    denom = jax.ops.segment_sum(expd, segment_ids, num_segments=num_segments)
    denom = jnp.maximum(denom, jnp.finfo(data.dtype).tiny)
    return expd / gather(denom, segment_ids)


def scatter(op: str, data, segment_ids, num_segments, mask=None):
    """Dispatch by name ('add' | 'mean' | 'max' | 'softmax') — the string
    interface the reference's aggregators use (mp_ops.scatter_)."""
    fns = {
        "add": scatter_add,
        "sum": scatter_add,
        "mean": scatter_mean,
        "max": scatter_max,
        "softmax": scatter_softmax,
    }
    return fns[op](data, segment_ids, num_segments, mask=mask)
