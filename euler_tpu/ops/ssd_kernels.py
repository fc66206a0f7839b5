"""Mamba-2's chunked state-space scan as two Pallas TPU kernels, forward
and pull-back (`seq_ops.chunked_ssd` where the chunk and the state are
whole 128-wide tiles and the head such tiles or the half tile, 64:
`seq_ops.ssd_by_kernels`), behind the scan's own `custom_vjp`
(`seq_ops._ssd`): they read u [B, H, T, P], the log-decays a [B, H, T]
and b, c [B, T, N] where they lie, padded to whole chunks, and leave the
output [B, H, T, P] and the state at each chunk's start [chunks, B, H,
P, N], the backward's one residual beside its inputs.

The grid is (B, chunks, H), chunks and heads sequential, the heads
innermost. One grid step holds ONE chunk of one head. Each head's state
[P, N] float32 is carried from chunk to chunk in fast memory (a VMEM
scratch of all H heads' states, 2 MiB at 64 x 64 x 128), zeroed at the
first chunk. b and c are common to the heads: their blocks stay put
while the heads of a chunk go by, and C B^T [C, C] is made once a chunk,
at its first head, into a scratch the others read, so the MXU does the
work the equations count and no more (at N 128 that product costs twice
a head's own (C B^T * L) U). Forward, a step writes its chunk's start
state and its output and moves the state on. Backward, the index maps
take the chunks from the last to the first and the state's cotangent is
carried instead: a step pulls `(d_state, d_out)` back through its chunk
from the kept start state, writes du and da, and adds its head's part of
db and dc to blocks that stay put over the chunk's heads; the heads'
cotangent of C B^T is summed in a scratch and turned into db and dc once,
at the chunk's last head. What `jax.vjp(seq_ops._ssd_chunk, ...)` makes,
written by hand.

Arithmetic is the XLA chunk's: the running sum of a, the decays, the
state and every cotangent float32; every product at what XLA's default
precision makes of a float32 dot on the platform — on the TPU its
operands enter the MXU as bfloat16 and accumulate in float32, as in
`ops/delta_rule_kernels.py`; interpreted off the TPU, float32. A
kernel's body is one chunk of one head, traced once, whatever the
sequence's length.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from euler_tpu.ops.delta_rule_kernels import _NN, _NT, _TN, _product
from euler_tpu.ops.masked_flash import _call, _traced_once

Array = jax.Array


class _Gates(NamedTuple):
    """A chunk's decays from its log-decays a [1, C]: columns [C, 1] but
    `end`, [1, 1], and the [C, C] ones."""

    lower: Array  # [C, C]: j at or before i
    eye: Array  # [C, C]
    decay: Array  # [C, C]: exp(G_i - G_j) for j <= i, else 0
    grow: Array  # exp(G)
    fall: Array  # exp(G_end - G)
    end: Array  # G_end


def _gates(a) -> _Gates:
    """G is the chunk's running sum of a; its exponents are masked before
    the exp, so none that is dropped can overflow."""
    chunk = a.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower, eye = lanes <= rows, lanes == rows
    run = jnp.sum(jnp.where(lower, a, 0.0), axis=1, keepdims=True)
    across = jnp.sum(jnp.where(eye, run, 0.0), axis=0, keepdims=True)  # [1, C], exactly
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    end = jnp.sum(jnp.where(last, run, 0.0), axis=0, keepdims=True)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, run - across, 0.0)), 0.0)
    return _Gates(lower, eye, decay, jnp.exp(run), jnp.exp(end - run), end)


def _forward_kernel(
    u_ref, a_ref, b_ref, c_ref, y_ref, start_ref, state_ref, cb_ref, *, operand,
):
    """One chunk of one head: its start state written, its output, the
    state moved on."""
    head = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[head] = jnp.zeros(state_ref.shape[1:], jnp.float32)

    @pl.when(head == 0)
    def _():
        cb_ref[...] = _product(c_ref[...], b_ref[...], _NT, operand)

    state = state_ref[head]
    start_ref[...] = state
    u, c = u_ref[...], c_ref[...]
    g = _gates(a_ref[...])
    y_ref[...] = _product(cb_ref[...] * g.decay, u, _NN, operand) + g.grow * _product(
        c, state, _NT, operand
    )
    state_ref[head] = state * jnp.exp(g.end) + _product(u * g.fall, b_ref[...], _TN, operand)


def _backward_kernel(
    u_ref, a_ref, b_ref, c_ref, start_ref, dy_ref,
    du_ref, da_ref, db_ref, dc_ref,
    d_state_ref, cb_ref, d_cb_ref, *, operand,
):
    """One chunk of one head, the chunks from the last: `(d_state, dy)`
    pulled back through it from its start state."""
    head, heads = pl.program_id(2), pl.num_programs(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state_ref[head] = jnp.zeros(d_state_ref.shape[1:], jnp.float32)

    @pl.when(head == 0)
    def _():
        cb_ref[...] = _product(c_ref[...], b_ref[...], _NT, operand)
        d_cb_ref[...] = jnp.zeros_like(d_cb_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    u, b, c, dy = u_ref[...], b_ref[...], c_ref[...], dy_ref[...]
    start, d_state = start_ref[...], d_state_ref[head]
    g = _gates(a_ref[...])
    cb = cb_ref[...]
    # Y = ((C B^T) * L) U + exp(G) (C S^T)
    d_u = _product(cb * g.decay, dy, _TN, operand)
    d_m = _product(dy, u, _NT, operand)
    d_cb_ref[...] += d_m * g.decay
    d_gap = d_m * cb * g.decay  # d(L) L: the exponent's cotangent, 0 above
    dy_grow = dy * g.grow
    d_grow = jnp.sum(dy * _product(c, start, _NT, operand), axis=1, keepdims=True) * g.grow
    # S' = exp(G_end) S + U^T (exp(G_end - G) B)
    d_u_fall = _product(b, d_state, _NT, operand)
    d_u = d_u + d_u_fall * g.fall
    d_fall = jnp.sum(u * d_u_fall, axis=1, keepdims=True) * g.fall
    dc_ref[...] += _product(dy_grow, start, _NN, operand)
    db_ref[...] += _product(u * g.fall, d_state, _NN, operand)
    d_end = jnp.sum(d_state * start, keepdims=True) * jnp.exp(g.end) + jnp.sum(
        d_fall, keepdims=True
    )
    d_state_ref[head] = d_state * jnp.exp(g.end) + _product(dy_grow, c, _TN, operand)
    # down to G, and from G to a: a_k is in every G_i with i >= k, and in G_end
    d_run = (
        d_grow - d_fall + jnp.sum(d_gap, axis=1, keepdims=True)
        - jnp.sum(jnp.where(g.eye, jnp.sum(d_gap, axis=0, keepdims=True), 0.0), axis=1, keepdims=True)
    )
    da_ref[...] = jnp.sum(jnp.where(g.lower, d_run, 0.0), axis=0, keepdims=True) + d_end
    du_ref[...] = d_u

    @pl.when(head == heads - 1)
    def _():
        d_cb = d_cb_ref[...]
        dc_ref[...] += _product(d_cb, b, _NN, operand)
        db_ref[...] += _product(d_cb, c, _TN, operand)


def _specs(u, b, chunk: int, order):
    """Blocks of one chunk of one head: its rows of u [B, H, T, P], its
    log-decays [B, H, chunks, 1, C], its rows of b, c [B, T, N] (the same
    for every head), its start state of [chunks, B, H, P, N]; `order(i)`
    is the chunk at grid step i."""
    head, state = u.shape[-1], b.shape[-1]
    rows = pl.BlockSpec((None, None, chunk, head), lambda i, j, h: (i, h, order(j), 0))
    gates = pl.BlockSpec((None, None, None, 1, chunk), lambda i, j, h: (i, h, order(j), 0, 0))
    shared = pl.BlockSpec((None, chunk, state), lambda i, j, h: (i, order(j), 0))
    starts = pl.BlockSpec((None, None, None, head, state), lambda i, j, h: (order(j), i, h, 0, 0))
    return rows, gates, shared, starts


def _both(kernel):
    """The kernel for Mosaic and for the interpreter: the products'
    operands as XLA's default precision takes them on each."""
    return {
        "kernel": functools.partial(kernel, operand=jnp.bfloat16),
        "interpreted": functools.partial(kernel, operand=jnp.float32),
    }


def _by_chunk(a, chunk: int):  # [B, H, T] -> [B, H, T / C, 1, C]
    return a.reshape(a.shape[:2] + (a.shape[2] // chunk, 1, chunk))


@_traced_once("chunk")
def forward(u, a, b, c, chunk):
    """The scan over u [B, H, T, P], a [B, H, T], b, c [B, T, N], T whole
    chunks -> (the output [B, H, T, P], the state at each chunk's start
    [chunks, B, H, P, N]), float32."""
    batch, heads, length, head = u.shape
    state, chunks = b.shape[-1], length // chunk
    rows, gates, shared, starts = _specs(u, b, chunk, lambda j: j)
    return _call(
        **_both(_forward_kernel),
        name="ssd_scan_forward",
        grid=(batch, chunks, heads),
        prefetch=0,
        reduced=2,
        in_specs=[rows, gates, shared, shared],
        out_specs=[rows, starts],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, jnp.float32),
            jax.ShapeDtypeStruct((chunks, batch, heads, head, state), jnp.float32),
        ],
        scratch=[
            pltpu.VMEM((heads, head, state), jnp.float32),  # each head's state, carried
            pltpu.VMEM((chunk, chunk), jnp.float32),  # C B^T of the chunk
        ],
    )(u, _by_chunk(a, chunk), b, c)


@_traced_once("chunk")
def backward(u, a, b, c, starts, d_out, chunk):
    """The cotangents of `forward`'s four inputs from its output's, the
    chunks from the last to the first, each from its start state."""
    batch, heads, length, head = u.shape
    state, chunks = b.shape[-1], length // chunk
    rows, gates, shared, kept = _specs(u, b, chunk, lambda j: chunks - 1 - j)
    d_u, d_a, d_b, d_c = _call(
        **_both(_backward_kernel),
        name="ssd_scan_backward",
        grid=(batch, chunks, heads),
        prefetch=0,
        reduced=2,
        in_specs=[rows, gates, shared, shared, kept, rows],
        out_specs=[rows, gates, shared, shared],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, jnp.float32),
            jax.ShapeDtypeStruct(_by_chunk(a, chunk).shape, jnp.float32),
            jax.ShapeDtypeStruct(b.shape, jnp.float32),
            jax.ShapeDtypeStruct(c.shape, jnp.float32),
        ],
        scratch=[
            pltpu.VMEM((heads, head, state), jnp.float32),  # each head's d_state, carried
            pltpu.VMEM((chunk, chunk), jnp.float32),  # C B^T of the chunk
            pltpu.VMEM((chunk, chunk), jnp.float32),  # its cotangent, over the heads
        ],
    )(u, _by_chunk(a, chunk), b, c, starts, d_out)
    return d_u, d_a.reshape(a.shape), d_b, d_c
