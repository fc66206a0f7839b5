"""The chunked gated delta rule's groups of chunks as two Pallas TPU
kernels, forward and pull-back (`seq_ops.chunk_gated_delta_rule` where
dk and dv are whole 128-lane tiles and the chunk a multiple of 8:
`seq_ops.delta_rule_by_kernels`), behind the rule's own `custom_vjp`
(`seq_ops._grouped_rule`): they read q, k, v [B, H, T, d] and the gates
[B, H, T] where they lie, padded to whole groups, and leave the output
[B, H, T, dv] and the state at each group's start [groups, B, H, dk, dv],
the backward's one residual beside its inputs.

The grid is (B, H, groups), the groups sequential. One grid step holds
ONE group of one head: `group` chunks of q, k, v [C, d] and their gates.
The state [dk, dv] float32 is carried from step to step in fast memory
(a VMEM scratch), zeroed at a head's first group. Forward, a step writes
its group's start state and its output and passes the state through its
chunks in order. Backward, the index maps take the groups from the last
to the first and the state's cotangent is carried instead: a step makes
its group again from the kept start state (keeping each chunk's start
state and each span's inverse, W and D in fast memory), then pulls
`(d_state, d_out)` back through its chunks in reverse and writes the
cotangents of q, k, v, g and beta — what `jax.vjp(seq_ops._one_group,
...)` makes, written by hand.

Inside a step the chunks are taken a span at a time: as many chunks as
fill the MXU's 128 lanes (two of 64). A span's chunk-local matrices are
block-diagonal [S, S] — one block a chunk, the blocks no chunk shares
zero — so a product of two of them is the two chunks' products in one
pass of the MXU, and the unit-lower inverse still stops after log2(C)
squarings; products that share their right-hand side are one product of
rows stacked on the M axis. Only the state's passage is chunk by chunk.
The spans are a `lax.fori_loop`: a kernel's body is one span, traced
once, whatever the group and the sequence's length.

Arithmetic is the XLA group's: the cumulative gate, decays, state and
every cotangent float32; the unit-lower inverse and its gradient at
`highest` (`seq_ops._INVERSE_PRECISION`); every other product at what
XLA's default precision makes of a float32 dot on the platform — on the
TPU its operands enter the MXU as bfloat16 and accumulate in float32, as
in `ops/masked_flash.py`; interpreted off the TPU, float32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from euler_tpu.ops import seq_ops
from euler_tpu.ops.masked_flash import LANES, _call, _traced_once

Array = jax.Array

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _product(a, b, dims, operand):
    """A product at XLA's default precision: operands as `operand`,
    float32 accumulation."""
    return jax.lax.dot_general(
        a.astype(operand), b.astype(operand), dims, preferred_element_type=jnp.float32
    )


def _exact(a, b, dims):
    return jax.lax.dot_general(
        a, b, dims, precision=seq_ops._INVERSE_PRECISION, preferred_element_type=jnp.float32
    )


def _stack(*rows):
    return jnp.concatenate(rows)


def _per_span(chunk: int, group: int) -> int:
    """Chunks a span holds: as many as fill the lanes and divide the group."""
    per = max(1, LANES // chunk)
    while group % per:
        per //= 2
    return per


class _Masks(NamedTuple):
    """Of a span of `per` chunks of C rows, S = per C: [S, S] unless said."""

    lower: Array  # key j at or before row i, in i's chunk
    strict: Array  # j before i, in i's chunk
    eye: Array
    same: Array  # j in i's chunk
    last_lane: Array  # j is the last row of i's chunk
    last_row: Array  # [S, 1]: i is its chunk's last row
    chunk_of: Array  # [S, 1]: i's chunk in the span


def _masks(span: int, chunk: int) -> _Masks:
    rows = jax.lax.broadcasted_iota(jnp.int32, (span, span), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (span, span), 1)
    same = rows // chunk == lanes // chunk
    column = jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)
    return _Masks(
        same & (lanes <= rows), same & (lanes < rows), lanes == rows, same,
        same & (lanes % chunk == chunk - 1), column % chunk == chunk - 1, column // chunk,
    )


def _column(row, m: _Masks):
    """[1, S] -> [S, 1], exactly (one term a sum)."""
    return jnp.sum(jnp.where(m.eye, row, 0.0), axis=1, keepdims=True)


def _row(column, m: _Masks):
    """[S, 1] -> [1, S], exactly."""
    return jnp.sum(jnp.where(m.eye, column, 0.0), axis=0, keepdims=True)


class _Gates(NamedTuple):
    """A span's gates, `seq_ops._chunk_local`'s, each chunk's own:
    columns [S, 1] but `ends` and `decay`."""

    beta: Array
    grow: Array  # exp(G)
    fall: Array  # exp(G_end - G)
    ends: tuple  # each chunk's G_end, [1, 1]
    decay: Array  # [S, S]: exp(G_i - G_j) for j <= i in i's chunk, else 0


def _gates(g, beta, m: _Masks, per: int) -> _Gates:
    """g, beta [1, S] -> the span's gates; G is each chunk's running
    sum of g."""
    run = jnp.sum(jnp.where(m.lower, g, 0.0), axis=1, keepdims=True)
    end = jnp.sum(jnp.where(m.last_lane, _row(run, m), 0.0), axis=1, keepdims=True)
    at_end = jnp.where(m.last_row, run, 0.0)
    ends = tuple(
        jnp.sum(jnp.where(m.chunk_of == c, at_end, 0.0), axis=0, keepdims=True)
        for c in range(per)
    )
    gap = run - _row(run, m)
    decay = jnp.where(m.lower, jnp.exp(jnp.where(m.lower, gap, 0.0)), 0.0)
    return _Gates(_column(beta, m), jnp.exp(run), jnp.exp(end - run), ends, decay)


def _span_local(q, k, v, gates: _Gates, m: _Masks, chunk: int, operand):
    """`seq_ops._chunk_local`'s products for a span: q, k [S, dk],
    v [S, dv] -> K beta K^T, the inverse, U, W and Q K^T (not decayed)."""
    span, values = q.shape[0], v.shape[1]
    k_beta = k * gates.beta
    both = _product(_stack(k_beta, q), k, _NT, operand)
    kk, qk = both[:span], both[span:]
    solve = seq_ops._inverse_by_squarings(jnp.where(m.strict, kk * gates.decay, 0.0), chunk)
    uw = _product(
        solve, jnp.concatenate([v * gates.beta, k_beta * gates.grow], axis=1), _NN, operand
    )
    return kk, solve, uw[:, :values], uw[:, values:], qk


def _through(state, u, w, q_grow, k_fall, gates: _Gates, chunk: int, operand, keep=None):
    """The state through a span's chunks in order -> (the state at its
    end, each chunk's D, the part of each chunk's output the state
    gives); `keep(c, state)` sees the state at chunk c's start."""
    ds, outs = [], []
    for c, end in enumerate(gates.ends):
        rows = slice(c * chunk, (c + 1) * chunk)
        if keep is not None:
            keep(c, state)
        both = _product(_stack(w[rows], q_grow[rows]), state, _NN, operand)
        d = u[rows] - both[:chunk]
        ds.append(d)
        outs.append(both[chunk:])
        state = state * jnp.exp(end) + _product(k_fall[rows], d, _TN, operand)
    return state, _stack(*ds), _stack(*outs)


def _rows(s, span: int):
    """Span `s` of a block of rows [group C, d]."""
    return pl.ds(pl.multiple_of(s * span, span), span)


def _forward_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, start_ref, state_ref, *, operand, chunk, per,
):
    """One group: its start state written, its spans in order."""
    span = per * chunk
    m = _masks(span, chunk)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    start_ref[...] = state_ref[...]

    def one(s, state):
        rows = _rows(s, span)
        q, k, v = q_ref[rows], k_ref[rows], v_ref[rows]
        gates = _gates(g_ref[s], beta_ref[s], m, per)
        _, _, u, w, qk = _span_local(q, k, v, gates, m, chunk, operand)
        state, d, by_state = _through(
            state, u, w, q * gates.grow, k * gates.fall, gates, chunk, operand
        )
        o_ref[rows] = by_state + _product(qk * gates.decay, d, _NN, operand)
        return state

    state_ref[...] = jax.lax.fori_loop(0, g_ref.shape[0], one, state_ref[...])


def _pull_back(d_state, d_out, q, k, v, gates: _Gates, kept, m: _Masks, chunk: int, operand):
    """A span's cotangents from its end state's and its output's:
    -> (its start state's, dq, dk, dv [S, d], dg, dbeta [1, S])."""
    states, solve, w, d, kk, qk = kept
    span = q.shape[0]
    k_beta = k * gates.beta
    q_grow, k_fall, v_beta = q * gates.grow, k * gates.fall, v * gates.beta
    k_grow = k_beta * gates.grow
    # O = (Q exp(G)) state + (Q K^T decay) D, chunk by chunk
    d_d = _product(qk * gates.decay, d_out, _TN, operand)
    d_qk = _product(d_out, d, _NT, operand)
    d_decay = d_qk * qk
    d_qk = d_qk * gates.decay
    per = len(states)
    d_ds, d_k_falls, d_q_grows, d_ws, d_ends = ([None] * per for _ in range(5))
    for c in reversed(range(per)):
        rows = slice(c * chunk, (c + 1) * chunk)
        state, end = states[c], gates.ends[c]
        # state' = state exp(G_end) + (K exp(G_end - G))^T D; D = U - W state
        d_dc = d_d[rows] + _product(k_fall[rows], d_state, _NN, operand)
        d_k_falls[c] = _product(d[rows], d_state, _NT, operand)
        d_ends[c] = jnp.sum(d_state * state, keepdims=True) * jnp.exp(end)
        d_state = d_state * jnp.exp(end) + _product(
            _stack(q_grow[rows], -w[rows]), _stack(d_out[rows], d_dc), _TN, operand
        )
        by_state = _product(_stack(d_out[rows], d_dc), state, _NT, operand)
        d_q_grows[c], d_ws[c], d_ds[c] = by_state[:chunk], -by_state[chunk:], d_dc
    d_d, d_k_fall, d_q_grow, d_w = (_stack(*a) for a in (d_ds, d_k_falls, d_q_grows, d_ws))
    # U = inverse (V beta), W = inverse (K beta exp(G))
    d_solve = _product(
        jnp.concatenate([d_d, d_w], axis=1), jnp.concatenate([v_beta, k_grow], axis=1),
        _NT, operand,
    )
    d_v_beta = _product(solve, d_d, _TN, operand)
    d_k_grow = _product(solve, d_w, _TN, operand)
    # inverse = (I + L)^-1: dL = -inverse^T d(inverse) inverse^T
    d_lower = -_exact(solve, _exact(d_solve, solve, _NT), _TN)
    d_lower = jnp.where(m.strict, d_lower, 0.0)
    d_decay = d_decay + d_lower * kk
    d_kk = d_lower * gates.decay
    # K beta K^T and Q K^T
    d_both = _stack(d_kk, d_qk)
    by_k = _product(d_both, k, _NN, operand)
    d_k_beta = by_k[:span] + d_k_grow * gates.grow
    d_q = by_k[span:] + d_q_grow * gates.grow
    d_k = (
        _product(d_both, _stack(k_beta, q), _TN, operand)
        + d_k_fall * gates.fall + d_k_beta * gates.beta
    )
    d_v = d_v_beta * gates.beta
    d_beta = jnp.sum(d_v_beta * v, axis=1, keepdims=True) + jnp.sum(
        d_k_beta * k, axis=1, keepdims=True
    )
    # the gates, down to each chunk's running sum G and from it to g
    d_fall = jnp.sum(d_k_fall * k, axis=1, keepdims=True) * gates.fall
    d_grow = (
        jnp.sum(d_q_grow * q, axis=1, keepdims=True)
        + jnp.sum(d_k_grow * k_beta, axis=1, keepdims=True)
    ) * gates.grow
    d_gap = jnp.where(m.lower, d_decay * gates.decay, 0.0)
    d_end = jnp.sum(jnp.where(m.same, _row(d_fall, m), 0.0), axis=1, keepdims=True)
    for c in range(per):
        d_end = d_end + jnp.where(m.chunk_of == c, d_ends[c], 0.0)
    d_run = (
        d_grow - d_fall + jnp.sum(d_gap, axis=1, keepdims=True)
        - _column(jnp.sum(d_gap, axis=0, keepdims=True), m)
        + jnp.where(m.last_row, d_end, 0.0)
    )
    d_g = jnp.sum(jnp.where(m.lower, d_run, 0.0), axis=0, keepdims=True)
    return d_state, d_q, d_k, d_v, d_g, _row(d_beta, m)


def _backward_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
    d_state_ref, states_ref, solve_ref, w_ref, d_ref, *, operand, chunk, per,
):
    """One group, the groups from the last: made again from its start
    state, then pulled back span by span from its last."""
    spans, span = g_ref.shape[0], per * chunk
    m = _masks(span, chunk)

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state_ref[...] = jnp.zeros_like(d_state_ref)

    def again(s, state):
        rows = _rows(s, span)
        q, k, v = q_ref[rows], k_ref[rows], v_ref[rows]
        gates = _gates(g_ref[s], beta_ref[s], m, per)
        _, solve, u, w, _ = _span_local(q, k, v, gates, m, chunk, operand)

        def keep(c, at):
            states_ref[s * per + c] = at

        state, d, _ = _through(
            state, u, w, q * gates.grow, k * gates.fall, gates, chunk, operand, keep
        )
        solve_ref[s], w_ref[s], d_ref[s] = solve, w, d
        return state

    jax.lax.fori_loop(0, spans, again, start_ref[...])

    def back(i, d_state):
        s = spans - 1 - i
        rows = _rows(s, span)
        q, k, v = q_ref[rows], k_ref[rows], v_ref[rows]
        gates = _gates(g_ref[s], beta_ref[s], m, per)
        both = _product(_stack(k * gates.beta, q), k, _NT, operand)
        states = [states_ref[s * per + c] for c in range(per)]
        kept = (states, solve_ref[s], w_ref[s], d_ref[s], both[:span], both[span:])
        d_state, d_q, d_k, d_v, dg_ref[s], dbeta_ref[s] = _pull_back(
            d_state, do_ref[rows], q, k, v, gates, kept, m, chunk, operand
        )
        dq_ref[rows], dk_ref[rows], dv_ref[rows] = d_q, d_k, d_v
        return d_state

    d_state_ref[...] = jax.lax.fori_loop(0, spans, back, d_state_ref[...])


def _specs(q, v, chunk: int, group: int, order, per: int):
    """Blocks of one group of one head: its rows of q, k [B, H, T, dk] and
    of v [B, H, T, dv], its gates [B, H, spans, 1, S] (`_by_span`), its
    start state of [groups, B, H, dk, dv]; `order(i)` is the group at
    grid step i."""
    dk, dv = q.shape[-1], v.shape[-1]
    rows = lambda d: pl.BlockSpec(  # noqa: E731
        (None, None, group * chunk, d), lambda b, h, i: (b, h, order(i), 0)
    )
    gates = pl.BlockSpec(
        (None, None, group // per, 1, per * chunk), lambda b, h, i: (b, h, order(i), 0, 0)
    )
    state = pl.BlockSpec((None, None, None, dk, dv), lambda b, h, i: (order(i), b, h, 0, 0))
    return rows(dk), rows(dv), gates, state


def _by_span(a, span: int):  # [B, H, T] -> [B, H, T / S, 1, S]
    return a.reshape(a.shape[:2] + (a.shape[2] // span, 1, span))


def _both(kernel, chunk: int, per: int):
    """The kernel for Mosaic and for the interpreter: the products'
    operands as XLA's default precision takes them on each."""
    return {
        "kernel": functools.partial(kernel, operand=jnp.bfloat16, chunk=chunk, per=per),
        "interpreted": functools.partial(kernel, operand=jnp.float32, chunk=chunk, per=per),
    }


@_traced_once("chunk", "group")
def forward(q, k, v, g, beta, chunk, group):
    """The rule over q, k [B, H, T, dk], v [B, H, T, dv], g, beta [B, H, T],
    T whole groups of `group` chunks of `chunk` -> (the output [B, H, T,
    dv], the state at each group's start [groups, B, H, dk, dv]),
    float32."""
    batch, heads, length, dk = q.shape
    dv = v.shape[-1]
    groups, per = length // (group * chunk), _per_span(chunk, group)
    keys, values, gates, state = _specs(q, v, chunk, group, lambda i: i, per)
    return _call(
        **_both(_forward_kernel, chunk, per),
        name="gdn_rule_forward",
        grid=(batch, heads, groups),
        prefetch=0,
        in_specs=[keys, keys, values, gates, gates],
        out_specs=[values, state],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
            jax.ShapeDtypeStruct((groups, batch, heads, dk, dv), jnp.float32),
        ],
        scratch=[pltpu.VMEM((dk, dv), jnp.float32)],
    )(q, k, v, _by_span(g, per * chunk), _by_span(beta, per * chunk))


@_traced_once("chunk", "group")
def backward(q, k, v, g, beta, starts, d_out, chunk, group):
    """The cotangents of `forward`'s five inputs from its output's, the
    groups from the last to the first, each made again from its start
    state."""
    batch, heads, length, dk = q.shape
    dv = v.shape[-1]
    groups, per = length // (group * chunk), _per_span(chunk, group)
    spans, span = group // per, per * chunk
    keys, values, gates, state = _specs(q, v, chunk, group, lambda i: groups - 1 - i, per)
    g, beta = _by_span(g, span), _by_span(beta, span)
    d_q, d_k, d_v, d_g, d_beta = _call(
        **_both(_backward_kernel, chunk, per),
        name="gdn_rule_backward",
        grid=(batch, heads, groups),
        prefetch=0,
        in_specs=[keys, keys, values, gates, gates, state, values],
        out_specs=[keys, keys, values, gates, gates],
        out_shape=[jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in (q, k, v, g, beta)],
        scratch=[
            pltpu.VMEM((dk, dv), jnp.float32),  # d_state, carried
            pltpu.VMEM((group, dk, dv), jnp.float32),  # each chunk's start state
            pltpu.VMEM((spans, span, span), jnp.float32),  # each span's inverse
            pltpu.VMEM((spans, span, dk), jnp.float32),  # W
            pltpu.VMEM((spans, span, dv), jnp.float32),  # D
        ],
    )(q, k, v, g, beta, starts, d_out)
    return d_q, d_k, d_v, d_g.reshape(d_out.shape[:3]), d_beta.reshape(d_out.shape[:3])
