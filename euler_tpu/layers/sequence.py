"""Sequence mixers of decoder-only language models and what they share:
`GatedDeltaNet` (linear attention by the gated delta rule),
`GatedShortConv` (a causal depthwise convolution of a few taps between
two gates), `Mamba2Mixer` (Mamba-2's state-space mixer),
`GatedAttention` (causal softmax attention with an output gate, over
every earlier key or over a sliding window of them),
`IndexedSparseAttention` (causal softmax attention over the keys a
learned indexer picks for each query), the zero-centred `RMSNorm` and
the rotary embedding, by one position or by three.

Inputs are [B, T, H] float32. Projections run at the backend's default
matmul precision; norms, gates, the delta rule's state, every softmax
and the indexer's KL term are float32 (ops/seq_ops.py). Each mixer names
its parts for a trace: `euler.gdn.{proj,conv,scan,out}`,
`euler.sconv.{proj,mix,out}`, `euler.ssd.{proj,conv,scan,out}`,
`euler.attn.{proj,core,out}` (`euler.swa.*` where the layer has a window),
`euler.dsa.{proj,index,select,core,aux,out}`. Every mixer is called as
`(x, positions) -> (y, its own loss or None)`. The two softmax mixers
and `GatedDeltaNet` name their core's output `CORE_OUTPUT`
(`_keep_core`): a rematerialised decoder layer keeps that value of its
forward, and with it the causal kernels' logsumexp and the delta rule's
state at each group's start (models/sequence_lm.py).
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from euler_tpu.ops import seq_ops
from euler_tpu.utils import trace

_MATRIX = nn.initializers.normal(stddev=0.02)

CORE_OUTPUT = "mixer_core"
_DT_RANGE = (1e-3, 1e-1)  # Mamba-2's dt_min, dt_max: where its steps start


def _keep_core(a):
    """Names what a rematerialised layer keeps of its mixer's core
    (models/sequence_lm.py: `save_only_these_names(CORE_OUTPUT)`): the
    core's output [B, G, R, T, d], the one thing the rest of the layer
    wants from it, where the core is the causal kernels
    (`seq_ops.causal_tile`) their logsumexp [B, G, R, T] as well, and
    where it is the delta rule the state [groups, B, H, dk, dv] at each
    group's start. How often the core then runs a step:

    - by dense blocks, and `IndexedSparseAttention` in either form: the
      blocks are checkpointed one by one and their residuals are their
      inputs, which the projections remake, so the layer's second forward
      has no use for the loop over the blocks and each block's forward
      runs twice (forward, and before its own backward), not three times;
    - `GatedAttention` by tiles: output and logsumexp are all the
      backward kernels read of the forward kernel, so that one runs once
      and each backward kernel once;
    - `GatedDeltaNet`: the rule's own backward
      (`seq_ops.chunk_gated_delta_rule`) makes a group again from the
      state at its start, which only a scan from the sequence's start
      gives: with output and start states kept the layer's second
      forward runs no scan, and each group's forward runs twice
      (forward, and before its own backward), not three times.

    A mixer that keeps its core counts itself as `mixer_core_kept`."""
    return checkpoint_name(a, CORE_OUTPUT)


def rms(x, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


class RMSNorm(nn.Module):
    """`(1 + w) * x / sqrt(mean(x^2) + eps)` over the last axis, `w` from
    zeros (a zero-centred weight; `w` from ones in `w * x / ...` is the
    same function)."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        w = self.param("w", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        return rms(x, self.eps) * (1.0 + w)


def rotary(x, theta: float, rotary_dim: int, positions=None, sections=None):
    """Rotary position embedding on the first `rotary_dim` of the last
    axis (HF `rotate_half` pairing: dimension i with i + rotary_dim/2).
    x [B, T, heads, d]. Without `positions` they are 0..T-1 along axis 1.
    With `positions` [A, B, T] (A axes: time, height, width) frequency
    pair j turns by the axis `sections` puts it in — consecutive runs of
    `sections[a]` pairs, which add up to `rotary_dim / 2` (Qwen2-VL's
    multi-axis rotary) — and by `positions[0]` when `sections` is None."""
    half = rotary_dim // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    if positions is None:
        angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
        cos = jnp.cos(angle)[None, :, None, :]
        sin = jnp.sin(angle)[None, :, None, :]
    else:
        axis = np.repeat(np.arange(len(sections)), sections) if sections else np.zeros(half, int)
        # [B, T, half]: pair j reads the position of its own axis
        angle = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[..., axis] * inv_freq
        cos = jnp.cos(angle)[:, :, None, :]
        sin = jnp.sin(angle)[:, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1
    )


class GatedDeltaNet(nn.Module):
    """Gated DeltaNet linear attention: one [dk, dv] state per value head,
    decayed by a per-head gate and corrected by the delta rule at every
    step, computed chunk by chunk (`seq_ops.chunk_gated_delta_rule`).

    `[q, k, v, z] = x W_qkvz`, `[b, a] = x W_ba`; (q, k, v) pass a causal
    depthwise conv of `conv_kernel` taps and SiLU; q, k are L2-normalised
    per head, q scaled by dk^-0.5, key heads repeated to pair with value
    heads; `beta = sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)`;
    the result is RMS-normalised per head, gated by SiLU(z) and projected
    back. Columns of W_qkvz lie [q | k | v | z], each head by head (HF
    interleaves them by key head: a permutation of columns).
    x [B, T, H] -> (y [B, T, H], None: no loss of its own); the order of
    the sequence is all the position it knows. A layer is tallied
    `gdn_rule_kernel` where the rule runs its groups of chunks in the
    Pallas kernels and `gdn_rule_xla` where they are XLA's
    (`seq_ops.delta_rule_by_kernels`: the shapes decide).
    """

    num_k_heads: int
    num_v_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_kernel: int = 4
    chunk: int = 64
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions=None):
        batch, length, hidden = x.shape
        nk, nv, dk, dv = (
            self.num_k_heads, self.num_v_heads, self.head_k_dim, self.head_v_dim
        )
        key_dim, value_dim = nk * dk, nv * dv
        conv_dim = 2 * key_dim + value_dim
        w_qkvz = self.param(
            "in_proj_qkvz", _MATRIX, (hidden, conv_dim + value_dim), jnp.float32
        )
        w_ba = self.param("in_proj_ba", _MATRIX, (hidden, 2 * nv), jnp.float32)
        w_conv = self.param(
            "conv", _MATRIX, (conv_dim, self.conv_kernel), jnp.float32
        )
        a_log = self.param("A_log", nn.initializers.zeros, (nv,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (nv,), jnp.float32)
        w_norm = self.param("norm", nn.initializers.zeros, (dv,), jnp.float32)
        w_out = self.param("out_proj", _MATRIX, (value_dim, hidden), jnp.float32)

        with trace.scope("gdn.proj"):
            qkvz = x @ w_qkvz
            ba = (x @ w_ba).astype(jnp.float32)
            mixed, z = qkvz[..., :conv_dim], qkvz[..., conv_dim:]
            beta = jax.nn.sigmoid(ba[..., :nv])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., nv:] + dt_bias)
        with trace.scope("gdn.conv"):
            mixed = jax.nn.silu(seq_ops.causal_conv1d(mixed, w_conv))
        with trace.scope("gdn.scan"):

            def heads(a, n, d):  # [B, T, n*d] -> [B, n, T, d]
                return a.reshape(batch, length, n, d).transpose(0, 2, 1, 3)

            q = heads(mixed[..., :key_dim], nk, dk)
            k = heads(mixed[..., key_dim : 2 * key_dim], nk, dk)
            v = heads(mixed[..., 2 * key_dim :], nv, dv)
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
            q = jnp.repeat(q * dk**-0.5, nv // nk, axis=1)
            k = jnp.repeat(k, nv // nk, axis=1)
            by_kernels = seq_ops.delta_rule_by_kernels(q, v, self.chunk)
            trace.count("gdn_rule_kernel" if by_kernels else "gdn_rule_xla")
            o = seq_ops.chunk_gated_delta_rule(
                q, k, v, g.transpose(0, 2, 1), beta.transpose(0, 2, 1),
                chunk=self.chunk, keep=_keep_core,
            )
            trace.count("mixer_core_kept")
        with trace.scope("gdn.out"):
            o = o.transpose(0, 2, 1, 3)  # [B, T, nv, dv]
            gate = jax.nn.silu(z.reshape(batch, length, nv, dv))
            o = rms(o, self.eps) * (1.0 + w_norm) * gate
            return o.reshape(batch, length, value_dim) @ w_out, None


class GatedShortConv(nn.Module):
    """LFM2's short convolution (HF `Lfm2ShortConv`) as a layer's whole
    mixer: `[B | C | x~] = x W_in` (columns in that order), `u = B * x~`,
    `c_t = sum_j w[:, j] u_{t-(taps-1)+j}` with zeros before the
    sequence (`seq_ops.causal_conv1d`: depthwise, no bias), `y = (C * c)
    W_out`. No activation and no norm inside, no state beyond the
    `taps - 1` earlier steps; the order of the sequence is all the
    position it knows. `euler.sconv.mix` holds the two gates and the
    taps and nothing else: element-wise passes over [T, H], bound by
    memory. It names no `CORE_OUTPUT`, so a rematerialised layer makes
    it again whole. Counts itself `sconv_layers` and its `sconv_taps`.
    x [B, T, H] -> (y [B, T, H], None: no loss of its own)."""

    taps: int = 3

    @nn.compact
    def __call__(self, x, positions=None):
        hidden = x.shape[-1]
        w_in = self.param("in_proj", _MATRIX, (hidden, 3 * hidden), jnp.float32)
        # torch's default for a depthwise `Conv1d`: uniform +-fan_in^-0.5
        w_conv = self.param(
            "conv", nn.initializers.normal(stddev=(3 * self.taps) ** -0.5),
            (hidden, self.taps), jnp.float32,
        )
        w_out = self.param("out_proj", _MATRIX, (hidden, hidden), jnp.float32)
        trace.count("sconv_layers")
        trace.count("sconv_taps", self.taps)
        with trace.scope("sconv.proj"):
            gates = x @ w_in
        with trace.scope("sconv.mix"):
            b, c, fed = jnp.split(gates, 3, axis=-1)
            mixed = c * seq_ops.causal_conv1d(b * fed, w_conv)
        with trace.scope("sconv.out"):
            return mixed @ w_out, None


class Mamba2Mixer(nn.Module):
    """Mamba-2's mixer (HF `Mamba2Mixer`, one group): `[z | xBC | dt] = x
    W_in` (columns d_inner | d_inner + 2 N | heads); `xBC = SiLU(causal
    conv of `conv_kernel` taps + bias)`, `[x | B | C] = xBC`; `dt =
    softplus(dt + dt_bias)`, `A = -exp(A_log)`; per head the state-space
    scan `S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`, `y_t = S_t C_t + D
    x_t` (`seq_ops.chunked_ssd`, chunks of `chunk`); `y = RMSNorm(y *
    SiLU(z)) W_out`, the norm over all d_inner. B and C are shared by the
    heads. The order of the sequence is all the position it knows.

    `A_log`, `dt_bias`, `D` and the norm are written as departures from
    where Mamba-2 starts them, as the zero-centred `RMSNorm` writes its
    weight (the same function, the same gradient): `A_log = log(1..heads)
    + w`, `dt_bias = softplus^-1(dt0) + w` with `dt0` spread
    log-uniformly over [1e-3, 1e-1] by head, `D = 1 + w`, the norm's `1 +
    w`, every `w` from zeros. Scopes `euler.ssd.{proj,conv,scan,out}`;
    nothing is named `CORE_OUTPUT`, so a rematerialised layer makes the
    mixer again whole and the scan's forward runs twice a step. Counts
    itself `ssd_layers` and its state size into `ssd_state`, and
    `ssd_scan_kernel` where the scan runs in the Pallas kernels or
    `ssd_scan_xla` where it is XLA's (`seq_ops.ssd_by_kernels`: the
    shapes decide). x [B, T, H] -> (y [B, T, H], None: no loss of its
    own)."""

    num_heads: int
    head_dim: int
    state_size: int
    conv_kernel: int = 4
    chunk: int = 256
    eps: float = 1e-5

    def starts(self):
        """(A_log, dt_bias) where Mamba-2 starts them, by head: A =
        -(1..heads), dt log-uniform over `_DT_RANGE`."""
        lo, hi = np.log(_DT_RANGE)
        dt = np.exp(lo + (hi - lo) * (np.arange(self.num_heads) + 0.5) / self.num_heads)
        return (
            np.log(np.arange(1, self.num_heads + 1, dtype=np.float32)),
            (dt + np.log(-np.expm1(-dt))).astype(np.float32),
        )

    @nn.compact
    def __call__(self, x, positions=None):
        batch, length, hidden = x.shape
        heads, p, n = self.num_heads, self.head_dim, self.state_size
        inner = heads * p
        conv_dim = inner + 2 * n
        w_in = self.param("in_proj", _MATRIX, (hidden, inner + conv_dim + heads), jnp.float32)
        # torch's default for a depthwise `Conv1d`: uniform +-fan_in^-0.5
        w_conv = self.param(
            "conv", nn.initializers.normal(stddev=(3 * self.conv_kernel) ** -0.5),
            (conv_dim, self.conv_kernel), jnp.float32,
        )
        b_conv = self.param(
            "conv_bias", nn.initializers.normal(stddev=(3 * self.conv_kernel) ** -0.5),
            (conv_dim,), jnp.float32,
        )
        w_a = self.param("A_log", nn.initializers.zeros, (heads,), jnp.float32)
        w_dt = self.param("dt_bias", nn.initializers.zeros, (heads,), jnp.float32)
        w_d = self.param("D", nn.initializers.zeros, (heads,), jnp.float32)
        w_norm = self.param("norm", nn.initializers.zeros, (inner,), jnp.float32)
        w_out = self.param("out_proj", _MATRIX, (inner, hidden), jnp.float32)
        a_log, dt_bias = self.starts()
        trace.count("ssd_layers")
        trace.count("ssd_state", n)
        with trace.scope("ssd.proj"):
            zxbcdt = x @ w_in
            z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner : inner + conv_dim]
            dt = jax.nn.softplus(
                zxbcdt[..., inner + conv_dim :].astype(jnp.float32) + dt_bias + w_dt
            )
        with trace.scope("ssd.conv"):
            xbc = jax.nn.silu(seq_ops.causal_conv1d(xbc, w_conv, b_conv))
        with trace.scope("ssd.scan"):
            xs = xbc[..., :inner].reshape(batch, length, heads, p)
            b, c = xbc[..., inner : inner + n], xbc[..., inner + n :]
            by_kernels = seq_ops.ssd_by_kernels(xs, b, self.chunk)
            trace.count("ssd_scan_kernel" if by_kernels else "ssd_scan_xla")
            a = dt * -jnp.exp(a_log + w_a)  # [B, T, heads], <= 0
            y = seq_ops.chunked_ssd(
                (xs * dt[..., None]).transpose(0, 2, 1, 3), a.transpose(0, 2, 1), b, c,
                chunk=self.chunk,
            )
        with trace.scope("ssd.out"):
            y = y.transpose(0, 2, 1, 3) + xs * (1.0 + w_d)[:, None]
            y = y.reshape(batch, length, inner) * jax.nn.silu(z)
            return (rms(y, self.eps) * (1.0 + w_norm)) @ w_out, None


class GatedAttention(nn.Module):
    """Causal softmax attention with grouped queries, a zero-centred
    RMSNorm on each query and key head, rotary embedding on the first
    `rotary_dim` of the head (0: no rotary, and the layer knows no
    position at all), and a sigmoid gate on the output, computed from
    the same projection as the query (W_q holds, head by head,
    [query | gate]). Gate and head norms are the model's choice: with
    `gated` False W_q is [hidden, heads * d], no sigmoid is applied and
    the layer counts itself `attn_ungated`; with `head_norms` False
    there are no `q_norm` / `k_norm` leaves. With a `window`, a query
    sees that many keys, itself
    among them, and the layer's scopes are `euler.swa.*`, so that a trace
    tells the two kinds of layer apart; without one it sees every earlier
    key, under `euler.attn.*`. A layer counts itself `swa_layers` and its
    window into `swa_window`, or `attn_full_layers`. The softmax
    (`seq_ops.blockwise_causal_attention`) runs tile by tile in Pallas
    kernels, one call a layer over the whole sequence, where length and
    `block` are whole 128-wide tiles of the chip and the head is such
    tiles or the half tile, 64 (`seq_ops.causal_tile`: the shapes
    decide), and block by block as dense float32 tensors everywhere
    else; a layer is tallied `attn_core_kernel` or `attn_core_dense`, and
    `attn_head_64` where the kernels run at that head. The scores are
    scaled by `scale`, head_dim^-0.5 unless the model says otherwise. What
    it returns, before the gate, is the layer's `CORE_OUTPUT` (`_keep_core`).
    x [B, T, H] -> (y [B, T, H], None: no loss of its own)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 1e7
    rotary_dim: int = 64
    block: int = 512
    eps: float = 1e-6
    window: int | None = None
    gated: bool = True
    head_norms: bool = True
    scale: float | None = None  # the scores' factor; None: head_dim^-0.5

    @nn.compact
    def __call__(self, x, positions=None):
        batch, length, hidden = x.shape
        nq, nkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        q_cols = 2 * d if self.gated else d  # a head's [query | gate], or its query
        w_q = self.param("q_proj", _MATRIX, (hidden, nq * q_cols), jnp.float32)
        w_k = self.param("k_proj", _MATRIX, (hidden, nkv * d), jnp.float32)
        w_v = self.param("v_proj", _MATRIX, (hidden, nkv * d), jnp.float32)
        w_o = self.param("o_proj", _MATRIX, (nq * d, hidden), jnp.float32)
        kind = "attn" if self.window is None else "swa"
        trace.count("mixer_core_kept")
        if self.window is None:
            trace.count("attn_full_layers")
        else:
            trace.count("swa_layers")
            trace.count("swa_window", self.window)
        if not self.gated:
            trace.count("attn_ungated")
        with trace.scope(f"{kind}.proj"):
            q = (x @ w_q).reshape(batch, length, nq, q_cols)
            if self.gated:
                q, gate = q[..., :d], q[..., d:]
            k = (x @ w_k).reshape(batch, length, nkv, d)
            v = (x @ w_v).reshape(batch, length, nkv, d)

            def turn(a, norm):
                if self.head_norms:
                    a = RMSNorm(self.eps, name=norm)(a)
                return rotary(a, self.rope_theta, self.rotary_dim) if self.rotary_dim else a

            q, k = turn(q, "q_norm"), turn(k, "k_norm")
        with trace.scope(f"{kind}.core"):
            q = q.reshape(batch, length, nkv, nq // nkv, d).transpose(0, 2, 3, 1, 4)
            by_tiles = seq_ops.causal_tile(q, self.block)
            trace.count("attn_core_kernel" if by_tiles else "attn_core_dense")
            if by_tiles and d == 64:
                trace.count("attn_head_64")
            o = seq_ops.blockwise_causal_attention(
                q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                scale=d**-0.5 if self.scale is None else self.scale,
                block=self.block, window=self.window, keep=_keep_core,
            )
        with trace.scope(f"{kind}.out"):
            o = o.transpose(0, 3, 1, 2, 4).reshape(batch, length, nq, d)
            if self.gated:
                o = o * jax.nn.sigmoid(gate.astype(jnp.float32))
            return o.reshape(batch, length, nq * d) @ w_o, None


def query_runs(length: int, block: int, topk: int) -> list:
    """How `IndexedSparseAttention` cuts its queries: `(first row, blocks,
    rows a block, keys)` for each run of equal blocks that attend to the
    first `keys` keys — a stretch that holds the run's last row. The
    stretches double from `max(topk, block)` up to `length`, so a context
    of any length is a handful of programs (not one a block) and the
    scores computed are at most two thirds of the square, where whole
    blocks over the causal half would be a half. The first run's queries
    have `topk` keys or fewer when `topk` is whole blocks; a last block
    that is not whole is a run of its own."""
    block = min(block, length)
    whole, done, runs = length // block, 0, []
    keys = -(-max(topk, block) // block) * block
    while done < whole:
        upto = min(keys // block, whole)
        runs.append((done * block, upto - done, block, min(keys, length)))
        done, keys = upto, 2 * keys
    if length % block:
        runs.append((whole * block, 1, length % block, length))
    return runs


class IndexedSparseAttention(nn.Module):
    """Causal softmax attention with grouped queries in which a query
    attends to the `topk` keys a learned indexer scores highest for it
    (DeepSeek sparse attention's lightning indexer), one set for all
    heads; a query that has `topk` keys or fewer attends to all of them.
    x [B, T, H], positions [3, B, T] -> (y [B, T, H], the indexer's loss).

    Attention: zero-centred RMSNorm on each query and key head, rotary by
    three position axes over the whole head (`sections`), no output gate.
    Indexer, fed `x` with the gradient stopped: `index_heads` queries of
    `index_dim` and one key, LayerNorm on the key, rotary by the time
    position over the whole of `index_dim`, head weights `x W_w` scaled
    by `index_heads^-0.5 index_dim^-0.5`; `I[t, s] = sum_j w[t, j]
    relu(q[t, j] . k[s])`. Its loss: the mean over the queries of
    `KL(p_t || softmax over the picked keys of I[t])`, p_t the heads'
    attention probabilities summed and normalised, as a constant. The
    pick passes no gradient, so the language-model loss does not reach
    the indexer, and this loss reaches nothing else.

    One block of `block` queries at a time: scores, pick, attention
    under the pick's mask (a gather of the picked rows would cost more
    than the masked products it saves), the heads' probabilities
    averaged, KL. The blocks of a run (`query_runs`) see the same stretch
    of keys and are one loop over one program; a block is rematerialised
    before its own backward, so its [block, keys] index scores do not
    outlive it, and its residuals are its inputs. That backward remakes
    the indexer's [B, index_heads, block, keys] products a second time,
    a head at a time (`seq_ops.indexer_scores` has a backward of its
    own, tallied `dsa_index_vjp`): forward and in the block's second
    forward they exist whole, in the backward never. Where a run's blocks
    are whole tiles of the chip (rows, keys and head multiples of 128:
    `seq_ops.attends_by_tiles`) the attention and the average are Pallas
    kernels and a block's [B, G, R, block, keys] scores and probabilities
    never exist (23 GB a layer at 16,384 tokens); elsewhere they are
    dense float32 tensors that live as long as the block. A layer is
    tallied `dsa_core_kernel`, `dsa_core_masked`, or both if its runs
    differ; it counts itself `dsa_layers` and its `topk` into
    `dsa_topk`. The runs' outputs put together, [B, G, R, T, d], are the
    layer's `CORE_OUTPUT`: a decoder layer that is rematerialised keeps
    them, so its second forward remakes the projections and never runs
    the loops.
    """

    num_heads: int
    num_kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int
    topk: int
    rope_theta: float = 1e7
    sections: tuple = ()
    block: int = 512
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions):
        batch, length, hidden = x.shape
        nq, nkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        ni, di = self.index_heads, self.index_dim
        w_q = self.param("q_proj", _MATRIX, (hidden, nq * d), jnp.float32)
        w_k = self.param("k_proj", _MATRIX, (hidden, nkv * d), jnp.float32)
        w_v = self.param("v_proj", _MATRIX, (hidden, nkv * d), jnp.float32)
        w_o = self.param("o_proj", _MATRIX, (nq * d, hidden), jnp.float32)
        i_q = self.param("index_q", _MATRIX, (hidden, ni * di), jnp.float32)
        i_k = self.param("index_k", _MATRIX, (hidden, di), jnp.float32)
        i_w = self.param("index_w", _MATRIX, (hidden, ni), jnp.float32)
        i_scale = self.param("index_k_norm_w", nn.initializers.zeros, (di,), jnp.float32)
        i_bias = self.param("index_k_norm_b", nn.initializers.zeros, (di,), jnp.float32)
        trace.count("dsa_layers")
        trace.count("dsa_topk", self.topk)
        trace.count("dsa_index_vjp")  # `seq_ops.indexer_scores` brings its own backward
        trace.count("mixer_core_kept")
        with trace.scope("dsa.proj"):
            q = (x @ w_q).reshape(batch, length, nq, d)
            k = (x @ w_k).reshape(batch, length, nkv, d)
            v = (x @ w_v).reshape(batch, length, nkv, d)
            turn = functools.partial(
                rotary, theta=self.rope_theta, rotary_dim=d,
                positions=positions, sections=self.sections,
            )
            q = turn(RMSNorm(self.eps, name="q_norm")(q))
            k = turn(RMSNorm(self.eps, name="k_norm")(k))
            q = q.reshape(batch, length, nkv, nq // nkv, d).transpose(0, 2, 3, 1, 4)
            k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            fed = jax.lax.stop_gradient(x)
            qi = rotary(
                (fed @ i_q).reshape(batch, length, ni, di),
                self.rope_theta, di, positions[:1],
            )
            ki = (fed @ i_k).astype(jnp.float32)
            ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
            ki = rms(ki, self.eps) * (1.0 + i_scale) + i_bias
            ki = rotary(ki[:, :, None, :], self.rope_theta, di, positions[:1])[:, :, 0]
            wi = (fed @ i_w).astype(jnp.float32) * (ni**-0.5 * di**-0.5)

        def one(q_b, qi_b, wi_b, first, k_r, v_r, ki_r):
            with trace.scope("dsa.index"):
                scores = seq_ops.indexer_scores(qi_b, ki_r, wi_b, first)
            with trace.scope("dsa.select"):
                # a run whose last query has `topk` keys or fewer picks
                # every key it may see
                keep = scores > -jnp.inf
                if k_r.shape[2] > self.topk:
                    keep = seq_ops.topk_mask(jax.lax.stop_gradient(scores), self.topk)
            with trace.scope("dsa.core"):
                o_b, lse = seq_ops.masked_attention(q_b, k_r, v_r, keep, d**-0.5)
            with trace.scope("dsa.aux"):
                p = seq_ops.attention_share(q_b, k_r, keep, lse, d**-0.5)
                return o_b, seq_ops.index_kl(p, scores, keep)

        outs, kl, forms = [], jnp.zeros((), jnp.float32), set()
        for first, count, rows, keys in query_runs(length, self.block, self.topk):
            upto = first + count * rows
            cut = lambda a, axis: jnp.moveaxis(  # noqa: E731  blocks to the front
                a.reshape(a.shape[:axis] + (count, rows) + a.shape[axis + 1 :]), axis, 0
            )
            # cut outside the loop: the blocks' cotangents of the stretch add
            # up at its own length, and are padded to the sequence's once
            stretch = (k[:, :, :keys], v[:, :, :keys], ki[:, :keys])
            forms.add(seq_ops.attends_by_tiles(q[:, :, :, :rows], stretch[0]))
            o_r, kl_r = jax.lax.map(
                lambda xs: jax.checkpoint(one)(*xs, *stretch),
                (
                    cut(q[:, :, :, first:upto], 3), cut(qi[:, first:upto], 1),
                    cut(wi[:, first:upto], 1), first + rows * jnp.arange(count),
                ),
            )
            o_r = jnp.moveaxis(o_r, 0, 3)  # [B, G, R, count, rows, d]
            outs.append(o_r.reshape(o_r.shape[:3] + (count * rows, d)))
            kl = kl + jnp.sum(kl_r)
        # a layer counts under each form any of its runs took
        for by_tiles in forms:
            trace.count("dsa_core_kernel" if by_tiles else "dsa_core_masked")
        with trace.scope("dsa.out"):
            o = _keep_core(jnp.concatenate(outs, axis=3)).transpose(0, 3, 1, 2, 4)
            return o.reshape(batch, length, nq * d) @ w_o, kl / (batch * length)
