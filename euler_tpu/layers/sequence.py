"""Sequence mixers of hybrid linear/softmax-attention language models
(Qwen3-Next: HF `modeling_qwen3_next.py`): `GatedDeltaNet`,
`GatedAttention` and the zero-centred `RMSNorm` they share.

Inputs are [B, T, H] float32. Projections run at the backend's default
matmul precision; norms, gates, the delta rule's state and the softmax
are float32 (ops/seq_ops.py). Each mixer names its parts for a trace:
`euler.gdn.{proj,conv,scan,out}`, `euler.attn.{proj,core,out}`.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from euler_tpu.ops import seq_ops
from euler_tpu.utils import trace

_MATRIX = nn.initializers.normal(stddev=0.02)


def rms(x, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


class RMSNorm(nn.Module):
    """`(1 + w) * x / sqrt(mean(x^2) + eps)` over the last axis, `w` from
    zeros (Qwen3-Next's zero-centred weight)."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        w = self.param("w", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        return rms(x, self.eps) * (1.0 + w)


def rotary(x, theta: float, rotary_dim: int):
    """Rotary position embedding on the first `rotary_dim` of the last
    axis (HF `rotate_half` pairing: dimension i with i + rotary_dim/2),
    positions 0..T-1 along axis 1. x [B, T, heads, d]."""
    half = rotary_dim // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
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
    """

    num_k_heads: int
    num_v_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_kernel: int = 4
    chunk: int = 64
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
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
            o = seq_ops.chunk_gated_delta_rule(
                q, k, v, g.transpose(0, 2, 1), beta.transpose(0, 2, 1),
                chunk=self.chunk,
            )
        with trace.scope("gdn.out"):
            o = o.transpose(0, 2, 1, 3)  # [B, T, nv, dv]
            gate = jax.nn.silu(z.reshape(batch, length, nv, dv))
            o = rms(o, self.eps) * (1.0 + w_norm) * gate
            return o.reshape(batch, length, value_dim) @ w_out


class GatedAttention(nn.Module):
    """Causal softmax attention with grouped queries, a zero-centred
    RMSNorm on each query and key head, rotary embedding on the first
    `rotary_dim` of the head, and a sigmoid gate on the output, computed
    from the same projection as the query (W_q holds, head by head,
    [query | gate]). The softmax runs block by block
    (`seq_ops.blockwise_causal_attention`)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 1e7
    rotary_dim: int = 64
    block: int = 512
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        batch, length, hidden = x.shape
        nq, nkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        w_q = self.param("q_proj", _MATRIX, (hidden, nq * d * 2), jnp.float32)
        w_k = self.param("k_proj", _MATRIX, (hidden, nkv * d), jnp.float32)
        w_v = self.param("v_proj", _MATRIX, (hidden, nkv * d), jnp.float32)
        w_o = self.param("o_proj", _MATRIX, (nq * d, hidden), jnp.float32)
        with trace.scope("attn.proj"):
            qg = (x @ w_q).reshape(batch, length, nq, 2 * d)
            q, gate = qg[..., :d], qg[..., d:]
            k = (x @ w_k).reshape(batch, length, nkv, d)
            v = (x @ w_v).reshape(batch, length, nkv, d)
            q = rotary(RMSNorm(self.eps, name="q_norm")(q), self.rope_theta, self.rotary_dim)
            k = rotary(RMSNorm(self.eps, name="k_norm")(k), self.rope_theta, self.rotary_dim)
        with trace.scope("attn.core"):
            q = q.reshape(batch, length, nkv, nq // nkv, d).transpose(0, 2, 3, 1, 4)
            o = seq_ops.blockwise_causal_attention(
                q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                scale=d**-0.5, block=self.block,
            )
        with trace.scope("attn.out"):
            o = o.transpose(0, 3, 1, 2, 4).reshape(batch, length, nq, d)
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32))
            return o.reshape(batch, length, nq * d) @ w_o
