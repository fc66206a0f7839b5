"""Device-side primitives of sequence layers (layers/sequence.py,
layers/moe.py): a causal depthwise convolution over time, the chunked
gated delta rule, causal softmax attention by query blocks (over every
earlier key, or over a sliding window of them), the parts of
indexed sparse attention (an indexer's scores, the k largest of a row as
a mask, softmax attention under that mask, the heads' probabilities
averaged, the indexer's KL term), and the grouped matmul over rows sorted
by expert.

Plain XLA over static shapes, but for the two softmax attentions where
their shapes are whole tiles of the chip: the one under a mask and the
causal one are Pallas kernels (ops/masked_flash.py, imported when such a
shape is first traced).
Matmuls run at the backend's default precision (bf16 inputs, float32
accumulation on the TPU) unless said. Inputs and results are float32; so
are the delta rule's state and gates and the attention's softmax.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Array = jax.Array

# the chunk-local inverse multiplies a matrix by itself log2(chunk) times:
# rounding its inputs to bf16 at every level would compound
_INVERSE_PRECISION = jax.lax.Precision.HIGHEST


def causal_conv1d(x: Array, weight: Array) -> Array:
    """Depthwise convolution over time that sees no later step, no bias.

    x [B, T, C], weight [C, K]: y_t = sum_j weight[:, j] * x_{t-(K-1)+j},
    with zeros before the sequence's start (torch `Conv1d(groups=C,
    padding=K-1)` cut to T).
    """
    length, taps = x.shape[1], weight.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(
        padded[:, j : j + length, :] * weight[:, j] for j in range(taps)
    )


@jax.custom_vjp
def _unit_lower_inverse(lower: Array) -> Array:
    """(I + L)^-1 for strictly lower-triangular L [..., C, C]. L is
    nilpotent, so the Neumann series ends and factors into
    (I - L)(I + L^2)(I + L^4)... — log2(C) squarings on the MXU where a
    forward substitution would take C dependent steps. Its gradient is
    taken from the result alone (`d(A^-1) = -A^-1 dA A^-1`), not through
    the squarings."""
    size = lower.shape[-1]
    eye = jnp.eye(size, dtype=lower.dtype)
    power = -lower
    inverse = eye + power
    reach = 2  # `inverse` holds the series up to power reach - 1
    while reach < size:
        power = jnp.matmul(power, power, precision=_INVERSE_PRECISION)
        inverse = inverse + jnp.matmul(
            inverse, power, precision=_INVERSE_PRECISION
        )
        reach *= 2
    return inverse


def _unit_lower_inverse_fwd(lower):
    inverse = _unit_lower_inverse(lower)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, cotangent):
    t = jnp.swapaxes(inverse, -1, -2)
    inner = jnp.matmul(cotangent, t, precision=_INVERSE_PRECISION)
    return (-jnp.matmul(t, inner, precision=_INVERSE_PRECISION),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _chunk_local(q, k, v, g, beta):
    """What a chunk needs that does not depend on the state: for chunks
    [n, B, H, C, ...] the scan's inputs (U, W, masked Q K^T, exp(G) Q,
    exp(G_end - G) K, G_end)."""
    chunk = q.shape[-2]
    run = jnp.cumsum(g, axis=-1)  # G: [n, B, H, C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(G_i - G_j) for j <= i, where the exponent is <= 0; 0 above
    gap = run[..., :, None] - run[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gap, 0.0)), 0.0)
    k_beta = k * beta[..., None]
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    kk = jnp.einsum("...id,...jd->...ij", k_beta, k)
    solve = _unit_lower_inverse(jnp.where(strict, kk * decay, 0.0))
    u = jnp.matmul(solve, v * beta[..., None])  # (I+L)^-1 beta V
    w = jnp.matmul(solve, k_beta * jnp.exp(run)[..., None])
    qk = jnp.einsum("...id,...jd->...ij", q, k) * decay
    q_run = q * jnp.exp(run)[..., None]
    end = run[..., -1]  # G at the chunk's end: [n, B, H]
    k_end = k * jnp.exp(end[..., None] - run)[..., None]
    return u, w, qk, q_run, k_end, end


def _chunk_step(state, xs):
    """One chunk against the state [B, H, dk, dv] at its start."""
    u, w, qk, q_run, k_end, end = xs
    d = u - jnp.matmul(w, state)
    out = jnp.matmul(q_run, state) + jnp.matmul(qk, d)
    state = state * jnp.exp(end)[..., None, None] + jnp.einsum(
        "...cd,...ce->...de", k_end, d
    )
    return state, out


def _one_group(state, xs):
    """A group of chunks [group, B, H, C, ...] against the state at its
    start -> (the state at its end, its output [group, B, H, C, dv])."""
    return jax.lax.scan(_chunk_step, state, _chunk_local(*xs))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _grouped_rule(q, k, v, g, beta, keep):
    return _grouped_rule_fwd(q, k, v, g, beta, keep)[0]


def _grouped_rule_fwd(q, k, v, g, beta, keep):
    """The groups [groups, group, B, H, C, ...] in turn -> the output
    [groups, group, B, H, C, dv]. Of this forward the backward reads the
    state at every group's start, [groups, B, H, dk, dv], and nothing
    else that its arguments do not remake; that and the output pass
    through `keep`."""
    xs = (q, k, v, g, beta)

    def forward(state, xs):
        end, out = _one_group(state, xs)
        return end, (state, out)

    state = jnp.zeros(q.shape[2:4] + (q.shape[-1], v.shape[-1]), jnp.float32)
    _, (starts, out) = jax.lax.scan(forward, state, xs)
    starts = keep(starts)
    return keep(out), (xs, starts)


def _grouped_rule_bwd(keep, residuals, d_out):
    """The groups from the last to the first, the cotangent of the state
    carried: each is made again from its start (its chunk-local matrices
    and its scan) and `(d_state, d_out)` pulled back through it."""
    xs, starts = residuals

    def backward(d_state, group):
        state, xs, d_out = group
        _, pull = jax.vjp(_one_group, state, xs)
        return pull((d_state, d_out))

    _, d_xs = jax.lax.scan(
        backward, jnp.zeros_like(starts[0]), (starts, xs, d_out), reverse=True
    )
    return d_xs


_grouped_rule.defvjp(_grouped_rule_fwd, _grouped_rule_bwd)


def chunk_gated_delta_rule(
    q: Array, k: Array, v: Array, g: Array, beta: Array,
    chunk: int = 64, group: int = 4, keep=lambda a: a,
) -> Array:
    """The gated delta rule, chunk by chunk (Yang et al., Gated Delta
    Networks; the WY form of HF `torch_chunk_gated_delta_rule`).

    q, k [B, H, T, dk] (already normalised and scaled), v [B, H, T, dv],
    g [B, H, T] the log of the decay (<= 0), beta [B, H, T]. Per head,
    from S_0 = 0:

        S~ = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S~^T k_t)
        S_t = S~ + k_t d_t^T;   o_t = S_t^T q_t

    Inside a chunk, with G the running sum of g and S the state at the
    chunk's start, the d_t solve (I + L) D = beta V - (beta exp(G) K) S,
    L_ij = beta_i exp(G_i - G_j) k_i.k_j below the diagonal; then
    O = (exp(G) Q) S + tril(Q K^T exp(G_i - G_j)) D and the state moves
    on by exp(G_end) S + (exp(G_end - G) K)^T D. Only that last part is
    sequential: a `lax.scan` over the chunks' states [dk, dv], in float32.

    The chunks are taken `group` at a time: a group's chunk-local
    matrices are made together (batched matmuls) and its states scanned.
    The backward is the rule's own (`_grouped_rule`): it keeps the state
    at each group's start and makes one group again at a time, from the
    last to the first, so only one group's [C, C] matrices and states
    are ever alive — they are several times the inputs — and a group's
    forward runs twice a step: forward, and before its own backward. The
    output and the groups' start states [groups, B, H, dk, dv] pass
    through `keep`: a caller that is rematerialised and saves what `keep`
    names (`layers/sequence.py:_keep_core`) has all the backward reads
    that its own second forward does not remake, so that one runs no
    scan; a caller that keeps nothing runs every group a third time.
    With the starts kept a group is no unit of rematerialisation any
    more, only of batching, and on the TPU four chunks are the fastest
    (value and gradient of [2, 32, 8192, 128] at chunk 64: 52.7 ms at 4,
    55.3 at 2, 61.1 at 8, 67.3 at 16, 85.3 at 32; PERF.md section 6,
    PR 43); the results are the same to the bit. A length that is not a
    whole number of groups is padded with steps that leave the state as
    it is (beta = 0, g = 0).
    Returns o [B, H, T, dv].
    """
    length = q.shape[2]
    group = min(group, -(-length // chunk))
    pad = -length % (chunk * group)
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, 0), (0, pad))) for a in (g, beta))
    groups = (length + pad) // (chunk * group)

    def split(a):  # [B, H, T, ...] -> [groups, group, B, H, C, ...]
        a = a.reshape(a.shape[:2] + (groups, group, chunk) + a.shape[3:])
        return jnp.moveaxis(a, (2, 3), (0, 1))

    out = _grouped_rule(*(split(a) for a in (q, k, v, g, beta)), keep)
    # [groups, group, B, H, C, dv] -> [B, H, T, dv]
    out = jnp.moveaxis(out, (0, 1), (2, 3))
    out = out.reshape(out.shape[:2] + (-1, out.shape[-1]))
    return out[:, :, :length]


def causal_tile(q: Array, block: int) -> int:
    """Which of its two forms `blockwise_causal_attention` takes for
    queries q [B, G, R, T, d] cut by `block`: the tile of the kernels
    (ops/masked_flash.py) — the largest of 512, 256, 128 that divides T
    and `block` and at which a tile of a key/value head's R query heads,
    [R, tile, d] float32, is at most 4 MiB (the cells': 512, at 0.5 to
    4 MiB) — where T and `block` are whole 128-wide tiles of the chip
    and d is such tiles or the half tile, 64 (the kernels' blocks then
    end in an extent of 64: Mosaic takes them, and a score's product is
    half as deep); 0, the dense blocks, everywhere else: a head of 16 or
    96, blocks of 16, a length of 576. The shapes decide; nothing a
    caller sets."""
    heads, length, head_dim = q.shape[2:]
    if head_dim % 128 and head_dim != 64:
        return 0
    block = min(block, length)
    return next(
        (
            tile for tile in (512, 256, 128)
            if length % tile == 0 and block % tile == 0
            and heads * tile * head_dim * 4 <= 4 * 2**20
        ),
        0,
    )


def blockwise_causal_attention(
    q: Array, k: Array, v: Array, scale: float, block: int = 512,
    window: int | None = None, keep=lambda a: a,
) -> Array:
    """Causal softmax attention, grouped queries, over every earlier key
    or, with a `window` shorter than the sequence, over the keys s with
    `t - window < s <= t` (`window` of them, the query's own among them).
    A window that holds the whole sequence is no window: the same
    program, to the letter. In neither of its two forms (`causal_tile`)
    does the whole [T, T] square of scores exist, and what lies beyond
    the causal line or before the window is not computed.

    By tiles, where the shapes are whole tiles of the chip (or the head
    half a tile, 64): one forward
    and two backward Pallas kernels a call, over the whole sequence
    (`masked_flash.causal_attention`). The mask is arithmetic on a
    tile's place: a tile of queries is given the tiles of keys it sees
    and no other (5 under a window of 2,048 at tiles of 512), the tiles
    wholly inside are run bare, the two the causal line and the window's
    far edge cut get an additive tile made from iotas. Scores and
    probabilities exist one [tile, tile] a head at a time, on the chip;
    q, k, v, the probabilities and the cotangents enter the MXU as
    bfloat16, everything else is float32. Of the forward the backward
    reads the output and the logsumexp [B, G, R, T], and both pass
    through `keep`: a caller that is rematerialised and saves what `keep`
    names (`layers/sequence.py:_keep_core`) runs the forward kernel once
    a step and each backward kernel once.

    By dense blocks, everywhere else (`keep` is applied to the result):
    one block of `block` queries at a time, a block's scores against the
    keys up to its end the largest tensor there is
    ([B, G, R, block, end] float32).
    Each block is rematerialised before its own backward, so no block's
    scores outlive it and a block's residuals are its inputs: a caller
    that is itself rematerialised and keeps the result never runs the
    blocks in its second forward, and a block's forward runs twice a
    step. Under a window a block is scored against the keys from
    `window - 1` before its first row — rounded down to a whole 128-key
    tile, so that the stretch starts where a tile does — to its own end,
    not from key 0. The first blocks' stretches begin at key 0 and grow,
    one program each as without a window; from the block whose stretch
    no longer reaches key 0 on, every whole block sees a stretch of one
    length and the blocks are one program run in a loop (`jax.lax.map`);
    a last block that is not whole is a program of its own.

    q [B, G, R, T, d] (G key/value heads, R query heads to each),
    k, v [B, G, T, d]. Returns [B, G, R, T, d].
    """
    length = q.shape[3]
    block = min(block, length)
    if window is not None and window >= length:
        window = None
    if by_tiles := causal_tile(q, block):
        from euler_tpu.ops import masked_flash

        return masked_flash.causal_attention(q, k, v, scale, by_tiles, window, keep)

    def one(q_b, k_b, v_b, first, key0):
        """Rows `first ..` against the keys `key0 ..`."""
        scores = jnp.einsum("bgrtd,bgsd->bgrts", q_b, k_b) * scale
        rows = first + jnp.arange(q_b.shape[3])[:, None]
        keys = jnp.arange(k_b.shape[2])[None, :]
        if window is None:
            seen = keys <= rows
        else:
            keys = key0 + keys
            seen = (keys <= rows) & (keys > rows - window)
        scores = jnp.where(seen, scores.astype(jnp.float32), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bgrts,bgsd->bgrtd", probs, v_b)

    # keys before a block's first row that it is scored against
    tile = math.gcd(block, 128)
    back = length if window is None else -(-(window - 1) // tile) * tile
    firsts = range(0, length, block)
    run = [first for first in firsts if back <= first <= length - block]

    def alone(first):
        """A block that is a program of its own, against the keys from
        `back` before its first row, or from key 0, to its end."""
        key0, end = max(first - back, 0), first + block
        return jax.checkpoint(one, static_argnums=(3, 4))(
            q[:, :, :, first:end], k[:, :, key0:end], v[:, :, key0:end], first, key0
        )

    # stretches that begin at key 0 and grow: one program each
    outs = [alone(first) for first in firsts if first < back]
    if run:  # whole blocks that see `back + block` keys each: one program

        @jax.checkpoint
        def sliding(q_b, first, k, v):
            # the stretch is cut inside the block's own rematerialisation:
            # cut outside, every block's keys would be kept for its backward
            cut = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                a, first - back, back + block, axis=2
            )
            return one(q_b, cut(k), cut(v), first, first - back)

        q_r = q[:, :, :, run[0] : run[-1] + block]
        q_r = q_r.reshape(q_r.shape[:3] + (len(run), block, q_r.shape[-1]))
        o_r = jax.lax.map(
            lambda xs: sliding(*xs, k, v), (jnp.moveaxis(q_r, 3, 0), jnp.asarray(run))
        )
        o_r = jnp.moveaxis(o_r, 0, 3)  # [B, G, R, blocks, block, d]
        outs.append(o_r.reshape(o_r.shape[:3] + (len(run) * block, o_r.shape[-1])))
    # a last block that is not whole
    outs += [alone(first) for first in firsts if first >= back and first not in run]
    return keep(jnp.concatenate(outs, axis=3))


def _seen(first, rows: int, keys: int) -> Array:
    """[rows, keys] bool: key s is no later than row r's position
    `first + r`."""
    return jnp.arange(keys)[None, :] <= first + jnp.arange(rows)[:, None]


@jax.custom_vjp
def indexer_scores(q: Array, k: Array, w: Array, first: int) -> Array:
    """A block of queries' index scores against the keys up to its end:
    `I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])`, -inf where s > t.

    q [B, R, J, d] (J indexer heads), k [B, S, d] (one key for all of
    them), w [B, R, J]; row r is position `first + r`. Returns float32
    [B, R, S]. Forward, the [B, J, R, S] products are the largest tensor
    alive. The backward is written by hand (`_indexer_cotangents`): it
    keeps q, k and w and makes the products again a head at a time, so
    no value of it holds all the heads' products.
    """
    dots = jnp.einsum("brjd,bsd->bjrs", q, k).astype(jnp.float32)
    weight = jnp.moveaxis(w.astype(jnp.float32), 2, 1)[..., None]
    scores = jnp.sum(weight * jax.nn.relu(dots), axis=1)
    return jnp.where(_seen(first, q.shape[1], k.shape[1]), scores, -jnp.inf)


def _indexer_cotangents(kept, d_scores):
    """The cotangents of `indexer_scores`' q, k and w (`first` has none)
    from its result's, dI [B, R, S]: with `g_j = w_j (q_j . k > 0) dI`
    under the causal line, `dq_j = g_j k`, `dk = sum_j g_j^T q_j`, `dw_j
    = sum_s relu(q_j . k) dI`. A loop over the heads, each making its own
    products [B, R, S] again, keys along the lanes: what `jax.grad`
    makes of the forward's three lines is the whole [B, J, R, S]
    cotangent, laid out anew for each of the three (on the TPU a fifth
    of the rate these products reach here)."""
    q, k, w, first = kept
    d_scores = jnp.where(_seen(first, q.shape[1], k.shape[1]), d_scores, 0.0)

    def head(dk, q_w):
        q_j, w_j = q_w  # [B, R, d], [B, R]
        dots = jnp.einsum("brd,bsd->brs", q_j, k).astype(jnp.float32)
        g = jnp.where(dots > 0, w_j[..., None] * d_scores, 0.0)
        dk = dk + jnp.einsum("brs,brd->bsd", g, q_j)
        dw_j = jnp.sum(jax.nn.relu(dots) * d_scores, axis=-1)
        return dk, (jnp.einsum("brs,bsd->brd", g, k), dw_j)

    dk, (dq, dw) = jax.lax.scan(
        head, jnp.zeros(k.shape, jnp.float32),
        (jnp.moveaxis(q, 2, 0), jnp.moveaxis(w.astype(jnp.float32), 2, 0)),
    )
    return (
        jnp.moveaxis(dq, 0, 2).astype(q.dtype), dk.astype(k.dtype),
        jnp.moveaxis(dw, 0, 2).astype(w.dtype), None,
    )


indexer_scores.defvjp(
    lambda q, k, w, first: (indexer_scores(q, k, w, first), (q, k, w, first)),
    _indexer_cotangents,
)


def _ordered_bits(x: Array) -> Array:
    """float32 -> uint32 that orders as the floats do (-0.0 as +0.0)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    flip = jnp.where(bits >> 31 == 1, jnp.uint32(0xFFFFFFFF), jnp.uint32(0x80000000))
    return bits ^ flip


def topk_mask(scores: Array, k: int) -> Array:
    """True at the k largest entries of each row of `scores` [..., S]
    that are not -inf (all of them where a row has k or fewer), ties to
    the lower index: what a stable descending sort would keep.

    No sort: the k-th largest value of a row is found bit by bit, from
    the top, over the floats' bits put in order — 32 passes that each
    count the entries at or over a candidate — and the entries equal to
    it are taken from the left until the row has its k. `jax.lax.top_k`
    at k in the thousands is a full sort of the row on the TPU.
    """
    seen = scores > -jnp.inf
    keys = _ordered_bits(scores)
    want = jnp.minimum(jnp.sum(seen, axis=-1, dtype=jnp.int32), k)[..., None]

    def refine(i, kth):
        trial = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= trial, axis=-1, keepdims=True, dtype=jnp.int32) >= want
        return jnp.where(enough, trial, kth)

    kth = jax.lax.fori_loop(0, 32, refine, jnp.zeros_like(want, jnp.uint32))
    over = keys > kth
    level = keys == kth
    room = want - jnp.sum(over, axis=-1, keepdims=True, dtype=jnp.int32)
    return seen & (over | (level & (jnp.cumsum(level, axis=-1, dtype=jnp.int32) <= room)))


def attends_by_tiles(q: Array, k: Array) -> bool:
    """Which of its two forms `masked_attention` (and `attention_share`)
    takes for a block of queries q [B, G, R, t, d] against keys
    k [B, G, S, d]: the kernels (ops/masked_flash.py), where t, S and d
    are whole 128-wide tiles of the chip — the cell's blocks of 512
    queries at head 128 against 2,048 to 16,384 keys — and a key/value
    head's dq [R, t, d], which the backward kernel holds on the chip,
    is at most 8 MiB (the cell's: 2); the dense tensors below everywhere
    else: a block of 8, a last block that is not whole, a head of 64.
    The shapes decide; nothing a caller sets."""
    heads, rows, head_dim = q.shape[2:]
    return (
        rows % 128 == 0 and k.shape[2] % 128 == 0 and head_dim % 128 == 0
        and heads * rows * head_dim * 4 <= 8 * 2**20
    )


def masked_attention(q: Array, k: Array, v: Array, keep: Array, scale: float):
    """Softmax attention of a block of queries over the keys `keep`
    marks, grouped queries, softmax in float32.

    q [B, G, R, t, d], k, v [B, G, S, d], keep [B, t, S] bool with at
    least one key a row. Returns (o [B, G, R, t, d], the logsumexp of a
    row's kept scores [B, G, R, t], a constant: `attention_share` makes
    the probabilities again from it).

    By tiles (`attends_by_tiles`) the scores [B, G, R, t, S] exist only a
    tile at a time, on the chip, forward and backward; otherwise they
    and the probabilities are whole float32 tensors.
    """
    if attends_by_tiles(q, k):
        from euler_tpu.ops import masked_flash

        return masked_flash.attention(q, k, v, keep, scale)
    scores = jnp.einsum("bgrtd,bgsd->bgrts", q, k) * scale
    scores = jnp.where(keep[:, None, None], scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    lse = jax.lax.stop_gradient(jax.nn.logsumexp(scores, axis=-1))
    return jnp.einsum("bgrts,bgsd->bgrtd", probs, v), lse


def attention_share(q: Array, k: Array, keep: Array, lse: Array, scale: float) -> Array:
    """The heads' attention probabilities on the kept keys, averaged
    over the G R heads, as a constant: `p[t, s] = mean_h exp(scale
    q_h[t] . k[s] - lse_h[t])`, 0 off `keep` — a row sums to 1. float32
    [B, t, S], 1 / (G R) of the probabilities it stands for; by tiles no
    more than that ever exists. Arguments as `masked_attention` took and
    left them."""
    if attends_by_tiles(q, k):
        from euler_tpu.ops import masked_flash

        return masked_flash.share(q, k, keep, lse, scale)
    scores = (jnp.einsum("bgrtd,bgsd->bgrts", q, k) * scale).astype(jnp.float32)
    probs = jnp.where(keep[:, None, None], jnp.exp(scores - lse[..., None]), 0.0)
    return jax.lax.stop_gradient(jnp.mean(probs, axis=(1, 2)))


def index_kl(p: Array, scores: Array, keep: Array) -> Array:
    """sum over rows of KL(p || softmax over the kept keys of `scores`),
    p the heads' attention probabilities averaged (`attention_share`)
    and L1-normalised, taken as a constant (the indexer is trained
    towards the attention, never the attention towards the indexer).

    p (zero off the kept keys), scores, keep [B, t, S].
    """
    p = jax.lax.stop_gradient(p)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    live = keep & (p > 0)
    return jnp.sum(
        jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0)) - jnp.where(live, log_q, 0.0)), 0.0)
    )


def grouped_matmul(rows: Array, weights: Array, group_sizes: Array) -> Array:
    """rows [M, K] sorted by group, weights [G, K, N], group_sizes [G]:
    row i of group e is multiplied by weights[e]. Rows past
    sum(group_sizes) belong to no group: they are read as zeros and come
    out as zeros, and so do their cotangents. The TPU's grouped-matmul
    kernel leaves such rows unwritten, forward and transposed, and what
    lies there may be NaN: masked on the way in and on the way out,
    nothing of it reaches a result or a gradient, not even times zero.
    `jax.lax.ragged_dot` is a native grouped matmul on the TPU, forward
    and both transposes."""
    live = (jnp.arange(rows.shape[0]) < jnp.sum(group_sizes))[:, None]
    out = jax.lax.ragged_dot(jnp.where(live, rows, 0.0), weights, group_sizes)
    return jnp.where(live, out, 0.0)
