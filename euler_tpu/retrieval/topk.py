"""Brute-force top-K over an embedding corpus — the compute half of
retrieval serving.

`TopKIndex` stages one (immutable) corpus's lane-row table on device and
answers masked dot/cosine top-K through jitted bucket-padded programs:
one compiled program per (query-bucket, k) pair, reused across requests,
scored by `paged_topk_score` (ops/paged_ops.py).

Bit-determinism contract (PARITY.md "Retrieval scoring"):

  * scoring operands are significand-truncated to 12 bits (corpus.py
    `quantize_sig12` — corpus rows at build time, queries here), so
    every q*x product is EXACT in f32 and FMA contraction cannot
    perturb it;
  * scores accumulate strictly left-to-right in f32 (the kernel's
    contract), so they are bit-identical to NumPy;
  * ties break (score desc, id asc): corpus rows are sorted by id
    ascending and `lax.top_k` prefers the lower index on equal values;
  * filtered retrieval masks scores to -inf BEFORE selection, so a
    filter can only remove candidates, never perturb surviving scores.

`numpy_topk_oracle` is the independent pure-NumPy implementation of the
same spec (its own normalization loop, scoring loop, and lexsort
selection — no JAX, no shared code path) and `merge_topk` is the
canonical-order heap merge the router uses to fuse per-shard answers;
fleet == single shard == oracle bitwise is pinned in
tests/test_retrieval.py.
"""

from __future__ import annotations

import heapq

import numpy as np

from euler_tpu.retrieval.corpus import (
    INVALID_ID,
    EmbeddingCorpus,
    normalize_rows,
    quantize_sig12,
)

# query-batch buckets: requests pad up to the smallest fitting bucket so
# a steady mix of batch sizes compiles a handful of programs, not one
# per distinct B; beyond the largest bucket, pad to its next multiple
BUCKETS = (1, 4, 16, 64)


def bucket_for(b: int, buckets=BUCKETS) -> int:
    for cand in buckets:
        if b <= cand:
            return cand
    top = buckets[-1]
    return -(-b // top) * top


class TopKIndex:
    """Jitted bucket-padded top-K over one staged EmbeddingCorpus."""

    def __init__(self, corpus: EmbeddingCorpus, buckets=BUCKETS):
        import jax.numpy as jnp

        self.corpus = corpus
        self.buckets = tuple(buckets)
        self._n = corpus.num_rows
        self._dp = corpus.dim_padded
        # the paged HBM table: staged once per corpus version, shared by
        # every program (the hot-swap unit is the whole TopKIndex)
        self._table2d = jnp.asarray(corpus.lane_rows()) if self._n else None
        self._all_rows = np.ones(max(self._n, 1), dtype=bool)
        self._programs: dict[tuple[int, int], object] = {}

    def _program(self, bp: int, keff: int):
        key = (bp, keff)
        fn = self._programs.get(key)
        if fn is None:
            import jax
            import jax.numpy as jnp

            from euler_tpu.ops.paged_ops import paged_topk_score

            n, dp = self._n, self._dp

            @jax.jit
            def run(table2d, q, mask):
                scores = paged_topk_score(table2d, q, n, dp)
                scores = jnp.where(mask[None, :], scores, -jnp.inf)
                return jax.lax.top_k(scores, keff)

            self._programs[key] = fn = run
        return fn

    def warmup(self, k: int, buckets=None) -> int:
        """Compile the (bucket, k) programs off the serving path — the
        hot-swap discipline builds + warms the NEW index here before the
        engine reference flips. Returns programs compiled."""
        before = len(self._programs)
        if self._n:
            keff = min(int(k), self._n)
            probe = np.zeros((1, self.corpus.dim), np.float32)
            for b in buckets or self.buckets:
                self.search(np.repeat(probe, b, axis=0), keff)
        return len(self._programs) - before

    def search(self, q: np.ndarray, k: int, mask: np.ndarray | None = None):
        """(ids u64[B, k], scores f32[B, k], valid bool[B, k]) — the
        top-k rows per query in canonical (score desc, id asc) order;
        under-filled slots carry INVALID_ID / -inf / False."""
        import jax.numpy as jnp

        q = np.ascontiguousarray(q, dtype=np.float32)
        if q.ndim != 2 or q.shape[1] != self.corpus.dim:
            raise ValueError(
                f"queries must be [B, {self.corpus.dim}], got {q.shape}"
            )
        b, k = q.shape[0], int(k)
        if k <= 0:
            raise ValueError(f"k must be positive, got {k}")
        ids = np.full((b, k), INVALID_ID, dtype=np.uint64)
        scores = np.full((b, k), -np.inf, dtype=np.float32)
        valid = np.zeros((b, k), dtype=bool)
        if b == 0 or self._n == 0:
            return ids, scores, valid
        if self.corpus.metric == "cosine":
            q = normalize_rows(q)
        q = quantize_sig12(q)  # exact-product scoring canon (corpus.py)
        if self._dp != q.shape[1]:
            q = np.pad(q, ((0, 0), (0, self._dp - q.shape[1])))
        bp = bucket_for(b, self.buckets)
        if bp != b:
            q = np.pad(q, ((0, bp - b), (0, 0)))
        keff = min(k, self._n)
        m = self._all_rows if mask is None else np.asarray(mask, dtype=bool)
        vals, idx = self._program(bp, keff)(
            self._table2d, jnp.asarray(q), jnp.asarray(m)
        )
        vals = np.asarray(vals)[:b]
        idx = np.asarray(idx)[:b]
        ok = vals > -np.inf
        ids[:, :keff] = np.where(
            ok, self.corpus.ids[np.clip(idx, 0, self._n - 1)], INVALID_ID
        )
        scores[:, :keff] = vals
        valid[:, :keff] = ok
        return ids, scores, valid


def numpy_topk_oracle(ids, vectors, q, k, metric="dot", mask=None):
    """INDEPENDENT reference: the PARITY.md retrieval-scoring spec in
    pure NumPy (no JAX, no shared scoring code) — left-to-right f32
    score accumulation, canonical cosine normalization, lexsort
    (score desc, id asc) selection. `mask` (optional bool) is aligned
    with the input row order. Returns the same (ids, scores, valid)
    triple as TopKIndex.search; bitwise equality against the served
    path is the retrieval parity claim."""
    ids = np.asarray(ids, dtype=np.uint64).reshape(-1)
    x = np.ascontiguousarray(vectors, dtype=np.float32)
    # private copy: the cosine branch normalizes in place
    q = np.array(q, dtype=np.float32, order="C", copy=True)
    keep = np.ones(len(ids), dtype=bool) if mask is None else (
        np.asarray(mask, dtype=bool).copy()
    )
    order = np.argsort(ids, kind="stable")
    ids, x, keep = ids[order], x[order], keep[order]
    if metric == "cosine":
        for arr in (x, q):
            nrm2 = np.zeros(arr.shape[0], dtype=np.float32)
            for d in range(arr.shape[1]):
                nrm2 = nrm2 + arr[:, d] * arr[:, d]
            inv = np.ones_like(nrm2)
            ok = nrm2 > 0
            inv[ok] = np.float32(1.0) / np.sqrt(nrm2[ok])
            arr *= inv[:, None]
    elif metric != "dot":
        raise ValueError(f"unknown metric {metric!r}")
    # exact-product canon: truncate significands to 12 bits (own bit
    # expression of the corpus.py spec constant) so every product below
    # is exact in f32 and the sum order is the only rounding story
    x = (x.view(np.uint32) & np.uint32(0xFFFFF000)).view(np.float32)
    q = (
        np.ascontiguousarray(q).view(np.uint32) & np.uint32(0xFFFFF000)
    ).view(np.float32)
    b, n, k = q.shape[0], len(ids), int(k)
    out_ids = np.full((b, k), INVALID_ID, dtype=np.uint64)
    out_scores = np.full((b, k), -np.inf, dtype=np.float32)
    out_valid = np.zeros((b, k), dtype=bool)
    if n == 0:
        return out_ids, out_scores, out_valid
    scores = np.zeros((b, n), dtype=np.float32)
    for d in range(x.shape[1]):
        scores = scores + q[:, d][:, None] * x[:, d][None, :]
    scores = np.where(keep[None, :], scores, np.float32(-np.inf))
    take = min(k, n)
    for i in range(b):
        top = np.lexsort((ids, -scores[i]))[:take]
        s = scores[i][top]
        ok = s > -np.inf
        out_ids[i, :take] = np.where(ok, ids[top], INVALID_ID)
        out_scores[i, :take] = s
        out_valid[i, :take] = ok
    return out_ids, out_scores, out_valid


def merge_topk(parts, k: int):
    """Fuse per-shard top-k answers into the global top-k, per query.

    `parts` is a list of (ids, scores, valid) triples, each [B, k_s]
    and already in canonical (score desc, id asc) order — exactly what
    TopKIndex.search returns. A k-way heap merge in the same canonical
    order makes the fleet answer bit-identical to a single-shard search
    over the union corpus: shard scores are per-row (independent of
    co-resident rows), shards partition the rows, and each shard
    returning its own top k means the global top k is always inside the
    merged candidate set."""
    if not parts:
        raise ValueError("merge_topk needs at least one shard answer")
    b = parts[0][0].shape[0]
    k = int(k)
    out_ids = np.full((b, k), INVALID_ID, dtype=np.uint64)
    out_scores = np.full((b, k), -np.inf, dtype=np.float32)
    out_valid = np.zeros((b, k), dtype=bool)
    def _stream(ids_row, scores_row, valid_row):
        # a def, not a genexp: lazy genexps close over the part-loop
        # variables by reference and would all read the LAST shard
        for j, s in enumerate(scores_row):
            if valid_row[j]:
                yield (float(-s), int(ids_row[j]))

    for i in range(b):
        streams = [
            _stream(ids_p[i], scores_p[i], valid_p[i])
            for ids_p, scores_p, valid_p in parts
        ]
        for slot, (neg, nid) in enumerate(heapq.merge(*streams)):
            if slot >= k:
                break
            out_ids[i, slot] = np.uint64(nid)
            out_scores[i, slot] = np.float32(-neg)
            out_valid[i, slot] = True
    return out_ids, out_scores, out_valid
