"""Fully on-device graph sampling (GraphSAGE fanouts + random walks).

The host flows (sage.py, walk.py) sample subgraphs and walks on the CPU
and ship int32 feature rows over PCIe/network every step — the lean wire
minimizes the bytes, but every dispatch still pays a host→device
transfer for ~10^5 rows/step. This module removes the wire
entirely: the padded adjacency lives in HBM next to the feature cache,
and every step of the scanned train loop *traces* root sampling +
multi-hop fanout (or walk + skip-gram pair generation) as XLA ops.
Per-step host→device traffic is zero; the only inputs are PRNG keys.

This is the TPU-first answer to the reference's sample_fanout and
random_walk kernels (euler/core/kernels/sample_fanout_op.cc,
random_walk_op.cc, and the TF custom ops in tf_euler/python/euler_ops):
instead of a host-side C++ sampler feeding the accelerator, the sampler
IS accelerator code — a [N+1, D] int32 gather plus vectorized uniform
draws, fused by XLA into the same program as the model. Weighted graphs
are first-class: edge draws invert a per-row cumulative-weight CDF with
a [W, k, D] compare-reduce (pure VPU work; D is the guarded max degree),
and weighted root draws binary-search a uint32-quantized node-weight CDF
— the same weighted-with-replacement distribution the host samplers and
the C++ engine's alias tables draw from (graph_engine.cc `AliasTable`).
Batches from a weighted graph carry bf16 edge weights, matching the host
weighted-lean wire (sage.py `_lean_w`) leaf-for-leaf.

Memory — two layouts:

- `layout="dense"`: padded adjacency, (N+1)·Dmax·4 bytes of HBM (row+1
  encoding, 0 = padding). For bounded-degree graphs this is small (200k
  nodes × deg 15 ≈ 12 MB); power-law graphs with hub nodes blow the
  table up — `max_degree` (default 512) is a GUARD that fails
  construction loudly in that case (truncating would bias sampling).
  The fan-out flows (`DeviceSageFlow` and its subclasses) round Dmax up
  to whole 128-lane tiles, which is what a row costs on the chip once
  it is contiguous: their hops read one plane row a frontier node.
- `layout="paged"`: ragged neighbor PAGES — fixed-size pages (default
  16 slots) in a flat HBM buffer plus a per-node page table
  (`page_start`), so a hub node spans ⌈deg/P⌉ pages instead of widening
  every row: HBM ∝ edges (+ N·4 B of page table), no `max_degree`
  failure mode. The access shape is the Ragged Paged Attention
  indirection (PAPERS.md, arxiv 2604.15464); the page reads are the
  `paged_gather`/`paged_cdf_count` functions of ops/paged_ops.py.

`layout="auto"` (the default) picks dense when the graph's max degree
fits `max_degree` and paged otherwise, for the SAGE-family flows;
flows that need the dense planes (walk bias, per-relation type planes,
layerwise scatter) always stage dense.

Weighted draws in BOTH layouts invert the same per-row uint32-quantized
CDF staged at construction (exact f64 cumsum per row, quantized once),
so paged and dense lanes draw bit-identical neighbors under the same
keys — pinned by tests/test_paged_flow.py. The parity story stays one
lane wide.

Remote graphs stage too: when the shards are RemoteShard handles the
construction sweep enumerates each shard's node table over the wire
(`ids_by_rows`) and walks the same chunked get_full_neighbor +
lookup_rows path through the Graph facade — deterministic verbs, so the
PR-5 client ReadCache serves repeats. Per-step traffic afterwards is
zero, exactly like the local staging.

Staging cost (one-time, at construction): the chunked
get_full_neighbor + lookup_rows sweep runs at ~3.7M edges/s on one host
core (0.8 s for the bench's 200k×15 graph; ~2 min per half-billion
edges) — amortized over a training run it is noise next to the
per-step wire it removes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from euler_tpu.ops.paged_ops import PAGE_LANES as _LANES
from euler_tpu.utils import trace
from euler_tpu.utils.staged import StagedTables

from .base import Block, MiniBatch

_STAGE_CHUNK = 16384
# host-side staging temp budget: the chunked get_full_neighbor sweep
# allocates [chunk, cap] padded arrays — on power-law graphs cap is the
# hub degree, so the chunk length adapts to keep the temp bounded
_STAGE_TEMP_BYTES = 64 << 20
_U32_MAX = np.uint32(0xFFFFFFFF)
# `_LANES`, the lanes of a TPU tile: a 2-D table whose width is a whole
# number of them lies row-major on the chip by default; any other width
# lies column-major, a row spread over as many tiles as it has slots, and
# a row read (`plane[cur]`) makes XLA copy the whole plane first, each step


def _node_table(graph):
    """(ids u64, weights f64, types i32) for every node, shard-major —
    the same row order as Graph.lookup_rows. Local shards read their
    columns directly; remote shards sweep the `ids_by_rows` verb in row
    chunks (deterministic → served by the client ReadCache on repeats).
    """
    shards = graph.shards
    if all(
        hasattr(s, "node_ids") and hasattr(s, "node_weights")
        for s in shards
    ):
        return (
            np.concatenate([np.asarray(s.node_ids) for s in shards]),
            np.concatenate(
                [np.asarray(s.node_weights, np.float64) for s in shards]
            ),
            np.concatenate(
                [np.asarray(s.node_types, np.int32) for s in shards]
            ),
        )
    ids_p, wn_p, nt_p = [], [], []
    for sh in shards:
        n = int(sh.num_nodes)
        for lo in range(0, n, _STAGE_CHUNK):
            rows = np.arange(lo, min(lo + _STAGE_CHUNK, n), dtype=np.int64)
            try:
                i, w, t = sh.ids_by_rows(rows)
            except RuntimeError as e:
                if "unknown op" in str(e):
                    raise ValueError(
                        "remote device staging needs servers speaking the "
                        "ids_by_rows verb — upgrade the shard servers or "
                        "keep the host flows"
                    ) from e
                raise
            ids_p.append(np.asarray(i, np.uint64))
            wn_p.append(np.asarray(w, np.float64))
            nt_p.append(np.asarray(t, np.int32))
    if not ids_p:
        return (
            np.empty(0, np.uint64),
            np.empty(0, np.float64),
            np.empty(0, np.int32),
        )
    return np.concatenate(ids_p), np.concatenate(wn_p), np.concatenate(nt_p)


def _quantize_rows(wblock: np.ndarray, valid: np.ndarray) -> np.ndarray:
    """Per-row uint32-quantized CDF over the compacted weight block —
    the ONE quantization both layouts stage, so their draws invert
    identical integers. Exact f64 cumsum per row; invalid slots and
    zero-total rows fill 0xFFFFFFFF (never drawn below r == MAX, which
    the callers' deg-1 clamp absorbs)."""
    cum = np.cumsum(
        np.where(valid, wblock, 0.0).astype(np.float64), axis=1
    )
    total = cum[:, -1:]
    safe = np.maximum(total, np.finfo(np.float64).tiny)
    q = np.floor(cum / safe * np.float64(2**32 - 1))
    q = q.astype(np.uint64).astype(np.uint32)
    return np.where(valid & (total > 0), q, _U32_MAX)


def _segment_arange(counts: np.ndarray) -> np.ndarray:
    """[0..c0), [0..c1), ... concatenated (vectorized per-segment iota)."""
    counts = np.asarray(counts, np.int64)
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, np.int64)
    ends = np.cumsum(counts)
    return np.arange(total) - np.repeat(ends - counts, counts)


# ---------------------------------------------------------------------------
# analytics frontier staging (euler_tpu/analytics)
# ---------------------------------------------------------------------------
# The whole-graph engine keeps per-shard dense f64 vertex state; staging
# it in HBM needs jax's x64 mode, which this repo leaves OFF globally
# (conftest runs f32). The scoped jax.enable_x64 context preserves f64 end
# to end. The order-sensitive segment reductions stay on the host in
# primitives.reduce_messages on every backend.

# How far the device lane's f64 multiply may sit from numpy's, relative to
# the product. The CPU backend is IEEE-exact (difference 0, and the tests
# hold it to that); a TPU emulates f64 and rounds differently — 5.6e-15 on
# the v5e (my chip run, PR 21). chip_smoke.py holds the chip to this bound.
FRONTIER_F64_RTOL = 1e-13


def stage_frontier(values: np.ndarray):
    """Put one frontier shard's f64 state on device."""
    values = np.ascontiguousarray(values, np.float64)
    with jax.enable_x64(True):
        return jax.device_put(values)


def frontier_contrib(weights, global_vec, src_rows):
    """Per-edge w[e] * frontier[src[e]] on device (f64 gather + multiply);
    returns a host f64 array. Equal to the numpy host path bit for bit on
    the CPU backend and within FRONTIER_F64_RTOL of it on a TPU, so an
    analytics run with device=True on the chip is deterministic but not
    bit-equal to a host run."""
    with jax.enable_x64(True):
        vec = jnp.asarray(np.asarray(global_vec, np.float64))
        w = jnp.asarray(np.asarray(weights, np.float64))
        out = w * jnp.take(
            vec, jnp.asarray(np.asarray(src_rows, np.int64)), axis=0
        )
        return np.asarray(out, np.float64)


def _pick_slots(rows, idx):
    """rows [W, D], idx [W, k] in [0, D) -> [W, k], `rows[w, idx[w, j]]`
    with no gather: every draw masks its row down to its one slot and
    sums the lanes, which is exact for integers."""
    lane = jnp.arange(rows.shape[1], dtype=idx.dtype)
    return jnp.where(lane == idx[:, :, None], rows[:, None, :], 0).sum(-1)


class DeviceGraphTables(StagedTables):
    """HBM-resident graph tables + traced draw primitives.

    Stages (once, host-side) the padded adjacency, degree vector, raw
    edge-weight rows (weighted graphs only — the per-row CDF is a cumsum
    on the gathered rows at draw time), a quantized node-weight CDF
    (non-uniform node weights only), and the id↔row maps. Subclasses
    compose `_draw_roots` / `_draw_neighbors` into batch shapes; all
    draws are jit-traceable. The Estimator's programs take the staged
    arrays as an argument (`StagedTables`: `tables()` / `bind()`), so a
    `refresh_rows` is read by the next dispatch; `jax.jit(flow.sample)`
    on the flow itself compiles them in as constants instead.
    """

    is_device_flow = True

    def __call__(self):
        raise TypeError(
            f"{type(self).__name__} is not a host batch_fn; pass it to an "
            "Estimator (detected via is_device_flow) or call .sample(key) "
            "inside jit"
        )

    @staticmethod
    def _quantize_cdf(weights, what: str):
        """f64 weights → device uint32 CDF (exact adjacent values where a
        f32 cumsum over millions of entries would swallow small weights);
        raises on an empty or zero-total distribution."""
        cum = np.cumsum(np.asarray(weights, dtype=np.float64))
        if cum.size == 0 or cum[-1] <= 0:
            raise ValueError(f"{what} weights sum to zero")
        return jax.device_put(
            np.floor(cum / cum[-1] * np.float64(2**32 - 1)).astype(np.uint32)
        )

    def _stage_flat_edges(self, graph, edge_type: int = -1,
                          stage_er: bool = False):
        """Stage the flat (src, [type,] dst) edge columns + a weight CDF —
        the right layout for whole-edge draws on any degree distribution
        (8-12 bytes/edge, one searchsorted per draw, no max_degree
        guard). Edges with endpoints absent from the node table are
        dropped (the padded-adjacency path collapsed them to masked
        padding; flat staging must not emit them as real samples). Sets
        eh/et (int32, host id-truncation parity), er when stage_er (KG
        relations; LINE never reads it), num_edges, and edge_cdf (None
        when weights are uniform)."""
        if not all(hasattr(s, "edge_src") for s in graph.shards):
            raise ValueError(
                "flat edge staging needs local shards with edge columns "
                "(remote graphs keep the host batch sources)"
            )
        h = np.concatenate([np.asarray(s.edge_src) for s in graph.shards])
        t = np.concatenate([np.asarray(s.edge_dst) for s in graph.shards])
        r = np.concatenate([np.asarray(s.edge_types) for s in graph.shards])
        w = np.concatenate(
            [np.asarray(s.edge_weights, np.float64) for s in graph.shards]
        )
        rows_ht = graph.lookup_rows(np.concatenate([h, t]))
        keep = (rows_ht[: len(h)] >= 0) & (rows_ht[len(h) :] >= 0)
        if edge_type >= 0:
            keep &= r == edge_type
        h, t, r, w = h[keep], t[keep], r[keep], w[keep]
        if len(h) == 0 or np.sum(w) <= 0:
            # host sample_edge parity: empty or all-zero-weight edge
            # sets are unsampleable even when the weights are all equal
            raise ValueError("graph has no sampleable edges")
        to32 = lambda x: x.astype(np.int64).astype(np.int32)  # noqa: E731
        self.eh = jax.device_put(to32(h))
        self.et = jax.device_put(to32(t))
        self.er = jax.device_put(r.astype(np.int32)) if stage_er else None
        self.num_edges = len(h)
        self.edge_cdf = (
            None if np.all(w == w[0]) else self._quantize_cdf(w, "edge")
        )

    def _draw_edges(self, key, count: int):
        """[count] indices into the staged flat edge list, ∝ weight."""
        if self.edge_cdf is not None:
            rb = jax.random.bits(key, (count,), dtype=jnp.uint32)
            return jnp.minimum(
                jnp.searchsorted(self.edge_cdf, rb, side="right"),
                self.num_edges - 1,
            )
        return jax.random.randint(key, (count,), 0, self.num_edges)

    # SAGE-family tables draw only through _draw_neighbors and may stage
    # paged; flows that read the dense planes directly (walk bias,
    # per-relation type planes, layerwise scatter) override this False
    _PAGED_OK = True
    # flows whose draws read whole rows of the dense planes (a fan-out of
    # k > 1, `_draw_neighbors`) stage them a whole number of lane tiles
    # wide, so that a row is contiguous on the chip: the slots past the
    # max degree are padding like any row's tail, and no draw changes
    _ROW_READS = False

    def __init__(
        self,
        graph,
        edge_types=None,
        max_degree: int = 512,
        roots_pool: np.ndarray | None = None,
        root_node_type: int = -1,
        mesh=None,
        stage_types: bool = False,
        layout: str = "auto",
        page_size: int = 16,
    ):
        """roots_pool: optional node ids to sample roots from (e.g. a
        train split); root_node_type restricts root draws to one node
        type instead (host sample_node(node_type) parity; ignored when a
        pool is given); default is every node. Root draws are proportional
        to node weights either way (uniform when weights are constant —
        host sample_node parity). max_degree is a guard on the DENSE
        staged adjacency width ((N+1)·Dmax·4 bytes of HBM): construction
        raises when the graph's true max degree exceeds it — truncation
        would bias sampling, so it is never done silently.

        layout: "dense" | "paged" | "auto". "auto" (default) picks dense
        while the max degree fits `max_degree` and otherwise stages the
        ragged paged layout (HBM ∝ edges; hub nodes span multiple
        fixed-size pages), so power-law graphs train on the device lane
        instead of raising. page_size must divide 128 (one page per DMA
        lane row). Paged and dense draws are bit-identical under the
        same keys (shared quantized-CDF inversion).

        mesh: a jax.sharding.Mesh for data-parallel training — sampled
        batch leaves are sharding-constrained along the mesh's data axis
        (each device materializes only its own batch slice; the staged
        tables replicate), so one traced sample() drives every device.
        Values are identical to the unsharded program for the same key.
        """
        self.mesh = mesh
        local = all(
            hasattr(s, "node_ids") and hasattr(s, "node_weights")
            for s in graph.shards
        )
        if not local and not all(
            hasattr(s, "call") for s in graph.shards
        ):
            raise ValueError(
                "device flows stage the adjacency host-side and need "
                "local shards or remote shards (wire staging)"
            )
        with trace.span("stage.graph"):
            ids, wn, nt = _node_table(graph)
            # kept host-side for refresh_rows: the published-mutation
            # restage resolves global rows back to ids and re-fetches
            # their adjacency
            self._ids_host = ids
            self._edge_types = (
                None if edge_types is None else [int(t) for t in edge_types]
            )
            self._stage_adjacency(
                graph, ids, edge_types, max_degree, stage_types,
                layout=layout, page_size=page_size,
            )
            self._stage_nodes(graph, ids, wn, nt, roots_pool, root_node_type)

    def _stage_degrees(self, graph, ids, edge_types) -> np.ndarray:
        """Per-node total degree, swept in chunks (one bounded RPC per
        chunk on remote graphs; degree_sum is ReadCache-deterministic)."""
        degs = np.zeros(len(ids), np.int64)
        with trace.span("stage.graph.degrees"):
            for lo in range(0, len(ids), _STAGE_CHUNK):
                sub = ids[lo : lo + _STAGE_CHUNK]
                degs[lo : lo + len(sub)] = graph.degree_sum(sub, edge_types)
        return degs

    def _stage_adjacency(
        self,
        graph,
        ids,
        edge_types,
        max_degree: int,
        stage_types: bool,
        layout: str = "auto",
        page_size: int = 16,
    ):
        if layout not in ("auto", "dense", "paged"):
            raise ValueError(f"unknown layout {layout!r}")
        degs = self._stage_degrees(graph, ids, edge_types)
        dmax = max(int(degs.max(initial=0)), 1)
        paged_ok = self._PAGED_OK and not stage_types
        if layout == "auto":
            layout = "paged" if (dmax > max_degree and paged_ok) else "dense"
        if layout == "paged" and not paged_ok:
            raise ValueError(
                f"{type(self).__name__} reads the dense adjacency planes "
                "directly (bias/type/layerwise math) — the paged layout "
                "serves the SAGE-family flows only"
            )
        if layout == "dense" and dmax > max_degree:
            raise ValueError(
                f"graph max degree {dmax} exceeds max_degree={max_degree}; "
                f"the dense staged adjacency would cost (N+1)*{dmax}*4 "
                "bytes — use the paged device lane instead "
                "(layout='paged', or layout='auto' which selects it "
                "automatically: fixed-size neighbor pages, HBM ∝ edges), "
                "or raise the cap explicitly after the memory math"
            )
        self.layout = layout
        if layout == "paged":
            self._stage_paged(graph, ids, degs, edge_types, page_size)
            return
        n = len(ids)
        width = -(-dmax // _LANES) * _LANES if self._ROW_READS else dmax
        adj = np.zeros((n + 1, width), dtype=np.int32)
        deg = np.zeros(n + 1, dtype=np.int32)
        wtab = np.zeros((n + 1, width), dtype=np.float32)
        ttab = (
            np.full((n + 1, width), -1, dtype=np.int32) if stage_types else None
        )
        unit_w = True
        with trace.span("stage.graph.sweep"):
            for lo in range(0, n, _STAGE_CHUNK):
                sub = ids[lo : lo + _STAGE_CHUNK]
                nbr, w, tt, mask, _ = graph.get_full_neighbor(
                    sub, edge_types, max_degree=dmax
                )
                unit_w = unit_w and bool(np.all(w[mask] == 1.0))
                rows = graph.lookup_rows(nbr.ravel()).reshape(nbr.shape)
                # row+1 encoding, 0 = padding (matches DeviceFeatureCache's
                # zero row); masked or unknown neighbors collapse to padding
                block = np.where(mask & (rows >= 0), rows + 1, 0)
                block = block.astype(np.int32)
                # compact valid entries to the front so idx < deg hits them
                order = np.argsort(block == 0, axis=1, kind="stable")
                sl = slice(1 + lo, 1 + lo + len(sub))
                adj[sl, : block.shape[1]] = np.take_along_axis(
                    block, order, axis=1
                )
                wtab[sl, : block.shape[1]] = np.take_along_axis(
                    np.where(block > 0, w, 0.0).astype(np.float32),
                    order, axis=1,
                )
                if ttab is not None:  # edge types of each slot (KG relations)
                    ttab[sl, : block.shape[1]] = np.take_along_axis(
                        np.where(block > 0, tt, -1).astype(np.int32),
                        order, axis=1,
                    )
                deg[sl] = (block > 0).sum(axis=1)
        with trace.span("stage.graph.planes"):
            # a positive-degree row whose weights are all zero is unsampleable
            # (host _WeightedSampler semantics: zero total → padding)
            # per-node out-strength (edge-weight row sums): zero-strength rows
            # are unsampleable, and DeviceGaeFlow draws edge sources ∝ it
            strength = wtab.sum(axis=1, dtype=np.float64)
            deg[strength <= 0.0] = 0
            self._out_strength = strength
            self.adj = jax.device_put(adj)
            self.deg = jax.device_put(deg)
            self.unit_w = unit_w
            # weighted graphs stage the RAW weight rows (exact values for
            # edge_w and bias math) plus the per-row quantized CDF — the ONE
            # inversion table shared bit-for-bit with the paged layout
            # (trailing f64 cumsum at staging; device keeps uint32)
            self.wtab = None if unit_w else jax.device_put(wtab)
            if unit_w:
                self.qtab = None
            else:
                valid = (
                    np.arange(width)[None, :] < deg[:, None]
                )
                self.qtab = jax.device_put(_quantize_rows(wtab, valid))
            self.ttab = jax.device_put(ttab) if ttab is not None else None
            self.max_deg = dmax

    def _stage_paged(self, graph, ids, degs, edge_types, page_size: int):
        """Ragged paged staging: compacted neighbor entries (same order
        as the dense compaction, so draws land on the same slots) packed
        into fixed-size pages in one flat buffer; per-node page table in
        `page_start`. HBM ∝ edges — no max_degree failure mode."""
        from euler_tpu.distributed.codec import page_dtype
        from euler_tpu.ops.paged_ops import (
            PAGE_LANES,
            _as_lane_rows,
            pack_bf16_words,
        )

        P = int(page_size)
        if P <= 0 or PAGE_LANES % P:
            raise ValueError(
                f"page_size must divide {PAGE_LANES} (a page may not "
                f"straddle a row of the staged buffer); got {P}"
            )
        n = len(ids)
        deg = np.zeros(n + 1, dtype=np.int32)
        strength = np.zeros(n + 1, dtype=np.float64)
        unit_w = True
        vals_p, w_p, q_p = [], [], []
        lo = 0
        with trace.span("stage.graph.sweep"):
            while lo < n:
                # temp budget: [chunk, cap] padded host arrays per sweep step
                cap_hint = max(
                    int(degs[lo : lo + _STAGE_CHUNK].max(initial=1)), 1
                )
                chunk = max(
                    256, min(_STAGE_CHUNK, _STAGE_TEMP_BYTES // (cap_hint * 8))
                )
                sub = ids[lo : lo + chunk]
                cap = max(int(degs[lo : lo + len(sub)].max(initial=0)), 1)
                nbr, w, _, mask, _ = graph.get_full_neighbor(
                    sub, edge_types, max_degree=cap
                )
                unit_w = unit_w and bool(np.all(w[mask] == 1.0))
                rows = graph.lookup_rows(nbr.ravel()).reshape(nbr.shape)
                blk0 = np.where(mask & (rows >= 0), rows + 1, 0)
                blk0 = blk0.astype(np.int32)
                order = np.argsort(blk0 == 0, axis=1, kind="stable")
                block = np.take_along_axis(blk0, order, axis=1)
                wblk = np.take_along_axis(
                    np.where(blk0 > 0, w, 0.0).astype(np.float32), order, axis=1
                )
                d = (block > 0).sum(axis=1).astype(np.int32)
                st = wblk.sum(axis=1, dtype=np.float64)
                d[st <= 0.0] = 0  # zero-strength rows are unsampleable
                sl = slice(1 + lo, 1 + lo + len(sub))
                deg[sl] = d
                strength[sl] = st
                valid = np.arange(block.shape[1])[None, :] < d[:, None]
                vals_p.append(block[valid])
                w_p.append(wblk[valid])
                q_p.append(_quantize_rows(wblk, valid)[valid])
                lo += len(sub)
        with trace.span("stage.graph.planes"):
            self._out_strength = strength
            npages = -(-deg.astype(np.int64) // P)  # ceil(deg/P); 0 for deg 0
            ps = np.zeros(n + 2, dtype=np.int64)
            ps[1:] = np.cumsum(npages)
            total_pages = max(int(ps[-1]), 1)
            flat = np.zeros(total_pages * P, dtype=np.int32)
            flat_w = np.zeros(total_pages * P, dtype=np.float32)
            flat_q = np.full(total_pages * P, _U32_MAX, dtype=np.uint32)
            # entries of node r (row+1 space) land at ps[r]*P + [0, deg_r)
            dest = np.repeat(ps[:-1] * P, deg) + _segment_arange(deg)
            if len(dest):
                flat[dest] = np.concatenate(vals_p)
                flat_w[dest] = np.concatenate(w_p)
                flat_q[dest] = np.concatenate(q_p)
            self.pages2d = _as_lane_rows(jnp.asarray(flat))
            self._ps_host = ps  # page table, host copy (refresh_rows spans)
            self.page_start = jax.device_put(ps.astype(np.int32))
            self.deg = jax.device_put(deg)
            self.unit_w = unit_w
            # EULER_TPU_PAGE_DTYPE=bf16 packs the weight plane two-bf16-per-
            # u32 (half the HBM + DMA bytes) and dequantizes inside the
            # gather. Emitted batches already ship bf16 edge weights, and
            # bf16(bf16(x)) == bf16(x), so packed draws stay BIT-IDENTICAL
            # to the f32 plane — this lane spends no accuracy budget. Odd
            # page sizes would let a row's page span straddle a packed word
            # at refresh time, so P=1 stays unpacked.
            self._page_w_packed = (
                not unit_w and page_dtype() == "bf16" and P % 2 == 0
            )
            if unit_w:
                self.page_w2d = self.page_q2d = self.page_bound = None
            else:
                self.page_w2d = _as_lane_rows(
                    pack_bf16_words(flat_w)
                    if self._page_w_packed
                    else jnp.asarray(flat_w)
                )
                self.page_q2d = _as_lane_rows(jnp.asarray(flat_q))
                # per-page boundary = the page's last valid quantized-CDF
                # value (pads are U32_MAX, and a node's final page ends at
                # U32_MAX anyway, so a plain per-page max is exact)
                self.page_bound = jax.device_put(
                    flat_q.reshape(total_pages, P).max(axis=1)
                )
            self.page_size = P
            # clamp caps for masked draws: a trailing degree-0 node's
            # page_start equals total_pages, and its (deg>0-masked) gather
            # index must still stay inside the buffers — XLA clips gathers,
            # but the kernel DMAs must never be handed an OOB row
            self._page_cap = total_pages - 1
            self._slot_cap = total_pages * P - 1
            self.max_pages = int(npages.max(initial=0))
            # binary-search depth over a node's page range (static at trace)
            self._search_iters = max(1, int(self.max_pages).bit_length() + 1)
            self.max_deg = max(int(deg.max(initial=0)), 1)
            # dense planes absent on purpose: flows that need them are gated
            # by _PAGED_OK at staging time
            self.adj = self.wtab = self.qtab = self.ttab = None

    # -- published-mutation restage --------------------------------------

    def refresh_rows(self, graph, rows) -> int:
        """Re-stage ONLY the given GLOBAL node rows after a published
        graph mutation (feed it ``GraphWriter.publish()["rows"]``) — the
        adjacency twin of ``DeviceFeatureCache.refresh_rows``. Dense
        layout patches the touched ``[row]`` slices of the adj/deg/
        weight planes; paged layout re-packs only the ⌈deg/P⌉ pages of
        the mutated rows (page-granular, the Ragged-Paged-Attention
        indirection shape). Structural changes a patch cannot express —
        node count changed, a degree outgrowing its staged capacity, or
        a unit-weight staging turning weighted — raise ValueError: build
        a fresh flow for those. Post-restage draws are bit-identical to
        a from-scratch staging of the merged graph under the same key
        (pinned by tests/test_delta.py). Returns rows re-staged."""
        rows = np.unique(np.asarray(rows, dtype=np.int64).reshape(-1))
        rows = rows[rows >= 0]
        if not len(rows):
            return 0
        total = int(sum(int(s.num_nodes) for s in graph.shards))
        if total != self.num_nodes:
            raise ValueError(
                f"node count changed ({self.num_nodes} staged, {total} "
                "now) — a row patch cannot re-shape the staged tables; "
                "build a fresh device flow"
            )
        if int(rows.max()) >= self.num_nodes:
            raise ValueError("refresh_rows: row out of range")
        ids = self._ids_host[rows]
        degs = np.asarray(
            graph.degree_sum(ids, self._edge_types), np.int64
        )
        if self.layout == "paged":
            return self._refresh_paged(graph, rows, ids, degs)
        return self._refresh_dense(graph, rows, ids, degs)

    def _refresh_block(self, graph, ids, cap: int):
        """Chunk of the staging sweep for a row subset: compacted
        neighbor block + weights + degree + strength, the exact shapes
        `_stage_adjacency`/`_stage_paged` put in the tables."""
        nbr, w, tt, mask, _ = graph.get_full_neighbor(
            ids, self._edge_types, max_degree=cap
        )
        rws = graph.lookup_rows(nbr.ravel()).reshape(nbr.shape)
        blk0 = np.where(mask & (rws >= 0), rws + 1, 0).astype(np.int32)
        order = np.argsort(blk0 == 0, axis=1, kind="stable")
        block = np.take_along_axis(blk0, order, axis=1)
        wblk = np.take_along_axis(
            np.where(blk0 > 0, w, 0.0).astype(np.float32), order, axis=1
        )
        ttb = np.take_along_axis(
            np.where(blk0 > 0, tt, -1).astype(np.int32), order, axis=1
        )
        d = (block > 0).sum(axis=1).astype(np.int32)
        st = wblk.sum(axis=1, dtype=np.float64)
        d[st <= 0.0] = 0
        unit = bool(np.all(w[mask] == 1.0)) if mask.any() else True
        if self.unit_w and not unit:
            raise ValueError(
                "mutation introduced non-unit edge weights on a "
                "unit-weight staging — build a fresh device flow"
            )
        return block, wblk, ttb, d, st

    def _refresh_dense(self, graph, rows, ids, degs) -> int:
        if int(degs.max(initial=0)) > self.max_deg:
            raise ValueError(
                f"mutated degree {int(degs.max())} outgrew the staged "
                f"dense width {self.max_deg} — build a fresh device flow "
                "(or the paged layout, which has no width to outgrow)"
            )
        width = int(self.adj.shape[1])  # max_deg, or its lane tiles
        block, wblk, ttb, d, st = self._refresh_block(graph, ids, width)
        r1 = rows + 1
        self.adj = self.adj.at[r1].set(jnp.asarray(block))
        self.deg = self.deg.at[r1].set(jnp.asarray(d))
        self._out_strength[r1] = st
        if self.ttab is not None:
            self.ttab = self.ttab.at[r1].set(jnp.asarray(ttb))
        if not self.unit_w:
            valid = np.arange(width)[None, :] < d[:, None]
            self.wtab = self.wtab.at[r1].set(jnp.asarray(wblk))
            self.qtab = self.qtab.at[r1].set(
                jnp.asarray(_quantize_rows(wblk, valid))
            )
        return len(rows)

    def _refresh_paged(self, graph, rows, ids, degs) -> int:
        P = self.page_size
        ps = self._ps_host
        r1 = rows + 1
        alloc = ps[r1 + 1] - ps[r1]  # pages staged for each row
        need = -(-degs // P)
        if np.any(need > alloc):
            over = rows[need > alloc][:4]
            raise ValueError(
                f"mutated degree outgrew the staged page allocation for "
                f"rows {over.tolist()} (⌈deg/{P}⌉ pages are fixed at "
                "staging) — build a fresh device flow"
            )
        cap = max(int(degs.max(initial=0)), 1)
        block, wblk, _, d, st = self._refresh_block(graph, ids, cap)
        # rewrite each row's WHOLE allocated span (stale tail slots and
        # pages become padding), so only ⌈deg/P⌉ pages per mutated row
        # are touched and untouched rows' pages never move
        spans = (alloc * P).astype(np.int64)
        total = int(spans.sum())
        vals = np.zeros(total, np.int32)
        wv = np.zeros(total, np.float32)
        qv = np.full(total, _U32_MAX, dtype=np.uint32)
        dest = np.repeat(ps[r1] * P, spans) + _segment_arange(spans)
        src_rows = np.repeat(np.arange(len(rows)), spans)
        src_cols = _segment_arange(spans)
        put = src_cols < np.repeat(d.astype(np.int64), spans)
        sr, sc = src_rows[put], np.minimum(src_cols[put], block.shape[1] - 1)
        vals[put] = block[sr, sc]
        wv[put] = wblk[sr, sc]
        self.deg = self.deg.at[r1].set(jnp.asarray(d))
        self._out_strength[r1] = st
        lanes = int(self.pages2d.shape[1])
        self.pages2d = self.pages2d.at[dest // lanes, dest % lanes].set(
            jnp.asarray(vals)
        )
        if not self.unit_w:
            valid = np.arange(block.shape[1])[None, :] < d[:, None]
            q = _quantize_rows(wblk, valid)
            qv[put] = q[sr, sc]
            if getattr(self, "_page_w_packed", False):
                # every span is a whole-page run and P is even, so spans
                # start word-aligned with even length: pack the patch
                # values pairwise and rewrite whole u32 words — no
                # read-modify-write of half-covered words can occur
                from euler_tpu.ops.paged_ops import pack_bf16_words

                words = pack_bf16_words(wv)
                wdest = dest[0::2] // 2
                self.page_w2d = self.page_w2d.at[
                    wdest // lanes, wdest % lanes
                ].set(words)
            else:
                self.page_w2d = self.page_w2d.at[
                    dest // lanes, dest % lanes
                ].set(jnp.asarray(wv))
            self.page_q2d = self.page_q2d.at[
                dest // lanes, dest % lanes
            ].set(jnp.asarray(qv))
            touched_pages = np.repeat(ps[r1], alloc) + _segment_arange(
                alloc
            )
            self.page_bound = self.page_bound.at[touched_pages].set(
                jnp.asarray(qv.reshape(-1, P).max(axis=1))
            )
        return len(rows)

    def _stage_nodes(
        self, graph, ids, wn, nt, roots_pool, root_node_type: int
    ):
        n = len(ids)
        # weight-proportional root draws (host sample_node parity): a
        # uint32-quantized CDF, binary-searched on device — over all nodes,
        # or over roots_pool's members when a pool restricts the draw.
        # Integer quantization keeps adjacent cum values exact where f32
        # cumsum over >1e6 nodes would swallow small weights.
        wn = np.asarray(wn, dtype=np.float64)
        # global (unrestricted) node CDF — negative sampling draws from
        # ALL nodes even when roots are pool/type-restricted (host
        # unsupervised_batches neg_type=-1 parity)
        self.global_cdf = (
            self._quantize_cdf(wn, "graph node")
            if wn.size and not np.all(wn == wn[0])
            else None
        )
        pool_rows = None
        if roots_pool is not None:
            pool_rows = graph.lookup_rows(
                np.asarray(roots_pool, dtype=np.uint64)
            )
            if np.any(pool_rows < 0):
                raise ValueError("roots_pool contains unknown node ids")
            wn = wn[pool_rows]
        elif root_node_type >= 0:
            pool_rows = np.nonzero(
                np.asarray(nt) == root_node_type
            )[0].astype(np.int64)
            if not len(pool_rows):
                raise ValueError(
                    f"no nodes of type {root_node_type} to sample roots from"
                )
            wn = wn[pool_rows]
        self.node_cdf = (
            self._quantize_cdf(wn, "root node")
            if wn.size and not np.all(wn == wn[0])
            else None
        )
        # int32 view of the u64 id space (host flows apply the same
        # truncation); index 0 (padding) maps to -1
        node_id = np.full(n + 1, -1, dtype=np.int32)
        node_id[1:] = ids.astype(np.int64).astype(np.int32)
        self.node_id = jax.device_put(node_id)
        self.roots = (
            jax.device_put(pool_rows.astype(np.int32) + 1)
            if pool_rows is not None
            else None
        )
        self.num_nodes = n

    # -- traced draw primitives ------------------------------------------

    def _dp(self, x):
        """Constrain a batch-leading array to the mesh's data axis (same
        divisibility rule as parallel.shard_batch); no-op without a mesh."""
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P

        from euler_tpu.parallel import DATA_AXIS

        nd = self.mesh.shape[DATA_AXIS]
        spec = P(DATA_AXIS) if x.ndim >= 1 and x.shape[0] % nd == 0 else P()
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec)
        )

    def _draw_roots(self, key, count: int):
        """[count] root draws in row+1 space, weight-proportional."""
        if self.node_cdf is not None:
            r = jax.random.bits(key, (count,), dtype=jnp.uint32)
            pick = jnp.searchsorted(self.node_cdf, r, side="right")
            pick = jnp.minimum(pick, len(self.node_cdf) - 1).astype(jnp.int32)
            return self.roots[pick] if self.roots is not None else pick + 1
        if self.roots is not None:
            pick = jax.random.randint(key, (count,), 0, len(self.roots))
            return self.roots[pick]
        return jax.random.randint(key, (count,), 1, self.num_nodes + 1)

    def _draw_global_nodes(self, key, count: int):
        """[count] draws over ALL nodes (ignores roots_pool/root_node_type)
        — the negative-sampling distribution (sample_node(-1) parity)."""
        if self.global_cdf is not None:
            r = jax.random.bits(key, (count,), dtype=jnp.uint32)
            pick = jnp.searchsorted(self.global_cdf, r, side="right")
            return jnp.minimum(pick, self.num_nodes - 1).astype(jnp.int32) + 1
        return jax.random.randint(key, (count,), 1, self.num_nodes + 1)

    def _draw_neighbors_typed(self, cur, key, k: int, rel: int):
        """[W] rows → per-RELATION draws: ([W·k] rows, [W·k] f32 weights,
        [W·k] valid mask). Requires stage_types=True. Weights are masked
        to slots of type `rel` before the CDF inversion — the same
        distribution as the host sample_neighbor(cur, [rel], k)."""
        width = cur.shape[0]
        nbr_rows = self.adj[cur]  # [W, D]
        w = (
            self.wtab[cur]
            if self.wtab is not None
            else (nbr_rows > 0).astype(jnp.float32)
        )
        w = w * (self.ttab[cur] == rel)
        cw = jnp.cumsum(w, axis=1)
        total = cw[:, -1]
        u = jax.random.uniform(key, (width, k)) * total[:, None]
        idx = (cw[:, None, :] <= u[:, :, None]).sum(axis=-1)
        idx = jnp.minimum(idx, self.adj.shape[1] - 1)
        # type-r support is NON-contiguous, so the u→1 f32 overshoot can
        # land on a wrong-relation or padded slot (w there is 0); redirect
        # those draws to the row's LAST in-support slot (the sibling
        # _draw_neighbors' deg-1 clamp, generalized to a masked row)
        wpick = jnp.take_along_axis(w, idx, axis=1)
        last = jnp.argmax(
            jnp.where(w > 0, jnp.arange(w.shape[1]), -1), axis=1
        )
        idx = jnp.where(wpick > 0, idx, last[:, None])
        alive = total > 0
        nbr = jnp.where(
            alive[:, None], jnp.take_along_axis(nbr_rows, idx, axis=1), 0
        )
        ew = jnp.where(
            alive[:, None], jnp.take_along_axis(w, idx, axis=1), 0.0
        )
        valid = (nbr > 0).reshape(-1)
        return nbr.reshape(-1), ew.reshape(-1), valid

    def _stage_edge_src_cdf(self):
        """Quantized CDF over per-node out-strength: drawing a source from
        it and then a neighbor within the row draws an edge ∝ weight
        (P(e) = strength(src)/W · w(e)/strength(src) = w(e)/W — the host
        sample_edge alias-table distribution)."""
        self.edge_src_cdf = self._quantize_cdf(
            self._out_strength[1:], "edge-source out-strength"
        )

    def _draw_edge_sources(self, key, count: int):
        """[count] edge-source rows (row+1 space) ∝ out-strength."""
        r = jax.random.bits(key, (count,), dtype=jnp.uint32)
        pick = jnp.searchsorted(self.edge_src_cdf, r, side="right")
        return jnp.minimum(pick, self.num_nodes - 1).astype(jnp.int32) + 1

    def _draw_neighbors(self, cur, key, k: int):
        """[W] rows → ([W·k] rows, [W·k] bf16 weights or None, [W, k] slot idx).

        Uniform graphs draw a slot index directly; weighted graphs invert
        the per-row uint32-quantized CDF staged at construction — the
        SAME integers in both layouts, so the paged lane below draws
        bit-identical neighbors under the same key. Padding rows (0)
        yield padding. A fan-out (k > 1) over planes staged in whole lane
        tiles (`_ROW_READS`) gathers one plane row a frontier node and
        picks its k slots from the row (`_pick_slots`); a single draw,
        or a plane of any other width, gathers slot by slot. Which it
        was is tallied (`draw_rows` / `draw_elements`; the program's
        `step.first_call` span carries both).
        """
        if getattr(self, "layout", "dense") == "paged":
            return self._draw_neighbors_paged(cur, key, k)
        width = cur.shape[0]
        deg = self.deg[cur]
        if self.unit_w:
            u = jax.random.uniform(key, (width, k))
            idx = (u * deg[:, None]).astype(jnp.int32)
            ew = None
        else:
            r = jax.random.bits(key, (width, k), dtype=jnp.uint32)
            qrow = self.qtab[cur]  # [W, D] uint32 per-row CDF
            idx = (
                (qrow[:, None, :] <= r[:, :, None])
                .sum(axis=-1)
                .astype(jnp.int32)
            )
        idx = jnp.minimum(idx, jnp.maximum(deg[:, None] - 1, 0))
        alive = deg[:, None] > 0
        if k > 1 and self.adj.shape[1] % _LANES == 0:
            # one gather index a frontier node, not one a draw
            trace.count("draw_rows")
            picked = _pick_slots(self.adj[cur], idx)
        else:
            # one draw a row saves no index; a plane of any other width
            # has no contiguous rows to read (`_LANES`)
            trace.count("draw_elements")
            picked = self.adj[cur[:, None], idx]
        nbr = jnp.where(alive, picked, 0).reshape(-1)
        if not self.unit_w:
            # exact staged weight of the drawn edge (zero on padded slots)
            ew = (
                jnp.take_along_axis(self.wtab[cur], idx, axis=1)
                .reshape(-1)
                .astype(jnp.bfloat16)
            )
        return nbr, ew, idx

    def _walk_rows(self, cur, key, length: int, step=None, scan: bool = False):
        """[W] start rows -> [W, length + 1] rows (0 = dead): a chain of
        single-neighbour draws, transition i under `split(key, length)[i]`;
        `step(cur, prev, key)` in place of the uniform draw where a flow
        biases its walk.
        `scan=False` traces the L draws one after another, which suits a
        few tens of them; `scan=True` is one `lax.scan` over the keys,
        for walks of hundreds (the draws are the same ones)."""

        def move(carry, key):
            cur, prev = carry
            if step is not None:
                nxt = step(cur, prev, key)
            else:
                nxt, _, _ = self._draw_neighbors(cur, key, 1)
            nxt = self._dp(nxt)
            return (nxt, cur), nxt

        carry = (cur, jnp.zeros_like(cur))
        keys = jax.random.split(key, length)
        if scan:
            _, rest = jax.lax.scan(move, carry, keys)
            return jnp.concatenate([cur[:, None], rest.T], axis=1)
        walk = [cur]
        for key in keys:
            carry, nxt = move(carry, key)
            walk.append(nxt)
        return jnp.stack(walk, axis=1)

    def _draw_neighbors_paged(self, cur, key, k: int):
        """Paged twin of _draw_neighbors: two-level quantized-CDF
        inversion (page-boundary binary search + in-page count) and
        neighbor/weight gathers through the page indirection — identical
        integers to the dense inversion, different memory layout."""
        from euler_tpu.ops.paged_ops import (
            paged_cdf_count,
            paged_gather,
            paged_gather_dequant,
            paged_page_search,
        )

        width = cur.shape[0]
        deg = self.deg[cur]
        ps = self.page_start[cur]
        P = self.page_size
        if self.unit_w:
            u = jax.random.uniform(key, (width, k))
            idx = (u * deg[:, None]).astype(jnp.int32)
            ew = None
        else:
            r = jax.random.bits(key, (width, k), dtype=jnp.uint32)
            npages = self.page_start[cur + 1] - ps
            pg = paged_page_search(
                self.page_bound, ps, npages, r, self._search_iters
            )
            pgc = jnp.minimum(pg, jnp.maximum(npages[:, None] - 1, 0))
            page = jnp.minimum(ps[:, None] + pgc, self._page_cap)
            cnt = paged_cdf_count(self.page_q2d, page, r, P)
            idx = pgc * P + cnt
        idx = jnp.minimum(idx, jnp.maximum(deg[:, None] - 1, 0))
        fidx = jnp.minimum(ps[:, None] * P + idx, self._slot_cap)
        nbr = jnp.where(
            deg[:, None] > 0,
            paged_gather(self.pages2d, fidx),
            0,
        ).reshape(-1)
        if not self.unit_w:
            # packed plane: bf16 dequantized AT the gather (half the
            # DMA bytes); the trailing bf16 cast below makes the packed
            # and f32 planes emit bit-identical weights either way
            wvals = (
                paged_gather_dequant(self.page_w2d, fidx)
                if getattr(self, "_page_w_packed", False)
                else paged_gather(self.page_w2d, fidx)
            )
            ew = (
                jnp.where(deg[:, None] > 0, wvals, 0.0)
                .reshape(-1)
                .astype(jnp.bfloat16)
            )
        return nbr, ew, idx


class DeviceSageFlow(DeviceGraphTables):
    """HBM-resident adjacency + traced fanout sampling → lean MiniBatch.

    Pass an instance as an Estimator's `batch_fn`: the Estimator detects
    `is_device_flow` and generates batches inside the jitted train step
    from per-step PRNG keys (estimator.py `_train_step_scan`). The batch
    pytree is identical to what a lean host `SageDataFlow` ships after
    device_put, so models, hydration, and the feature cache are shared.
    """

    _ROW_READS = True  # every hop of a fan-out reads its frontier's rows

    def __init__(
        self,
        graph,
        fanouts,
        batch_size: int,
        label_feature: str | None = None,
        edge_types=None,
        max_degree: int = 512,
        roots_pool: np.ndarray | None = None,
        root_node_type: int = -1,
        mesh=None,
        with_hop_ids: bool = False,
        layout: str = "auto",
        page_size: int = 16,
    ):
        """with_hop_ids=True ships per-hop int32 node ids in the batch —
        what id-embedding models (ShallowEncoder with max_id) consume.
        The host LEAN wire must omit hop_ids (they cost wire bytes); on
        device they are a free node_id gather, so id-embedding models
        run through the device flow at no extra cost.

        layout="auto" stages the dense padded adjacency while the max
        degree fits `max_degree` and the ragged paged layout otherwise
        (power-law graphs; HBM ∝ edges) — draws are bit-identical either
        way under the same keys. The dense planes are staged a whole
        number of 128-lane tiles wide (`_ROW_READS`): (N+1)·⌈Dmax/128⌉·512
        bytes each."""
        super().__init__(
            graph, edge_types, max_degree, roots_pool, root_node_type, mesh,
            layout=layout, page_size=page_size,
        )
        self.fanouts = [int(k) for k in fanouts]
        self.batch_size = int(batch_size)
        self.with_hop_ids = bool(with_hop_ids)
        if label_feature is not None:
            from euler_tpu.estimator.feature_cache import DeviceFeatureCache

            self.label_table = DeviceFeatureCache(graph, [label_feature]).table
        else:
            self.label_table = None

    def _fanout_batch(self, roots, key) -> MiniBatch:
        """Traced multi-hop fanout from [B] root rows → lean MiniBatch."""
        cur = self._dp(roots)
        feats = [cur]
        blocks = []
        width = roots.shape[0]
        for k, hk in zip(self.fanouts, jax.random.split(key, len(self.fanouts))):
            nbr, ew, _ = self._draw_neighbors(cur, hk, k)
            nbr = self._dp(nbr)
            if ew is not None:
                # weighted-lean wire parity: bf16 weights ride the batch
                ew = self._dp(ew)
            blocks.append(
                Block(
                    edge_src=None, edge_dst=None, edge_w=ew, mask=None,
                    n_src=width * k, n_dst=width, grid=k, src_in_order=True,
                )
            )
            feats.append(nbr)
            cur = nbr
            width *= k
        labels = (
            self.label_table[feats[0]] if self.label_table is not None else None
        )
        if labels is not None:
            labels = self._dp(labels)
        return MiniBatch(
            feats=tuple(feats),
            masks=None,
            blocks=tuple(blocks),
            root_idx=self._dp(self.node_id[feats[0]]),
            labels=labels,
            # pad rows map to id -1 (host non-lean parity); the encoder
            # clips them to 0, but hydrate_blocks derives hop masks from
            # the rows-mode feats before the model applies, so pad-slot
            # embeddings never reach the aggregation
            hop_ids=(
                tuple(self._dp(self.node_id[f]) for f in feats)
                if self.with_hop_ids
                else None
            ),
        )

    def sample(self, key) -> MiniBatch:
        """key → lean MiniBatch, jit-traceable (call inside the train step)."""
        kroot, khops = jax.random.split(key)
        return self._fanout_batch(
            self._draw_roots(kroot, self.batch_size), khops
        )



class DeviceUnsupSageFlow(DeviceSageFlow):
    """On-device (src, pos, negs) fanout triples for GraphSAGEUnsupervised.

    Host parity: estimator.unsupervised_batches — pos is a sampled 1-hop
    neighbor of src (falling back to src itself when src has none), negs
    are globally drawn nodes; each of the three gets its own multi-hop
    lean fanout batch. sample(key) returns the 3-tuple of MiniBatches the
    model's (src, pos, negs) signature consumes.
    """

    def __init__(
        self,
        graph,
        fanouts,
        batch_size: int,
        num_negs: int = 5,
        edge_types=None,
        max_degree: int = 512,
        roots_pool: np.ndarray | None = None,
        root_node_type: int = -1,
        mesh=None,
        with_hop_ids: bool = False,
        layout: str = "auto",
        page_size: int = 16,
    ):
        super().__init__(
            graph, fanouts, batch_size, None, edge_types, max_degree,
            roots_pool, root_node_type, mesh, with_hop_ids=with_hop_ids,
            layout=layout, page_size=page_size,
        )
        self.num_negs = int(num_negs)

    def sample(self, key) -> tuple:
        kroot, kpos, kneg, ks, kp, kn = jax.random.split(key, 6)
        src = self._draw_roots(kroot, self.batch_size)
        nbr, _, _ = self._draw_neighbors(src, kpos, 1)
        pos = jnp.where(nbr > 0, nbr, src)
        negs = self._draw_global_nodes(kneg, self.batch_size * self.num_negs)
        return (
            self._fanout_batch(src, ks),
            self._fanout_batch(pos, kp),
            self._fanout_batch(negs, kn),
        )


class DeviceWalkFlow(DeviceGraphTables):
    """On-device random walks + skip-gram pairs for DeepWalk/node2vec.

    Replaces the host walk pipeline (graph.random_walk → dataflow.walk
    gen_pair → negative draws, models/embedding_models.deepwalk_batches)
    with traced XLA ops: the walk is a length-L chain of single-neighbor
    draws against the HBM adjacency, the sliding-window pair extraction
    is a static column gather, and negatives ride the same node CDF.
    `sample(key)` returns the exact dict batch `SkipGramModel` consumes
    (src/pos int32 ids, negs [P, num_negs], mask) with identical padding
    semantics (-1 ids on dead-walk slots are excluded by the mask).

    node2vec bias (p/q ≠ 1, random_walk_op.cc:27-90): each step biases
    the current node's weight row by 1/p toward the previous node, 1 for
    neighbors of the previous node, 1/q elsewhere — the membership test
    is a [W, D, D] compare against prev's adjacency row, so the biased
    path is gated to max degree ≤ 64 (guarded at construction).
    """

    _PAGED_OK = False  # _walk_step reads the dense adj plane directly

    def __init__(
        self,
        graph,
        batch_size: int,
        walk_len: int = 5,
        window: int = 2,
        num_negs: int = 5,
        p: float = 1.0,
        q: float = 1.0,
        edge_types=None,
        max_degree: int = 512,
        roots_pool: np.ndarray | None = None,
        root_node_type: int = -1,
        mesh=None,
        layout: str = "auto",
    ):
        super().__init__(
            graph, edge_types, max_degree, roots_pool, root_node_type, mesh,
            layout=layout,
        )
        self.batch_size = int(batch_size)
        self.walk_len = int(walk_len)
        self.num_negs = int(num_negs)
        self.p, self.q = float(p), float(q)
        self.biased = not (p == 1.0 and q == 1.0)
        if self.biased and self.max_deg > 64:
            raise ValueError(
                f"node2vec bias needs a [W, D, D] membership test; max "
                f"degree {self.max_deg} > 64 makes that table too wide — "
                "use the host random_walk for this graph"
            )
        # static sliding-window column indices (walk.py gen_pair parity):
        # for each offset, source columns [lo, hi) pair with context
        # columns [lo+off, hi+off); padded tail slots point at a dead
        # column marked invalid
        length = self.walk_len + 1
        src_cols, ctx_cols, valid = [], [], []
        for off in range(-window, window + 1):
            if off == 0:
                continue
            lo, hi = max(0, -off), min(length, length - off)
            cols = np.arange(length)
            s = np.where(cols < hi - lo, cols + lo, 0)
            c = np.where(cols < hi - lo, cols + lo + off, 0)
            src_cols.append(s)
            ctx_cols.append(c)
            valid.append(cols < hi - lo)
        self._src_cols = np.concatenate(src_cols)
        self._ctx_cols = np.concatenate(ctx_cols)
        self._col_valid = np.concatenate(valid)
        self.pairs_per_walk = len(self._src_cols)

    def _walk_step(self, cur, prev, key):
        """One biased transition (p/q): weight row × node2vec bias, then
        the same inverse-CDF draw as the unbiased path."""
        width = cur.shape[0]
        nbr_rows = self.adj[cur]  # [W, D]
        deg = self.deg[cur]
        if self.unit_w:
            w = (nbr_rows > 0).astype(jnp.float32)
        else:
            w = self.wtab[cur]
        # bias: 1/p back to prev, 1 if adjacent to prev, 1/q otherwise
        prev_nbrs = self.adj[prev]  # [W, D]
        is_back = nbr_rows == prev[:, None]
        near = (
            (nbr_rows[:, :, None] == prev_nbrs[:, None, :])
            & (prev_nbrs[:, None, :] > 0)
        ).any(axis=-1)
        bias = jnp.where(
            is_back, 1.0 / self.p, jnp.where(near, 1.0, 1.0 / self.q)
        )
        bias = jnp.where((prev > 0)[:, None], bias, 1.0)
        bw = w * bias * (nbr_rows > 0)
        cum = jnp.cumsum(bw, axis=1)
        u = jax.random.uniform(key, (width, 1)) * cum[:, -1][:, None]
        idx = (cum <= u).sum(axis=1)
        idx = jnp.minimum(idx, jnp.maximum(deg - 1, 0))
        alive = (deg > 0) & (cum[:, -1] > 0)
        return jnp.where(alive, nbr_rows[jnp.arange(width), idx], 0)

    def sample(self, key) -> dict:
        """key → SkipGramModel batch dict, jit-traceable."""
        kroot, kneg, kwalk = jax.random.split(key, 3)
        cur = self._dp(self._draw_roots(kroot, self.batch_size))
        walks = self._walk_rows(  # [B, L+1] rows (0 = dead)
            cur, kwalk, self.walk_len,
            step=self._walk_step if self.biased else None,
        )
        src = walks[:, self._src_cols] * self._col_valid  # [B, M]
        ctx = walks[:, self._ctx_cols] * self._col_valid
        mask = (src > 0) & (ctx > 0)
        negs = self._draw_roots(
            kneg, self.batch_size * self.pairs_per_walk * self.num_negs
        )
        to_id = lambda r: self.node_id[r]  # noqa: E731  (-1 on padding)
        return {
            "src": self._dp(to_id(src.reshape(-1))),
            "pos": self._dp(to_id(ctx.reshape(-1))),
            "negs": self._dp(
                to_id(negs).reshape(-1, self.num_negs)
            ),
            "mask": self._dp(mask.reshape(-1)),
        }



class DeviceSequenceFlow(DeviceGraphTables):
    """Token sequences drawn on the device: the graph is a transition
    graph over a vocabulary (node = token), a document is a uniform random
    walk of `doc_len` nodes, and `docs_per_seq` documents are packed end
    to end into one sequence, unmasked from one another. The framework's
    own sampler is the corpus of a sequence model (`models/sequence_lm.py`).

    `sample(key)` returns int32 token ids [batch_size, seq_len + 1]: the
    inputs and, shifted by one, the next-token targets; the further id is
    the node the sequence's last walk moves to next. A token is its
    node's id less 1 (ids count from 1). `split(key, 2)` gives the root
    key and the walk key; transition i draws under `split(walk_key,
    doc_len)[i]` as `DeviceWalkFlow`'s walks do.
    """

    def __init__(
        self,
        graph,
        batch_size: int,
        seq_len: int,
        doc_len: int,
        edge_types=None,
        max_degree: int = 512,
        mesh=None,
        layout: str = "auto",
    ):
        if seq_len % doc_len:
            raise ValueError(
                f"seq_len {seq_len} is not a whole number of documents of "
                f"{doc_len} tokens"
            )
        super().__init__(
            graph, edge_types, max_degree, mesh=mesh, layout=layout
        )
        self.batch_size = int(batch_size)
        self.seq_len = int(seq_len)
        self.doc_len = int(doc_len)
        self.docs_per_seq = self.seq_len // self.doc_len

    def sample(self, key):
        """key -> int32 [batch_size, seq_len + 1] token ids, jit-traceable."""
        kroot, kwalk = jax.random.split(key)
        walks = self.batch_size * self.docs_per_seq
        cur = self._dp(self._draw_roots(kroot, walks))
        rows = self._walk_rows(  # [walks, doc_len + 1]
            cur, kwalk, self.doc_len, scan=True
        )
        ids = self.node_id[rows].reshape(
            self.batch_size, self.docs_per_seq, self.doc_len + 1
        )
        packed = ids[:, :, : self.doc_len].reshape(self.batch_size, -1)
        return self._dp(
            jnp.concatenate([packed, ids[:, -1, self.doc_len :]], axis=1) - 1
        )


class _FlatEdgeFlow(DeviceGraphTables):
    """Shared staging for flows that draw whole edges from the flat list
    (LINE, KG): edge columns + weight CDF + node tables for negatives."""

    def __init__(self, graph, batch_size: int, num_negs: int,
                 edge_type: int = -1, mesh=None, stage_er: bool = False):
        self.mesh = mesh
        self.batch_size = int(batch_size)
        self.num_negs = int(num_negs)
        self._stage_flat_edges(graph, edge_type, stage_er=stage_er)
        ids, wn, nt = _node_table(graph)
        self._stage_nodes(graph, ids, wn, nt, None, -1)


class DeviceEdgeFlow(_FlatEdgeFlow):
    """On-device weighted edge sampling for LINE (examples/line parity).

    Replaces the host `line_batches` source (graph.sample_edge +
    sample_node negatives, models/embedding_models.py). Stages the FLAT
    edge list — the right layout for whole-edge draws on any degree
    distribution (no max_degree guard; power-law graphs welcome) — and
    draws each edge with one searchsorted over the weight CDF, the same
    distribution the host alias tables sample. `sample(key)` returns the
    SkipGramModel dict batch.
    """

    def __init__(self, graph, batch_size: int, num_negs: int = 5,
                 edge_type: int = -1, mesh=None):
        super().__init__(graph, batch_size, num_negs, edge_type, mesh)

    def sample(self, key) -> dict:
        """key → SkipGramModel batch dict, jit-traceable."""
        kedge, kneg = jax.random.split(key)
        pick = self._draw_edges(kedge, self.batch_size)
        negs = self._draw_global_nodes(kneg, self.batch_size * self.num_negs)
        return {
            "src": self._dp(self.eh[pick]),
            "pos": self._dp(self.et[pick]),
            "negs": self._dp(
                self.node_id[negs].reshape(-1, self.num_negs)
            ),
            "mask": self._dp(jnp.ones(self.batch_size, bool)),
        }


class DeviceKGFlow(_FlatEdgeFlow):
    """On-device (h, r, t) triple sampling + corrupted negatives for the
    TransX family (models/kg.py `kg_batches` parity).

    KG graphs are exactly the power-law case where a padded [N, Dmax]
    adjacency is the wrong layout (FB15k hub entities have thousands of
    out-edges), so this flow stages the FLAT edge list (shared
    `_stage_flat_edges`: int32 (h, r, t) columns, 12 bytes/edge — 6 MB
    for FB15k's 483k triples — one searchsorted per draw, exact, any
    degree distribution). Corrupted heads/tails draw from the global
    node CDF (host sample_node(-1) parity). `sample(key)` returns the
    exact dict batch `TransX.__call__` consumes.
    """

    def __init__(self, graph, batch_size: int, num_negs: int = 8,
                 edge_type: int = -1, mesh=None):
        super().__init__(
            graph, batch_size, num_negs, edge_type, mesh, stage_er=True
        )

    def sample(self, key) -> dict:
        """key → TransX batch dict, jit-traceable."""
        kedge, kneg = jax.random.split(key)
        pick = self._draw_edges(kedge, self.batch_size)
        negs = self.node_id[
            self._draw_global_nodes(
                kneg, self.batch_size * self.num_negs * 2
            )
        ].reshape(2, self.batch_size, self.num_negs)
        return {
            "h": self._dp(self.eh[pick]),
            "r": self._dp(self.er[pick]),
            "t": self._dp(self.et[pick]),
            "neg_h": self._dp(negs[0]),
            "neg_t": self._dp(negs[1]),
        }


class DeviceRelationFlow(DeviceGraphTables):
    """On-device per-relation fanouts for RGCN (relation.py parity).

    One staged table set (adjacency + weight + type planes) serves every
    relation: each hop's per-relation draw masks the type plane before
    the CDF inversion (`_draw_neighbors_typed`), exactly the host
    sample_neighbor(cur, [r], k) distribution, without R per-relation
    adjacency copies. sample(key) returns the RelMiniBatch the RGCN
    model consumes, with dense features gathered in-flow from an HBM
    feature table (RelMiniBatch has no rows-mode hydration path).
    """

    _PAGED_OK = False  # typed draws mask the dense type plane

    def __init__(
        self,
        graph,
        feature_names,
        num_relations: int,
        batch_size: int,
        fanout: int = 5,
        num_hops: int = 2,
        label_feature: str | None = None,
        max_degree: int = 512,
        roots_pool: np.ndarray | None = None,
        root_node_type: int = -1,
        mesh=None,
    ):
        super().__init__(
            graph, None, max_degree, roots_pool, root_node_type, mesh,
            stage_types=True,
        )
        from euler_tpu.estimator.feature_cache import DeviceFeatureCache

        self.num_relations = int(num_relations)
        self.batch_size = int(batch_size)
        self.fanout = int(fanout)
        self.num_hops = int(num_hops)
        self.feat_table = DeviceFeatureCache(graph, list(feature_names)).table
        self.label_table = (
            DeviceFeatureCache(graph, [label_feature]).table
            if label_feature is not None
            else None
        )

    def sample(self, key) -> "RelMiniBatch":
        from euler_tpu.dataflow.relation import RelMiniBatch

        k, nr = self.fanout, self.num_relations
        keys = jax.random.split(key, 1 + self.num_hops * nr)
        cur = self._dp(self._draw_roots(keys[0], self.batch_size))
        hop_rows = [cur]
        hop_masks = [cur > 0]
        rel_blocks = []
        ki = 1
        for _ in range(self.num_hops):
            n = cur.shape[0]
            nxt = []
            blocks = []
            for r in range(nr):
                nbr, ew, valid = self._draw_neighbors_typed(
                    cur, keys[ki], k, r
                )
                ki += 1
                nxt.append(nbr.reshape(n, k))
                # src slots for relation r sit at rows [i*nr*k + r*k + j]
                src = (
                    np.arange(n)[:, None] * nr * k
                    + r * k
                    + np.arange(k)[None, :]
                ).reshape(-1)
                blocks.append(
                    Block(
                        edge_src=jnp.asarray(src, jnp.int32),
                        edge_dst=jnp.repeat(
                            jnp.arange(n, dtype=jnp.int32), k
                        ),
                        edge_w=self._dp(ew.astype(jnp.float32)),
                        mask=self._dp(valid),
                        n_src=n * nr * k,
                        n_dst=n,
                    )
                )
            rel_blocks.append(tuple(blocks))
            # next hop interleaves relations: [n, nr, k] flattened, same
            # slot layout the edge_src indices above address
            cur = self._dp(
                jnp.stack(nxt, axis=1).reshape(-1)
            )
            hop_rows.append(cur)
            hop_masks.append(cur > 0)
        feats = tuple(self._dp(self.feat_table[rw]) for rw in hop_rows)
        labels = (
            self._dp(self.label_table[hop_rows[0]])
            if self.label_table is not None
            else None
        )
        return RelMiniBatch(
            feats=feats,
            masks=tuple(hop_masks),
            rel_blocks=tuple(rel_blocks),
            root_idx=self._dp(self.node_id[hop_rows[0]]),
            labels=labels,
            hop_ids=tuple(
                self._dp(self.node_id[rw]) for rw in hop_rows
            ),
        )



class DeviceLayerwiseFlow(DeviceGraphTables):
    """On-device LADIES layer sampling (layerwise.py parity).

    Each layer draw IS the exact host algorithm as XLA ops: candidate
    incident weights scatter-add into an [N+1] vector, Gumbel top-k picks
    `count` layer nodes without replacement (log w + Gumbel noise — the
    store's layerwise_from_full recipe), and the dense batch→layer
    adjacency is a [W, D, count] membership einsum, row-normalized. When
    the whole frontier fits in `count` the layer is exact, like the host.
    sample(key) returns the LayerwiseBatch `LayerwiseGCN` consumes (dense
    in-flow-gathered features).
    """

    _PAGED_OK = False  # the layer scatter reads the dense adj/w planes

    def __init__(
        self,
        graph,
        feature_names,
        batch_size: int,
        layer_sizes=(128, 128),
        label_feature: str | None = None,
        normalize: bool = True,
        edge_types=None,
        max_degree: int = 512,
        roots_pool: np.ndarray | None = None,
        root_node_type: int = -1,
        mesh=None,
    ):
        super().__init__(
            graph, edge_types, max_degree, roots_pool, root_node_type, mesh
        )
        from euler_tpu.estimator.feature_cache import DeviceFeatureCache

        self.batch_size = int(batch_size)
        self.layer_sizes = [int(c) for c in layer_sizes]
        self.normalize = bool(normalize)
        self.feat_table = DeviceFeatureCache(graph, list(feature_names)).table
        self.label_table = (
            DeviceFeatureCache(graph, [label_feature]).table
            if label_feature is not None
            else None
        )

    def _sample_layer(self, cur, key, count: int):
        """[W] rows → ([count] layer rows, f32[W, count] adjacency,
        bool[count] layer mask)."""
        nbr = self.adj[cur]  # [W, D]
        w = (
            self.wtab[cur]
            if self.wtab is not None
            else (nbr > 0).astype(jnp.float32)
        )
        wsum = (
            jnp.zeros(self.num_nodes + 1)
            .at[nbr.reshape(-1)]
            .add(w.reshape(-1))
            .at[0]
            .set(0.0)
        )
        g = jax.random.gumbel(key, (self.num_nodes + 1,))
        score = jnp.where(wsum > 0, jnp.log(wsum) + g, -jnp.inf)
        top, layer = jax.lax.top_k(score, count)
        lmask = top > -jnp.inf
        layer = jnp.where(lmask, layer, 0).astype(jnp.int32)
        hit = (nbr[:, :, None] == layer[None, None, :]) & (
            layer[None, None, :] > 0
        )
        adj = jnp.einsum("wd,wdc->wc", w, hit.astype(w.dtype))
        if self.normalize:
            adj = adj / jnp.maximum(adj.sum(axis=1, keepdims=True), 1e-9)
        return layer, adj, lmask

    def sample(self, key) -> "LayerwiseBatch":
        from euler_tpu.dataflow.layerwise import LayerwiseBatch

        keys = jax.random.split(key, 1 + len(self.layer_sizes))
        cur = self._dp(self._draw_roots(keys[0], self.batch_size))
        layer_rows = [cur]
        layer_masks = [cur > 0]
        adjs = []
        for count, lk in zip(self.layer_sizes, keys[1:]):
            layer, adj, lmask = self._sample_layer(cur, lk, count)
            adjs.append(self._dp(adj))
            cur = self._dp(layer)
            layer_rows.append(cur)
            layer_masks.append(lmask)
        feats = tuple(self._dp(self.feat_table[rw]) for rw in layer_rows)
        labels = (
            self._dp(self.label_table[layer_rows[0]])
            if self.label_table is not None
            else None
        )
        return LayerwiseBatch(
            feats=feats,
            masks=tuple(layer_masks),
            adjs=tuple(adjs),
            root_idx=self._dp(self.node_id[layer_rows[0]]),
            labels=labels,
            hop_ids=tuple(self._dp(self.node_id[rw]) for rw in layer_rows),
        )



class DeviceGaeFlow(DeviceSageFlow):
    """On-device (src, dst, neg) fanout triples for GAE/VGAE
    (models/autoencoders.py `gae_batches` parity): src draws ∝ edge
    weight through the shared edge-source CDF, dst is the drawn edge's
    endpoint, neg is a global node draw; each gets its own fanout batch.
    """

    def __init__(self, graph, fanouts, batch_size, edge_types=None,
                 max_degree: int = 512, mesh=None, layout: str = "auto",
                 page_size: int = 16):
        super().__init__(
            graph, fanouts, batch_size, None, edge_types, max_degree,
            mesh=mesh, layout=layout, page_size=page_size,
        )
        self._stage_edge_src_cdf()

    def sample(self, key) -> tuple:
        ksrc, kdst, kneg, k1, k2, k3 = jax.random.split(key, 6)
        src = self._draw_edge_sources(ksrc, self.batch_size)
        dst, _, _ = self._draw_neighbors(src, kdst, 1)
        neg = self._draw_global_nodes(kneg, self.batch_size)
        return (
            self._fanout_batch(src, k1),
            self._fanout_batch(dst, k2),
            self._fanout_batch(neg, k3),
        )


class DeviceDgiFlow(DeviceSageFlow):
    """On-device (real, corrupted) batches for DGI (`dgi_batches`
    parity): corruption permutes the feature rows across the batch —
    with rows-mode feats a row permutation IS the standard DGI feature
    shuffle (hydration gathers the permuted rows into permuted dense
    features)."""

    def sample(self, key) -> tuple:
        kmb, kperm = jax.random.split(key)
        mb = super().sample(kmb)
        # one permutation per hop, shared by the feature rows and (when
        # with_hop_ids is on) the id plane: ids, features, and the masks
        # hydration derives from the rows must move together, or pad
        # slots in the un-permuted plane land under valid-mask positions
        perms = tuple(
            jax.random.permutation(pk, f.shape[0])
            for pk, f in zip(
                jax.random.split(kperm, len(mb.feats)), mb.feats
            )
        )
        perm_feats = tuple(f[p] for f, p in zip(mb.feats, perms))
        perm_ids = (
            tuple(h[p] for h, p in zip(mb.hop_ids, perms))
            if mb.hop_ids is not None
            else None
        )
        return (mb, mb.replace(feats=perm_feats, hop_ids=perm_ids))


class DeviceWholeGraphFlow(DeviceGraphTables):
    """Dataset-on-device whole-graph batches for graph classification
    (whole.py `WholeGraphDataFlow` + `graph_label_batches` parity).

    Graph-classification datasets are small (every labeled graph padded
    to max_nodes × max_degree), so the entire padded dataset stages into
    HBM once — per-graph feature/mask/edge/label tensors stacked along a
    leading graph axis — and a training batch is a uniform label draw
    (host sample_graph_label parity) plus gathers, with edge indices
    offset into the batch's flattened node table. Staging reuses the
    host flow's padding/slot logic by querying it one label at a time.
    """

    def __init__(
        self,
        graph,
        feature_names,
        batch_size: int,
        max_nodes: int = 32,
        max_degree: int = 8,
        edge_types=None,
        mesh=None,
        host_flow=None,
    ):
        """host_flow: an already-built WholeGraphDataFlow to stage from
        (its max_nodes/max_degree then govern the padding — callers that
        also evaluate through the host flow pass it to keep one source
        of truth); built internally otherwise."""
        from euler_tpu.dataflow.whole import WholeGraphDataFlow

        self.mesh = mesh
        self.batch_size = int(batch_size)
        host = host_flow or WholeGraphDataFlow(
            graph, feature_names, max_nodes=max_nodes,
            max_degree=max_degree, edge_types=edge_types,
        )
        if host.num_labels == 0:
            raise ValueError("graph has no graph labels to sample")
        self.num_classes = host.num_classes
        ng, nmax = host.num_labels, host.max_nodes
        # ONE batched host query stages every labeled graph; per-graph
        # tensors are reshaped slices (the host's i*nmax edge offsets are
        # subtracted here and re-added per batch slot in sample())
        all_b = host.query(np.arange(ng))
        put = jax.device_put
        self.gfeats = put(np.asarray(all_b.feats).reshape(ng, nmax, -1))
        self.gmask = put(np.asarray(all_b.node_mask).reshape(ng, nmax))
        self.grid = int(all_b.block.grid)
        e = nmax * self.grid
        local = np.arange(ng, dtype=np.int32)[:, None] * nmax
        emask = np.asarray(all_b.block.mask).reshape(ng, e)
        # masked padding edges carry global slot 0 in the host layout;
        # localize them to 0 (not -i*nmax) so the batch offset re-added in
        # sample() can never go negative
        self.gesrc = put(np.where(
            emask, np.asarray(all_b.block.edge_src).reshape(ng, e) - local, 0
        ).astype(np.int32))
        # dst is the aggregation center — structurally valid for masked
        # edges too, so plain localization stays in [0, nmax)
        self.gedst = put(
            (np.asarray(all_b.block.edge_dst).reshape(ng, e) - local).astype(
                np.int32
            )
        )
        self.gew = put(np.asarray(all_b.block.edge_w).reshape(ng, e))
        self.gemask = put(emask)
        self.glabels = put(np.asarray(all_b.labels))
        self.ghop = put(np.asarray(all_b.hop_ids).reshape(ng, nmax))
        self.nmax = nmax
        self.num_graphs = ng

    def sample(self, key) -> "GraphBatch":
        from euler_tpu.dataflow.whole import GraphBatch

        b, nmax = self.batch_size, self.nmax
        pick = jax.random.randint(key, (b,), 0, self.num_graphs)
        off_n = (jnp.arange(b, dtype=jnp.int32) * nmax)[:, None]
        block = Block(
            edge_src=self._dp((self.gesrc[pick] + off_n).reshape(-1)),
            edge_dst=self._dp((self.gedst[pick] + off_n).reshape(-1)),
            edge_w=self._dp(self.gew[pick].reshape(-1)),
            mask=self._dp(self.gemask[pick].reshape(-1)),
            n_src=b * nmax,
            n_dst=b * nmax,
            grid=self.grid,
        )
        return GraphBatch(
            feats=self._dp(self.gfeats[pick].reshape(b * nmax, -1)),
            node_mask=self._dp(self.gmask[pick].reshape(-1)),
            block=block,
            graph_ids=self._dp(
                jnp.repeat(jnp.arange(b, dtype=jnp.int32), nmax)
            ),
            labels=self._dp(self.glabels[pick]),
            hop_ids=self._dp(self.ghop[pick].reshape(-1)),
            n_graphs=b,
        )
