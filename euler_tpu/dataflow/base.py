"""Padded mini-batch subgraph containers + base dataflow.

The reference's `DataFlow`/`Block` abstraction (tf_euler/python/dataflow/
base_dataflow.py:23-52) builds *dynamic* subgraphs with `tf.unique`; XLA needs
static shapes, so the TPU design pads instead (SURVEY.md §7): hop i holds
exactly batch * prod(fanouts[:i]) node slots, invalid slots carry a mask, and
every downstream op is a fixed-shape gather, segment or window reduce —
fusable by XLA and trivially shardable along the batch axis of a device mesh.

A `Block` is the bipartite edge set between hop i+1 ("src", the sampled
neighbors) and hop i ("dst"); node tables are per-hop feature matrices.
"""

from __future__ import annotations

import flax.struct
import jax
import numpy as np

Array = jax.Array


@flax.struct.dataclass
class Block:
    """Edges from a src node table into a dst node table (one hop)."""

    edge_src: Array  # int32[E] rows into the src hop table
    edge_dst: Array  # int32[E] rows into the dst hop table
    edge_w: Array  # f32[E] edge weights (0 where masked)
    mask: Array  # bool[E] valid-edge mask
    n_src: int = flax.struct.field(pytree_node=False)
    n_dst: int = flax.struct.field(pytree_node=False)
    # >0 when edges are grid-structured (dst row i owns slots [i*g, (i+1)*g),
    # i.e. edge_dst[e] == e // g): convs then aggregate by summing each run
    # of g consecutive message rows (ops.grid_add), with no index traffic
    grid: int = flax.struct.field(pytree_node=False, default=0)
    # True when edge_src is arange(n_src): src row e IS edge e's message
    # (sampled fanout), so a conv's message step is x_src itself, not a gather
    src_in_order: bool = flax.struct.field(pytree_node=False, default=False)
    # optional TRUE graph degrees (f32, self-loop not included): full-graph
    # degrees of the src/dst hop's nodes, for exact GCN symmetric
    # normalization in full-neighbor/whole-graph flows (the reference
    # computes in-batch degrees, gcn_conv.py:32-54, which only equal true
    # degrees when every incident edge is present in the block)
    src_deg: Array | None = None  # f32[n_src]
    dst_deg: Array | None = None  # f32[n_dst]


@flax.struct.dataclass
class MiniBatch:
    """One padded multi-hop subgraph batch, ready for device_put.

    feats[i]  — f32[N_i, F] node features of hop i (hop 0 = roots)
    masks[i]  — bool[N_i] node validity
    blocks[i] — edges hop i+1 → hop i  (len == num hops)
    root_idx  — int32[B] root node ids (for embedding lookups / neg sampling)
    labels    — optional f32[B, L] supervised targets
    """

    feats: tuple
    masks: tuple
    blocks: tuple
    root_idx: Array
    labels: Array | None = None
    hop_ids: tuple | None = None  # int32 per-hop node ids (for id embeddings)
    # whole-graph flows: rows of the hop-0 table whose outputs participate
    # in the loss/metric (labels then has one row per target); None means
    # every hop-0 row is a target (the sampled-flow contract)
    target_idx: Array | None = None


def gather_unique(ids_list, fetch):
    """Cross-hop unique-ID coalescing: ONE deduplicated fetch covers
    every hop, results scattered back by inverse index.

    A 2-hop SAGE batch re-cites the same hot node in (on power-law
    graphs) most of its slots, and cites hop-1 nodes again in hop 2 —
    fetching per hop ships every duplicate id AND its result row L×.
    `fetch(uniq)` sees each id once; because the fetched verbs are
    deterministic per id, `fetch(uniq)[inverse]` is bit-identical to
    fetching each hop directly.

    ids_list: 1-D id (or row) arrays. fetch(uniq) -> array whose leading
    dim is len(uniq). Returns one array per input list, same leading
    lengths, remaining dims from the fetch result.
    """
    arrs = [np.asarray(a).reshape(-1) for a in ids_list]
    flat = np.concatenate(arrs) if arrs else np.empty(0, np.uint64)
    uniq, inv = np.unique(flat, return_inverse=True)
    vals = np.asarray(fetch(uniq))
    ndup = int(flat.size - uniq.size)
    if ndup and len(uniq):
        from euler_tpu.distributed.cache import note_gather_dedup

        note_gather_dedup(ndup, vals.nbytes // len(uniq))
    out_flat = vals[inv]
    offs = np.cumsum([0] + [a.size for a in arrs])
    return [out_flat[offs[i] : offs[i + 1]] for i in range(len(arrs))]


class DataFlow:
    """Base: fetches features/labels; subclasses build the hop structure.

    query(roots) → MiniBatch of numpy arrays (host); training loops
    device_put them (or feed through an infeed pipeline).
    """

    def __init__(
        self,
        graph,
        feature_names: list[str],
        label_feature: str | None = None,
        label_dim: int | None = None,
        rng: np.random.Generator | None = None,
        feature_mode: str = "dense",
    ):
        self.graph = graph
        self.feature_names = list(feature_names)
        self.label_feature = label_feature
        self.label_dim = label_dim
        self.rng = rng if rng is not None else np.random.default_rng()
        if feature_mode not in ("dense", "rows"):
            raise ValueError(f"unknown feature_mode {feature_mode!r}")
        self.feature_mode = feature_mode

    # -- helpers ---------------------------------------------------------

    def node_feats(self, ids: np.ndarray) -> np.ndarray:
        if self.feature_mode == "rows":
            # ship int32 rows into a DeviceFeatureCache table instead of the
            # dense payload; row 0 is the cache's zero/padding row
            rows = self.graph.lookup_rows(ids)
            return np.where(rows >= 0, rows + 1, 0).astype(np.int32)
        if not self.feature_names:
            return np.zeros((len(ids), 0), dtype=np.float32)
        return self.graph.get_dense_feature(ids, self.feature_names)

    def node_feats_hops(self, ids_list) -> tuple:
        """Per-hop `node_feats`, with ids deduplicated ACROSS hops before
        the (possibly remote) fetch — one unique-id round instead of L+1
        rounds re-shipping every duplicate's feature row. Bit-identical
        to `tuple(self.node_feats(ids) for ids in ids_list)`."""
        if self.feature_mode == "rows":
            def fetch(u):
                rows = np.asarray(self.graph.lookup_rows(u))
                return np.where(rows >= 0, rows + 1, 0).astype(np.int32)
        elif not self.feature_names:
            return tuple(
                np.zeros((len(np.asarray(i)), 0), np.float32)
                for i in ids_list
            )
        else:
            def fetch(u):
                return self.graph.get_dense_feature(u, self.feature_names)
        return tuple(gather_unique(ids_list, fetch))

    def labels_of(self, ids: np.ndarray) -> np.ndarray | None:
        if self.label_feature is None:
            return None
        return self.graph.get_dense_feature(ids, [self.label_feature])

    def query(self, roots: np.ndarray) -> MiniBatch:
        raise NotImplementedError

    def query_padded(
        self, roots: np.ndarray, batch_size: int
    ) -> tuple[MiniBatch, int]:
        """query() at a FIXED root count: pads `roots` to `batch_size` by
        repeating the final id, so callers with variable request sizes
        (online serving buckets, tail inference chunks) always execute the
        one program compiled for that size. Returns (batch, n_valid) —
        rows [n_valid:] of the output are padding and must be sliced off."""
        roots = np.asarray(roots, dtype=np.uint64)
        n = len(roots)
        if n == 0 or n > batch_size:
            raise ValueError(
                f"need 1..{batch_size} roots for this bucket, got {n}"
            )
        if n < batch_size:
            roots = np.concatenate(
                [roots, np.repeat(roots[-1:], batch_size - n)]
            )
        return self.query(roots), n


def fanout_block(
    batch: int,
    fanout: int,
    w: np.ndarray,
    mask: np.ndarray,
    lazy: bool = False,
    ship_w: bool = True,
    ship_mask: bool = True,
    w_dtype=np.float32,
) -> Block:
    """Block for sampled fanout: src j feeds dst j // fanout.

    lazy=True skips materializing edge_src/edge_dst — they are a pure
    function of (batch, fanout), so shipping them to the device every step
    wastes host→device bandwidth; `hydrate_blocks` rebuilds them on device.
    ship_mask=False / ship_w=False likewise omit the edge mask / weights
    from the wire: hydrate_blocks rederives the mask from the rows-mode
    validity of the src hop and sets edge_w to exactly 1.0 where valid.
    Only valid for rows-mode batches whose consumer is weight-agnostic
    (mask-normalized mean/attention aggregators) or whose graph weights
    are all 1.0 — a uniform weight c != 1 would be rebuilt as 1.
    w_dtype picks the wire dtype for shipped weights; the weighted-lean
    path ships bfloat16 (half the bytes, graph weights need no more
    precision) and hydrate_blocks upcasts on device.
    """
    e = batch * fanout
    return Block(
        edge_src=None if lazy else np.arange(e, dtype=np.int32),
        edge_dst=None if lazy else np.repeat(
            np.arange(batch, dtype=np.int32), fanout
        ),
        edge_w=w.reshape(-1).astype(w_dtype) if ship_w else None,
        mask=mask.reshape(-1) if ship_mask else None,
        n_src=e,
        n_dst=batch,
        grid=fanout,
        src_in_order=True,
    )


def upgrade_lean_host(batch: MiniBatch) -> MiniBatch:
    """Host-side (numpy) rebuild of a LEAN batch's masks and edge weights,
    giving it the same pytree structure as a downgraded batch from the
    same lean flow. Exact for batches that satisfy the lean invariants
    (unit weights, no id aliasing, no dangling rows) — which is every
    batch a lean flow actually shipped lean. Lets steps_per_call windows
    that mix lean and downgraded batches stack instead of crashing."""
    if not isinstance(batch, MiniBatch) or batch.masks is not None:
        return batch
    masks = tuple(
        (np.asarray(f) > 0)
        if np.issubdtype(np.asarray(f).dtype, np.integer)
        else np.ones(np.asarray(f).shape[0], bool)
        for f in batch.feats
    )
    masks = (np.asarray(batch.root_idx) != -1,) + masks[1:]
    blocks = []
    for h, b in enumerate(batch.blocks):
        if b.mask is None:
            b = b.replace(mask=masks[h + 1].reshape(-1))
        if b.edge_w is None:
            b = b.replace(edge_w=np.asarray(b.mask, np.float32))
        elif np.asarray(b.edge_w).dtype != np.float32:
            b = b.replace(  # weighted-lean wire ships bf16
                edge_w=np.asarray(b.edge_w, np.float32)
            )
        blocks.append(b)
    return batch.replace(masks=masks, blocks=tuple(blocks))


def hydrate_blocks(batch: MiniBatch) -> MiniBatch:
    """Rebuild wire-omitted batch pieces on device (jit-safe).

    - lazy grid blocks' edge ids: on-device iota
    - batch.masks is None (lean wire): node validity = rows-mode feat > 0
    - block.mask is None: the src hop's node mask (grid layout aligns them)
    - block.edge_w is None: uniform weights (mask as f32)
    """
    import jax.numpy as jnp

    if not isinstance(batch, MiniBatch):
        return batch
    masks = batch.masks
    if masks is None:  # lean wire: validity rides the int32 rows (0 = pad)
        masks = tuple(
            (f > 0)
            if jnp.issubdtype(jnp.asarray(f).dtype, jnp.integer)
            else jnp.ones(f.shape[0], bool)
            for f in batch.feats
        )
        # hop 0 keeps the non-lean invariant: any non-DEFAULT_ID root is
        # valid even when absent from the feature store (its features are
        # the zero row). root_idx truncates DEFAULT_ID to int32 -1.
        masks = (batch.root_idx != -1,) + masks[1:]
    blocks = []
    for h, b in enumerate(batch.blocks):
        if b.mask is None:
            b = b.replace(mask=masks[h + 1].reshape(-1))
        if b.edge_w is None:
            b = b.replace(edge_w=b.mask.astype(jnp.float32))
        elif jnp.asarray(b.edge_w).dtype != jnp.float32:
            b = b.replace(  # weighted-lean wire ships bf16; upcast on device
                edge_w=jnp.asarray(b.edge_w).astype(jnp.float32)
            )
        if b.edge_src is None:
            b = b.replace(
                edge_src=jnp.arange(b.n_src, dtype=jnp.int32),
                edge_dst=jnp.repeat(
                    jnp.arange(b.n_dst, dtype=jnp.int32), b.grid
                ),
                src_in_order=True,
            )
        blocks.append(b)
    if masks is batch.masks and all(
        a is b for a, b in zip(blocks, batch.blocks)
    ):
        return batch
    return batch.replace(masks=masks, blocks=tuple(blocks))
