"""Bulk-synchronous whole-graph primitives (ISSUE 12 tentpole).

Euler 2.0's third pillar is whole-graph computation; DrJAX (PAPERS.md
arxiv 2403.07128) shows MapReduce-style broadcast/map/reduce building
blocks compose cleanly over sharded array state. This module is that
layer for our per-shard CSR partitions:

  ``WholeGraphEngine``   pins one published graph epoch, pulls every
      shard's out-adjacency once (local arrays in-process, the bulk
      ``edges_by_rows`` verb over the wire), and repartitions the edge
      list by DESTINATION owner into reduction-ready parts.
  ``ShardedFrontier``    per-shard dense f64 vertex state, host- or
      device-resident (f64 staged under jax's x64 context: bit-identical
      to the host path on the CPU backend, within
      dataflow.device.FRONTIER_F64_RTOL per multiply on a TPU, which
      emulates f64).
  ``broadcast`` / ``map_shards`` / ``reduce_scatter_frontier``
      the BSP step: materialize the global frontier, run a per-part
      kernel producing (row, key, val) messages, reduce them per
      destination row — locally or via the ``frontier_exchange`` verb on
      the owning shard's server.

Bit-determinism across shard counts is the load-bearing property and it
is bought entirely with ORDER, never with tolerance: every part's edges
are lexsorted by (dst_local_row, src_node_id, edge_type, weight_bits) —
all shard-count-independent keys — and ``reduce_messages`` reduces each
row's segment left-to-right in that order. The same function serves the
in-process fast path and the server's ``frontier_exchange`` arm, so
local and remote execution agree bit-for-bit by construction.

Epoch consistency: the engine captures the shard list and their arrays
at construction. A concurrent ``GraphWriter.publish`` swaps the facade's
shard references but never mutates the pinned stores, so a running
sweep keeps computing against exactly the epoch it pinned.
"""

from __future__ import annotations

import numpy as np

from euler_tpu.distributed.errors import RpcError

# Client-side verb table for the analytics lane — graftlint's
# wire-protocol checker and tests/test_wire_parity.py union this with
# RemoteShard/GraphWriter/query-plan tables against the server's
# HANDLED_VERBS gate. `frontier_exchange` is sent from THIS module (the
# engine ships boundary messages straight to the owning shard);
# `edges_by_rows` rides the RemoteShard client method.
WIRE_VERBS = frozenset({"frontier_exchange"})

_MSG_BYTES = 24  # one (row i64, key i64, val f64) message on the wire


def _f64_bits(vals: np.ndarray) -> np.ndarray:
    """Total-order sort key for f64 (bit pattern): not numeric order —
    just ANY canonical order so equal multisets sort identically
    regardless of which shard contributed which element."""
    return np.ascontiguousarray(np.asarray(vals, np.float64)).view(np.uint64)


def _ragged_take(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Element indices of the ragged slices [starts[i], starts[i]+lens[i])."""
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, np.int64)
    out = np.repeat(starts.astype(np.int64), lens)
    step = np.arange(total, dtype=np.int64)
    step -= np.repeat(np.cumsum(lens, dtype=np.int64) - lens, lens)
    return out + step


def reduce_messages(rows, keys, vals, mode: str):
    """Deterministically reduce (row, key, val) messages per row.

    The ONE reduction everybody shares — the engine's in-process path
    and the server's ``frontier_exchange`` dispatch arm both land here,
    which is what makes local and remote execution bit-identical.

    Canonical order: lexsort by (val_bits, key, row) — row-major
    segments, ties broken by key then by the value's bit pattern, so any
    permutation of the same message multiset reduces identically.

    mode: "sum" (left-to-right f64 segment sums), "min" (segment
    minima), "vote" (per-(row, key) weight sums, winner = highest sum,
    ties to the smallest key).

    Returns (rows u. i64 ascending, vals f64, keys i64): for sum/min the
    reduced value per row (keys zeros); for vote the winning key per row
    (vals = the winning weight sum).
    """
    rows = np.asarray(rows, np.int64)
    keys = np.asarray(keys, np.int64)
    vals = np.asarray(vals, np.float64)
    if len(rows) == 0:
        e = np.empty(0, np.int64)
        return e, np.empty(0, np.float64), np.empty(0, np.int64)
    order = np.lexsort((_f64_bits(vals), keys, rows))
    r, k, v = rows[order], keys[order], vals[order]
    if mode in ("sum", "min"):
        uniq, starts = np.unique(r, return_index=True)
        if mode == "sum":
            # np.bincount accumulates in data order — the lexsorted
            # canonical order — so the per-row sum is an ordered
            # left-to-right reduction, not an unordered one
            dense = np.bincount(
                np.searchsorted(uniq, r), weights=v, minlength=len(uniq)
            )
            return uniq, dense.astype(np.float64), np.zeros(len(uniq), np.int64)
        return uniq, np.minimum.reduceat(v, starts), np.zeros(len(uniq), np.int64)
    if mode != "vote":
        raise ValueError(f"unknown reduce mode {mode!r}")
    # vote: sum val per (row, key) group, then argmax per row with ties
    # going to the smallest key — all comparisons, no accumulation races
    grp = np.flatnonzero(np.diff(r) | np.diff(k))
    starts = np.concatenate([[0], grp + 1])
    gr, gk = r[starts], k[starts]
    gsum = np.add.reduceat(v, starts)
    pick = np.lexsort((gk, -gsum, gr))
    gr, gk, gsum = gr[pick], gk[pick], gsum[pick]
    uniq, first = np.unique(gr, return_index=True)
    return uniq, gsum[first], gk[first]


def stage_frontier_part(values: np.ndarray):
    """Stage one frontier shard's f64 state on device (delegates to
    dataflow/device so the device-residency policy lives in one place)."""
    from euler_tpu.dataflow import device as _device

    return _device.stage_frontier(values)


class ShardedFrontier:
    """Per-shard dense vertex state (f64), host- or device-resident.

    ``offsets`` is the shard-major global row map (cumsum of per-shard
    node counts); part p holds rows [offsets[p], offsets[p+1]).
    Memory per shard is N/shards * 8 bytes — the frontier stays sharded
    and only ``to_global`` materializes the full vector (SCALE.md).
    """

    def __init__(self, offsets: np.ndarray, values=None, device: bool = False):
        self.offsets = np.asarray(offsets, np.int64)
        self.device = bool(device)
        n = int(self.offsets[-1])
        if values is None:
            values = np.zeros(n, np.float64)
        values = np.asarray(values, np.float64)
        if len(values) != n:
            raise ValueError(
                f"frontier length {len(values)} != row space {n}"
            )
        self.parts = []
        for p in range(len(self.offsets) - 1):
            part = np.ascontiguousarray(
                values[self.offsets[p]:self.offsets[p + 1]]
            )
            self.parts.append(
                stage_frontier_part(part) if self.device else part
            )

    @classmethod
    def from_global(cls, offsets, values, device=False):
        return cls(offsets, values, device=device)

    @property
    def num_rows(self) -> int:
        return int(self.offsets[-1])

    def to_global(self) -> np.ndarray:
        """Materialize the full f64 vector on the host (shard-major)."""
        if not self.parts:
            return np.zeros(0, np.float64)
        return np.concatenate([np.asarray(p, np.float64) for p in self.parts])


def broadcast(frontier: ShardedFrontier) -> np.ndarray:
    """BSP broadcast: every shard's kernel sees the full frontier."""
    return frontier.to_global()


def map_shards(engine, fn, parts=None):
    """Run ``fn(part_index, part)`` over the engine's edge parts,
    collecting per-part results in shard order (deterministic)."""
    parts = engine.parts if parts is None else parts
    return [fn(p, part) for p, part in enumerate(parts)]


def reduce_scatter_frontier(engine, messages, mode: str, out: np.ndarray):
    """Reduce per-part (rows_local, keys, vals) messages into ``out``
    (a global f64 vector), via the owning shard's ``frontier_exchange``
    verb when the engine runs in remote-exchange mode. Rows with no
    messages keep their prior value in ``out``. Returns the global rows
    that received a reduction (and, for vote mode, writes winning keys
    as f64 values)."""
    touched = []
    for p, msg in enumerate(messages):
        if msg is None:
            continue
        rows, keys, vals = msg
        if len(rows) == 0:
            continue
        u, v, k = engine.exchange(p, rows, keys, vals, mode)
        g = u + engine.offsets[p]
        out[g] = k.astype(np.float64) if mode == "vote" else v
        touched.append(g)
    if not touched:
        return np.empty(0, np.int64)
    return np.concatenate(touched)


class WholeGraphEngine:
    """Pinned-epoch whole-graph view: per-shard CSR export repartitioned
    by destination owner into reduction-ready parts.

    exchange: "auto" reduces in-process for local shards and via the
    ``frontier_exchange`` verb for remote ones; "local" never leaves the
    process; "remote" forces the verb wherever the shard has a wire
    (falling back per shard on old servers' unknown-op answers).
    """

    def __init__(
        self,
        graph,
        edge_types=None,
        device: bool = False,
        exchange: str = "auto",
        rows_per_call: int = 65536,
        symmetric: bool = False,
    ):
        if exchange not in ("auto", "local", "remote"):
            raise ValueError(f"exchange mode {exchange!r}")
        self.graph = graph
        self.edge_types = (
            None if edge_types is None
            else [int(t) for t in edge_types]
        )
        self.device = bool(device)
        self.exchange_mode = exchange
        self.rows_per_call = max(int(rows_per_call), 1)
        self.symmetric = bool(symmetric)
        # pin the shard list NOW: publish swaps the facade's references
        # but never mutates the stores behind them, so this engine keeps
        # reading exactly the epoch it pinned even under live writers
        self._shards = list(graph.shards)
        self.num_shards = len(self._shards)
        self._exchange_wire = [True] * self.num_shards
        self.stats = {
            "rows_fetched": 0,
            "rows_refetched": 0,
            "exchange_bytes": 0,
            "exchange_calls": 0,
            "dropped_edges": 0,
        }
        self._shard_n = [int(s.num_nodes) for s in self._shards]
        self.offsets = np.cumsum([0] + self._shard_n).astype(np.int64)
        self.num_rows = int(self.offsets[-1])
        self.node_ids = np.concatenate(
            [self._shard_node_ids(p) for p in range(self.num_shards)]
        ) if self.num_rows else np.empty(0, np.uint64)
        # raw per-shard out-adjacency: (counts, dst_ids, w_f64, types)
        self._raw = [
            self._fetch_rows(p, np.arange(self._shard_n[p], dtype=np.int64))
            for p in range(self.num_shards)
        ]
        self.stats["rows_fetched"] = self.num_rows
        self.epoch_pin = self._read_epochs()
        self._build()

    # -- per-shard data plane -------------------------------------------

    def _shard_node_ids(self, p: int) -> np.ndarray:
        sh = self._shards[p]
        if not hasattr(sh, "call"):
            return np.asarray(sh.node_ids, np.uint64)
        n = self._shard_n[p]
        chunks = []
        for lo in range(0, n, self.rows_per_call):
            rows = np.arange(
                lo, min(lo + self.rows_per_call, n), dtype=np.int64
            )
            chunks.append(np.asarray(sh.ids_by_rows(rows)[0], np.uint64))
        return (
            np.concatenate(chunks) if chunks else np.empty(0, np.uint64)
        )

    def _fetch_rows(self, p: int, rows: np.ndarray):
        """Out-adjacency export for `rows` of shard p: (counts i64,
        dst_ids u64, w f64, types i32), type-major per row — local array
        slices in-process, the ``edges_by_rows`` bulk verb on the wire
        (chunked; RemoteShard degrades to per-row fallback on old
        servers)."""
        sh = self._shards[p]
        if hasattr(sh, "call"):
            counts, dst, w, tt = [], [], [], []
            for lo in range(0, len(rows), self.rows_per_call):
                sub = rows[lo:lo + self.rows_per_call]
                c, d, ww, t = sh.edges_by_rows(sub, self.edge_types)
                counts.append(np.asarray(c, np.int64))
                dst.append(np.asarray(d, np.uint64))
                w.append(np.asarray(ww, np.float64))
                tt.append(np.asarray(t, np.int32))
            if not counts:
                return (np.empty(0, np.int64), np.empty(0, np.uint64),
                        np.empty(0, np.float64), np.empty(0, np.int32))
            return (np.concatenate(counts), np.concatenate(dst),
                    np.concatenate(w), np.concatenate(tt))
        types = (
            range(len(sh.adj)) if self.edge_types is None
            else [t for t in self.edge_types if 0 <= t < len(sh.adj)]
        )
        row_pos, dst, w, tt = [], [], [], []
        for t in types:
            c = sh.adj[t]
            lens = c.degrees(rows)
            idx = _ragged_take(c.indptr[rows].astype(np.int64), lens)
            row_pos.append(np.repeat(np.arange(len(rows), dtype=np.int64), lens))
            dst.append(np.asarray(c.dst[idx], np.uint64))
            w.append(np.asarray(c.w[idx], np.float64))
            tt.append(np.full(len(idx), t, np.int32))
        if not row_pos:
            return (np.zeros(len(rows), np.int64), np.empty(0, np.uint64),
                    np.empty(0, np.float64), np.empty(0, np.int32))
        row_pos = np.concatenate(row_pos)
        dst = np.concatenate(dst)
        w = np.concatenate(w)
        tt = np.concatenate(tt)
        # type-major per row, preserving within-type CSR order — the
        # same layout the edges_by_rows server arm ships
        order = np.lexsort((tt, row_pos))
        counts = np.bincount(row_pos, minlength=len(rows)).astype(np.int64)
        return counts, dst[order], w[order], tt[order]

    def _read_epochs(self) -> tuple:
        pins = []
        for sh in self._shards:
            if hasattr(sh, "call"):
                pins.append(int(sh.stats().get("graph_epoch", 0)))
            else:
                pins.append(int(getattr(sh, "graph_epoch", 0)))
        return tuple(pins)

    # -- derived edge partitions ----------------------------------------

    def _build(self):
        """Globalize the raw per-shard edge lists and partition by
        destination owner, each part lexsorted into canonical reduction
        order — (dst_local, src_node_id, type, weight_bits): every key
        is shard-count independent, so a row's segment reduces to the
        same bits no matter how the graph is partitioned."""
        srcs, dsts, ws, tts, src_ids = [], [], [], [], []
        for p in range(self.num_shards):
            counts, dst_ids, w, tt = self._raw[p]
            local = np.repeat(np.arange(len(counts), dtype=np.int64), counts)
            srcs.append(local + self.offsets[p])
            ids_p = self.node_ids[self.offsets[p]:self.offsets[p + 1]]
            src_ids.append(np.repeat(ids_p, counts))
            dsts.append(dst_ids)
            ws.append(w)
            tts.append(tt)
        src = np.concatenate(srcs) if srcs else np.empty(0, np.int64)
        src_id = (
            np.concatenate(src_ids) if src_ids else np.empty(0, np.uint64)
        )
        dst_id = np.concatenate(dsts) if dsts else np.empty(0, np.uint64)
        w = np.concatenate(ws) if ws else np.empty(0, np.float64)
        tt = np.concatenate(tts) if tts else np.empty(0, np.int32)
        # resolve destination rows from the PINNED id table (the facade's
        # lookup would chase post-publish state)
        id_order = np.argsort(self.node_ids, kind="stable")
        ids_sorted = self.node_ids[id_order]
        pos = np.searchsorted(ids_sorted, dst_id)
        pos = np.clip(pos, 0, max(len(ids_sorted) - 1, 0))
        if len(dst_id) and len(ids_sorted):
            found = ids_sorted[pos] == dst_id
            dst = np.where(found, id_order[pos], -1).astype(np.int64)
        else:
            found = np.zeros(len(dst_id), bool)
            dst = np.full(len(dst_id), -1, np.int64)
        self.stats["dropped_edges"] = int(len(dst_id) - found.sum())
        keep = dst >= 0
        src, src_id, dst, w, tt = (
            src[keep], src_id[keep], dst[keep], w[keep], tt[keep]
        )
        self.edge_src = src
        self.edge_dst = dst
        self.edge_src_id = src_id
        self.edge_w = w
        self.edge_tt = tt
        if self.symmetric:
            # undirected view: every edge also propagates dst → src
            src = np.concatenate([self.edge_src, self.edge_dst])
            dst = np.concatenate([self.edge_dst, self.edge_src])
            src_id = np.concatenate(
                [self.edge_src_id, self.node_ids[self.edge_dst]]
            )
            w = np.concatenate([self.edge_w, self.edge_w])
            tt = np.concatenate([self.edge_tt, self.edge_tt])
        self.num_edges = len(src)
        owner = np.searchsorted(self.offsets, dst, side="right") - 1
        self.boundary_edges = int(
            (owner != np.searchsorted(self.offsets, src, side="right") - 1)
            .sum()
        )
        # weighted out-degree sums in canonical (src, dst_id, type,
        # w_bits) order — the PageRank normalizer, bit-stable across
        # shard counts for the same reason the parts are
        dst_ids_all = self.node_ids[dst] if len(dst) else np.empty(0, np.uint64)
        o = np.lexsort((_f64_bits(w), tt, dst_ids_all, src))
        self.out_w = np.bincount(
            src[o], weights=w[o], minlength=self.num_rows
        ).astype(np.float64)
        self.parts = []
        for p in range(self.num_shards):
            sel = owner == p
            ps, pd, pid, pw, ptt = (
                src[sel], dst[sel], src_id[sel], w[sel], tt[sel]
            )
            dloc = pd - self.offsets[p]
            o = np.lexsort((_f64_bits(pw), ptt, pid, dloc))
            n_p = self._shard_n[p]
            dloc = dloc[o]
            indptr = np.searchsorted(dloc, np.arange(n_p + 1, dtype=np.int64))
            self.parts.append({
                "indptr": indptr.astype(np.int64),
                "dst_local": dloc.astype(np.int64),
                "src": ps[o].astype(np.int64),
                "w": pw[o],
                "tt": ptt[o].astype(np.int32),
            })
        # src-grouped out-rows CSR for incremental dirty propagation
        o = np.argsort(src, kind="stable")
        self._out_indptr = np.searchsorted(
            src[o], np.arange(self.num_rows + 1, dtype=np.int64)
        ).astype(np.int64)
        self._out_dst = dst[o].astype(np.int64)

    # -- incremental refresh --------------------------------------------

    def refresh_rows(self, mutated_global_rows: np.ndarray) -> None:
        """Re-read ONLY the mutated rows' adjacency from the (new-epoch)
        shards and rebuild the derived partitions — the data-plane half
        of ``rerun_incremental``. Raises ValueError if any shard's node
        count moved (the row space changed; callers fall back to a full
        engine rebuild)."""
        rows = np.unique(np.asarray(mutated_global_rows, np.int64))
        self._shards = list(self.graph.shards)
        for p, sh in enumerate(self._shards):
            if int(sh.num_nodes) != self._shard_n[p]:
                raise ValueError(
                    f"shard {p} node count moved "
                    f"({self._shard_n[p]} -> {int(sh.num_nodes)})"
                )
        for p in range(self.num_shards):
            local = rows[(rows >= self.offsets[p])
                         & (rows < self.offsets[p + 1])] - self.offsets[p]
            if len(local) == 0:
                continue
            counts, dst, w, tt = self._raw[p]
            new_c, new_d, new_w, new_t = self._fetch_rows(p, local)
            self.stats["rows_refetched"] += len(local)
            # ragged row splice: cut each mutated row's old slice out,
            # splice the refetched one in
            starts = np.concatenate(
                [[0], np.cumsum(counts, dtype=np.int64)]
            )
            keep = np.ones(int(starts[-1]), bool)
            keep[_ragged_take(starts[local], counts[local])] = False
            parts_d = [new_d, dst[keep]]
            parts_w = [new_w, w[keep]]
            parts_t = [new_t, tt[keep]]
            # rebuild type-major-per-row order over the merged list
            row_pos = np.concatenate([
                np.repeat(local, new_c),
                np.repeat(np.arange(len(counts), dtype=np.int64),
                          counts)[keep],
            ])
            d = np.concatenate(parts_d)
            ww = np.concatenate(parts_w)
            t = np.concatenate(parts_t)
            order = np.lexsort((t, row_pos))
            merged_counts = counts.copy()
            merged_counts[local] = new_c
            self._raw[p] = (
                merged_counts, d[order], ww[order], t[order]
            )
        self.epoch_pin = self._read_epochs()
        self._build()

    # -- reduction plane -------------------------------------------------

    def exchange(self, p: int, rows, keys, vals, mode: str):
        """Reduce one part's messages on the owning shard — remotely via
        ``frontier_exchange`` (deadline envelope + borrow-mode decode
        ride the normal call path) or in-process through the SAME
        ``reduce_messages``, so both transports agree bit-for-bit. Old
        servers answer unknown-op; the engine degrades that shard to the
        local path once and stays there (sticky)."""
        sh = self._shards[p]
        remote_ok = (
            hasattr(sh, "call")
            and self.exchange_mode != "local"
            and self._exchange_wire[p]
        )
        self.stats["exchange_bytes"] += len(rows) * _MSG_BYTES
        if remote_ok:
            try:
                u, v, k = sh.call(
                    "frontier_exchange",
                    [np.asarray(rows, np.int64),
                     np.asarray(keys, np.int64),
                     np.asarray(vals, np.float64), mode],
                )
                self.stats["exchange_calls"] += 1
                return (np.asarray(u, np.int64), np.asarray(v, np.float64),
                        np.asarray(k, np.int64))
            except RpcError as e:
                if "unknown op" not in str(e):
                    raise
                self._exchange_wire[p] = False  # sticky old-server degrade
        return reduce_messages(rows, keys, vals, mode)

    # -- kernels ---------------------------------------------------------

    def gather_edges(self, p: int, rows_local=None):
        """Message slots for part p: (msg_rows, edge_idx) covering the
        given local rows' in-edge segments (all rows when None). The
        edge index doubles as the exchange KEY — it encodes the part's
        canonical order, so subset, full, local and remote reductions
        all see identical per-row orderings."""
        part = self.parts[p]
        if rows_local is None:
            idx = np.arange(len(part["src"]), dtype=np.int64)
            return part["dst_local"], idx
        rows_local = np.asarray(rows_local, np.int64)
        starts = part["indptr"][rows_local]
        lens = part["indptr"][rows_local + 1] - starts
        idx = _ragged_take(starts, lens)
        return np.repeat(rows_local, lens), idx

    def contrib(self, p: int, edge_idx: np.ndarray, global_vec, weights):
        """Per-edge contribution weights[e] * frontier[src[e]] — the
        elementwise half of a BSP step. Host numpy by default; with
        device=True the multiply runs as f64 jax ops over the staged
        frontier (bit-identical to numpy on CPU; within
        FRONTIER_F64_RTOL on a TPU — dataflow/device.py)."""
        src = self.parts[p]["src"][edge_idx]
        w = weights[edge_idx]
        if self.device:
            from euler_tpu.dataflow import device as _device

            return _device.frontier_contrib(w, global_vec, src)
        return w * np.asarray(global_vec, np.float64)[src]

    def by_id(self, values: np.ndarray):
        """(node_ids ascending, values) — the shard-count-independent
        presentation every parity test compares on."""
        order = np.argsort(self.node_ids, kind="stable")
        return self.node_ids[order], np.asarray(values)[order]
