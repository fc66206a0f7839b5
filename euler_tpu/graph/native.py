"""ctypes binding for the native C++ graph engine (cpp/graph_engine.cc).

Builds the shared library on demand (g++, cached next to the source) and
exposes `NativeGraphStore`, a GraphStore drop-in whose hot queries (global
sampling, neighbor sampling, dense features, walks) run in C++ over mmapped
shard files; everything else falls back to the numpy store.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

from euler_tpu.graph.meta import GraphMeta
from euler_tpu.graph.store import GraphStore

_CPP_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "cpp")
_SO_PATH = os.path.abspath(os.path.join(_CPP_DIR, "libeuler_tpu_engine.so"))
_SRC_PATH = os.path.abspath(os.path.join(_CPP_DIR, "graph_engine.cc"))

_lib = None


def build_engine(force: bool = False) -> str:
    """Compile the engine .so if missing or stale; returns its path.

    force=True rebuilds from cpp/graph_engine.cc whatever is on disk —
    the chip smoke and the device bench use it so they never dlopen a
    library some other machine built. The compiler writes to a
    per-process temp name and the result is renamed into place, so a
    concurrent starter can never dlopen a half-written library."""
    if (
        not force
        and os.path.exists(_SO_PATH)
        and os.path.getmtime(_SO_PATH) >= os.path.getmtime(_SRC_PATH)
    ):
        return _SO_PATH
    tmp = f"{_SO_PATH}.tmp-{os.getpid()}"
    cmd = [
        "g++",
        "-O3",
        "-std=c++17",
        "-shared",
        "-fPIC",
        "-pthread",
        _SRC_PATH,
        "-o",
        tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, _SO_PATH)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return _SO_PATH


def _u64p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def _i64p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _i32p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _f32p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _u8p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _types_arr(edge_types):
    return np.ascontiguousarray(
        [] if edge_types is None else list(edge_types), dtype=np.int32
    )


def _load_lib():
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(build_engine())
    c = ctypes
    u64p, i64p = c.POINTER(c.c_uint64), c.POINTER(c.c_int64)
    i32p, f32p, u8p = (
        c.POINTER(c.c_int32),
        c.POINTER(c.c_float),
        c.POINTER(c.c_uint8),
    )
    lib.etpu_load.restype = c.c_void_p
    lib.etpu_load.argtypes = [c.c_char_p, c.c_int64, c.c_int64]
    lib.etpu_free.argtypes = [c.c_void_p]
    lib.etpu_num_nodes.restype = c.c_int64
    lib.etpu_num_nodes.argtypes = [c.c_void_p]
    lib.etpu_num_edges.restype = c.c_int64
    lib.etpu_num_edges.argtypes = [c.c_void_p]
    lib.etpu_lookup.argtypes = [c.c_void_p, u64p, c.c_int64, i64p]
    lib.etpu_sample_node.argtypes = [
        c.c_void_p, c.c_int64, c.c_int32, c.c_uint64, u64p,
    ]
    lib.etpu_sample_edge.argtypes = [
        c.c_void_p, c.c_int64, c.c_int32, c.c_uint64, u64p,
    ]
    lib.etpu_sample_neighbor.argtypes = [
        c.c_void_p, u64p, c.c_int64, i32p, c.c_int64, c.c_int64,
        c.c_uint64, u64p, f32p, i32p, u8p, i64p,
    ]
    lib.etpu_get_dense.argtypes = [
        c.c_void_p, u64p, c.c_int64, c.c_int64, c.c_int64, f32p,
    ]
    lib.etpu_random_walk.argtypes = [
        c.c_void_p, u64p, c.c_int64, i32p, c.c_int64, c.c_int64,
        c.c_uint64, u64p,
    ]
    lib.etpu_sample_fanout.argtypes = [
        c.c_void_p, u64p, c.c_int64, i32p, c.c_int64, i64p, c.c_int64,
        c.c_uint64, u64p, i64p, f32p, i32p, u8p,
    ]
    lib.etpu_get_dense_rows.argtypes = [
        c.c_void_p, i64p, c.c_int64, c.c_int64, c.c_int64, f32p,
    ]
    lib.etpu_stats.argtypes = [c.c_void_p, u64p]
    lib.etpu_reset_stats.argtypes = [c.c_void_p]
    lib.etpu_degree_sum.argtypes = [
        c.c_void_p, u64p, c.c_int64, i32p, c.c_int64, c.c_uint8, i64p,
    ]
    lib.etpu_full_neighbor.argtypes = [
        c.c_void_p, u64p, c.c_int64, i32p, c.c_int64, c.c_int64,
        c.c_uint8, c.c_int32, u64p, f32p, i32p, u8p, i64p,
    ]
    lib.etpu_varlen_lens.argtypes = [
        c.c_void_p, i64p, c.c_int64, c.c_uint8, c.c_int32, c.c_int64, i64p,
    ]
    lib.etpu_varlen_gather_u64.argtypes = [
        c.c_void_p, i64p, c.c_int64, c.c_uint8, c.c_int32, c.c_int64,
        c.c_int64, u64p, u8p,
    ]
    lib.etpu_varlen_gather_u8.argtypes = [
        c.c_void_p, i64p, c.c_int64, c.c_uint8, c.c_int32, c.c_int64,
        c.c_int64, u8p,
    ]
    lib.etpu_layerwise.argtypes = [
        c.c_void_p, u64p, c.c_int64, i32p, c.c_int64, c.c_int64,
        c.c_uint64, u64p, f32p, u8p,
    ]
    lib.etpu_sample_neighbor_dir.argtypes = [
        c.c_void_p, u64p, c.c_int64, i32p, c.c_int64, c.c_int64,
        c.c_uint8, c.c_uint64, u64p, f32p, i32p, u8p, i64p,
    ]
    lib.etpu_sample_neighbor_rows.argtypes = [
        c.c_void_p, u64p, c.c_int64, i32p, c.c_int64, c.c_int64,
        c.c_uint64, u64p, u8p, i64p,
    ]
    _lib = lib
    return lib


# per-op counters exported by the engine (Op enum order in graph_engine.cc)
STAT_OPS = (
    "lookup",
    "sample_node",
    "sample_edge",
    "sample_neighbor",
    "get_dense",
    "random_walk",
    "sample_fanout",
    "full_neighbor",
    "degree_sum",
    "varlen_feature",
    "layerwise",
)


def engine_available() -> bool:
    try:
        _load_lib()
        return True
    except Exception:
        return False


class NativeGraphStore(GraphStore):
    """GraphStore whose hot paths run in the C++ engine.

    Loads the same on-disk tensor dir twice: mmapped numpy views (for the
    cold paths and feature metadata) + the C++ store (hot queries).
    """

    def __init__(self, meta: GraphMeta, arrays, part: int, directory: str):
        super().__init__(meta, arrays, part)
        lib = _load_lib()
        self._lib = lib
        self._h = lib.etpu_load(
            directory.encode(), meta.num_node_types, meta.num_edge_types
        )
        if not self._h:
            raise RuntimeError(f"native engine failed to load {directory}")

    def __del__(self):
        if getattr(self, "_h", None) and self._lib is not None:
            self._lib.etpu_free(self._h)
            self._h = None

    # -- hot paths -------------------------------------------------------

    def _seed(self, rng) -> int:
        if rng is None:
            rng = np.random.default_rng()
        return int(rng.integers(0, 2**63 - 1))

    def lookup(self, ids):
        ids = np.ascontiguousarray(ids, dtype=np.uint64)
        rows = np.empty(len(ids), dtype=np.int64)
        self._lib.etpu_lookup(
            ctypes.c_void_p(self._h),
            _u64p(ids),
            len(ids),
            rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        return rows

    def sample_node(self, count, node_type=-1, rng=None):
        out = np.empty(count, dtype=np.uint64)
        self._lib.etpu_sample_node(
            ctypes.c_void_p(self._h),
            count,
            ctypes.c_int32(node_type),
            ctypes.c_uint64(self._seed(rng)),
            _u64p(out),
        )
        return out

    def sample_edge(self, count, edge_type=-1, rng=None):
        out = np.empty((count, 3), dtype=np.uint64)
        self._lib.etpu_sample_edge(
            ctypes.c_void_p(self._h),
            count,
            ctypes.c_int32(edge_type),
            ctypes.c_uint64(self._seed(rng)),
            _u64p(out),
        )
        return out

    def sample_neighbor(self, ids, edge_types=None, count=10, rng=None, in_edges=False):
        if in_edges and not self.inadj:  # no in-CSRs on this shard
            return super().sample_neighbor(ids, edge_types, count, rng, in_edges)
        ids = np.ascontiguousarray(ids, dtype=np.uint64)
        n = len(ids)
        types = _types_arr(edge_types)
        nbr = np.empty((n, count), dtype=np.uint64)
        w = np.empty((n, count), dtype=np.float32)
        tt = np.empty((n, count), dtype=np.int32)
        mask = np.empty((n, count), dtype=np.uint8)
        eidx = np.empty((n, count), dtype=np.int64)
        self._lib.etpu_sample_neighbor_dir(
            ctypes.c_void_p(self._h),
            _u64p(ids),
            n,
            _i32p(types),
            len(types),
            count,
            ctypes.c_uint8(1 if in_edges else 0),
            ctypes.c_uint64(self._seed(rng)),
            _u64p(nbr),
            _f32p(w),
            _i32p(tt),
            _u8p(mask),
            _i64p(eidx),
        )
        return nbr, w, tt, mask.astype(bool), eidx

    def sample_neighbor_rows(self, ids, edge_types=None, count=10, rng=None):
        """Lean leaf draw: (nbr, mask, local_rows) with rows pre-resolved
        from the engine's load-time dst_row cache (-1 for off-shard dsts).
        No weight/type/edge-id outputs — the distributed lean fanout never
        needs them and they dominate the coordinator's byte-shuffling."""
        ids = np.ascontiguousarray(ids, dtype=np.uint64)
        n = len(ids)
        types = _types_arr(edge_types)
        nbr = np.empty((n, count), dtype=np.uint64)
        mask = np.empty((n, count), dtype=np.uint8)
        rows = np.empty((n, count), dtype=np.int64)
        self._lib.etpu_sample_neighbor_rows(
            ctypes.c_void_p(self._h),
            _u64p(ids),
            n,
            _i32p(types),
            len(types),
            count,
            ctypes.c_uint64(self._seed(rng)),
            _u64p(nbr),
            _u8p(mask),
            _i64p(rows),
        )
        return nbr, mask.astype(bool), rows

    def degree_sum(self, ids, edge_types=None, in_edges=False):
        if in_edges and not self.inadj:
            return super().degree_sum(ids, edge_types, in_edges)
        ids = np.ascontiguousarray(ids, dtype=np.uint64)
        types = _types_arr(edge_types)
        out = np.empty(len(ids), dtype=np.int64)
        self._lib.etpu_degree_sum(
            ctypes.c_void_p(self._h),
            _u64p(ids),
            len(ids),
            _i32p(types),
            len(types),
            ctypes.c_uint8(1 if in_edges else 0),
            _i64p(out),
        )
        return out

    def get_full_neighbor(
        self, ids, edge_types=None, max_degree=None, in_edges=False, sort_by=None
    ):
        """Padded full adjacency served from the engine (node.h:82-112).

        sort_by: None (storage order) | 'id' | 'weight' (desc); sorting
        happens per row inside the C++ kernel.
        """
        if in_edges and not self.inadj:
            return super().get_full_neighbor(
                ids, edge_types, max_degree, in_edges, sort_by
            )
        ids = np.ascontiguousarray(ids, dtype=np.uint64)
        n = len(ids)
        if max_degree is None:
            degs = self.degree_sum(ids, edge_types, in_edges)
            cap = int(degs.max(initial=0))
        else:
            cap = int(max_degree)
        cap = max(cap, 1)
        types = _types_arr(edge_types)
        sort_mode = {None: 0, "id": 1, "weight": 2}[sort_by]
        nbr = np.empty((n, cap), dtype=np.uint64)
        w = np.empty((n, cap), dtype=np.float32)
        tt = np.empty((n, cap), dtype=np.int32)
        mask = np.empty((n, cap), dtype=np.uint8)
        eidx = np.empty((n, cap), dtype=np.int64)
        self._lib.etpu_full_neighbor(
            ctypes.c_void_p(self._h),
            _u64p(ids),
            n,
            _i32p(types),
            len(types),
            cap,
            ctypes.c_uint8(1 if in_edges else 0),
            ctypes.c_int32(sort_mode),
            _u64p(nbr),
            _f32p(w),
            _i32p(tt),
            _u8p(mask),
            _i64p(eidx),
        )
        return nbr, w, tt, mask.astype(bool), eidx

    def sample_neighbor_layerwise(
        self, batch_ids, edge_types=None, count=128, rng=None
    ):
        """LADIES-style layer sampling in one engine call."""
        batch_ids = np.ascontiguousarray(batch_ids, dtype=np.uint64)
        n = len(batch_ids)
        types = _types_arr(edge_types)
        layer = np.empty(count, dtype=np.uint64)
        adj = np.empty((n, count), dtype=np.float32)
        lmask = np.empty(count, dtype=np.uint8)
        self._lib.etpu_layerwise(
            ctypes.c_void_p(self._h),
            _u64p(batch_ids),
            n,
            _i32p(types),
            len(types),
            count,
            ctypes.c_uint64(self._seed(rng)),
            _u64p(layer),
            _f32p(adj),
            _u8p(lmask),
        )
        return layer, adj, lmask.astype(bool)

    # -- variable-length features (sparse u64 / binary bytes) ------------

    def _varlen_lens(self, rows, node: bool, kind: int, fid: int):
        rows = np.ascontiguousarray(rows, dtype=np.int64)
        lens = np.empty(len(rows), dtype=np.int64)
        self._lib.etpu_varlen_lens(
            ctypes.c_void_p(self._h),
            _i64p(rows),
            len(rows),
            ctypes.c_uint8(1 if node else 0),
            ctypes.c_int32(kind),
            fid,
            _i64p(lens),
        )
        return lens

    def _varlen_by_rows(self, rows, names, kind, node: bool, max_len=None):
        from euler_tpu.graph.store import SPARSE

        if kind != SPARSE:  # binary handled by get_*_binary_feature below
            return super()._varlen_by_rows(rows, names, kind, node, max_len)
        rows = np.ascontiguousarray(rows, dtype=np.int64)
        out = []
        for nm in names:
            spec = self.meta.feature_spec(nm, node=node)
            lens = self._varlen_lens(rows, node, 0, spec.fid)
            cap = int(max_len) if max_len else max(int(lens.max(initial=0)), 1)
            vals = np.empty((len(rows), cap), dtype=np.uint64)
            mask = np.empty((len(rows), cap), dtype=np.uint8)
            self._lib.etpu_varlen_gather_u64(
                ctypes.c_void_p(self._h),
                _i64p(rows),
                len(rows),
                ctypes.c_uint8(1 if node else 0),
                ctypes.c_int32(0),
                spec.fid,
                cap,
                _u64p(vals),
                _u8p(mask),
            )
            out.append((vals, mask.astype(bool)))
        return out

    def _binary_by_rows(self, rows, names, node: bool):
        rows = np.ascontiguousarray(rows, dtype=np.int64)
        out = []
        for nm in names:
            spec = self.meta.feature_spec(nm, node=node)
            lens = self._varlen_lens(rows, node, 1, spec.fid)
            cap = max(int(lens.max(initial=0)), 1)
            vals = np.empty((len(rows), cap), dtype=np.uint8)
            self._lib.etpu_varlen_gather_u8(
                ctypes.c_void_p(self._h),
                _i64p(rows),
                len(rows),
                ctypes.c_uint8(1 if node else 0),
                ctypes.c_int32(1),
                spec.fid,
                cap,
                _u8p(vals),
            )
            out.append(
                [bytes(vals[i, : lens[i]]) for i in range(len(rows))]
            )
        return out

    def get_binary_feature(self, ids, names):
        return self._binary_by_rows(self.lookup(ids), names, node=True)

    def get_edge_binary_feature(self, edge_ids, names):
        return self._binary_by_rows(self._edge_rows(edge_ids), names, node=False)

    def get_dense_feature(self, ids, names):
        ids = np.ascontiguousarray(ids, dtype=np.uint64)
        cols = []
        for nm in names:
            spec = self.meta.feature_spec(nm, node=True)
            out = np.empty((len(ids), spec.dim), dtype=np.float32)
            self._lib.etpu_get_dense(
                ctypes.c_void_p(self._h),
                _u64p(ids),
                len(ids),
                spec.fid,
                spec.dim,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            )
            cols.append(out)
        return (
            np.concatenate(cols, axis=1)
            if cols
            else np.zeros((len(ids), 0), np.float32)
        )

    def fanout_with_rows(self, ids, edge_types, counts, rng=None):
        """Fused multi-hop fanout in one engine call.

        Returns (hop_ids, hop_w, hop_tt, hop_mask, hop_rows) — lists over
        hops 0..len(counts), hop i flat with n*prod(counts[:i]) entries.
        hop_rows are local store rows (-1 invalid), ready for the device
        feature cache without a second lookup pass.
        """
        ids = np.ascontiguousarray(ids, dtype=np.uint64)
        n = len(ids)
        types = _types_arr(edge_types)
        counts_arr = np.ascontiguousarray(counts, dtype=np.int64)
        widths = [n]
        for c in counts:
            widths.append(widths[-1] * int(c))
        total = int(np.sum(widths))
        ids_out = np.empty(total, dtype=np.uint64)
        rows_out = np.empty(total, dtype=np.int64)
        w_out = np.empty(total, dtype=np.float32)
        tt_out = np.empty(total, dtype=np.int32)
        mask_out = np.empty(total, dtype=np.uint8)
        ct = ctypes
        self._lib.etpu_sample_fanout(
            ct.c_void_p(self._h),
            _u64p(ids),
            n,
            types.ctypes.data_as(ct.POINTER(ct.c_int32)),
            len(types),
            counts_arr.ctypes.data_as(ct.POINTER(ct.c_int64)),
            len(counts),
            ct.c_uint64(self._seed(rng)),
            _u64p(ids_out),
            rows_out.ctypes.data_as(ct.POINTER(ct.c_int64)),
            w_out.ctypes.data_as(ct.POINTER(ct.c_float)),
            tt_out.ctypes.data_as(ct.POINTER(ct.c_int32)),
            mask_out.ctypes.data_as(ct.POINTER(ct.c_uint8)),
        )
        from euler_tpu.graph.store import split_hops

        ids_h, w_h, tt_h, mask_h, rows_h = split_hops(
            n, counts, ids_out, w_out, tt_out, mask_out, rows_out
        )
        return (
            ids_h,
            w_h,
            tt_h,
            [m.astype(bool) for m in mask_h],
            rows_h,
        )

    def get_dense_by_rows(self, rows, names):
        """Dense features by pre-resolved rows (-1 → zeros); skips lookup."""
        rows = np.ascontiguousarray(rows, dtype=np.int64)
        cols = []
        for nm in names:
            spec = self.meta.feature_spec(nm, node=True)
            out = np.empty((len(rows), spec.dim), dtype=np.float32)
            self._lib.etpu_get_dense_rows(
                ctypes.c_void_p(self._h),
                rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                len(rows),
                spec.fid,
                spec.dim,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            )
            cols.append(out)
        return (
            np.concatenate(cols, axis=1)
            if cols
            else np.zeros((len(rows), 0), np.float32)
        )

    def op_stats(self) -> dict:
        """Per-op (calls, total_ms) timing counters from the engine."""
        out = np.zeros(2 * len(STAT_OPS), dtype=np.uint64)
        self._lib.etpu_stats(
            ctypes.c_void_p(self._h),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        )
        k = len(STAT_OPS)
        return {
            name: {"calls": int(out[i]), "ms": float(out[k + i]) / 1e6}
            for i, name in enumerate(STAT_OPS)
        }

    def reset_op_stats(self):
        self._lib.etpu_reset_stats(ctypes.c_void_p(self._h))

    def random_walk(self, ids, edge_types=None, walk_len=3, p=1.0, q=1.0, rng=None):
        if p != 1.0 or q != 1.0:  # node2vec bias → numpy path
            return super().random_walk(ids, edge_types, walk_len, p, q, rng)
        ids = np.ascontiguousarray(ids, dtype=np.uint64)
        types = _types_arr(edge_types)
        out = np.empty((len(ids), walk_len + 1), dtype=np.uint64)
        self._lib.etpu_random_walk(
            ctypes.c_void_p(self._h),
            _u64p(ids),
            len(ids),
            types.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            len(types),
            walk_len,
            ctypes.c_uint64(self._seed(rng)),
            _u64p(out),
        )
        return out
