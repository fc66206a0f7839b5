"""Elastic online resharding: live shard split/merge with fenced cutover.

`ReshardCoordinator` grows or shrinks a running cluster P -> P' under
concurrent writer / trainer / serving traffic, as a durable phase
machine (spirit of the elastic-consistent-hashing line of work,
arXiv 2112.01075 — minimize rows moved, never stop the world):

  plan      compute the `id % P` -> `id % P'` row-movement schedule
            over lcm(P, P') residue classes — residues whose shard
            number is unchanged never move.
  copy      bulk-move state via the existing replication snapshot
            payload (`wal_ship want="snapshot"`), re-CRC'd by the
            codec frame on every blob.
  catch_up  tail each source's WAL suffix over `wal_ship` until the
            total lag is under EULER_TPU_RESHARD_LAG bytes.
  cutover   fence every source (term-bumped, durable marker), drain
            the fencing-window tail, replay it, repartition to P',
            boot destination shards at generation G+1 (invisible to
            clients), then atomically publish the new topology through
            the registry — `connect()`'s topology watch re-routes and
            read caches fully flush on the bumped topology epoch.
  abort     any pre-commit failure (or a resumed post-kill coordinator
            that finds the topology unflipped) unfences the sources,
            kills half-born destinations and removes their state:
            zero data loss, the old topology keeps serving.

Every phase transition is appended to a CRC'd JSONL phase log
(`<state>/phases.jsonl`, fsync'd) so a kill -9'd coordinator can be
re-run with `--resume`: if the registry topology already flipped the
reshard rolls forward to done; otherwise it rolls back to aborted —
never a mixed state. The registry `set_topology` rename is the single
commit point.

Destination boot recipe: the post-tail repartitioned arrays are the
pristine base (`part_<d>` tensor dirs + meta at P'), staged-but-
unpublished source records are re-scattered into each destination's
WAL (same batch keys, so post-cutover client retries dedupe), and a
seeded snapshot carries the merged applied-key window with every
publish result sanitized to the full-flush sentinel.

Bit-parity contract: the resharded cluster equals a from-scratch
`build_from_json` at the new shard count over the canonically-ordered
equivalent graph.json — pinned by tests/test_reshard.py through
`cluster_signature` (repartition to one shard + hash, order-free).

The module also carries the minimal load-driven autoscaling policy:
`propose_scaling` turns serving/retrieval `server_stats` and per-shard
store/WAL pressure into typed `Recommendation`s (scale replicas,
split/merge shards); `AutoscaleLoop` polls it on an interval.

CLI:
    python -m euler_tpu.distributed.reshard \
        --registry /path/reg --shards 2 --to 3 --state /path/reshard
    (add --resume after a coordinator crash, --abort to roll back)

Knobs:
    EULER_TPU_RESHARD_LAG            catch-up exit lag, bytes (65536)
    EULER_TPU_RESHARD_CATCHUP_S      catch-up budget, seconds (120)
    EULER_TPU_RESHARD_FENCE_TIMEOUT_S  per-source fence deadline (30)
    EULER_TPU_RESHARD_BOOT_TIMEOUT_S   destination boot deadline (60)
    EULER_TPU_RESHARD_KILL_AT        chaos: SIGKILL self right after
                                     this phase record lands (tests)
    EULER_TPU_RESHARD_SPLIT_WAL_MB   autoscaler split threshold (64)
    EULER_TPU_RESHARD_SPLIT_ROWS     autoscaler split threshold (1e6)
    EULER_TPU_AUTOSCALE_QPS_HIGH     per-replica scale-up qps (100)
    EULER_TPU_AUTOSCALE_QPS_LOW      per-replica scale-down qps (10)
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import hashlib
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import zlib

import numpy as np

from euler_tpu.graph import format as tformat
from euler_tpu.graph import wal as walmod
from euler_tpu.graph.builder import _csr_adjacency
from euler_tpu.distributed.errors import RpcError
from euler_tpu.graph.delta import DeltaStore, _segment_arange
from euler_tpu.graph.meta import DENSE, SPARSE, GraphMeta

# every verb this client surface sends — graftlint's wire-protocol
# checker proves it is a subset of the server's HANDLED_VERBS, and
# tests/test_wire_parity.py pins the runtime twin
WIRE_VERBS = frozenset(
    {
        "fence",
        "get_meta",
        "ping",
        "publish_epoch",
        "stats",
        "unfence",
        "wal_pos",
        "wal_ship",
    }
)


# ---------------------------------------------------------------------------
# movement schedule


def plan_moves(num_shards: int, new_num_shards: int) -> list[dict]:
    """Row-movement schedule for `id % P` -> `id % P'`.

    One entry per residue class modulo lcm(P, P'): ids congruent to
    `residue` live on shard `src` today and `dst` afterwards; `moved`
    is False exactly when the shard number is unchanged, so the
    schedule is movement-minimal for modulo partitioning (only
    residues whose home actually changes ship any bytes)."""
    p, p2 = int(num_shards), int(new_num_shards)
    if p < 1 or p2 < 1:
        raise ValueError(f"shard counts must be >= 1, got {p} -> {p2}")
    lcm = math.lcm(p, p2)
    return [
        {
            "residue": r,
            "src": r % p,
            "dst": r % p2,
            "moved": (r % p) != (r % p2),
        }
        for r in range(lcm)
    ]


# ---------------------------------------------------------------------------
# repartitioning (the bulk data plane, pure numpy, bit-parity with builder)


def _gather_ragged(indptr, values, rows):
    """Gather ragged rows (CSR indptr/values) at `rows`, preserving
    per-row order — the vectorized `np.repeat + segment-arange` idiom
    from graph/delta.py."""
    indptr = np.asarray(indptr, dtype=np.int64)
    rows = np.asarray(rows, dtype=np.int64)
    counts = indptr[rows + 1] - indptr[rows]
    idx = np.repeat(indptr[rows], counts) + _segment_arange(counts)
    new_indptr = np.zeros(len(rows) + 1, dtype=np.int64)
    np.cumsum(counts, out=new_indptr[1:])
    return new_indptr, np.asarray(values)[idx]


def _concat_feature_planes(parts, specs, prefix):
    """Splice per-part feature arrays into global planes keyed by the
    on-disk array base name. Dense -> ("dense", matrix); ragged ->
    (kind, indptr, values) with part offsets folded in."""
    out = {}
    for kind, fid in sorted({(s.kind, s.fid) for s in specs.values()}):
        if kind == DENSE:
            name = f"{prefix}_dense_{fid}"
            out[name] = (
                "dense",
                np.vstack([np.asarray(p[name], dtype=np.float32) for p in parts]),
            )
            continue
        tag = "sparse" if kind == SPARSE else "bin"
        base = f"{prefix}_{tag}_{fid}"
        ips = [np.asarray(p[f"{base}_indptr"], dtype=np.int64) for p in parts]
        vals = [np.asarray(p[f"{base}_values"]) for p in parts]
        offs = np.concatenate([[0], np.cumsum([len(v) for v in vals])])
        indptr = np.concatenate(
            [np.zeros(1, dtype=np.int64)]
            + [ip[1:] + off for ip, off in zip(ips, offs[:-1])]
        )
        out[base] = (kind, indptr, np.concatenate(vals))
    return out


def repartition_arrays(
    meta: GraphMeta, parts: list[dict], new_p: int
) -> tuple[GraphMeta, list[dict]]:
    """Repartition a cluster's array dicts from P = len(parts) shards
    to `new_p`, bit-identical to `build_from_json` at `new_p` over the
    canonically-ordered equivalent graph.json (nodes by id; edges by
    (src, dst, type, weight-bits) — unique (src, dst, type) triples
    make that ordering total).

    Nodes land on `id % new_p`; out-edges on `src % new_p`; in-edges on
    `dst % new_p` (builder partitioning), each dest preserving canonical
    order, so per-dest arrays match the builder's input-order contract.
    Returns (meta_at_new_p, per-dest array dicts); the fresh meta
    recomputes per-dest weight sums with the builder's exact f64
    accumulation order."""
    new_p = int(new_p)
    if new_p < 1:
        raise ValueError(f"new_p must be >= 1, got {new_p}")
    netypes = int(meta.num_edge_types)

    nid = np.concatenate([np.asarray(p["node_ids"], dtype=np.uint64) for p in parts])
    ntt = np.concatenate([np.asarray(p["node_types"], dtype=np.int32) for p in parts])
    nw = np.concatenate(
        [np.asarray(p["node_weights"], dtype=np.float32) for p in parts]
    )
    esrc = np.concatenate([np.asarray(p["edge_src"], dtype=np.uint64) for p in parts])
    edst = np.concatenate([np.asarray(p["edge_dst"], dtype=np.uint64) for p in parts])
    ett = np.concatenate([np.asarray(p["edge_types"], dtype=np.int32) for p in parts])
    ew = np.concatenate(
        [np.asarray(p["edge_weights"], dtype=np.float32) for p in parts]
    )

    node_feats = _concat_feature_planes(parts, meta.node_features, "nf")
    edge_feats = _concat_feature_planes(parts, meta.edge_features, "ef")

    num_labels = len(meta.graph_labels)
    glabel_global = []
    for i in range(num_labels):
        segs = [
            np.asarray(p["glabel_nodes"], dtype=np.uint64)[
                int(p["glabel_indptr"][i]) : int(p["glabel_indptr"][i + 1])
            ]
            for p in parts
        ]
        glabel_global.append(np.concatenate(segs))

    # canonical global edge order: lexsort is last-key-primary, so src
    # is the primary key — partitioned by src this reproduces each
    # dest's builder input order
    wbits = np.ascontiguousarray(ew).view(np.uint32)
    perm = np.lexsort((wbits, ett, edst, esrc))
    esrc_s, edst_s = esrc[perm], edst[perm]
    ett_s, ew_s = ett[perm], ew[perm]

    meta2 = GraphMeta.from_dict(meta.to_dict())
    meta2.num_partitions = new_p
    meta2.node_weight_sums = []
    meta2.edge_weight_sums = []

    n_res = (nid % np.uint64(new_p)).astype(np.int64)
    o_res = (esrc_s % np.uint64(new_p)).astype(np.int64)
    i_res = (edst_s % np.uint64(new_p)).astype(np.int64)
    out_parts = []
    for d in range(new_p):
        rows = np.flatnonzero(n_res == d)
        rows = rows[np.argsort(nid[rows], kind="stable")]
        node_ids_d = nid[rows]
        osel = o_res == d
        out_pos = np.flatnonzero(osel)
        in_pos = np.flatnonzero(i_res == d)
        arrays: dict[str, np.ndarray] = {
            "node_ids": node_ids_d,
            "node_types": ntt[rows],
            "node_weights": nw[rows],
            "edge_src": esrc_s[out_pos],
            "edge_dst": edst_s[out_pos],
            "edge_types": ett_s[out_pos],
            "edge_weights": ew_s[out_pos],
        }
        arrays.update(
            _csr_adjacency(
                node_ids_d,
                esrc_s[out_pos],
                edst_s[out_pos],
                ett_s[out_pos],
                ew_s[out_pos],
                np.arange(len(out_pos), dtype=np.int64),
                netypes,
                "adj",
            )
        )
        # in-edge eidx points at the LOCAL out-edge row when this dest
        # also owns the edge's src half, else -1 (builder contract)
        local_out = np.cumsum(osel) - 1
        in_eidx = np.where(osel[in_pos], local_out[in_pos], -1).astype(np.int64)
        arrays.update(
            _csr_adjacency(
                node_ids_d,
                edst_s[in_pos],
                esrc_s[in_pos],
                ett_s[in_pos],
                ew_s[in_pos],
                in_eidx,
                netypes,
                "inadj",
            )
        )
        for base, plane in node_feats.items():
            if plane[0] == "dense":
                arrays[base] = plane[1][rows]
            else:
                ip, vals = _gather_ragged(plane[1], plane[2], rows)
                arrays[f"{base}_indptr"] = ip
                arrays[f"{base}_values"] = vals
        orig = perm[out_pos]  # feature rows ride with the src-owned half
        for base, plane in edge_feats.items():
            if plane[0] == "dense":
                arrays[base] = plane[1][orig]
            else:
                ip, vals = _gather_ragged(plane[1], plane[2], orig)
                arrays[f"{base}_indptr"] = ip
                arrays[f"{base}_values"] = vals
        gl_indptr = np.zeros(num_labels + 1, dtype=np.int64)
        gl_flat = []
        for i in range(num_labels):
            g = glabel_global[i]
            mine = np.sort(g[(g % np.uint64(new_p)).astype(np.int64) == d])
            gl_flat.append(mine)
            gl_indptr[i + 1] = gl_indptr[i] + len(mine)
        arrays["glabel_indptr"] = gl_indptr
        arrays["glabel_nodes"] = (
            np.concatenate(gl_flat) if gl_flat else np.zeros(0, dtype=np.uint64)
        )

        nw_sum = np.zeros(meta.num_node_types, dtype=np.float64)
        np.add.at(
            nw_sum, arrays["node_types"], arrays["node_weights"].astype(np.float64)
        )
        ew_sum = np.zeros(netypes, dtype=np.float64)
        np.add.at(
            ew_sum, arrays["edge_types"], arrays["edge_weights"].astype(np.float64)
        )
        meta2.node_weight_sums.append(nw_sum.tolist())
        meta2.edge_weight_sums.append(ew_sum.tolist())
        out_parts.append(arrays)
    return meta2, out_parts


def cluster_signature(meta: GraphMeta, parts: list[dict]) -> str:
    """Shard-count-independent content hash: repartition to one shard
    (canonical order) and digest every array's name/dtype/shape/bytes.
    Equal signatures <=> bit-identical logical graphs — the reshard
    correctness oracle."""
    _m1, one = repartition_arrays(meta, parts, 1)
    h = hashlib.sha256()
    for name in sorted(one[0]):
        a = np.ascontiguousarray(one[0][name])
        h.update(name.encode())
        h.update(b"\x00")
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def load_cluster(data_dir: str) -> tuple[GraphMeta, list[dict]]:
    """Read a convert_json-layout dir (euler.meta.json + part_<p>/)
    back into (meta, array dicts) — the handle tests and the bench
    hand to `cluster_signature`."""
    meta = GraphMeta.load(data_dir)
    parts = [
        dict(tformat.read_arrays(os.path.join(data_dir, f"part_{p}"), mmap=False))
        for p in range(meta.num_partitions)
    ]
    return meta, parts


# ---------------------------------------------------------------------------
# durable phase log


class _PhaseLog:
    """Append-only CRC'd JSONL — the coordinator's durable memory.

    Each line is `<json>\\t<crc32 hex>`; append is write+flush+fsync so
    a phase record is on disk before the phase's side effects begin.
    Loading stops at the first torn/corrupt line (a kill mid-append
    loses only that line, mirroring the WAL's torn-tail discipline)."""

    def __init__(self, path: str):
        self.path = path
        self._seq = len(self._repair())

    def _repair(self) -> list[dict]:
        """Load the valid prefix and truncate any torn tail, so a later
        append is never glued onto a half-written line (which would CRC-
        fail the COMBINED line and silently lose the new record)."""
        out = []
        try:
            with open(self.path, "rb") as f:
                blob = f.read()
        except OSError:
            return out
        valid = 0
        pos = 0
        for line in blob.split(b"\n"):
            end = pos + len(line)
            if line:
                # a line missing its newline is torn even if the CRC
                # happens to pass — append() writes line+\n as one unit
                rec = None
                if end < len(blob):
                    payload, _tab, crc = line.rpartition(b"\t")
                    try:
                        if format(zlib.crc32(payload), "08x").encode() == crc:
                            rec = json.loads(payload)
                    except (ValueError, json.JSONDecodeError):
                        rec = None
                if rec is None:
                    break
                out.append(rec)
                valid = end + 1
            pos = end + 1
        if valid < len(blob):
            with open(self.path, "ab") as f:
                f.truncate(valid)
                f.flush()
                os.fsync(f.fileno())
        return out

    def records(self) -> list[dict]:
        out = []
        try:
            with open(self.path, "rb") as f:
                blob = f.read()
        except OSError:
            return out
        for line in blob.split(b"\n"):
            if not line:
                continue
            payload, _tab, crc = line.rpartition(b"\t")
            try:
                if format(zlib.crc32(payload), "08x").encode() != crc:
                    break
                out.append(json.loads(payload))
            except (ValueError, json.JSONDecodeError):
                break
        return out

    def append(self, phase: str, **data) -> dict:
        rec = {"seq": self._seq, "phase": phase, **data}
        payload = json.dumps(rec, sort_keys=True)
        line = f"{payload}\t{format(zlib.crc32(payload.encode()), '08x')}\n"
        with open(self.path, "a") as f:
            f.write(line)
            f.flush()
            os.fsync(f.fileno())
        self._seq += 1
        return rec


# ---------------------------------------------------------------------------
# coordinator


def _env_f(name: str, default: str) -> float:
    return float(os.environ.get(name, default))


class ReshardCoordinator:
    """Drive one live reshard P -> P' to completion (or clean abort).

    `registry` must be a shared-dir registry spec (the coordinator
    passes it to destination shard subprocesses and reads gen'd
    heartbeats back). Sources must be solo durable shards (wal_dir'd;
    replica-group reshard is ROADMAP future work — the fence verb only
    reaches the receiving primary)."""

    def __init__(
        self,
        registry: str,
        num_shards: int,
        new_num_shards: int,
        state_dir: str,
        host: str = "127.0.0.1",
        env: dict | None = None,
    ):
        from euler_tpu.distributed.rendezvous import make_registry

        if not isinstance(registry, str):
            raise TypeError("registry must be a spec string (shared dir)")
        self.registry_spec = registry
        self.registry = make_registry(registry)
        if not hasattr(self.registry, "members"):
            raise RuntimeError(
                "reshard needs a shared-dir registry (members/meta reads)"
            )
        self.num_shards = int(num_shards)
        self.new_num_shards = int(new_num_shards)
        if self.new_num_shards < 1 or self.new_num_shards == self.num_shards:
            raise ValueError(
                f"bad shard counts {self.num_shards} -> {self.new_num_shards}"
            )
        self.state_dir = state_dir
        os.makedirs(state_dir, exist_ok=True)
        self.host = host
        self.env = dict(env or {})
        self.log = _PhaseLog(os.path.join(state_dir, "phases.jsonl"))
        # adopt the logged generation on resume so a re-run coordinator
        # agrees with its dead predecessor about the commit point
        plan_rec = next(
            (r for r in self.log.records() if r["phase"] == "plan"), None
        )
        if plan_rec is not None:
            if (
                int(plan_rec["P"]) != self.num_shards
                or int(plan_rec["P2"]) != self.new_num_shards
            ):
                raise RuntimeError(
                    f"state dir belongs to a {plan_rec['P']}->"
                    f"{plan_rec['P2']} reshard, not "
                    f"{self.num_shards}->{self.new_num_shards}"
                )
            self.gen = int(plan_rec["gen"])
            self.gen2 = int(plan_rec["gen2"])
            self.src_topology_epoch = int(plan_rec.get("topology_epoch", 0))
        else:
            topo = self.registry.topology()
            self.gen = int(topo["gen"]) if topo else 0
            self.gen2 = self.gen + 1
            self.src_topology_epoch = 0
        self.token = f"reshard-g{self.gen2}"
        self.dest_root = os.path.join(state_dir, f"gen_{self.gen2}")
        self.meta: GraphMeta | None = None
        self.report: dict = {"token": self.token, "gen2": self.gen2}
        self._src_handles = None
        self._state: list[dict] = []
        self._dest_procs: list = []

    # -- plumbing ---------------------------------------------------------

    def _sources(self):
        from euler_tpu.distributed.client import RemoteShard

        if self._src_handles is None:
            table = self.registry.wait_for(self.num_shards, timeout=30.0)
            self._src_handles = [
                RemoteShard(s, table[s]) for s in range(self.num_shards)
            ]
        return self._src_handles

    def _checkpoint(self, phase: str, **data):
        """Durable phase record + the chaos injection point: with
        EULER_TPU_RESHARD_KILL_AT=<phase> the process SIGKILLs itself
        the instant the record is on disk — tests drive every
        phase-boundary crash deterministically through it."""
        self.log.append(phase, **data)
        print(f"reshard {self.token}: phase {phase}", flush=True)
        if os.environ.get("EULER_TPU_RESHARD_KILL_AT") == phase:
            os.kill(os.getpid(), signal.SIGKILL)

    # -- snapshot / tail transport ---------------------------------------

    def _fetch_snapshot(self, sh) -> dict:
        """Pull one source's publish-consistent snapshot over the
        replication bootstrap payload (v2 codec-aware or legacy)."""
        from euler_tpu.distributed import codec

        reply = sh.call(
            "wal_ship",
            [0, 0, None, "snapshot", None, None, None, codec.wire_codec()],
            deadline_s=_env_f("EULER_TPU_RESHARD_FENCE_TIMEOUT_S", "30") * 4,
        )
        term, epoch, wal_pos = int(reply[0]), int(reply[1]), int(reply[2])
        head = json.loads(reply[4])
        if isinstance(head, dict):
            use = str(head["codec"])
            applied = walmod._applied_from_blob(
                codec.decompress(use, bytes(np.ascontiguousarray(reply[3])))
            )
            arrays = {}
            for n, dt, shape, blob in zip(
                head["names"], head["dtypes"], head["shapes"], reply[5:]
            ):
                raw = codec.decompress(use, bytes(np.ascontiguousarray(blob)))
                arrays[n] = (
                    np.frombuffer(raw, np.dtype(dt)).reshape(shape).copy()
                )
        else:
            applied = walmod._applied_from_blob(
                bytes(np.ascontiguousarray(reply[3]))
            )
            arrays = {n: np.array(a, copy=True) for n, a in zip(head, reply[5:])}
        return {
            "term": term,
            "epoch": epoch,
            "pos": wal_pos,
            "applied": applied,
            "arrays": arrays,
        }

    def _copy_source(self, s: int):
        """(Re)copy one source: force a publish-consistent snapshot
        state, then pull it. Also the need_snapshot recovery path when
        the WAL prefix gets trimmed under a tail fetch."""
        sh = self._sources()[s]
        st = self._state[s] if s < len(self._state) else None
        n = 0 if st is None else st.get("copies", 0)
        # an EMPTY publish still captures a publish-consistent snapshot
        # state server-side, so want="snapshot" always has one to ship
        sh.call("publish_epoch", [f"{self.token}:pre:{s}:{n}"])
        snap = self._fetch_snapshot(sh)
        snap.update(fetched=snap["pos"], buf=bytearray(), copies=n + 1)
        if st is None:
            self._state.append(snap)
        else:
            self._state[s] = snap

    def _fetch_tail(self, s: int, upto: int):
        """Append the source's raw WAL records in [fetched, upto) to
        its buffer. Positions are logical offsets; `read_raw` always
        ships the first record whole so progress is guaranteed."""
        from euler_tpu.distributed import codec

        offer = codec.wire_codec()
        sh = self._sources()[s]
        while self._state[s]["fetched"] < upto:
            st = self._state[s]
            reply = sh.call(
                "wal_ship",
                [st["fetched"], 1 << 20, None, "log", None, None, None,
                 offer, st["fetched"]],
            )
            if bool(reply[3]):  # need_snapshot: prefix trimmed under us
                self._copy_source(s)
                continue
            raw = (
                bytes(np.ascontiguousarray(reply[1])) if len(reply[1]) else b""
            )
            blob = (
                codec.decompress(str(reply[4]), raw)
                if (len(reply) >= 6 and raw)
                else raw
            )
            if not blob:
                break
            st["buf"] += blob
            st["fetched"] = int(reply[2])

    # -- phases -----------------------------------------------------------

    def _phase_plan(self):
        srcs = self._sources()
        self.meta = GraphMeta.from_dict(json.loads(srcs[0].call("get_meta", [])[0]))
        if int(self.meta.num_partitions) != self.num_shards:
            raise RuntimeError(
                f"cluster is {self.meta.num_partitions}-way, coordinator"
                f" was told {self.num_shards}"
            )
        stats = [json.loads(sh.call("stats", [])[0]) for sh in srcs]
        self.src_topology_epoch = max(
            int(s.get("topology_epoch", 0)) for s in stats
        )
        moves = plan_moves(self.num_shards, self.new_num_shards)
        moved = sum(1 for m in moves if m["moved"])
        self.report["plan"] = {
            "residues": len(moves),
            "moved_residues": moved,
            "moved_fraction": moved / len(moves),
        }
        self._checkpoint(
            "plan",
            P=self.num_shards,
            P2=self.new_num_shards,
            gen=self.gen,
            gen2=self.gen2,
            residues=len(moves),
            moved_residues=moved,
            topology_epoch=self.src_topology_epoch,
        )

    def _phase_copy(self):
        t0 = time.perf_counter()
        self._state = []
        for s in range(self.num_shards):
            self._copy_source(s)
        self.report["copy_s"] = round(time.perf_counter() - t0, 3)
        self._checkpoint(
            "copy",
            positions=[int(st["pos"]) for st in self._state],
            epochs=[int(st["epoch"]) for st in self._state],
        )

    def _phase_catch_up(self):
        t0 = time.perf_counter()
        lag_max = int(float(os.environ.get("EULER_TPU_RESHARD_LAG", "65536")))
        budget = _env_f("EULER_TPU_RESHARD_CATCHUP_S", "120")
        srcs = self._sources()
        while True:
            total = 0
            for s, sh in enumerate(srcs):
                end = int(sh.call("wal_pos", [])[2])
                if end > self._state[s]["fetched"]:
                    self._fetch_tail(s, end)
                total += max(0, end - self._state[s]["fetched"])
            if total <= lag_max:
                break
            if time.perf_counter() - t0 > budget:
                raise RuntimeError(
                    f"catch_up lag {total}B still above {lag_max}B after"
                    f" {budget}s — writers outrun the tail fetch"
                )
        self.report["catch_up_s"] = round(time.perf_counter() - t0, 3)
        self._checkpoint("catch_up", lag=int(total))

    def _replay_source(self, s: int) -> dict:
        """Replay one source's shipped WAL suffix onto its snapshot
        arrays — the exact `wal.recover` loop (staged keys land in the
        applied window, publish records merge per round, records after
        the last publish stay pending)."""
        from euler_tpu.graph.store import GraphStore

        st = self._state[s]
        store = GraphStore(self.meta, dict(st["arrays"]), s)
        store.graph_epoch = int(st["epoch"])
        recs, valid_end = walmod.parse_records(bytes(st["buf"]), st["pos"])
        if valid_end != st["fetched"]:
            raise RuntimeError(
                f"source {s}: shipped tail torn at {valid_end}, expected"
                f" {st['fetched']}"
            )
        applied = collections.OrderedDict(st["applied"])
        delta = None
        pending: list[tuple[str, list]] = []
        for op, a, _end, _term in recs:
            if op == "publish_epoch":
                key = a[0] if a else None
                if key is not None and f"pub:{key}" in applied:
                    continue
                d, delta = delta, None
                pending = []
                if d is None or d.empty:
                    result = (
                        int(store.graph_epoch),
                        np.empty(0, np.int64),
                        np.empty(0, np.uint64),
                        int(store.num_nodes),
                    )
                else:
                    store, rows, ids = store.merge_delta(d)
                    result = (
                        int(store.graph_epoch),
                        rows,
                        ids,
                        int(store.num_nodes),
                    )
                if key is not None:
                    applied[f"pub:{key}"] = result
            else:
                key = str(a[0])
                if key in applied:
                    continue
                if delta is None:
                    delta = DeltaStore(
                        s, self.meta.num_partitions, max_rows=2**62
                    )
                walmod.stage_record(delta, op, a)
                applied[key] = True
                pending.append((op, a))
        return {
            "arrays": store.arrays,
            "epoch": int(store.graph_epoch),
            "applied": applied,
            "pending": pending,
        }

    def _seed_dest_wal(self, d, arrays_d, replayed, epoch):
        """Build destination d's WAL dir: re-scattered pending records
        (same batch keys -> post-cutover client retries dedupe) plus a
        seeded snapshot carrying the merged applied window with every
        publish result sanitized to the full-flush sentinel."""
        from euler_tpu.distributed.writer import GraphWriter

        wal_dir = os.path.join(self.dest_root, f"wal_{d}")
        os.makedirs(wal_dir, exist_ok=True)
        wal = walmod.WriteAheadLog(os.path.join(wal_dir, walmod.WAL_FILE))
        pending_keys = set()
        for r in replayed:
            for op, a in r["pending"]:
                pending_keys.add(str(a[0]))
                for dest, sub in GraphWriter._resplit(
                    op, list(a[1:]), self.new_num_shards
                ):
                    if dest == d:
                        wal.append(op, [a[0]] + list(sub))
        # merged applied window: batch keys are unique to one source so
        # the union is well defined; pending keys are EXCLUDED — their
        # WAL records re-add them during destination recovery (seeding
        # them here would make recovery skip the re-staged rows)
        applied_d: collections.OrderedDict = collections.OrderedDict()
        dest_n = int(len(arrays_d["node_ids"]))
        for r in replayed:
            for k, v in r["applied"].items():
                if k in pending_keys:
                    continue
                if k.startswith("pub:"):
                    ep = int(v[0]) if isinstance(v, tuple) else int(epoch)
                    # rows/ids None = the client's full-flush sentinel —
                    # source row numbering is meaningless at P'
                    applied_d[k] = (ep, None, None, dest_n)
                else:
                    applied_d[k] = True
        muts = [k for k in applied_d if not k.startswith("pub:")]
        for k in muts[: max(0, len(muts) - 4096)]:
            del applied_d[k]
        walmod.write_snapshot(wal_dir, int(epoch), arrays_d, applied_d, 0)

    def _spawn_dests(self, data_dir: str) -> list[int]:
        env = dict(os.environ)
        env.update(self.env)
        env.pop("EULER_TPU_RESHARD_KILL_AT", None)  # chaos targets US
        for d in range(self.new_num_shards):
            cmd = [
                sys.executable, "-m", "euler_tpu.distributed.service",
                "--data", data_dir,
                "--shard", str(d),
                "--host", self.host,
                "--port", "0",
                "--registry", self.registry_spec,
                "--wal-dir", os.path.join(self.dest_root, f"wal_{d}"),
                "--no-native",
                "--generation", str(self.gen2),
                "--topology-epoch", str(self.src_topology_epoch + 1),
            ]
            logf = open(os.path.join(self.dest_root, f"dest_{d}.log"), "ab")
            self._dest_procs.append(
                subprocess.Popen(
                    cmd, env=env, stdout=logf, stderr=logf,
                    start_new_session=True,
                )
            )
            logf.close()
        return [p.pid for p in self._dest_procs]

    def _await_dests(self, epoch: int) -> dict:
        from euler_tpu.distributed.client import RemoteShard

        deadline = time.monotonic() + _env_f(
            "EULER_TPU_RESHARD_BOOT_TIMEOUT_S", "60"
        )
        table = {}
        for d in range(self.new_num_shards):
            while True:
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"destination shard {d} (gen {self.gen2}) did not"
                        " become ready"
                    )
                # an aborted earlier attempt at this SAME generation can
                # leave stale heartbeats from its kill -9'd destinations
                # (dead processes never deregister) — probe every
                # gen-matching candidate and let the live one win
                hit = None
                for h, p, m in self.registry.members(d):
                    if int((m or {}).get("gen", 0)) != self.gen2:
                        continue
                    addr = (h, p)
                    try:
                        sh = RemoteShard(d, [addr])
                        sh.call("ping", [])
                        got = int(sh.call("wal_pos", [])[3])
                    except (OSError, ConnectionError, RpcError):
                        continue
                    if got == int(epoch):
                        hit = addr
                        break
                    raise RuntimeError(
                        f"destination {d} booted at epoch {got},"
                        f" expected {epoch}"
                    )
                if hit is not None:
                    table[d] = f"{hit[0]}:{hit[1]}"
                    break
                time.sleep(0.05)
        return table

    def _phase_cutover(self):
        srcs = self._sources()
        # durable intent BEFORE the first fence lands: a coordinator
        # killed past this point knows (on resume) it may have fenced
        # sources and must either roll forward or unfence them
        self._checkpoint("fence_begin", token=self.token)
        t0 = time.perf_counter()
        fence_to = _env_f("EULER_TPU_RESHARD_FENCE_TIMEOUT_S", "30")
        ends = []
        for sh in srcs:
            reply = sh.call(
                "fence", [self.token, self.gen2], deadline_s=fence_to
            )
            ends.append(int(reply[1]))
        # distinct kill point: every source IS fenced now, so an abort
        # from any later phase owes each of them an unfence
        self._checkpoint("fenced", ends=ends)
        # the fence reply's wal_end is final (the flag is checked before
        # staging and the fence serializes behind in-flight stages), so
        # one drain to wal_end captures the whole fencing-window tail
        for s in range(self.num_shards):
            self._fetch_tail(s, ends[s])
            if self._state[s]["fetched"] != ends[s]:
                raise RuntimeError(
                    f"source {s}: tail drain stalled at"
                    f" {self._state[s]['fetched']} < {ends[s]}"
                )
        replayed = [self._replay_source(s) for s in range(self.num_shards)]
        epoch = max(r["epoch"] for r in replayed)
        all_nid = np.concatenate(
            [np.asarray(r["arrays"]["node_ids"], np.uint64) for r in replayed]
        )
        self.report["rows_moved"] = int(
            np.count_nonzero(
                (all_nid % np.uint64(self.num_shards))
                != (all_nid % np.uint64(self.new_num_shards))
            )
        )
        meta2, parts2 = repartition_arrays(
            self.meta, [r["arrays"] for r in replayed], self.new_num_shards
        )
        data_dir = os.path.join(self.dest_root, "data")
        os.makedirs(data_dir, exist_ok=True)
        for d in range(self.new_num_shards):
            tformat.write_arrays(
                os.path.join(data_dir, f"part_{d}"), parts2[d], fsync=True
            )
        meta2.save(data_dir)
        for d in range(self.new_num_shards):
            self._seed_dest_wal(d, parts2[d], replayed, epoch)
        pids = self._spawn_dests(data_dir)
        self._checkpoint("dests_spawned", pids=pids, data_dir=data_dir)
        self.report["dests"] = self._await_dests(epoch)
        # THE commit point: one atomic rename in the registry flips
        # every connect()'s topology watch to the new generation
        self.registry.set_topology(self.new_num_shards, self.gen2, int(epoch))
        unavail_ms = round((time.perf_counter() - t0) * 1e3, 3)
        self.report.update(
            epoch=int(epoch), cutover_ms=unavail_ms, unavail_ms=unavail_ms
        )
        self._checkpoint(
            "committed", gen2=self.gen2, epoch=int(epoch), cutover_ms=unavail_ms
        )
        # sources stay fenced (durable marker) and gen-invisible; the
        # operator retires them once the new generation is warm

    # -- lifecycle --------------------------------------------------------

    def run(self, resume: bool = False) -> dict:
        recs = self.log.records()
        if recs:
            last = recs[-1]["phase"]
            if last in ("done", "aborted"):
                self.report["outcome"] = last
                return self.report
            if not resume:
                raise RuntimeError(
                    f"{self.state_dir}: unfinished reshard (last phase"
                    f" {last!r}) — rerun with resume=True (CLI --resume)"
                    " or abort"
                )
            return self._resume(recs)
        try:
            self._phase_plan()
            self._phase_copy()
            self._phase_catch_up()
            self._phase_cutover()
        except BaseException:
            self._abort("phase failure")
            raise
        self._checkpoint("done")
        self.report["outcome"] = "done"
        return self.report

    def _resume(self, recs: list[dict]) -> dict:
        """Post-kill recovery: the registry topology flip is the commit
        point — at or past it, roll forward; before it, roll back."""
        committed = any(r["phase"] == "committed" for r in recs)
        topo = self.registry.topology()
        if committed or (topo is not None and int(topo.get("gen", 0)) >= self.gen2):
            self._checkpoint("done", note="resume roll-forward")
            self.report["outcome"] = "done"
            return self.report
        self._abort("resume pre-commit roll-back")
        return self.report

    def abort(self) -> dict:
        recs = self.log.records()
        if recs and recs[-1]["phase"] in ("done", "aborted"):
            self.report["outcome"] = recs[-1]["phase"]
            return self.report
        self._abort("operator abort")
        return self.report

    def _abort(self, reason: str):
        """Roll back with zero data loss: kill half-born destinations,
        unfence every source (writes resume on the OLD topology),
        remove destination state, persist the terminal record."""
        recs = self.log.records()
        pids = [
            pid for r in recs if r["phase"] == "dests_spawned"
            for pid in r.get("pids", [])
        ]
        pids += [p.pid for p in self._dest_procs]
        for pid in set(pids):
            try:
                os.kill(int(pid), signal.SIGKILL)
            except (OSError, ProcessLookupError):
                pass
        for p in self._dest_procs:
            try:
                p.wait(timeout=5)
            except Exception:
                pass
        fenced = any(r["phase"] == "fence_begin" for r in recs)
        if fenced:
            try:
                for sh in self._sources():
                    try:
                        sh.call("unfence", [self.token])
                    except (OSError, ConnectionError):
                        # source mid-respawn: its durable fence marker
                        # names OUR token; retry once it heartbeats back
                        time.sleep(0.5)
                        sh.call("unfence", [self.token])
            except Exception:
                self.log.append("abort_unfence_failed", reason=reason)
                raise
        shutil.rmtree(self.dest_root, ignore_errors=True)
        self._checkpoint("aborted", reason=reason)
        self.report["outcome"] = "aborted"


# ---------------------------------------------------------------------------
# load-driven autoscaling policy


@dataclasses.dataclass(frozen=True)
class Recommendation:
    """One typed scaling action.

    kind: scale_serving_replicas | scale_retrieval_replicas |
          split_shard | merge_shards
    target: proposed replica count (scale_*) or shard count (split/merge)
    """

    kind: str
    target: int
    reason: str
    metrics: dict = dataclasses.field(default_factory=dict)


def _fleet_pressure(fleet: dict) -> tuple[int, float, float]:
    """(live_replicas, avg_qps_per_replica, overload_rejects) from a
    `fleet_stats()`-shaped dict (addr -> server_stats json; entries
    with an "error" key are unreachable)."""
    live = [s for s in fleet.values() if isinstance(s, dict) and "error" not in s]
    qps = []
    rejects = 0.0
    for s in live:
        b = s.get("batcher", s)
        up = float(s.get("uptime_s", 0.0)) or 1.0
        qps.append(float(b.get("requests", 0)) / up)
        rejects += float(b.get("rejected_overload", 0))
    avg = sum(qps) / len(qps) if qps else 0.0
    return len(live), avg, rejects


def _scale_fleet(kind: str, fleet: dict, high: float, low: float):
    n, avg, rejects = _fleet_pressure(fleet)
    if n == 0:
        return None
    if rejects > 0 or avg > high:
        return Recommendation(
            kind,
            n + 1,
            f"{'overload rejects' if rejects > 0 else 'qps'} above budget"
            f" ({avg:.1f} qps/replica, {int(rejects)} rejects)",
            {"replicas": n, "qps_per_replica": avg, "rejected_overload": rejects},
        )
    if avg < low and n > 1:
        return Recommendation(
            kind,
            n - 1,
            f"idle fleet ({avg:.1f} qps/replica < {low})",
            {"replicas": n, "qps_per_replica": avg},
        )
    return None


def propose_scaling(
    serving: dict | None = None,
    retrieval: dict | None = None,
    shards: dict | None = None,
    num_shards: int | None = None,
) -> list[Recommendation]:
    """Pure policy: stats in, typed `Recommendation`s out (no side
    effects — the operator or a supervisor loop acts on them).

    serving / retrieval: `fleet_stats()`-shaped dicts.
    shards: shard -> {"wal_bytes": .., "num_nodes": ..} store/WAL
    pressure (e.g. from `server_stats`'s "graph_shards" block).
    """
    high = _env_f("EULER_TPU_AUTOSCALE_QPS_HIGH", "100")
    low = _env_f("EULER_TPU_AUTOSCALE_QPS_LOW", "10")
    split_wal = _env_f("EULER_TPU_RESHARD_SPLIT_WAL_MB", "64") * (1 << 20)
    split_rows = _env_f("EULER_TPU_RESHARD_SPLIT_ROWS", "1000000")
    out: list[Recommendation] = []
    if serving:
        rec = _scale_fleet("scale_serving_replicas", serving, high, low)
        if rec:
            out.append(rec)
    if retrieval:
        rec = _scale_fleet("scale_retrieval_replicas", retrieval, high, low)
        if rec:
            out.append(rec)
    if shards:
        p = int(num_shards if num_shards is not None else len(shards))
        hot = []
        for sid, st in sorted(shards.items()):
            wal_b = float(st.get("wal_bytes", 0) or 0)
            rows = float(st.get("num_nodes", 0) or 0)
            if wal_b > split_wal or rows > split_rows:
                hot.append((sid, wal_b, rows))
        if hot:
            sid, wal_b, rows = hot[0]
            out.append(
                Recommendation(
                    "split_shard",
                    p + 1,
                    f"shard {sid} over pressure threshold"
                    f" (wal {int(wal_b)}B, {int(rows)} rows)",
                    {"shard": sid, "wal_bytes": wal_b, "num_nodes": rows,
                     "hot_shards": [h[0] for h in hot]},
                )
            )
        elif p > 1 and all(
            float(st.get("wal_bytes", 0) or 0) < split_wal / 4
            and float(st.get("num_nodes", 0) or 0) < split_rows / 4
            for st in shards.values()
        ):
            out.append(
                Recommendation(
                    "merge_shards",
                    p - 1,
                    f"all {p} shards under a quarter of the split"
                    " thresholds",
                    {"num_shards": p},
                )
            )
    return out


class AutoscaleLoop:
    """Poll a stats source and hand `Recommendation`s to a callback.

    `stats_fn` returns the `propose_scaling` kwargs (serving=...,
    retrieval=..., shards=..., num_shards=...); `on_recommend` receives
    each non-empty recommendation list. Polling faults are swallowed —
    an unreachable fleet must not kill the policy loop."""

    def __init__(self, stats_fn, on_recommend, interval_s: float | None = None):
        self.stats_fn = stats_fn
        self.on_recommend = on_recommend
        self.interval_s = (
            _env_f("EULER_TPU_AUTOSCALE_INTERVAL_S", "10")
            if interval_s is None
            else float(interval_s)
        )
        self.ticks = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def tick(self) -> list[Recommendation]:
        try:
            recs = propose_scaling(**(self.stats_fn() or {}))
        except (OSError, ConnectionError, ValueError, KeyError):
            return []
        self.ticks += 1
        if recs:
            self.on_recommend(recs)
        return recs

    def _run(self):
        while not self._stop.is_set():
            self.tick()
            self._stop.wait(self.interval_s)

    def start(self):
        self._thread = threading.Thread(
            target=self._run, name="euler-autoscale", daemon=True
        )
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--registry", required=True)
    ap.add_argument("--shards", type=int, required=True,
                    help="current shard count P")
    ap.add_argument("--to", type=int, required=True,
                    help="target shard count P'")
    ap.add_argument("--state", required=True,
                    help="coordinator state dir (phase log + dest state)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--resume", action="store_true",
                    help="recover a killed coordinator: roll forward if"
                         " the topology flipped, else roll back")
    ap.add_argument("--abort", action="store_true",
                    help="roll back an unfinished reshard")
    args = ap.parse_args(argv)
    co = ReshardCoordinator(
        args.registry, args.shards, args.to, args.state, host=args.host
    )
    if args.abort:
        report = co.abort()
    else:
        report = co.run(resume=args.resume)
    print(json.dumps(report, sort_keys=True, default=str), flush=True)
    return 0 if report.get("outcome") in ("done", "aborted") else 1


if __name__ == "__main__":
    sys.exit(main())
