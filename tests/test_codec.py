"""Shrunk byte paths (ISSUE 16).

The contract split this file pins:

- **Lossless**: stream codecs (wal_ship / snapshot / backup frames) and
  the varint neighbor planes are bit-identical after decode, and a flip
  of ANY single byte of any framed blob is a typed ValueError — never
  silently-wrong bytes.
- **Lossy, budgeted, opt-in**: dense-feature quantization ("bf16" /
  "int8") stays inside codec.quant_error_budget per element (the
  PARITY.md budget); "f32" (the default) is bitwise exact.
- **Degrade, pinned**: an old client gets the byte-identical pre-codec
  reply shapes (raw 4-tuple wal_ship, single-f32 dense block, raw u64
  neighbor planes); a new client against an old server sticks to exact
  f32 after one degraded answer.
- **Pipelined replication**: EULER_TPU_SHIP_PIPELINE on or off, the
  follower converges bit-identically to the from-scratch oracle.
"""

import os

import numpy as np
import pytest

from euler_tpu.distributed import codec, connect
from euler_tpu.distributed.client import RemoteShard
from euler_tpu.distributed.service import GraphService
from euler_tpu.distributed.writer import GraphWriter
from euler_tpu.graph import Graph
from euler_tpu.graph import backup as bk
from euler_tpu.graph import wal as walmod
from euler_tpu.graph.builder import build_from_json

from test_backup import (
    _dispatch_muts,
    _publish_all,
    _recover_restored,
    _rounds,
)
from test_replication import (  # noqa: F401  (patient_client is a fixture)
    _assert_bit_identical,
    _boot_group,
    _muts,
    _wait_converged,
    _wait_single_primary,
    patient_client,
)
from test_supervisor import _apply_json, _graph_dict, _route


# -- stream codecs -------------------------------------------------------


_PAYLOADS = [
    b"",
    b"x",
    bytes(range(256)) * 16,  # compressible structure
    np.random.default_rng(3).integers(0, 256, 4096, dtype=np.uint8)
    .tobytes(),  # incompressible
]


def test_stream_codec_roundtrip_every_codec():
    for name in codec.available_codecs():
        for raw in _PAYLOADS:
            blob = codec.compress(name, raw)
            assert codec.decompress(name, blob) == raw
    # zlib actually shrinks structured payloads
    structured = _PAYLOADS[2]
    assert len(codec.compress("zlib", structured)) < len(structured)
    with pytest.raises(ValueError, match="unknown stream codec"):
        codec.compress("lz4", b"x")
    with pytest.raises(ValueError, match="unknown stream codec"):
        codec.decompress("lz4", codec.compress("id", b"x"))


def test_stream_codec_flip_every_byte_is_typed():
    """The corruption sweep the issue pins: flip a byte at EVERY offset
    of a compressed blob — header, stream, anywhere — and decompress
    must raise ValueError (crc/length/version framing), never return."""
    raw = bytes(range(200))
    for name in ("id", "zlib"):
        blob = bytearray(codec.compress(name, raw))
        for off in range(len(blob)):
            bad = bytearray(blob)
            bad[off] ^= 0xFF
            with pytest.raises(ValueError):
                codec.decompress(name, bytes(bad))
        # truncation at every length is typed too
        for cut in range(len(blob)):
            with pytest.raises(ValueError):
                codec.decompress(name, bytes(blob[:cut]))


# -- varint neighbor planes ----------------------------------------------


def test_varint_delta_roundtrip_bit_identical():
    rng = np.random.default_rng(11)
    cases = [
        np.empty(0, np.uint64),
        np.asarray([0], np.uint64),
        np.asarray([7, 7, 7, 7], np.uint64),
        np.sort(rng.integers(0, 10_000, 500, dtype=np.uint64)),
        rng.integers(0, 2**64, 300, dtype=np.uint64),  # any order, full range
        np.asarray([2**64 - 1, 0, 2**63, 1], np.uint64),  # wraparound deltas
    ]
    for arr in cases:
        out = codec.decode_u64_delta(codec.encode_u64_delta(arr))
        assert out.dtype == np.uint64
        assert np.array_equal(out, arr)
    # sortedness is the efficiency case: dense sorted ids beat raw u64
    sorted_ids = np.arange(1000, 3000, dtype=np.uint64)
    assert len(codec.encode_u64_delta(sorted_ids)) < sorted_ids.nbytes / 3


def test_varint_flip_every_byte_is_typed():
    ids = np.sort(
        np.random.default_rng(5).integers(0, 5000, 64, dtype=np.uint64)
    )
    blob = bytearray(codec.encode_u64_delta(ids))
    for off in range(len(blob)):
        bad = bytearray(blob)
        bad[off] ^= 0xFF
        with pytest.raises(ValueError):
            codec.decode_u64_delta(bytes(bad))
    for cut in range(len(blob)):
        with pytest.raises(ValueError):
            codec.decode_u64_delta(bytes(blob[:cut]))


# -- float quantizers ----------------------------------------------------


def test_quant_budgets_per_dtype():
    rng = np.random.default_rng(7)
    # mixed magnitudes: normals, a huge-magnitude row, a constant row,
    # a zero row, and a tight row living FAR from the origin (the int8
    # range must be the true row min/max — clamping it to include 0
    # would blow the documented (rowmax-rowmin)/254 budget here)
    vals = np.concatenate(
        [
            rng.normal(size=(30, 16)).astype(np.float32),
            (rng.normal(size=(2, 16)) * 1e6).astype(np.float32),
            np.full((1, 16), 3.25, np.float32),
            np.zeros((1, 16), np.float32),
            (rng.normal(size=(2, 16)) + 1000.0).astype(np.float32),
        ]
    )
    # f32 is the exact default: bitwise, not approximately
    (back,) = codec.quantize("f32", vals)
    assert back.tobytes() == vals.tobytes()
    for kind in ("bf16", "int8"):
        parts = codec.quantize(kind, vals)
        deq = codec.dequantize(kind, parts)
        err = np.abs(deq - vals)
        budget = codec.quant_error_budget(kind, vals)
        assert (err <= budget[:, None] + 1e-30).all(), (
            kind,
            float(err.max()),
        )
    # and the quantized payloads actually shrink: bf16 halves, int8 ~4x
    assert codec.quantize("bf16", vals)[0].nbytes == vals.nbytes // 2
    q = codec.quantize("int8", vals)
    assert sum(p.nbytes for p in q) < vals.nbytes // 2


def test_quant_malformed_payloads_are_typed():
    with pytest.raises(ValueError, match="unknown page dtype"):
        codec.quantize("f16", np.zeros((2, 2), np.float32))
    with pytest.raises(ValueError, match="unknown page dtype"):
        codec.dequantize("f16", [np.zeros((2, 2), np.float32)])
    with pytest.raises(ValueError, match="needs"):
        codec.dequantize("int8", [np.zeros((2, 2), np.uint8)])
    with pytest.raises(ValueError, match="dtype"):
        codec.dequantize(
            "int8",
            [
                np.zeros((2, 2), np.float32),  # q plane must be uint8
                np.ones(2, np.float32),
                np.zeros(2, np.float32),
            ],
        )


# -- quantized dense wire, end to end ------------------------------------


@pytest.fixture
def solo(tmp_path):
    base = _graph_dict(n=40, feat_dim=8)
    g = Graph.from_json(base, num_partitions=1)
    svc = GraphService(g.shards[0], g.meta, 0).start()
    try:
        yield g, svc
    finally:
        svc.stop()


def _fresh_handle(svc, monkeypatch, page_dtype=None, wire_codec=None):
    if page_dtype is not None:
        monkeypatch.setenv("EULER_TPU_PAGE_DTYPE", page_dtype)
    if wire_codec is not None:
        monkeypatch.setenv("EULER_TPU_WIRE_CODEC", wire_codec)
    return RemoteShard(0, [(svc.host, svc.port)])


def test_dense_wire_quantized_within_budget(solo, monkeypatch):
    g, svc = solo
    ids = np.arange(1, 33, dtype=np.uint64)
    exact = g.shards[0].get_dense_feature(ids, ["feat"])

    rs = _fresh_handle(svc, monkeypatch, page_dtype="f32")
    f32 = rs.get_dense_feature(ids, ["feat"])
    # the default is BIT-identical, not close
    assert f32.dtype == np.float32 and f32.tobytes() == exact.tobytes()
    f32_wire = rs.wire_bytes_in["get_dense_feature"]

    for kind in ("bf16", "int8"):
        rq = _fresh_handle(svc, monkeypatch, page_dtype=kind)
        got = rq.get_dense_feature(ids, ["feat"])
        budget = codec.quant_error_budget(kind, exact)
        assert (np.abs(got - exact) <= budget[:, None] + 1e-30).all(), kind
        # the wire reply actually shrank vs the exact leg
        assert rq.wire_bytes_in["get_dense_feature"] < f32_wire, kind


def test_dense_bf16_wire_is_lossy_and_its_error_small(solo, monkeypatch):
    """bf16 pages are lossy (the error is not zero) and, on features of
    unit scale, off by less than 0.05 anywhere."""
    g, svc = solo
    ids = np.arange(1, 33, dtype=np.uint64)
    exact = g.shards[0].get_dense_feature(ids, ["feat"])
    rq = _fresh_handle(svc, monkeypatch, page_dtype="bf16")
    got = rq.get_dense_feature(ids, ["feat"])
    assert 0 < np.max(np.abs(got - exact)) < 0.05


def test_dense_old_server_sticky_degrade(solo, monkeypatch):
    """A server predating the trailing wire-dtype arg answers the exact
    f32 block; ONE such answer pins the handle to f32 — bit-identical
    old behavior, and no re-offer on the next call."""
    g, svc = solo
    ids = np.arange(1, 17, dtype=np.uint64)
    exact = g.shards[0].get_dense_feature(ids, ["feat"])
    rs = _fresh_handle(svc, monkeypatch, page_dtype="bf16")
    sent_kinds = []
    orig = rs.call

    def old_server_call(op, values, **kw):
        if op == "get_dense_feature":
            sent_kinds.append(values[2] if len(values) > 2 else None)
            values = values[:2]  # an old server never sees the offer
        return orig(op, values, **kw)

    monkeypatch.setattr(rs, "call", old_server_call)
    got = rs.get_dense_feature(ids, ["feat"])
    assert got.tobytes() == exact.tobytes()  # verbatim, not re-quantized
    assert rs._dense_wire is False  # sticky
    rs.get_dense_feature(np.asarray([5, 6], np.uint64), ["feat"])
    # first call offered bf16; after the sticky downgrade the handle
    # sends the OLD two-arg request — no offer at all
    assert sent_kinds[0] == "bf16" and sent_kinds[-1] is None


def test_old_client_reply_shapes_pinned(solo):
    """The wire a pre-PR-16 client sees: request args WITHOUT the
    trailing offers must produce the byte-identical old replies."""
    g, svc = solo
    ids = np.arange(1, 17, dtype=np.uint64)
    # dense: single exact f32 part
    out = svc.dispatch("get_dense_feature", [ids, ["feat"]])
    exact = g.shards[0].get_dense_feature(ids, ["feat"])
    assert len(out) == 1 and np.asarray(out[0]).dtype == np.float32
    assert np.asarray(out[0]).tobytes() == exact.tobytes()
    # full_nb: raw u64 neighbor plane without the "delta" offer...
    raw = svc.dispatch("get_full_neighbor", [ids, None, 8, False, None])
    assert np.asarray(raw[0]).dtype == np.uint64
    # ...and the offered u8 varint plane decodes to those exact ids
    compact = svc.dispatch(
        "get_full_neighbor", [ids, None, 8, False, None, "delta"]
    )
    plane = np.asarray(compact[0])
    assert plane.dtype == np.uint8
    assert plane.nbytes < np.asarray(raw[0]).nbytes
    decoded = codec.decode_u64_delta(plane.tobytes())
    assert np.array_equal(decoded, np.asarray(raw[0]).reshape(-1))
    for got, want in zip(compact[1:], raw[1:]):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_full_nb_codec_toggle_bit_parity(solo, monkeypatch):
    """EULER_TPU_WIRE_CODEC=id is the one switch back to raw wire; the
    delta leg returns the same bits over fewer wire bytes."""
    g, svc = solo
    ids = np.arange(1, 33, dtype=np.uint64)
    legs = {}
    for name in ("id", "zlib"):
        rs = _fresh_handle(svc, monkeypatch, wire_codec=name)
        out = rs.get_full_neighbor(ids, [0], max_degree=8)
        legs[name] = (out, rs.wire_bytes_in["get_full_neighbor"])
    raw_out, raw_bytes = legs["id"]
    delta_out, delta_bytes = legs["zlib"]
    for a, b in zip(raw_out, delta_out):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert delta_bytes < raw_bytes


def test_dense_mixed_fleet_never_mixes_cache_block_shapes(
    solo, monkeypatch
):
    """Rolling-upgrade fleet: one replica answers the quantized 3-part
    int8 block, another the old 1-part f32 block, through ONE handle's
    read cache. The 1-part reply must never enter the quantized cache
    key (mixed tuple shapes would break assembly) — the batch redoes on
    the exact f32 keyspace and the handle degrades sticky."""
    g, svc = solo
    rs = _fresh_handle(svc, monkeypatch, page_dtype="int8")
    old_replica = [False]
    orig = rs.call

    def mixed_fleet_call(op, values, **kw):
        if op == "get_dense_feature" and old_replica[0]:
            values = values[:2]  # this replica predates the offer arg
        return orig(op, values, **kw)

    monkeypatch.setattr(rs, "call", mixed_fleet_call)
    ids_a = np.arange(1, 9, dtype=np.uint64)
    ids_b = np.arange(9, 17, dtype=np.uint64)
    exact_a = g.shards[0].get_dense_feature(ids_a, ["feat"])
    exact_b = g.shards[0].get_dense_feature(ids_b, ["feat"])
    budget = codec.quant_error_budget("int8", exact_a)
    got_a = rs.get_dense_feature(ids_a, ["feat"])  # new replica: 3-part
    assert (np.abs(got_a - exact_a) <= budget[:, None] + 1e-30).all()
    old_replica[0] = True  # failover lands on a pre-codec replica
    got_b = rs.get_dense_feature(ids_b, ["feat"])
    assert got_b.tobytes() == exact_b.tobytes()  # verbatim f32, no crash
    assert rs._dense_wire is False  # sticky degrade
    # the whole fleet now reads exact f32 — including the ids the
    # quantized key cached earlier
    both = rs.get_dense_feature(
        np.concatenate([ids_a, ids_b]), ["feat"]
    )
    assert both.tobytes() == np.concatenate(
        [exact_a, exact_b]
    ).tobytes()


# -- empty long-poll replies on the codec-aware tail ----------------------


def test_ship_payload_empty_longpoll_reply_is_not_a_fault():
    """A codec-aware primary answers an expired wal_ship long poll with
    an EMPTY unframed payload; the follower must read that as 'no new
    records' — decoding it would throw every idle poll cycle and make
    _tail_loop drop and re-dial the link several times per second."""
    from euler_tpu.distributed.replication import (
        ReplicaCoordinator,
        _PrimaryLink,
    )

    link = _PrimaryLink("127.0.0.1", 1)
    empty_new = [0, np.empty(0, np.uint8), 0, False, "zlib", 0, 0]
    assert ReplicaCoordinator._ship_payload(link, empty_new) == b""
    assert link.new_proto is True  # still proven codec-aware
    # non-empty new-shape replies keep decoding (and keep raising on
    # damage — the corruption stance is unchanged)
    raw = b"record-bytes" * 40
    blob = np.frombuffer(codec.compress("zlib", raw), np.uint8)
    full_new = [0, blob, len(raw), False, "zlib", len(raw), len(raw)]
    assert ReplicaCoordinator._ship_payload(link, full_new) == raw
    bad = blob.copy()
    bad[-1] ^= 0xFF
    with pytest.raises(ValueError):
        ReplicaCoordinator._ship_payload(
            link, [0, bad, len(raw), False, "zlib", len(raw), len(raw)]
        )
    # old-shape empty replies stay the old no-op
    assert ReplicaCoordinator._ship_payload(
        link, [0, np.empty(0, np.uint8), 0, False]
    ) == b""
    assert link.new_proto is False


# -- wire byte counters, both sides --------------------------------------


def test_wire_byte_counters_client_and_server(solo, monkeypatch):
    g, svc = solo
    rs = _fresh_handle(svc, monkeypatch, page_dtype="f32")
    ids = np.arange(1, 9, dtype=np.uint64)
    rs.get_dense_feature(ids, ["feat"])
    rs.lookup(ids)
    st = rs.stats()
    # client half: per-verb counters on the handle AND in stats()
    for verb in ("get_dense_feature", "lookup"):
        assert rs.wire_bytes_out[verb] > 0
        assert rs.wire_bytes_in[verb] > 0
        assert st["client_wire_bytes_out"][verb] == rs.wire_bytes_out[verb]
        assert st["client_wire_bytes_in"][verb] == rs.wire_bytes_in[verb]
    # server half rides the stats reply; the two sides count the same
    # streams from opposite ends of one socket, so they agree exactly
    for verb in ("get_dense_feature", "lookup"):
        assert st["wire_bytes_in"][verb] == rs.wire_bytes_out[verb]
        assert st["wire_bytes_out"][verb] == rs.wire_bytes_in[verb]


# -- WAL: deferred durability --------------------------------------------


def test_wal_append_raw_durable_flag_and_sync(tmp_path):
    wal = walmod.WriteAheadLog(str(tmp_path / "log.wal"))
    try:
        rec = walmod.encode_record("upsert_nodes", ["k1", 1])
        p1 = wal.append_raw(rec, durable=False)
        assert wal.tell() == p1  # visible to read_raw/tell immediately
        assert wal._synced_seq < wal._written_seq  # fsync deferred
        wal.sync()
        assert wal._synced_seq == wal._written_seq
        # a durable append AFTER deferred ones covers everything written
        wal.append_raw(walmod.encode_record("upsert_nodes", ["k2", 2]),
                       durable=False)
        p3 = wal.append_raw(walmod.encode_record("upsert_nodes", ["k3", 3]))
        assert wal._synced_seq == wal._written_seq
        data, end = wal.read_raw(0, 1 << 20)
        assert end == p3 and len(data) == p3
    finally:
        wal.close()


# -- wal_ship: codec negotiation, floor, degrade -------------------------


def test_wal_ship_reply_shapes_and_codec(tmp_path, monkeypatch):
    monkeypatch.setenv("EULER_TPU_SNAPSHOT_EVERY", "0")
    base = _graph_dict()
    g = Graph.from_json(base, num_partitions=1)
    svc = GraphService(
        g.shards[0], g.meta, 0, wal_dir=str(tmp_path / "wal")
    )
    try:
        for r, muts in enumerate(_rounds(6, k=24)):
            _dispatch_muts([svc], muts, f"r{r}")
        raw, end = svc._wal.read_raw(0, 1 << 20)
        assert len(raw) > 4096  # big enough to clear the compress floor

        # old client: the pinned raw 4-tuple, byte-identical record bytes
        old = svc.dispatch("wal_ship", [0])
        assert len(old) == 4
        term, blob, got_end, need = old
        assert (not need) and got_end == end
        assert np.asarray(blob).tobytes() == raw

        # new client, zlib offer: 7-tuple, compressed, log_end attached
        new = svc.dispatch(
            "wal_ship", [0, 1 << 20, None, "log", None, None, 0.0, "zlib"]
        )
        assert len(new) == 7
        _, nblob, nend, nneed, used, raw_len, log_end = new
        assert used == "zlib" and raw_len == len(raw) and not nneed
        assert nend == end and log_end == svc._wal.tell()
        assert codec.decompress("zlib", np.asarray(nblob).tobytes()) == raw
        assert np.asarray(nblob).nbytes < len(raw)

        # sub-4KB batches skip compression (the serial-path floor): the
        # codec rides per-reply, so tiny steady-state batches stay "id"
        small = svc.dispatch(
            "wal_ship", [0, 2048, None, "log", None, None, 0.0, "zlib"]
        )
        assert small[4] == codec.IDENTITY
        assert (
            codec.decompress("id", np.asarray(small[1]).tobytes())
            == raw[: small[2]]
        )

        # an unknown offer degrades to identity, never an error
        unk = svc.dispatch(
            "wal_ship", [0, 1 << 20, None, "log", None, None, 0.0, "lz9"]
        )
        assert unk[4] == codec.IDENTITY
        assert codec.decompress("id", np.asarray(unk[1]).tobytes()) == raw

        # need_snapshot under an offer keeps the 7-shape with log_end
        ahead = svc.dispatch(
            "wal_ship",
            [end + 999, 1 << 20, None, "log", None, None, 0.0, "zlib"],
        )
        assert ahead[3] is True and len(ahead) == 7
        assert ahead[4] == codec.IDENTITY and ahead[6] == svc._wal.tell()
    finally:
        svc.stop()


def test_wal_ship_compresses_a_backlog_past_a_ratio_of_1_5(tmp_path, monkeypatch):
    """A follower's catch-up over a zlib offer ships the log in fewer
    than two thirds of its bytes."""
    monkeypatch.setenv("EULER_TPU_SNAPSHOT_EVERY", "0")
    g = Graph.from_json(_graph_dict(), num_partitions=1)
    svc = GraphService(g.shards[0], g.meta, 0, wal_dir=str(tmp_path / "wal"))
    try:
        for r, muts in enumerate(_rounds(6, k=24)):
            _dispatch_muts([svc], muts, f"r{r}")
        raw, _ = svc._wal.read_raw(0, 1 << 20)
        shipped = svc.dispatch(
            "wal_ship", [0, 1 << 20, None, "log", None, None, 0.0, "zlib"]
        )
        assert shipped[4] == "zlib"
        assert len(raw) / np.asarray(shipped[1]).nbytes > 1.5
    finally:
        svc.stop()


# -- pipelined replication: bit parity either way ------------------------


@pytest.mark.parametrize("pipeline", ["0", "1"])
def test_ship_pipeline_toggle_bit_parity(
    tmp_path, monkeypatch, patient_client, pipeline
):
    monkeypatch.setenv("EULER_TPU_SHIP_PIPELINE", pipeline)
    base, d, regdir, svcs = _boot_group(tmp_path, group_size=2)
    try:
        pri = _wait_single_primary(svcs)
        g = connect(registry_path=regdir, num_shards=1)
        w = GraphWriter(g)
        muts = []
        for seed in (31, 32, 33):
            batch = _muts(seed=seed, k=12)
            _route(w, batch)
            w.flush()
            muts += batch
        w.publish()
        w.close()
        _wait_converged(svcs, pri)
        merged = _apply_json(base, muts)
        _ref_meta, ref_shards = build_from_json(merged, 1)
        _assert_bit_identical(svcs, ref_shards[0])
        fol = next(s for s in svcs if s is not pri)
        st = fol._repl.status()
        assert st["ship_batches"] > 0
        # compression telemetry: wire bytes can exceed logical bytes
        # only by the per-batch codec frame header (tiny batches ride
        # identity under the 4KB floor), never more
        assert 0 < st["ship_wire_bytes"]
        assert st["ship_wire_bytes"] <= st["ship_bytes"] + 16 * st[
            "ship_batches"
        ]
        if pipeline == "0":
            assert st["ship_pipelined"] == 0
    finally:
        for s in svcs:
            s.stop()


# -- compressed backup archives ------------------------------------------


def test_backup_codec_zlib_roundtrip(tmp_path, monkeypatch):
    """EULER_TPU_BACKUP_CODEC=zlib: the archive shrinks and the restore
    is still bit-identical to the from-scratch oracle."""
    monkeypatch.setenv("EULER_TPU_SNAPSHOT_EVERY", "0")
    base = _graph_dict()
    g = Graph.from_json(base, num_partitions=1)
    wal_root = str(tmp_path / "wal")
    svc = GraphService(
        g.shards[0], g.meta, 0,
        wal_dir=os.path.join(wal_root, "shard_0"),
    )
    try:
        rounds = _rounds(2)
        _dispatch_muts([svc], rounds[0], "r0")
        _publish_all([svc], "pub0")
        assert svc.snapshot_now()
        _dispatch_muts([svc], rounds[1], "r1")
        _publish_all([svc], "pub1")

        def archive_size(arch):
            return sum(
                os.path.getsize(os.path.join(dp, f))
                for dp, _, fs in os.walk(arch)
                for f in fs
            )

        monkeypatch.setenv("EULER_TPU_BACKUP_CODEC", "id")
        arch_id = str(tmp_path / "arch_id")
        bk.backup_cluster(bk.collect_shard_dirs(wal_root), arch_id)
        monkeypatch.setenv("EULER_TPU_BACKUP_CODEC", "zlib")
        arch_zl = str(tmp_path / "arch_zl")
        man = bk.backup_cluster(bk.collect_shard_dirs(wal_root), arch_zl)
        assert man["shards"]["0"]["epoch"] == 2
        assert bk.verify_archive(arch_zl)["ok"]
        assert archive_size(arch_zl) < archive_size(arch_id)

        out = str(tmp_path / "restored")
        bk.restore_cluster(arch_zl, out)
        _, stores, _recs = _recover_restored(base, 1, out)
        _, ref = build_from_json(
            _apply_json(base, rounds[0] + rounds[1]), 1
        )
        _assert_bit_identical(
            [type("S", (), {"store": stores[0]})()], ref[0]
        )
        assert stores[0].graph_epoch == 2
    finally:
        svc.stop()
