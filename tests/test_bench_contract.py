"""bench.py is the driver's round artifact: its contract is ONE final
parseable JSON line with the headline metric. A regression here silently
destroys the round's recorded measurement, so the smoke path is gated."""

import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_device_entry_points_refuse_cpu(script):
    """No CPU fallback: on a host without a TPU the device bench (no
    --smoke) and chip_smoke.py exit non-zero in seconds, name the platform
    they found, and print no result line."""
    r = subprocess.run(
        [sys.executable, script],
        cwd=_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "tpu" in r.stderr, r.stderr[-500:]
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]


def test_chip_smoke_result_line_has_the_contract_keys_only():
    """The driver reads chip_smoke.py's last stdout line and refuses any
    key beyond ok / device{platform, kind, count}; the per-phase report
    goes on the `report:` line before it."""
    import jax

    sys.path.insert(0, _ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(_ROOT)
    devices = jax.devices()
    row = json.loads(chip_smoke.result_line(devices))
    assert row == {
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }


def test_bench_smoke_emits_final_json_line():
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        EULER_BENCH_REMOTE="0",  # local leg only: the contract's last line
    )
    r = subprocess.run(
        [sys.executable, "bench.py", "--smoke"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    json_lines = [
        ln for ln in r.stdout.splitlines() if ln.startswith("{")
    ]
    assert json_lines, r.stdout[-500:]
    row = json.loads(json_lines[-1])
    assert row["metric"] == "graphsage_sampled_edges_per_sec_per_chip"
    assert row["value"] > 0
    assert row["unit"] == "edges/s"
    assert "vs_baseline" in row and "backend" in row
    assert row["device_flow"] is True  # smoke covers the production default
    # the paged device-lane A/B (ISSUE 6) must not silently vanish: the
    # skewed weighted graph records paged vs dense sampling throughput
    # and the standing bit-identity oracle on the artifact
    assert row["paged"] is True, row
    assert row["paged_bit_identical"] is True
    assert row["paged_sample_edges_per_sec"] > 0
    assert row["dense_sample_edges_per_sec"] > 0
    assert row["paged_over_dense"] > 0
    # the streaming-mutation lane (ISSUE 8) must not silently vanish:
    # writer staging throughput, publish latency at both delta sizes,
    # post-publish read recovery, and the merged == from-scratch parity
    # oracle all ride the artifact
    assert row["mutation"] is True, row
    assert row["mutation_bit_parity"] is True, row
    assert row["mutation_upserts_per_sec"] > 0
    assert row["mutation_publish_ms_small"] > 0
    assert row["mutation_publish_ms_large"] > 0
    assert row["mutation_read_recovery_ms"] > 0
    assert row["mutation_read_rate_post_over_pre"] > 0
    # the durability lane (ISSUE 9) must not silently vanish: acked
    # writes/s with fsync on vs off (the cadence/throughput tradeoff),
    # snapshot cost, crash→recovered-first-read latency, and the
    # recovered == pre-crash bit-parity oracle all ride the artifact
    assert row["durability"] is True, row
    assert row["durability_recovered_bit_parity"] is True, row
    assert row["durability_acked_writes_per_sec_fsync"] > 0
    assert row["durability_acked_writes_per_sec_nofsync"] > 0
    # fsync can only cost throughput, never add it (allow noise)
    assert row["durability_fsync_overhead_x"] >= 0.8, row
    assert row["durability_snapshot_ms"] > 0
    assert row["durability_recovery_ms"] > 0
    # the availability lane (ISSUE 13) must not silently vanish: acked
    # rows/s under quorum vs async vs solo acks, the lease-bounded
    # write-unavailability window across a primary kill, follower
    # catch-up MB/s over wal_ship, and the caught-up follower ==
    # primary bit-parity oracle all ride the artifact
    assert row["availability"] is True, row
    assert row["availability_bit_parity"] is True, row
    assert row["availability_unavail_window_ms"] > 0
    assert row["availability_quorum_rows_per_sec"] > 0
    assert row["availability_async_rows_per_sec"] > 0
    assert row["availability_solo_rows_per_sec"] > 0
    # a quorum ack adds a follower round trip; it can only cost
    # throughput relative to solo, never add it (allow noise)
    assert row["availability_quorum_overhead_x"] >= 0.8, row
    assert row["availability_catchup_mb_per_sec"] > 0
    # the durable-training resume lane (ISSUE 10) must not silently
    # vanish: the sync-vs-async save stall A/B (the cadence/step-time
    # tradeoff), resume-to-first-step latency, retained-checkpoint disk
    # footprint, and the train-2N == train-N + resume-N bit-parity
    # oracle all ride the artifact
    assert row["resume"] is True, row
    assert row["resume_bit_parity"] is True, row
    assert row["resume_save_sync_ms"] > 0
    assert row["resume_save_async_stall_ms"] >= 0
    # the async writer exists to take the commit off the step path; the
    # stall it leaves (host snapshot + enqueue) must not exceed the
    # full inline commit (allow noise)
    assert (
        row["resume_save_async_stall_ms"]
        <= row["resume_save_sync_ms"] * 1.5
    ), row
    assert row["resume_to_first_step_ms"] > 0
    assert row["resume_ckpt_bytes"] > 0
    assert row["resume_retained_ckpts"] >= 1
    # the whole-graph analytics lane (ISSUE 12) must not silently
    # vanish: PageRank sweep rate over the sharded engine, frontier
    # exchange bytes, the incremental-vs-full replay speedup after a
    # live publish, and the 1-shard == 2-shard == incremental
    # bit-parity oracle all ride the artifact
    assert row["analytics"] is True, row
    assert row["analytics_bit_parity"] is True, row
    assert row["analytics_pagerank_sweeps_per_sec"] > 0
    assert row["analytics_exchange_bytes"] > 0
    assert row["analytics_incremental_speedup_x"] > 0
    # the incremental rerun must actually skip work, not just match bits
    assert 0 < row["analytics_rows_recomputed_ratio"] < 1, row
    # the disaster-recovery lane (ISSUE 15) must not silently vanish:
    # backup MB/s, total-loss restore-to-first-read latency, at-rest
    # scrub MB/s, the worst-case scrub-vs-reader interference ratio,
    # and the restored == archived bit-parity oracle all ride the
    # artifact
    assert row["dr"] is True, row
    assert row["dr_bit_parity"] is True, row
    assert row["dr_backup_mb_per_sec"] > 0
    assert row["dr_archive_mb"] > 0
    assert row["dr_restore_to_first_read_ms"] > 0
    assert row["dr_scrub_mb_per_sec"] > 0
    assert row["dr_read_rate_scrub_over_idle"] > 0
    # the byte-budget lane (ISSUE 16) must not silently vanish
    # (EULER_BENCH_BYTES=0 is the opt-out — default is on): quantized
    # dense wire A/B, warm-cache residency, delta-coded neighbor
    # planes, and the compressed + pipelined wal_ship A/B all ride the
    # artifact
    assert row["bytes"] is True, row
    assert row["bytes_dense_f32_per_batch"] > 0
    assert row["bytes_dense_bf16_per_batch"] > 0
    assert row["bytes_dense_int8_per_batch"] > 0
    # bf16 pages halve every dense payload; headers are noise at any
    # batch size, so the wire reduction holds even in smoke
    assert row["bytes_dense_reduction_pct"] >= 40, row
    # quantization error must be nonzero (it IS lossy) yet inside the
    # pinned per-row bf16 budget (PARITY.md)
    assert 0 < row["bytes_dense_bf16_max_err"] < 0.05, row
    assert row["bytes_warm_cache_saved_pct"] > 0, row
    # delta + varint must beat raw u64 planes on sorted neighbor ids
    assert row["bytes_full_nb_delta"] < row["bytes_full_nb_raw"], row
    # the wal_ship A/B: both codec legs measured in the same run
    assert row["bytes_catchup_mb_per_sec_id"] > 0
    assert row["bytes_catchup_mb_per_sec_zlib"] > 0
    assert row["bytes_quorum_overhead_x_id"] >= 0.8, row
    assert row["bytes_quorum_overhead_x_zlib"] >= 0.8, row
    # shipping WAL batches must actually compress...
    assert row["bytes_ship_compression_ratio"] > 1.5, row
    # ...and the follower must actually overlap apply with the next
    # fetch (speculative requests answered, not lockstep)
    assert row["bytes_ship_pipelined_batches"] >= 1, row
    # the retrieval-serving lane (ISSUE 17) must not silently vanish:
    # fleet top-K throughput, latency tails, the router's merge share,
    # the filtered/unfiltered ratio, and — the key that gates every
    # other number — the standing bitwise oracle
    assert row["retrieval"] is True, row
    assert row["retrieval_queries_per_sec"] > 0
    assert row["retrieval_p50_ms"] > 0
    assert row["retrieval_p99_ms"] >= row["retrieval_p50_ms"]
    assert row["retrieval_filtered_over_unfiltered"] > 0
    assert 0 <= row["retrieval_merge_overhead_pct"] <= 100
    assert row["retrieval_bit_parity"] is True, row
    # the elastic-reshard lane (ISSUE 19) must not silently vanish
    # (EULER_BENCH_RESHARD=0 is the opt-out — default is on): pure
    # repartition throughput, the fence-to-commit cutover window, the
    # writer-OBSERVED unavailability gap through a live 2 -> 3 split,
    # and the resharded == from-scratch bit-parity oracle
    assert row["reshard"] is True, row
    assert row["reshard_bit_parity"] is True, row
    assert row["reshard_rows_per_sec"] > 0
    assert row["reshard_cutover_ms"] > 0
    # the client kept writing through the cutover: the observed gap is
    # bounded (a few lease TTLs), not a stop-the-world migration
    assert 0 < row["reshard_unavail_ms"] < 60_000, row
    # the serving lane rode along: its own JSON line with latency
    # percentiles and the coalescing ratio, plus a summary on the
    # re-emitted headline
    serving = [
        json.loads(ln)
        for ln in json_lines
        if json.loads(ln).get("metric") == "gnn_serving_requests_per_sec"
    ]
    assert serving, json_lines
    srow = serving[-1]
    assert srow["value"] > 0 and srow["unit"] == "req/s"
    assert srow["p50_ms"] > 0 and srow["p99_ms"] >= srow["p50_ms"]
    # the micro-batcher must actually coalesce under 8 concurrent clients
    assert 0 < srow["batches_per_100_requests"] < 100
    assert row["serving_requests_per_sec"] == srow["value"]
    # the recovery lane rode along too: seeded replica kill, failover
    # proven by retry telemetry, deadline plumbing overhead recorded
    recovery = [
        json.loads(ln)
        for ln in json_lines
        if json.loads(ln).get("metric")
        == "rpc_recovery_time_to_first_batch_ms"
    ]
    assert recovery, json_lines
    rrow = recovery[-1]
    assert rrow["value"] > 0 and rrow["unit"] == "ms"
    assert rrow["failover_retries"] > 0
    assert rrow["per_batch_ms"] > 0
    assert "deadline_wire_overhead_pct" in rrow
    assert row["recovery_ttfb_ms"] == rrow["value"]
    # the serving-fleet lane rode along (ISSUE 7): replicated routing,
    # seeded-straggler hedging, and hot-reload parity on the artifact
    fleet = [
        json.loads(ln)
        for ln in json_lines
        if json.loads(ln).get("metric") == "gnn_fleet_requests_per_sec"
    ]
    assert fleet, json_lines
    frow = fleet[-1]
    assert frow["value"] > 0 and frow["unit"] == "req/s"
    assert frow["fleet_req_per_sec"] == frow["value"]
    assert frow["solo_req_per_sec"] > 0
    assert frow["fleet_scaling_4x"] > 0
    if frow["fleet_cores"] >= 4:
        # the 1->4 replica scaling claim needs cores to scale ONTO; on
        # smaller hosts the ratio is recorded but physically capped ~1x
        assert frow["fleet_scaling_4x"] >= 2.5, frow
    # hedging must measurably cut p99 under the seeded straggler while
    # staying inside the hedge token bucket
    assert frow["hedged_p99_ms"] > 0
    assert frow["hedged_p99_ms"] < frow["unhedged_p99_ms"], frow
    assert frow["hedges_issued"] > 0 and frow["hedged_within_budget"], frow
    # bit-parity proofs pinned on the artifact
    assert frow["fleet_bit_parity"] is True
    assert frow["reload_parity"] is True
    # fleet summary attached to the re-emitted headline
    assert row["fleet_req_per_sec"] == frow["value"]
    assert row["hedged_p99_ms"] == frow["hedged_p99_ms"]
    assert row["reload_parity"] is True
    assert "fleet_scaling_4x" in row


def test_bench_smoke_remote_lane_cache_fields():
    """The remote lane's artifact must carry the read-cache sub-metrics:
    hit rate, dedup byte accounting, and the uncached/cold/warm A/B
    (EULER_BENCH_CACHE=0 would drop them — default is on)."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench.py", "--smoke", "--remote-only"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    json_lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert json_lines, r.stdout[-500:]
    row = json.loads(json_lines[-1])
    assert row["metric"] == "graphsage_remote_edges_per_sec_per_chip"
    assert row["value"] > 0, row
    assert row["cache_hit_rate"] > 0
    assert row["dedup_bytes_saved"] > 0
    for k in (
        "cache_uncached_edges_per_sec",
        "cache_cold_edges_per_sec",
        "cache_warm_edges_per_sec",
        "cache_warm_over_uncached",
    ):
        assert row[k] > 0, (k, row)
    # the remote paged device sub-lane (ISSUE 6): the adjacency staged
    # over the wire, per-step sampling fully on device, and residual row
    # fetches served through the client ReadCache — these keys gone means
    # the lane silently vanished from the artifact
    assert row["device_flow"] is True, row
    assert row["paged"] is True, row
    assert row["paged_device_edges_per_sec"] > 0
    assert row["residual_fetch_hit_rate"] > 0, row
    assert row["residual_rows_refetched"] > 0


def test_lint_json_lane_per_checker_counts():
    """The lint lane's JSON line (graftlint v2): every registered checker
    must publish a count key — a checker silently dropping out of the
    counts dict means the lane stopped measuring it — and the full-run
    wall time rides along so regressions in analysis cost are visible."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "euler_tpu.tools.lint", "--json"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row["ok"] is True, row
    expected = {
        "blocking-under-lock",
        "borrowed-buffer-escape",
        "determinism",
        "durable-write",
        "executor-deadlock",
        "hot-swap-reread",
        "jit-purity",
        "lock-discipline",
        "typed-error-retry",
        "unbounded-cache",
        "wire-protocol",
    }
    assert set(row["counts"]) == expected, row["counts"]
    assert all(v == 0 for v in row["counts"].values()), row["counts"]
    assert row["files"] > 100, row
    assert isinstance(row["wall_s"], float) and row["wall_s"] > 0, row
