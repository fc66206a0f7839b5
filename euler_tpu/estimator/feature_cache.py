"""HBM-resident node feature cache.

The reference fetches dense features from the graph engine per batch and
ships them through the TF op boundary (feature_ops.py, get_dense_feature
kernels). On TPU the equivalent boundary — host→device transfer — is the
throughput ceiling: a 2-hop fanout batch carries ~B·k1·k2·F floats. The
TPU-native answer is to load the dense feature table into device HBM once
and ship only int32 row indices per batch; the gather runs on device inside
the jitted step, where XLA fuses it with the first layer's matmul.

Pair with DataFlow(feature_mode="rows"): hop feature slots then hold int32
rows into this cache's table (row 0 = zero/padding row), and
`hydrate(batch)` — called inside jit by the Estimator — turns them back
into dense per-hop matrices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from euler_tpu.dataflow.base import MiniBatch
from euler_tpu.utils import trace
from euler_tpu.utils.staged import StagedTables


def _is_rows(x) -> bool:
    return getattr(x, "ndim", None) == 1 and jnp.issubdtype(
        jnp.asarray(x).dtype, jnp.integer
    )


class DeviceFeatureCache(StagedTables):
    """Device copy of a graph's dense feature table, +1 zero padding row.
    The Estimator's programs take the table (and an int8 table's scale
    and zero point) as an argument, `tables()`, read at every dispatch."""

    def __init__(
        self,
        graph,
        feature_names,
        dtype=jnp.float32,
        sharding=None,
        quant: str | None = None,
    ):
        """quant: HBM page dtype — "f32" (exact, the default), "bf16" (half
        the HBM, one rounding per value), or "int8" (quarter the HBM,
        per-row affine scale/zero-point) — defaults to the
        EULER_TPU_PAGE_DTYPE env knob. Dequantize happens inside
        `gather`, where XLA fuses it with the first layer's matmul; the
        error budget per dtype is pinned in PARITY.md and enforced by
        tests. Explicit non-f32 `dtype` wins over `quant` (the caller
        already chose a representation)."""
        from euler_tpu.distributed.codec import page_dtype, quantize

        self.feature_names = list(feature_names)
        self.quant = (
            (quant if quant is not None else page_dtype())
            if np.dtype(dtype) == np.float32
            else "f32"
        )
        with trace.span("stage.features"):
            host = graph.dense_feature_table(self.feature_names)
            self.dim = host.shape[1]
            table = np.concatenate(
                [np.zeros((1, self.dim), np.float32), host], axis=0
            )
            if self.quant == "int8":
                q, scale, zero = quantize("int8", table)
                # padding row 0 dequantizes to exact zeros: q=0, zero=0
                zero[0] = 0.0
                self._scale = jax.device_put(scale)
                self._zero = jax.device_put(zero)
                table = q
            elif self.quant == "bf16":
                table = table.astype(jnp.bfloat16)
            else:
                table = table.astype(np.dtype(dtype))
            self.table = jax.device_put(table, sharding)

    def gather(self, rows) -> jnp.ndarray:
        """int32 rows (0 = padding) → dense [n, F]; jit-safe. Quantized
        tables dequantize here — next to the consuming matmul, so XLA
        fuses it and the host/HBM copies stay compact."""
        if self.quant == "int8":
            q = self.table[rows].astype(jnp.float32)
            return q * self._scale[rows][..., None] + (
                self._zero[rows][..., None]
            )
        if self.quant == "bf16":
            return self.table[rows].astype(jnp.float32)
        return self.table[rows]

    def _patch(self, rows, vals) -> None:
        """Write f32 values into table rows (row+1 space already applied
        by the caller), re-quantizing to the table's representation."""
        from euler_tpu.distributed.codec import quantize

        if self.quant == "int8":
            q, scale, zero = quantize(
                "int8", np.asarray(vals, np.float32)
            )
            self.table = self.table.at[rows].set(jnp.asarray(q))
            self._scale = self._scale.at[rows].set(jnp.asarray(scale))
            self._zero = self._zero.at[rows].set(jnp.asarray(zero))
            return
        self.table = self.table.at[rows].set(
            jnp.asarray(vals, dtype=self.table.dtype)
        )

    def refresh_rows(self, graph, rows) -> int:
        """Residual re-staging: refetch ONLY the given global rows and
        patch them into the device table (row+1 space, row 0 stays the
        zero/padding row). The cheap path after a `graph_epoch` bump —
        mutated hot rows re-stage in one small transfer instead of
        re-shipping the whole table. Against a remote graph the fetch
        rides `get_dense_by_rows`, so the client read cache's residual
        logic applies to it too. Returns how many rows were re-staged."""
        rows = np.unique(np.asarray(rows, dtype=np.int64).reshape(-1))
        rows = rows[(rows >= 0) & (rows + 1 < self.table.shape[0])]
        if not len(rows):
            return 0
        vals = np.asarray(
            graph.get_dense_by_rows(rows, self.feature_names), np.float32
        )
        self._patch(rows + 1, vals)
        return int(len(rows))

    def hydrate(self, batch):
        """MiniBatch with rows-mode feature slots → dense feature slots.

        Non-MiniBatch args and already-dense batches pass through, so the
        Estimator can apply this uniformly to every model argument.
        """
        if not isinstance(batch, MiniBatch) or not batch.feats:
            return batch
        if not _is_rows(batch.feats[0]):
            return batch
        return batch.replace(
            feats=tuple(self.gather(r) for r in batch.feats)
        )

    def hydrate_args(self, args: tuple) -> tuple:
        return tuple(self.hydrate(a) for a in args)


class ResidualFetchRing:
    """Double-buffered background re-stager for device-resident tables —
    the residual lane of the paged device-sampling flow.

    The device lane stages everything once at construction; afterwards
    the only host↔wire traffic is RESIDUAL: rows invalidated by a
    `graph_epoch` bump, or rows a caller wants re-warmed. Those fetches
    must never stall the device, so they run on a background worker into
    a bounded ring of host buffers (fetch job N+1 is on the wire while
    the trainer consumes job N) and `commit()` patches finished buffers
    into the device table between dispatches — the swap point. Against a
    remote graph the fetch path is `get_dense_by_rows`, a deterministic
    verb served by the PR-5 client ReadCache: staging warmed the cache,
    so residual fetches are mostly client-side hits and
    `stats()["residual_fetch_hit_rate"]` reports exactly that (the bench
    remote lane's telemetry key).

    Epoch handshake: `poll_epoch()` re-reads each remote shard's
    graph_epoch via `refresh_epoch()` (which already flushes that
    shard's ReadCache on a bump) and schedules a residual refresh of the
    tracked rows, so the device table converges on the new epoch without
    a full re-stage — `DeviceFeatureCache.refresh_rows` is the one-shot
    synchronous form of the same move.
    """

    def __init__(self, cache: DeviceFeatureCache, graph, depth: int = 2):
        import queue
        import threading

        self.cache = cache
        self.graph = graph
        self._jobs: "queue.Queue" = queue.Queue(maxsize=max(int(depth), 1))
        self._ready: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._inflight = 0
        self._epochs: dict[int, int] = {}  # bounded: one entry per shard
        # telemetry (GIL-racy increments fine — repo counter stance)
        self.fetched_rows = 0
        self.commits = 0
        base = self._cache_stats()
        self._hit_base = (
            {"hits": base.get("hits", 0), "misses": base.get("misses", 0)}
            if base
            else {"hits": 0, "misses": 0}
        )
        self._worker = threading.Thread(
            target=self._work, daemon=True, name="residual-fetch-ring"
        )
        self._worker.start()

    def _cache_stats(self) -> dict | None:
        from euler_tpu.distributed.cache import graph_cache_stats

        return graph_cache_stats(self.graph)

    # -- producer side ---------------------------------------------------

    def prefetch(self, rows) -> bool:
        """Schedule a residual fetch of the given global rows (row space
        of lookup_rows, NOT row+1). Non-blocking: False when the ring is
        full — the caller retries at the next swap point instead of
        stalling the step."""
        import queue

        rows = np.unique(np.asarray(rows, dtype=np.int64).reshape(-1))
        rows = rows[(rows >= 0) & (rows + 1 < self.cache.table.shape[0])]
        if not len(rows):
            return False
        with self._lock:
            try:
                self._jobs.put_nowait(rows)
            except queue.Full:
                return False
            self._inflight += 1
        return True

    def poll_epoch(self, hot_rows=None) -> bool:
        """Re-observe each shard's graph_epoch (refresh_epoch flushes the
        shard's ReadCache on a bump); on any bump, schedule a residual
        refresh of `hot_rows` (default: the whole table, best-effort —
        repeated polls converge when the ring was full). Returns True
        when a bump was observed."""
        bumped = False
        for sh in getattr(self.graph, "shards", []) or []:
            fn = getattr(sh, "refresh_epoch", None)
            ep = int(fn()) if fn is not None else int(
                getattr(sh, "graph_epoch", 0)
            )
            part = int(getattr(sh, "part", 0))
            with self._lock:
                last = self._epochs.get(part)
                self._epochs[part] = ep
            if last is not None and ep != last:
                bumped = True
        if bumped:
            rows = (
                np.arange(self.cache.table.shape[0] - 1, dtype=np.int64)
                if hot_rows is None
                else np.asarray(hot_rows, dtype=np.int64)
            )
            for lo in range(0, len(rows), 65536):
                if not self.prefetch(rows[lo : lo + 65536]):
                    break  # ring full: the next poll re-schedules
        return bumped

    def on_publish(self, result) -> bool:
        """Eager half of the epoch handshake when the WRITER lives in
        this process: feed `GraphWriter.publish()`'s dict straight in.
        The publish's mutated global rows are scheduled for residual
        refresh (whole table when the publish could not name them), and
        the per-shard epoch book syncs to the published epochs so the
        next `poll_epoch()` doesn't schedule the same refresh twice.
        Remote-only readers keep using `poll_epoch()` — this is the
        zero-latency path for the process that did the publishing.
        Returns True when a refresh was scheduled."""
        rows = result.get("rows") if isinstance(result, dict) else result
        if isinstance(result, dict):
            for part, ep in (result.get("epochs") or {}).items():
                with self._lock:
                    self._epochs[int(part)] = int(ep)
        rows = np.asarray(
            np.arange(self.cache.table.shape[0] - 1) if rows is None
            else rows,
            dtype=np.int64,
        )
        scheduled = False
        for lo in range(0, len(rows), 65536):
            if not self.prefetch(rows[lo : lo + 65536]):
                break  # ring full: poll_epoch/commit cadence catches up
            scheduled = True
        return scheduled

    # -- worker / consumer side ------------------------------------------

    def _work(self):
        while True:
            rows = self._jobs.get()
            if rows is None:
                return
            try:
                vals = np.asarray(
                    self.graph.get_dense_by_rows(
                        rows, self.cache.feature_names
                    ),
                    np.float32,
                )
                self._ready.put((rows, vals))
            except Exception as e:  # surfaced to the caller at commit()
                self._ready.put((rows, e))

    def commit(self) -> int:
        """Patch every FINISHED buffer into the device table (call
        between dispatches). Returns rows patched; re-raises the first
        fetch error, if any."""
        import queue

        n = 0
        err = None
        while True:
            try:
                rows, vals = self._ready.get_nowait()
            except queue.Empty:
                break
            with self._lock:
                self._inflight -= 1
            if isinstance(vals, Exception):
                err = err or vals
                continue
            self.cache._patch(rows + 1, vals)
            n += len(rows)
        if n:
            self.commits += 1
            self.fetched_rows += n
        if err is not None:
            raise err
        return n

    def flush(self, timeout_s: float = 30.0) -> int:
        """Wait for every in-flight fetch and commit it (test/shutdown
        convenience — the training loop uses commit() alone)."""
        import time

        deadline = time.monotonic() + timeout_s
        n = self.commit()
        while True:
            with self._lock:
                idle = self._inflight == 0
            if idle or time.monotonic() > deadline:
                break
            time.sleep(0.005)
            n += self.commit()
        return n + self.commit()

    def stats(self) -> dict:
        st = self._cache_stats() or {}
        hits = int(st.get("hits", 0)) - self._hit_base["hits"]
        misses = int(st.get("misses", 0)) - self._hit_base["misses"]
        lookups = hits + misses
        with self._lock:
            inflight = self._inflight
        return {
            "fetched_rows": self.fetched_rows,
            "commits": self.commits,
            "inflight": inflight,
            "residual_fetch_hit_rate": (
                round(hits / lookups, 4) if lookups else 0.0
            ),
        }

    def close(self):
        self._jobs.put(None)
        self._worker.join(timeout=5.0)
