"""TrainingSession — durable, preemption-safe training over the Estimator.

The last major component without crash-safety was the trainer itself:
`kill -9` mid-save destroyed the only checkpoint, SIGTERM was a hard
kill, resume never restored the batch stream position, and a NaN burst
or a hung device step took the run down silently. This module mirrors
the PR 9 shard discipline for trainer state:

- **Atomic retained checkpoints** (`checkpoint.CheckpointStore`): every
  cadence step commits a `ckpt_<step>/` dir via tmp + fsync + rename +
  COMMIT marker, keep-N retained. A crash mid-save can never lose the
  previous complete checkpoint.
- **Async save off the step path**: the device never stalls on disk —
  the step loop only snapshots host copies (one bounded device_get at
  cadence); a background writer commits them. `EULER_TPU_SAVE_ASYNC=0`
  forces inline saves.
- **Bit-exact resume**: the checkpoint carries the step, the opt_state,
  the batch-source cursor (`ResumableSource.cursor`), and the per-shard
  graph-epoch book. Under the standing seed contract, train-2N-straight
  equals train-N + kill -9 + resume-N, params and per-step losses
  bit-identical — the RNG streams (`_base_key`/`_flow_key`) are folded
  per GLOBAL step, so only the step and the source cursor need
  restoring.
- **Anomaly guard**: a jitted all-finite check over (loss, updated
  params) every `guard_every` steps, against a NON-donating step
  program (the pre-step state must survive a rejected update — see
  `_step_fn`). Policy "skip" drops the poisoned update and keeps the
  position; "rollback" reverts to the last-good in-memory snapshot and
  retries (transient-fault recovery); "abort" raises immediately. A
  bounded strike cap turns a persistent burst into a typed
  `AnomalyError` instead of an infinite skip/rollback loop.
- **Hung-step watchdog**: with `step_deadline_s` set, each step
  (draw + dispatch + guard fetch) runs under a wall-clock deadline on a
  watchdog worker; expiry dumps all-thread stacks to a diagnostic file
  and raises typed `HungStepError` instead of hanging the run.
- **SIGTERM drain**: the handler finishes the in-flight step, drains
  the on-device loss history, flushes a final checkpoint, and returns
  with `preempted=True` — the trainer-side analog of the PR 4 server
  drain.

Supervised restart closes the loop: `distributed.supervisor.
TrainerSupervisor` respawns a crashed `tools/train.py` with `--resume`,
so a `kill -9` of the trainer is a non-event end to end.
"""

from __future__ import annotations

import dataclasses
import faulthandler
import json
import os
import queue
import signal
import sys
import threading
import time

import numpy as np

from euler_tpu.training.checkpoint import CheckpointStore


class TrainingError(RuntimeError):
    """Base for typed trainer failures (never a silent hang/poison)."""


class AnomalyError(TrainingError):
    """Non-finite loss/params persisted past the strike cap (or the
    policy forbids recovery)."""


class HungStepError(TrainingError):
    """A step exceeded its wall-clock deadline; diagnostics were
    dumped before the abort."""


# ---------------------------------------------------------------------------
# resumable batch sources
# ---------------------------------------------------------------------------


class ResumableSource:
    """A batch source where draw i is a pure function of (seed, i).

    Each call derives a fresh Generator from SeedSequence([seed, i]) —
    the repo's standing per-draw seeding idiom — so `seek(i)` replays
    the stream from any position: the cursor IS the checkpointable
    dataflow position. `draw_fn(rng) -> tuple` builds one batch."""

    is_resumable = True

    def __init__(self, draw_fn, seed: int = 0, start: int = 0):
        self._draw_fn = draw_fn
        self._seed = int(seed)
        self._i = int(start)

    def __call__(self) -> tuple:
        rng = np.random.default_rng(
            np.random.SeedSequence([self._seed, self._i])
        )
        self._i += 1
        return self._draw_fn(rng)

    def cursor(self) -> int:
        """Number of draws taken so far (the checkpointed position)."""
        return self._i

    def seek(self, i: int) -> None:
        self._i = int(i)


def resumable_node_batches(
    graph, flow, batch_size: int, node_type: int = -1, seed: int = 0
) -> ResumableSource:
    """`node_batches` with a checkpointable cursor: roots AND the flow's
    neighbor sampling both draw from the per-step derived Generator, so
    a resumed trainer regenerates batch i bit-identically instead of
    inheriting a lost mid-run Generator state."""

    def draw(rng):
        if getattr(flow, "rng", None) is not None:
            flow.rng = rng  # sampling flows: make the draw pure in (seed, i)
        roots = graph.sample_node(batch_size, node_type, rng=rng)
        return (flow.query(roots),)

    return ResumableSource(draw, seed=seed)


# ---------------------------------------------------------------------------
# watchdog + async writer plumbing
# ---------------------------------------------------------------------------


class _DeadlineRunner:
    """Run closures on a daemon worker with a wall-clock deadline.

    A device step blocked in the runtime cannot be interrupted from
    Python; what CAN happen is the driver abandoning the wait, dumping
    diagnostics, and failing typed. A timed-out worker is left wedged
    (daemon) and a fresh one is spawned for any later call."""

    def __init__(self):
        self._lock = threading.Lock()
        self._q: queue.Queue | None = None

    def _ensure(self) -> queue.Queue:
        with self._lock:
            if self._q is None:
                self._q = queue.Queue()
                t = threading.Thread(
                    target=self._loop, args=(self._q,), daemon=True,
                    name="training-step-deadline",
                )
                t.start()
            return self._q

    @staticmethod
    def _loop(q: queue.Queue):
        while True:
            fn, box, done = q.get()
            try:
                box["result"] = fn()
            except BaseException as e:  # surfaced on the caller thread
                box["exc"] = e
            done.set()

    def call(self, fn, timeout_s: float):
        q = self._ensure()
        done = threading.Event()
        box: dict = {}
        q.put((fn, box, done))
        if not done.wait(timeout_s):
            with self._lock:
                self._q = None  # the worker is wedged; abandon it
            raise TimeoutError(f"step exceeded {timeout_s:.3f}s deadline")
        if "exc" in box:
            raise box["exc"]
        return box["result"]


class _AsyncSaver:
    """Background checkpoint writer: the step path hands over host
    snapshots; commits happen off it. Bounded queue (2) so a slow disk
    backpressures instead of accumulating whole-model host copies."""

    def __init__(self, store: CheckpointStore):
        self._store = store
        self._q: queue.Queue = queue.Queue(maxsize=2)
        self._lock = threading.Lock()
        self._error: Exception | None = None
        self._thread: threading.Thread | None = None

    def _ensure(self):
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name="training-ckpt-writer"
                )
                self._thread.start()

    def _loop(self):
        while True:
            step, p, o, meta = self._q.get()
            try:
                self._store.save_leaves(step, p, o, meta)
            except Exception as e:  # surfaced at the next submit/drain
                with self._lock:
                    self._error = e
            finally:
                self._q.task_done()

    def _raise_pending(self):
        with self._lock:
            err, self._error = self._error, None
        if err is not None:
            raise TrainingError(f"async checkpoint save failed: {err!r}") \
                from err

    def submit(self, step, p_leaves, o_leaves, meta):
        self._raise_pending()
        self._ensure()
        self._q.put((step, p_leaves, o_leaves, meta))

    def drain(self):
        """Block until every queued save committed; surface failures."""
        if self._thread is not None:
            self._q.join()
        self._raise_pending()


# ---------------------------------------------------------------------------
# the session
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SessionConfig:
    checkpoint_every: int = 50  # steps between retained checkpoints (0=end only)
    keep: int = 3  # retained complete checkpoints
    async_save: bool = True  # EULER_TPU_SAVE_ASYNC=0 overrides to False
    anomaly_policy: str = "skip"  # off | skip | rollback | abort
    guard_every: int = 1  # steps between all-finite checks (device sync each)
    max_strikes: int = 3  # anomalies per checkpoint interval before AnomalyError
    step_deadline_s: float = 0.0  # 0 = watchdog off
    handle_sigterm: bool = True  # drain + final checkpoint on SIGTERM
    drain_every: int = 1024  # on-device loss history drain chunk


class TrainingSession:
    """Durable training-session layer over one Estimator.

    `source` is the estimator's batch source when it supports the
    cursor protocol (`ResumableSource`); device flows need none (their
    batch stream derives from the global step). `graph` (optional)
    feeds the checkpointed graph-epoch book. Requires
    `cfg.steps_per_call == 1` on the estimator — multi-step scan
    dispatch puts checkpoint/anomaly boundaries inside one XLA call,
    which this layer deliberately refuses to blur."""

    def __init__(self, est, source=None, graph=None, cfg: SessionConfig | None = None):
        if int(getattr(est.cfg, "steps_per_call", 1)) > 1:
            raise ValueError(
                "TrainingSession drives single-step dispatches "
                "(steps_per_call=1): checkpoint, anomaly, and preemption "
                "boundaries must fall between optimizer steps"
            )
        self.est = est
        self.source = source
        self.graph = graph
        self.cfg = cfg or SessionConfig()
        if self.cfg.anomaly_policy not in ("off", "skip", "rollback", "abort"):
            raise ValueError(
                f"anomaly_policy: {self.cfg.anomaly_policy!r}"
            )
        if os.environ.get("EULER_TPU_SAVE_ASYNC", "1") == "0":
            self.cfg = dataclasses.replace(self.cfg, async_save=False)
        self.store = CheckpointStore(est.cfg.model_dir, keep=self.cfg.keep)
        self._saver = _AsyncSaver(self.store)
        self._runner = _DeadlineRunner()
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._guard = None
        self._step = None
        self._last_good: dict | None = None
        self._strikes = 0
        self._last_saved_step: int | None = None
        self._resumed_from: int | None = None
        self.telemetry = {
            "steps": 0,
            "saves": 0,
            "async_saves": 0,
            "save_stall_ms_total": 0.0,
            "anomalies": 0,
            "rollbacks": 0,
            "skipped_steps": [],
            "hung_aborts": 0,
            "preemptions": 0,
        }

    # -- state snapshot / restore ----------------------------------------

    def _cursor(self):
        if self.source is not None and hasattr(self.source, "cursor"):
            return int(self.source.cursor())
        if self.est._device_flow is not None:
            return int(self.est.step)  # keys fold per global step
        return None

    def _epoch_book(self) -> dict:
        """Per-shard graph epoch at checkpoint time: the resume proof's
        record of WHICH data version each step trained against (local
        stores expose graph_epoch; remote shards re-observe via the
        stats handshake)."""
        book: dict = {}
        g = self.graph
        for i, sh in enumerate(getattr(g, "shards", []) or []):
            ep = getattr(sh, "graph_epoch", None)
            if ep is None and hasattr(sh, "refresh_epoch"):
                try:
                    ep = sh.refresh_epoch()
                except Exception:
                    ep = None
            if ep is not None:
                book[str(i)] = int(ep)
        return book

    def _snapshot_state(self) -> dict:
        """Host copies of the full trainer state (the async writer's
        input AND the anomaly guard's rollback point)."""
        import jax

        est = self.est
        p_leaves, p_tdef = jax.tree_util.tree_flatten(est.params)
        o_leaves, o_tdef = jax.tree_util.tree_flatten(est.opt_state)
        # copy=True is load-bearing: on CPU device_get returns a VIEW of
        # the device buffer, and the donating train step deletes/reuses
        # that buffer on the very next dispatch — an aliased "snapshot"
        # would silently corrupt both the rollback point and the bytes
        # the async writer is committing
        host_p = [np.array(jax.device_get(x), copy=True) for x in p_leaves]
        host_o = [np.array(jax.device_get(x), copy=True) for x in o_leaves]
        return {
            "step": int(est.step),
            "cursor": self._cursor(),
            "p": host_p,
            "o": host_o,
            "p_sharding": [getattr(x, "sharding", None) for x in p_leaves],
            "o_sharding": [getattr(x, "sharding", None) for x in o_leaves],
            "p_tdef": p_tdef,
            "o_tdef": o_tdef,
        }

    def _install_state(self, snap: dict) -> None:
        import jax
        import jax.numpy as jnp

        def put(host, shardings, tdef):
            leaves = [
                jax.device_put(h, s) if s is not None else jnp.asarray(h)
                for h, s in zip(host, shardings)
            ]
            return jax.tree_util.tree_unflatten(tdef, leaves)

        est = self.est
        est.params = put(snap["p"], snap["p_sharding"], snap["p_tdef"])
        est.opt_state = put(snap["o"], snap["o_sharding"], snap["o_tdef"])
        est.step = int(snap["step"])
        if self.source is not None and snap.get("cursor") is not None and \
                hasattr(self.source, "seek"):
            self.source.seek(int(snap["cursor"]))

    def restore(self) -> dict | None:
        """Resume from the newest COMPLETE retained checkpoint: params,
        opt_state, step, source cursor. Returns the resume report (with
        the saved and live graph-epoch books), or None when there is
        nothing to resume from. A torn dir left by a crash mid-save is
        skipped by construction — `latest_step` only sees committed
        checkpoints."""
        step = self.store.latest_step()
        if step is None:
            return None
        est = self.est
        est._ensure_init()
        ckpt = self.store.load(step)
        import jax

        p_leaves, p_tdef = jax.tree_util.tree_flatten(est.params)
        o_leaves, o_tdef = jax.tree_util.tree_flatten(est.opt_state)
        if len(ckpt["params"]) != len(p_leaves) or \
                len(ckpt["opt_state"]) != len(o_leaves):
            raise TrainingError(
                f"checkpoint ckpt_{step:012d} has "
                f"{len(ckpt['params'])}+{len(ckpt['opt_state'])} leaves but "
                f"the live model has {len(p_leaves)}+{len(o_leaves)} — "
                "model/optimizer config drifted from the saved run"
            )
        snap = {
            "step": step,
            "cursor": ckpt["meta"].get("cursor"),
            "p": ckpt["params"],
            "o": ckpt["opt_state"],
            "p_sharding": [getattr(x, "sharding", None) for x in p_leaves],
            "o_sharding": [getattr(x, "sharding", None) for x in o_leaves],
            "p_tdef": p_tdef,
            "o_tdef": o_tdef,
        }
        self._install_state(snap)
        with self._lock:
            self._last_good = snap
            self._last_saved_step = step
            self._resumed_from = step
        saved_book = ckpt["meta"].get("graph_epochs") or {}
        live_book = self._epoch_book()
        return {
            "resumed": True,
            "step": step,
            "cursor": snap["cursor"],
            "graph_epochs": saved_book,
            "live_graph_epochs": live_book,
            "epoch_match": (
                all(live_book.get(k) == v for k, v in saved_book.items())
                if saved_book
                else None
            ),
        }

    # -- checkpointing ----------------------------------------------------

    def _checkpoint(self, final: bool = False) -> None:
        t0 = time.perf_counter()
        snap = self._snapshot_state()
        with self._lock:
            self._last_good = snap
            self._strikes = 0
        meta = {
            "cursor": snap["cursor"],
            "seed": int(self.est.cfg.seed),
            "graph_epochs": self._epoch_book(),
        }
        if self.cfg.async_save and not final:
            self._saver.submit(snap["step"], snap["p"], snap["o"], meta)
            with self._lock:
                self.telemetry["async_saves"] += 1
        else:
            # final flush orders behind every queued async commit
            self._saver.drain()
            self.store.save_leaves(snap["step"], snap["p"], snap["o"], meta)
        with self._lock:
            self.telemetry["saves"] += 1
            self.telemetry["save_stall_ms_total"] += (
                time.perf_counter() - t0
            ) * 1e3
            self._last_saved_step = snap["step"]

    def flush(self) -> None:
        """Commit every in-flight async save (operator surface)."""
        self._saver.drain()

    # -- the step program -------------------------------------------------

    def _step_fn(self):
        """The session's jitted single-step program — same math as the
        Estimator's shared step, but WITHOUT buffer donation.

        Donation is semantically at odds with this layer: the anomaly
        guard must be able to REJECT an update and keep the pre-step
        params/opt_state intact, and a donating step destroys them by
        design (worse: donating restore-produced device_put buffers is
        exactly the pattern that flakes on this backend — the rollback
        proof caught heap corruption there). The cost is keeping old and
        new state alive across one step; `Estimator.train()` keeps the
        donating fast path for guard-less runs."""
        with self._lock:
            if self._step is None:
                import jax

                from euler_tpu.estimator.estimator import (
                    _apply_update,
                    _bind_tables,
                    _step_args,
                )

                est = self.est

                def step(params, opt_state, tables, rngs, *batch):
                    flow, cache = _bind_tables(
                        est._device_flow, est.feature_cache, tables
                    )
                    return _apply_update(
                        est.model, est.tx, cache,
                        params, opt_state, rngs, _step_args(flow, batch),
                    )

                self._step = jax.jit(step)
            return self._step

    # -- anomaly guard ----------------------------------------------------

    def _guard_fn(self):
        with self._lock:
            if self._guard is None:
                import jax
                import jax.numpy as jnp

                @jax.jit
                def guard(loss, params):
                    # int leaves cast to f32 are always finite; float
                    # leaves carry a grad anomaly into the update, so
                    # all-finite(updated params) transitively covers
                    # all-finite(grads)
                    return jax.tree_util.tree_reduce(
                        lambda ok, leaf: ok & jnp.all(
                            jnp.isfinite(leaf.astype(jnp.float32))
                        ),
                        params,
                        jnp.all(
                            jnp.isfinite(jnp.asarray(loss, jnp.float32))
                        ),
                    )

                self._guard = guard
            return self._guard

    def _on_anomaly(self, step_no: int, history: list, losses: list):
        """One non-finite step. The non-donating step program means the
        pre-step params/opt_state are still intact, so policy "skip" is
        simply: drop the poisoned update, keep the position (the batch
        draw was consumed — cursor parity holds). Policy "rollback"
        reverts to the last-good snapshot and RETRIES from there
        (transient-fault recovery; a persistent anomaly re-trips and
        the strike cap converts it to a typed abort)."""
        with self._lock:
            self.telemetry["anomalies"] += 1
            self._strikes += 1
            strikes = self._strikes
        policy = self.cfg.anomaly_policy
        if policy == "abort" or strikes > self.cfg.max_strikes:
            raise AnomalyError(
                f"non-finite loss/params at step {step_no} "
                f"(policy={policy}, strike {strikes}/{self.cfg.max_strikes})"
            )
        if policy == "skip":
            self.est.step = step_no  # advance past the poisoned batch
            with self._lock:
                self.telemetry["skipped_steps"].append(step_no)
            return
        # policy == "rollback"
        replayable = (
            self.est._device_flow is not None
            or (self.source is not None and hasattr(self.source, "seek"))
        )
        if self._last_good is None or not replayable:
            raise AnomalyError(
                f"non-finite loss/params at step {step_no} "
                f"(policy=rollback, but last_good="
                f"{None if self._last_good is None else self._last_good['step']}"
                f" and replayable={replayable})"
            )
        snap = self._last_good
        self._install_state(snap)
        good = snap["step"]
        history[:] = [(s, x) for s, x in history if s <= good]
        losses[:] = [(s, v) for s, v in losses if s <= good]
        with self._lock:
            self.telemetry["rollbacks"] += 1

    # -- SIGTERM drain ----------------------------------------------------

    def _install_sigterm(self):
        if not self.cfg.handle_sigterm:
            return None
        if threading.current_thread() is not threading.main_thread():
            return None
        prev = signal.getsignal(signal.SIGTERM)

        def handler(signum, frame):
            self._stop.set()

        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:
            return None
        return prev

    # -- the loop ---------------------------------------------------------

    def _diag_dump(self, step_no: int, deadline_s: float) -> str:
        path = os.path.join(
            os.path.abspath(self.est.cfg.model_dir),
            f"hung_step_{step_no}.txt",
        )
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w", encoding="utf-8") as f:
                f.write(json.dumps({
                    "step": step_no,
                    "deadline_s": deadline_s,
                    "telemetry": {
                        k: v for k, v in self.telemetry.items()
                        if not isinstance(v, list)
                    },
                }) + "\n")
                faulthandler.dump_traceback(file=f, all_threads=True)
        except OSError:
            return "<diagnostic dump failed>"
        return path

    def run(self, steps: int | None = None, log: bool = False) -> dict:
        """Train `steps` more optimizer steps (default: cfg.total_steps)
        with durability, guard, watchdog, and drain semantics. Returns
        {"losses", "loss_steps", "start_step", "end_step", "preempted",
        "resumed_from", "telemetry"}."""
        est = self.est
        est._ensure_init()
        total = steps if steps is not None else est.cfg.total_steps
        target = est.step + int(total)
        step_fn = self._step_fn()
        guard_on = self.cfg.anomaly_policy != "off"
        guard = self._guard_fn() if guard_on else None
        prev_handler = self._install_sigterm()
        self._stop.clear()
        history: list = []  # (step, device loss) not yet drained
        losses: list = []  # (step, float)
        preempted = False
        t0 = time.time()

        def drain():
            if history:
                import jax.numpy as jnp

                stacked = np.asarray(jnp.stack([x for _, x in history]))
                losses.extend(
                    (s, float(v))
                    for (s, _), v in zip(history, stacked.tolist())
                )
                history.clear()

        try:
            while est.step < target:
                if self._stop.is_set():
                    preempted = True
                    with self._lock:
                        self.telemetry["preemptions"] += 1
                    break
                step_no = est.step + 1

                def one_step():
                    batch = est._next_batch(1)
                    p, o, loss, metric = step_fn(
                        est.params, est.opt_state, est._tables(),
                        est._rngs(est.step), *batch,
                    )
                    ok = True
                    if guard is not None and (
                        step_no % max(self.cfg.guard_every, 1) == 0
                    ):
                        ok = bool(guard(loss, p))
                    return p, o, loss, ok

                if self.cfg.step_deadline_s > 0:
                    try:
                        p, o, loss, ok = self._runner.call(
                            one_step, self.cfg.step_deadline_s
                        )
                    except TimeoutError:
                        with self._lock:
                            self.telemetry["hung_aborts"] += 1
                        diag = self._diag_dump(
                            step_no, self.cfg.step_deadline_s
                        )
                        raise HungStepError(
                            f"step {step_no} exceeded its "
                            f"{self.cfg.step_deadline_s:.3f}s deadline; "
                            f"all-thread diagnostics at {diag}"
                        ) from None
                else:
                    p, o, loss, ok = one_step()
                if not ok:
                    self._on_anomaly(step_no, history, losses)
                    continue
                est.params, est.opt_state = p, o
                est.step = step_no
                with self._lock:
                    self.telemetry["steps"] += 1
                history.append((step_no, loss))
                if len(history) >= max(self.cfg.drain_every, 1):
                    drain()
                if log and step_no % max(est.cfg.log_steps, 1) == 0:
                    drain()
                    dt = max(time.time() - t0, 1e-9)
                    print(
                        f"step {step_no}: loss={losses[-1][1]:.4f} "
                        f"({(step_no - (target - total)) / dt:.1f} it/s)"
                    )
                if (
                    self.cfg.checkpoint_every
                    and step_no % self.cfg.checkpoint_every == 0
                ):
                    self._checkpoint()
        finally:
            if prev_handler is not None:
                signal.signal(signal.SIGTERM, prev_handler)
            drain()
            # final flush: on clean exit AND on preemption; after an
            # exception est.params still hold the last ACCEPTED state
            # (poisoned updates are never installed), so a best-effort
            # save preserves real progress without masking the error
            exc_live = sys.exc_info()[0] is not None
            need_save = self._last_saved_step != est.step and est.params \
                is not None
            if need_save:
                if exc_live:
                    try:
                        self._checkpoint(final=True)
                    except Exception as e:
                        print(
                            f"# training: best-effort final checkpoint "
                            f"failed: {e!r}",
                            file=sys.stderr,
                        )
                else:
                    self._checkpoint(final=True)
            elif not exc_live:
                self._saver.drain()
        return {
            "losses": [v for _, v in losses],
            "loss_steps": [s for s, _ in losses],
            "start_step": target - total,
            "end_step": int(est.step),
            "preempted": preempted,
            "resumed_from": self._resumed_from,
            "telemetry": {
                k: (list(v) if isinstance(v, list) else v)
                for k, v in self.telemetry.items()
            },
        }
