"""Atomic retained checkpoints — the trainer's durability floor.

PR 9 gave every graph shard a crash-safe on-disk story (WAL + atomic
snapshots); this module is the same discipline for trainer state. The
old `Estimator.save()` overwrote ONE fixed Orbax path with ``force=True``
— a `kill -9` landing mid-save destroyed the only checkpoint in
existence. Here a checkpoint is a step-numbered directory that either
exists completely or not at all:

    model_dir/
      ckpt_000000000040/
        tensors.bin / tensors.idx / tensors.json   (graph/format.py —
            the params + opt_state leaves, flattened in tree order)
        meta.json    {step, leaf counts, session extras: source cursor,
                      graph-epoch book, seed}
        COMMIT       the commit marker, written + fsync'd LAST

Write protocol (`CheckpointStore.save`): everything lands in
``ckpt_<step>.tmp-<pid>`` first, every file is fsync'd, the COMMIT
marker is written last, then ONE ``os.replace`` publishes the directory
and the parent dir is fsync'd. A crash at ANY point leaves either the
previous complete checkpoints untouched plus a reapable ``.tmp-`` dir,
or the new checkpoint fully committed — there is no state in which a
reader can observe a torn checkpoint as current (the torn-dir sweep in
tests/test_training_session.py walks every crash point).

Read protocol: only directories whose COMMIT marker exists and parses
count. `latest_step` / `restore` pick the NEWEST complete one, so a
crash mid-save can never lose the previous good state, and the serving
hot-reload watcher (`watch_signature`) can never trigger on — or load —
a half-written checkpoint.

Retention: `keep` newest complete checkpoints survive each save
(default 3); older ones and stale tmp dirs are reaped after commit.
"""

from __future__ import annotations

import json
import os
import shutil
import time

import numpy as np

from euler_tpu.graph import format as tformat

PREFIX = "ckpt_"
MARKER = "COMMIT"


def _fsync_path(path: str) -> None:
    """fsync one already-written file (Linux allows fsync on O_RDONLY)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def step_of(name: str) -> int | None:
    """`ckpt_000000000040` -> 40; None for anything else (tmp dirs,
    unrelated files)."""
    if not name.startswith(PREFIX) or ".tmp-" in name:
        return None
    tail = name[len(PREFIX):]
    if not tail.isdigit():
        return None
    return int(tail)


def is_complete(path: str) -> bool:
    """A checkpoint dir counts only with a parseable COMMIT marker —
    the write protocol's last act, so marker present ⇒ every byte
    before it was fsync'd."""
    marker = os.path.join(path, MARKER)
    try:
        with open(marker, encoding="utf-8") as f:
            json.load(f)
        return True
    except (OSError, ValueError):
        return False


class CheckpointStore:
    """Keep-N atomic retained checkpoints under one model_dir."""

    def __init__(self, root: str, keep: int = 3):
        self.root = os.path.abspath(root)
        self.keep = max(int(keep), 1)

    def _path(self, step: int) -> str:
        return os.path.join(self.root, f"{PREFIX}{int(step):012d}")

    # -- read side -------------------------------------------------------

    def steps(self) -> list[int]:
        """Committed checkpoint steps, ascending."""
        if not os.path.isdir(self.root):
            return []
        out = []
        for name in os.listdir(self.root):
            s = step_of(name)
            if s is not None and is_complete(os.path.join(self.root, name)):
                out.append(s)
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.steps()
        return steps[-1] if steps else None

    def load(self, step: int | None = None) -> dict:
        """Load one complete checkpoint: {"step", "meta", "params",
        "opt_state"} with params/opt_state as leaf lists in tree-flatten
        order. step=None loads the newest complete one."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no complete checkpoint under {self.root!r}"
                )
        path = self._path(step)
        if not is_complete(path):
            raise FileNotFoundError(f"{path}: checkpoint is not complete")
        with open(os.path.join(path, "meta.json"), encoding="utf-8") as f:
            meta = json.load(f)
        arrays = tformat.read_arrays(path, mmap=False)
        n_p = int(meta["num_params_leaves"])
        n_o = int(meta["num_opt_leaves"])
        # the tensor-dir format promotes 0-d leaves to (1,) (an
        # ascontiguousarray artifact); the recorded shapes restore them
        p_shapes = meta.get("param_shapes") or [None] * n_p
        o_shapes = meta.get("opt_shapes") or [None] * n_o
        params = [
            arrays[f"p_{i:05d}"].reshape(p_shapes[i])
            if p_shapes[i] is not None
            else arrays[f"p_{i:05d}"]
            for i in range(n_p)
        ]
        opt = [
            arrays[f"o_{i:05d}"].reshape(o_shapes[i])
            if o_shapes[i] is not None
            else arrays[f"o_{i:05d}"]
            for i in range(n_o)
        ]
        return {
            "step": int(meta["step"]),
            "meta": meta,
            "params": params,
            "opt_state": opt,
        }

    # -- write side ------------------------------------------------------

    def save_leaves(
        self,
        step: int,
        params_leaves: list[np.ndarray],
        opt_leaves: list[np.ndarray],
        extra_meta: dict | None = None,
    ) -> str:
        """Commit one checkpoint atomically; returns the committed path.

        Leaves must already be HOST arrays (the async writer hands them
        over pre-snapshotted so this whole function can run off the step
        path). Single-writer discipline: concurrent savers to one
        model_dir are not supported (the supervisor guarantees one
        trainer per dir)."""
        final = self._path(step)
        if os.path.isdir(final) and is_complete(final):
            return final  # re-saving a committed step is a no-op
        os.makedirs(self.root, exist_ok=True)
        tmp = f"{final}.tmp-{os.getpid()}"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        arrays = {f"p_{i:05d}": np.asarray(v)
                  for i, v in enumerate(params_leaves)}
        arrays.update(
            {f"o_{i:05d}": np.asarray(v) for i, v in enumerate(opt_leaves)}
        )
        tformat.write_arrays(tmp, arrays)
        meta = {
            "version": 1,
            "step": int(step),
            "num_params_leaves": len(params_leaves),
            "num_opt_leaves": len(opt_leaves),
            "param_shapes": [
                list(np.asarray(v).shape) for v in params_leaves
            ],
            "opt_shapes": [list(np.asarray(v).shape) for v in opt_leaves],
            "ts": time.time(),
        }
        if extra_meta:
            meta.update(extra_meta)
        with open(os.path.join(tmp, "meta.json"), "w", encoding="utf-8") as f:
            json.dump(meta, f)
            f.flush()
            os.fsync(f.fileno())
        for name in ("tensors.bin", "tensors.idx", "tensors.json"):
            _fsync_path(os.path.join(tmp, name))
        # the marker goes LAST: its presence certifies every fsync above
        with open(os.path.join(tmp, MARKER), "w", encoding="utf-8") as f:
            json.dump({"step": int(step), "ts": meta["ts"]}, f)
            f.flush()
            os.fsync(f.fileno())
        _fsync_dir(tmp)
        if os.path.isdir(final):  # an incomplete husk from a dead writer
            shutil.rmtree(final)
        os.replace(tmp, final)
        _fsync_dir(self.root)
        self.gc()
        return final

    def gc(self) -> list[str]:
        """Reap stale tmp dirs and all but the newest `keep` complete
        checkpoints; returns removed paths. Torn dirs (no COMMIT) are
        aborted writes and always reaped."""
        removed: list[str] = []
        if not os.path.isdir(self.root):
            return removed
        complete = self.steps()
        drop_steps = set(complete[:-self.keep]) if len(complete) > self.keep \
            else set()
        for name in sorted(os.listdir(self.root)):
            path = os.path.join(self.root, name)
            if name.startswith(PREFIX) and ".tmp-" in name:
                shutil.rmtree(path, ignore_errors=True)
                removed.append(path)
                continue
            s = step_of(name)
            if s is None:
                continue
            if s in drop_steps or not is_complete(path):
                shutil.rmtree(path, ignore_errors=True)
                removed.append(path)
        return removed


# ---------------------------------------------------------------------------
# model_dir-level helpers (serving / tools)
# ---------------------------------------------------------------------------


def latest_complete(model_dir: str) -> str | None:
    """Path of the newest COMPLETE retained checkpoint under model_dir,
    or None."""
    store = CheckpointStore(model_dir)
    step = store.latest_step()
    return None if step is None else store._path(step)


def watch_signature(model_dir: str) -> tuple:
    """Change-detection token for the serving hot-reload watcher.

    Moves ONLY when a new COMPLETE checkpoint commits: (newest complete
    step, its COMMIT mtime). A half-written `ckpt_*.tmp-*` dir — or a
    torn dir left by a killed trainer — never changes the signature, so
    a watcher poll landing mid-write cannot trigger a swap onto a torn
    checkpoint. `("none", 0, 0.0)` while there is no complete one."""
    store = CheckpointStore(model_dir)
    step = store.latest_step()
    if step is None:
        return ("none", 0, 0.0)
    marker = os.path.join(store._path(step), MARKER)
    try:
        return ("retained", step, os.path.getmtime(marker))
    except OSError:
        return ("retained", step, 0.0)
