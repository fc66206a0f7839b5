"""Shard supervisor — spawn, monitor, and restart durable graph shards.

PR 4's chaos discipline made a dead shard SURVIVABLE (readers fail over,
retries stop, errors stay typed) but never brought it back: a `kill -9`'d
shard stayed dead forever. With the WAL + snapshot layer (graph/wal.py)
a restart is cheap and LOSSLESS, so the supervisor closes the loop:

- `start()` spawns one `python -m euler_tpu.distributed.service` process
  per shard with a per-shard `--wal-dir`. Ports are FIXED by default
  (clients holding static replica lists get the restart back on the
  address they already know); `dynamic_ports=True` drops that
  assumption — every (re)spawn binds a fresh OS-assigned port and
  clients discover it through the registry heartbeat (connect()'s
  watch), the same contract replica groups already use. `cluster()`
  always reports the LIVE port map.
- A monitor thread polls the children; an exited shard (crash, OOM-kill,
  `kill -9`) is respawned with exponential backoff, bounded by
  `max_restarts` within the backoff window (a healthy stretch of uptime
  resets the counter — crash loops stop, one-off crashes do not).
- The restarted process recovers from its WAL dir (newest snapshot +
  log-suffix replay — bit-identical to the pre-crash published epoch),
  re-registers its heartbeat, and resumes serving. Clients un-quarantine
  on their normal timed revival and re-run the ReadCache epoch handshake
  (transport faults void `_epoch_checked`), so readers resume without a
  restart on their side.

CLI (start a whole durable cluster under supervision):

    python -m euler_tpu.distributed.supervisor --data DIR --shards 2 \
        --registry /path/reg --wal-root /path/wal
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

from euler_tpu.distributed import wire


def _free_port(host: str) -> int:
    """An OS-assigned free port (released immediately — the standard
    pick-then-bind race, narrowed by SO_REUSEADDR on the server side)."""
    s = socket.socket()
    try:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((host, 0))
        return s.getsockname()[1]
    finally:
        s.close()


def _ping(host: str, port: int, timeout_s: float = 1.0):
    """One raw ping RPC; the shard index on success, None otherwise."""
    try:
        with socket.create_connection((host, port), timeout=timeout_s) as s:
            s.settimeout(timeout_s)
            wire.send_frame(s, wire.encode("ping", []))
            payload = wire.read_frame(s)
            if payload is None:
                return None
            status, result = wire.decode(payload)
            if status == "ok":
                return int(result[0])
    except (OSError, ValueError):
        return None
    return None


class _Shard:
    """Supervision state for one shard process."""

    def __init__(self, shard: int, port: int, wal_dir: str):
        self.shard = shard
        self.port = port
        self.wal_dir = wal_dir
        self.proc: subprocess.Popen | None = None
        self.restarts = 0
        self.window_restarts = 0  # restarts inside the current crash loop
        self.started_at = 0.0
        self.next_spawn_at = 0.0  # backoff gate
        self.failed = False  # crash loop exceeded max_restarts
        self.log_path: str | None = None


class ShardSupervisor:
    """Process supervisor for a durable multi-shard graph service."""

    def __init__(
        self,
        data_dir: str,
        num_shards: int,
        registry_path: str,
        wal_root: str,
        host: str = "127.0.0.1",
        ports: list[int] | None = None,
        max_restarts: int = 8,
        backoff_s: float = 0.25,
        backoff_max_s: float = 5.0,
        healthy_uptime_s: float = 30.0,
        poll_s: float = 0.1,
        native: bool = False,
        env: dict | None = None,
        scrub_s: float | None = None,
        dynamic_ports: bool = False,
    ):
        self.data_dir = data_dir
        self.num_shards = int(num_shards)
        self.registry_path = registry_path
        self.wal_root = wal_root
        self.host = host
        self.max_restarts = int(max_restarts)
        self.backoff_s = float(backoff_s)
        self.backoff_max_s = float(backoff_max_s)
        self.healthy_uptime_s = float(healthy_uptime_s)
        self.poll_s = float(poll_s)
        self.native = native
        self.env = dict(env) if env else None
        # at-rest integrity cadence for every child (EULER_TPU_SCRUB_S;
        # None inherits the supervisor's environment, 0 disables)
        self.scrub_s = scrub_s
        # dynamic_ports drops the fixed-port assumption: every (re)spawn
        # binds a fresh OS-assigned port and the registry heartbeat is
        # how clients (and cluster()) learn the live address — required
        # for elastic reshard flows where shard counts change and no
        # static replica list can stay valid anyway
        if dynamic_ports and ports is not None:
            raise ValueError("dynamic_ports is incompatible with ports=")
        self.dynamic_ports = bool(dynamic_ports)
        os.makedirs(wal_root, exist_ok=True)
        if dynamic_ports:
            ports = [0] * self.num_shards  # allocated per spawn
        else:
            ports = (
                list(ports)
                if ports is not None
                else [_free_port(host) for _ in range(self.num_shards)]
            )
        if len(ports) != self.num_shards:
            raise ValueError("need one port per shard")
        self.shards = [
            _Shard(i, int(ports[i]), os.path.join(wal_root, f"shard_{i}"))
            for i in range(self.num_shards)
        ]
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._monitor: threading.Thread | None = None

    # -- process control -------------------------------------------------

    def _spawn(self, sh: _Shard) -> None:
        # callers (start(), the monitor loop) hold self._lock across this
        os.makedirs(sh.wal_dir, exist_ok=True)
        if self.dynamic_ports:
            # fresh port every spawn — the registry heartbeat (not a
            # static list) is the contract clients route by
            # graftlint: disable=lock-unguarded-write -- every caller holds self._lock around _spawn
            sh.port = _free_port(self.host)
        cmd = [
            sys.executable, "-m", "euler_tpu.distributed.service",
            "--data", self.data_dir,
            "--shard", str(sh.shard),
            "--host", self.host,
            "--port", str(sh.port),
            "--registry", self.registry_path,
            "--wal-dir", sh.wal_dir,
        ]
        if not self.native:
            cmd.append("--no-native")
        sh.log_path = os.path.join(self.wal_root, f"shard_{sh.shard}.log")
        env = dict(os.environ if self.env is None else self.env)
        if self.scrub_s is not None:
            env["EULER_TPU_SCRUB_S"] = str(self.scrub_s)
        log = open(sh.log_path, "ab")
        try:
            # its own session: a Ctrl-C to the supervisor's group must
            # not take the children down uncontrolled — stop() drains
            # graftlint: disable=lock-unguarded-write -- every caller holds self._lock around _spawn
            sh.proc = subprocess.Popen(
                cmd, stdout=log, stderr=subprocess.STDOUT, env=env,
                start_new_session=True,
            )
        finally:
            log.close()
        # graftlint: disable=lock-unguarded-write -- every caller holds self._lock around _spawn
        sh.started_at = time.monotonic()

    def start(self) -> "ShardSupervisor":
        # under the lock: _spawn writes per-shard state the monitor and
        # stats() read under it (sh.proc / sh.started_at)
        with self._lock:
            for sh in self.shards:
                self._spawn(sh)
        self._monitor = threading.Thread(
            target=self._monitor_loop, daemon=True, name="shard-supervisor"
        )
        self._monitor.start()
        return self

    def _monitor_loop(self) -> None:
        while not self._stop.is_set():
            now = time.monotonic()
            with self._lock:
                for sh in self.shards:
                    p = sh.proc
                    if sh.failed or p is None:
                        continue
                    if p.poll() is None:
                        # a healthy stretch closes the crash-loop window
                        if (
                            sh.window_restarts
                            and now - sh.started_at > self.healthy_uptime_s
                        ):
                            sh.window_restarts = 0
                        continue
                    if sh.next_spawn_at == 0.0:
                        # just observed the exit: schedule the respawn
                        sh.window_restarts += 1
                        if sh.window_restarts > self.max_restarts:
                            sh.failed = True
                            print(
                                f"# supervisor: shard {sh.shard} crash-"
                                f"looped past max_restarts="
                                f"{self.max_restarts}; giving up on it"
                                f" (exit {p.returncode})",
                                file=sys.stderr, flush=True,
                            )
                            continue
                        pause = min(
                            self.backoff_s * 2 ** (sh.window_restarts - 1),
                            self.backoff_max_s,
                        )
                        sh.next_spawn_at = now + pause
                    elif now >= sh.next_spawn_at:
                        sh.next_spawn_at = 0.0
                        sh.restarts += 1
                        print(
                            f"# supervisor: restarting shard {sh.shard}"
                            f" (exit {p.returncode},"
                            f" restart #{sh.restarts})",
                            file=sys.stderr, flush=True,
                        )
                        self._spawn(sh)
            self._stop.wait(self.poll_s)

    # -- operator surface ------------------------------------------------

    def kill(self, shard: int, sig: int = signal.SIGKILL) -> None:
        """Send `sig` to one shard process (chaos harness + tests: the
        seeded `kill -9` the recovery proof injects)."""
        with self._lock:
            p = self.shards[shard].proc
        if p is not None and p.poll() is None:
            os.kill(p.pid, sig)

    def wait_healthy(self, timeout_s: float = 60.0) -> bool:
        """Block until EVERY shard answers ping on its fixed port (and
        with it has re-registered its heartbeat). False on timeout."""
        deadline = time.monotonic() + timeout_s
        pending = set(range(self.num_shards))
        while pending and time.monotonic() < deadline:
            for i in sorted(pending):
                sh = self.shards[i]
                if _ping(self.host, sh.port) == sh.shard:
                    pending.discard(i)
            if pending:
                time.sleep(0.1)
        return not pending

    def stats(self) -> dict:
        with self._lock:
            return {
                "shards": {
                    sh.shard: {
                        "port": sh.port,
                        "alive": bool(
                            sh.proc is not None and sh.proc.poll() is None
                        ),
                        "restarts": sh.restarts,
                        "failed": sh.failed,
                        "pid": getattr(sh.proc, "pid", None),
                    }
                    for sh in self.shards
                },
            }

    def cluster(self) -> dict[int, list[tuple[str, int]]]:
        """LIVE cluster spec for `distributed.connect(cluster=...)`.
        Fixed-port mode: stable across restarts. dynamic_ports mode: the
        map as of NOW — a respawn moves ports, so long-lived clients
        should connect through the registry instead and treat this as a
        point-in-time snapshot (registry heartbeats confirm it)."""
        with self._lock:
            return {
                sh.shard: [(self.host, sh.port)] for sh in self.shards
            }

    def stop(self, term_timeout_s: float = 10.0) -> None:
        """Stop supervising, then the children: SIGTERM (the service
        drains: deregister → finish in-flight → exit), SIGKILL
        stragglers."""
        self._stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5.0)
        with self._lock:
            procs = [sh.proc for sh in self.shards if sh.proc is not None]
        for p in procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + term_timeout_s
        for p in procs:
            remaining = max(deadline - time.monotonic(), 0.1)
            try:
                p.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                try:
                    p.kill()
                    p.wait(timeout=5.0)
                except (OSError, subprocess.TimeoutExpired):
                    pass


class _Member:
    """Supervision state for one replica process of one shard group."""

    def __init__(self, shard: int, rid: int, wal_dir: str):
        self.shard = shard
        self.rid = rid
        self.wal_dir = wal_dir
        self.port = 0  # fresh port at every (re)spawn
        self.proc: subprocess.Popen | None = None
        self.restarts = 0
        self.window_restarts = 0
        self.started_at = 0.0
        self.next_spawn_at = 0.0
        self.failed = False
        self.log_path: str | None = None


class ReplicaGroupSupervisor:
    """Supervise R replicas per shard as lease-coordinated groups.

    Where ShardSupervisor restarts ONE process per shard on a FIXED
    port (clients hold static replica lists), this spawns `replication`
    processes per shard, each a member of the shard's replica group
    (`--replica i --replicas R`): one holds the lease and serves
    writes, the rest tail its WAL. A respawned member comes back on a
    FRESH port — clients discover it through the registry topology
    watch (connect()'s `sync_replicas`), so the fixed-port constraint
    is gone. Per-member WAL dirs live at
    `wal_root/shard_<s>/replica_<r>`; a restarted member recovers from
    its own snapshot + log and rejoins the group (bootstrapping over
    the wire only when its log diverged or fell behind the primary's
    retained base).
    """

    def __init__(
        self,
        data_dir: str,
        num_shards: int,
        registry_path: str,
        wal_root: str,
        replication: int = 2,
        host: str = "127.0.0.1",
        lease_ttl: float | None = None,
        max_restarts: int = 8,
        backoff_s: float = 0.25,
        backoff_max_s: float = 5.0,
        healthy_uptime_s: float = 30.0,
        poll_s: float = 0.1,
        native: bool = False,
        env: dict | None = None,
        scrub_s: float | None = None,
    ):
        self.data_dir = data_dir
        self.num_shards = int(num_shards)
        self.registry_path = registry_path
        self.wal_root = wal_root
        self.replication = int(replication)
        if self.replication < 1:
            raise ValueError("replication must be >= 1")
        self.host = host
        self.lease_ttl = lease_ttl
        self.max_restarts = int(max_restarts)
        self.backoff_s = float(backoff_s)
        self.backoff_max_s = float(backoff_max_s)
        self.healthy_uptime_s = float(healthy_uptime_s)
        self.poll_s = float(poll_s)
        self.native = native
        self.env = dict(env) if env else None
        # integrity-scrub cadence forwarded to children as EULER_TPU_SCRUB_S
        self.scrub_s = scrub_s
        os.makedirs(wal_root, exist_ok=True)
        self.members = [
            _Member(
                s, r,
                os.path.join(wal_root, f"shard_{s}", f"replica_{r}"),
            )
            for s in range(self.num_shards)
            for r in range(self.replication)
        ]
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._monitor: threading.Thread | None = None

    def _registry(self):
        from euler_tpu.distributed.rendezvous import make_registry

        return make_registry(self.registry_path)

    # -- process control -------------------------------------------------

    def _spawn(self, m: _Member) -> None:
        # callers hold self._lock (same discipline as ShardSupervisor)
        os.makedirs(m.wal_dir, exist_ok=True)
        # graftlint: disable=lock-unguarded-write -- every caller holds self._lock around _spawn
        m.port = _free_port(self.host)
        cmd = [
            sys.executable, "-m", "euler_tpu.distributed.service",
            "--data", self.data_dir,
            "--shard", str(m.shard),
            "--host", self.host,
            "--port", str(m.port),
            "--registry", self.registry_path,
            "--wal-dir", m.wal_dir,
            "--replica", str(m.rid),
            "--replicas", str(self.replication),
        ]
        if self.lease_ttl is not None:
            cmd += ["--lease-ttl", str(self.lease_ttl)]
        if not self.native:
            cmd.append("--no-native")
        m.log_path = os.path.join(
            self.wal_root, f"shard_{m.shard}_r{m.rid}.log"
        )
        env = dict(os.environ if self.env is None else self.env)
        if self.scrub_s is not None:
            env["EULER_TPU_SCRUB_S"] = str(self.scrub_s)
        log = open(m.log_path, "ab")
        try:
            # graftlint: disable=lock-unguarded-write -- every caller holds self._lock around _spawn
            m.proc = subprocess.Popen(
                cmd, stdout=log, stderr=subprocess.STDOUT, env=env,
                start_new_session=True,
            )
        finally:
            log.close()
        # graftlint: disable=lock-unguarded-write -- every caller holds self._lock around _spawn
        m.started_at = time.monotonic()

    def start(self) -> "ReplicaGroupSupervisor":
        with self._lock:
            for m in self.members:
                self._spawn(m)
        self._monitor = threading.Thread(
            target=self._monitor_loop, daemon=True,
            name="replica-group-supervisor",
        )
        self._monitor.start()
        return self

    def _monitor_loop(self) -> None:
        while not self._stop.is_set():
            now = time.monotonic()
            with self._lock:
                for m in self.members:
                    p = m.proc
                    if m.failed or p is None:
                        continue
                    if p.poll() is None:
                        if (
                            m.window_restarts
                            and now - m.started_at > self.healthy_uptime_s
                        ):
                            m.window_restarts = 0
                        continue
                    if m.next_spawn_at == 0.0:
                        m.window_restarts += 1
                        if m.window_restarts > self.max_restarts:
                            m.failed = True
                            print(
                                f"# supervisor: shard {m.shard} replica"
                                f" {m.rid} crash-looped past max_restarts"
                                f"={self.max_restarts}; giving up on it"
                                f" (exit {p.returncode})",
                                file=sys.stderr, flush=True,
                            )
                            continue
                        pause = min(
                            self.backoff_s * 2 ** (m.window_restarts - 1),
                            self.backoff_max_s,
                        )
                        m.next_spawn_at = now + pause
                    elif now >= m.next_spawn_at:
                        m.next_spawn_at = 0.0
                        m.restarts += 1
                        print(
                            f"# supervisor: restarting shard {m.shard}"
                            f" replica {m.rid} (exit {p.returncode},"
                            f" restart #{m.restarts})",
                            file=sys.stderr, flush=True,
                        )
                        self._spawn(m)
            self._stop.wait(self.poll_s)

    # -- operator surface ------------------------------------------------

    def member(self, shard: int, rid: int) -> _Member:
        for m in self.members:
            if m.shard == shard and m.rid == rid:
                return m
        raise KeyError(f"no member shard={shard} replica={rid}")

    def kill(self, shard: int, rid: int, sig: int = signal.SIGKILL) -> None:
        """Send `sig` to one replica process (the chaos harness's
        seeded `kill -9`)."""
        with self._lock:
            p = self.member(shard, rid).proc
        if p is not None and p.poll() is None:
            os.kill(p.pid, sig)

    def primary_of(self, shard: int) -> int | None:
        """Replica id of the shard's current lease holder, or None.
        Matches the lease holder's `host:port` against live member
        processes — the port changes across respawns, so this is read
        fresh every call."""
        lease = self._registry().observe(f"shard_{shard}")
        if lease is None or lease["expires_in"] <= 0:
            return None
        holder = str(lease["holder"])
        with self._lock:
            for m in self.members:
                if (
                    m.shard == shard
                    and f"{self.host}:{m.port}" == holder
                    and m.proc is not None
                    and m.proc.poll() is None
                ):
                    return m.rid
        return None

    def kill_primary(
        self, shard: int, sig: int = signal.SIGKILL, timeout_s: float = 30.0
    ) -> int:
        """kill -9 the shard's CURRENT primary (whichever replica holds
        the lease right now); returns the replica id killed."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            rid = self.primary_of(shard)
            if rid is not None:
                self.kill(shard, rid, sig)
                return rid
            time.sleep(0.1)
        raise TimeoutError(f"shard {shard}: no live primary to kill")

    def wait_healthy(self, timeout_s: float = 60.0) -> bool:
        """Block until every shard group has ALL its replicas answering
        ping AND a live lease (a primary elected). False on timeout."""
        deadline = time.monotonic() + timeout_s
        reg = self._registry()
        while time.monotonic() < deadline:
            with self._lock:
                ports = {
                    (m.shard, m.rid): m.port
                    for m in self.members
                    if m.proc is not None and m.proc.poll() is None
                }
            ok = len(ports) == len(self.members) and all(
                _ping(self.host, port) == shard
                for (shard, _r), port in ports.items()
            )
            if ok:
                for s in range(self.num_shards):
                    lease = reg.observe(f"shard_{s}")
                    if lease is None or lease["expires_in"] <= 0:
                        ok = False
                        break
            if ok:
                return True
            time.sleep(0.1)
        return False

    def stats(self) -> dict:
        with self._lock:
            return {
                "members": {
                    f"{m.shard}/{m.rid}": {
                        "port": m.port,
                        "alive": bool(
                            m.proc is not None and m.proc.poll() is None
                        ),
                        "restarts": m.restarts,
                        "failed": m.failed,
                        "pid": getattr(m.proc, "pid", None),
                    }
                    for m in self.members
                },
            }

    def stop(self, term_timeout_s: float = 10.0) -> None:
        self._stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5.0)
        with self._lock:
            procs = [m.proc for m in self.members if m.proc is not None]
        for p in procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + term_timeout_s
        for p in procs:
            remaining = max(deadline - time.monotonic(), 0.1)
            try:
                p.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                try:
                    p.kill()
                    p.wait(timeout=5.0)
                except (OSError, subprocess.TimeoutExpired):
                    pass


class TrainerSupervisor:
    """Supervise ONE durable trainer process (`tools/train.py`).

    The trainer side of the shard story above: exit 0 means the run
    reached its target step — done, no respawn. ANY other exit (crash,
    OOM-kill, `kill -9`) respawns the trainer with `--resume` appended,
    under the same exponential backoff + crash-loop cap as shards; the
    respawned process restores the newest COMPLETE retained checkpoint
    (euler_tpu/training/checkpoint.py) and continues bit-exactly, so a
    trainer kill under live traffic is a non-event. Exit 3 (SIGTERM
    preemption drain) is treated as done-for-now and NOT respawned —
    preemption is an operator/scheduler decision, not a crash."""

    DONE_CODES = (0, 3)

    def __init__(
        self,
        train_args: list[str],
        log_path: str,
        max_restarts: int = 8,
        backoff_s: float = 0.25,
        backoff_max_s: float = 5.0,
        healthy_uptime_s: float = 30.0,
        poll_s: float = 0.1,
        env: dict | None = None,
    ):
        self.train_args = list(train_args)
        self.log_path = log_path
        self.max_restarts = int(max_restarts)
        self.backoff_s = float(backoff_s)
        self.backoff_max_s = float(backoff_max_s)
        self.healthy_uptime_s = float(healthy_uptime_s)
        self.poll_s = float(poll_s)
        self.env = dict(env) if env else None
        self.proc: subprocess.Popen | None = None
        self.restarts = 0
        self.exit_code: int | None = None
        self.failed = False  # crash loop exceeded max_restarts
        self._window_restarts = 0
        self._started_at = 0.0
        self._next_spawn_at = 0.0
        self._stop = threading.Event()
        self._done = threading.Event()
        self._lock = threading.Lock()
        self._monitor: threading.Thread | None = None

    def _spawn(self, resume: bool) -> None:
        # callers hold self._lock (same discipline as _Shard._spawn)
        argv = list(self.train_args)
        if resume and "--resume" not in argv:
            argv.append("--resume")
        cmd = [sys.executable, "-m", "euler_tpu.tools.train", *argv]
        env = dict(os.environ if self.env is None else self.env)
        log = open(self.log_path, "ab")
        try:
            # graftlint: disable=lock-unguarded-write -- callers hold self._lock around _spawn
            self.proc = subprocess.Popen(
                cmd, stdout=log, stderr=subprocess.STDOUT, env=env,
                start_new_session=True,
            )
        finally:
            log.close()
        # graftlint: disable=lock-unguarded-write -- callers hold self._lock around _spawn
        self._started_at = time.monotonic()

    def start(self, resume: bool = False) -> "TrainerSupervisor":
        with self._lock:
            self._spawn(resume)
        self._monitor = threading.Thread(
            target=self._monitor_loop, daemon=True, name="trainer-supervisor"
        )
        self._monitor.start()
        return self

    def _monitor_loop(self) -> None:
        while not self._stop.is_set():
            now = time.monotonic()
            with self._lock:
                p = self.proc
                if self.failed or self._done.is_set() or p is None:
                    return
                rc = p.poll()
                if rc is None:
                    if (
                        self._window_restarts
                        and now - self._started_at > self.healthy_uptime_s
                    ):
                        self._window_restarts = 0
                elif rc in self.DONE_CODES:
                    self.exit_code = rc
                    self._done.set()
                    return
                elif self._next_spawn_at == 0.0:
                    self._window_restarts += 1
                    if self._window_restarts > self.max_restarts:
                        self.failed = True
                        self.exit_code = rc
                        print(
                            f"# supervisor: trainer crash-looped past "
                            f"max_restarts={self.max_restarts}; giving up"
                            f" (exit {rc})",
                            file=sys.stderr, flush=True,
                        )
                        self._done.set()
                        return
                    pause = min(
                        self.backoff_s * 2 ** (self._window_restarts - 1),
                        self.backoff_max_s,
                    )
                    self._next_spawn_at = now + pause
                elif now >= self._next_spawn_at:
                    self._next_spawn_at = 0.0
                    self.restarts += 1
                    print(
                        f"# supervisor: restarting trainer with --resume"
                        f" (exit {rc}, restart #{self.restarts})",
                        file=sys.stderr, flush=True,
                    )
                    self._spawn(resume=True)
            self._stop.wait(self.poll_s)

    def kill(self, sig: int = signal.SIGKILL) -> None:
        """Chaos entry point: the seeded `kill -9` the resume proof
        injects."""
        with self._lock:
            p = self.proc
        if p is not None and p.poll() is None:
            os.kill(p.pid, sig)

    def wait(self, timeout_s: float = 300.0) -> bool:
        """Block until the run completes (exit 0/3) or crash-loops out;
        True iff the trainer finished rather than failed."""
        if not self._done.wait(timeout_s):
            return False
        return not self.failed

    def stats(self) -> dict:
        with self._lock:
            return {
                "alive": bool(
                    self.proc is not None and self.proc.poll() is None
                ),
                "restarts": self.restarts,
                "failed": self.failed,
                "done": self._done.is_set(),
                "exit_code": self.exit_code,
                "pid": getattr(self.proc, "pid", None),
            }

    def stop(self, term_timeout_s: float = 10.0) -> None:
        """Stop supervising, then SIGTERM the trainer (it drains: final
        checkpoint flush, exit 3); SIGKILL a straggler."""
        self._stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5.0)
        with self._lock:
            p = self.proc
        if p is None:
            return
        if p.poll() is None:
            try:
                p.terminate()
            except OSError:
                pass
        try:
            p.wait(timeout=term_timeout_s)
        except subprocess.TimeoutExpired:
            try:
                p.kill()
                p.wait(timeout=5.0)
            except (OSError, subprocess.TimeoutExpired):
                pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--data", required=True)
    ap.add_argument("--shards", type=int, required=True)
    ap.add_argument("--registry", required=True)
    ap.add_argument("--wal-root", required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--ports", default=None,
                    help="comma-separated fixed ports (default: auto)")
    ap.add_argument("--dynamic-ports", action="store_true",
                    help="fresh OS-assigned port per (re)spawn; clients"
                         " route via the registry heartbeat")
    ap.add_argument("--max-restarts", type=int, default=8)
    ap.add_argument("--native", action="store_true")
    ap.add_argument("--replication", type=int, default=1,
                    help="replicas per shard; >1 supervises lease-"
                         "coordinated replica groups on dynamic ports")
    ap.add_argument("--lease-ttl", type=float, default=None)
    args = ap.parse_args(argv)
    ports = (
        [int(p) for p in args.ports.split(",")] if args.ports else None
    )
    if args.replication > 1:
        if ports is not None:
            raise SystemExit("--ports is incompatible with --replication"
                             " (replica groups respawn on fresh ports)")
        sup = ReplicaGroupSupervisor(
            args.data, args.shards, args.registry, args.wal_root,
            replication=args.replication, host=args.host,
            lease_ttl=args.lease_ttl, max_restarts=args.max_restarts,
            native=args.native,
        ).start()
    else:
        sup = ShardSupervisor(
            args.data, args.shards, args.registry, args.wal_root,
            host=args.host, ports=ports, max_restarts=args.max_restarts,
            native=args.native, dynamic_ports=args.dynamic_ports,
        ).start()
    healthy = sup.wait_healthy(timeout_s=120.0)
    print(json.dumps({"healthy": healthy, **sup.stats()}), flush=True)
    done = threading.Event()

    def _term(signum, frame):
        done.set()

    signal.signal(signal.SIGTERM, _term)
    try:
        done.wait()
    except KeyboardInterrupt:
        pass
    sup.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
