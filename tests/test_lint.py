"""graftlint tier-1 gate + fixture proofs.

Three layers:
  1. THE GATE — the repo at HEAD must be lint-clean against the baseline
     (and the baseline must not go stale). This is what stops the next
     PR from shipping a jit-retrace / lock race / wire-verb mismatch /
     seed-hygiene bug the way PRs 1-2 nearly did.
  2. Fixture proofs — every checker must trip on its known-bad snippet
     (true-positive proof) and stay silent on the fixed form
     (false-positive proof). The lock fixture includes the pre-PR-2
     `_jit_cache` attribute-injection race as a regression.
  3. Mechanism proofs — suppression comments, baseline matching, stale
     detection, and the CLI exit-code contract.

Everything here is pure-AST (no jax import beyond conftest's), so the
whole file runs in seconds — well under the 30 s budget.
"""

import json
import os
import subprocess
import sys
from collections import Counter

import pytest

from euler_tpu import analysis
from euler_tpu.analysis.checkers.wire_protocol import (
    WireDomain,
    check_domain,
)
from euler_tpu.analysis.core import Module, Project

FIXTURES = os.path.join(os.path.dirname(__file__), "lint_fixtures")


def _fixture_project(*names) -> Project:
    return analysis.load_project(
        [os.path.join(FIXTURES, n) for n in names]
    )


def _check(project, checker):
    return analysis.CHECKERS[checker].check(project)


def _ids(findings):
    return Counter(f.check for f in findings)


# ---------------------------------------------------------------------------
# 1. the gate
# ---------------------------------------------------------------------------


def test_repo_is_lint_clean():
    project = analysis.load_project()
    report = analysis.run(project, baseline=analysis.load_baseline())
    assert report.ok, "graftlint findings at HEAD:\n" + "\n".join(
        f.render() for f in report.findings
    )
    assert not report.stale_baseline, (
        "stale baseline entries (fixed code still listed — delete them): "
        f"{report.stale_baseline}"
    )


def test_gate_covers_the_package():
    project = analysis.load_project()
    rels = set(project.by_relpath)
    # the modules whose hazard classes motivated the suite must be in scope
    for must in (
        "euler_tpu/serving/batcher.py",
        "euler_tpu/serving/server.py",
        # the serving-fleet lane (ISSUE 7): hedge/quota shared state is
        # exactly what lock-discipline + unbounded-cache exist to audit
        "euler_tpu/serving/router.py",
        "euler_tpu/serving/client.py",
        "euler_tpu/serving/runtime.py",
        "euler_tpu/distributed/service.py",
        "euler_tpu/distributed/client.py",
        "euler_tpu/distributed/chaos.py",
        "euler_tpu/distributed/retry.py",
        "euler_tpu/estimator/feature_cache.py",
        "euler_tpu/estimator/prefetch.py",
        "euler_tpu/query/plan.py",
        # the paged device-sampling lane (ISSUE 6): traced draw code,
        # its page readers, and the read-cache plumbing it leans on
        "euler_tpu/dataflow/device.py",
        "euler_tpu/ops/paged_ops.py",
        "euler_tpu/distributed/cache.py",
        # the streaming-mutation lane (ISSUE 8): delta buffers merged
        # under the store lock and the batched writer client — exactly
        # the lock-discipline / unbounded-cache hazard classes
        "euler_tpu/graph/delta.py",
        "euler_tpu/distributed/writer.py",
        # the durability lane (ISSUE 9): group-committed WAL appends and
        # the process supervisor's monitor/restart state — lock-discipline
        # territory, plus the wire-wal-drift lockstep gate below
        "euler_tpu/graph/wal.py",
        "euler_tpu/distributed/supervisor.py",
        # the durable-training lane (ISSUE 10): the async checkpoint
        # writer + watchdog threads and the atomic state-file commits —
        # lock-discipline and durable-write territory
        "euler_tpu/training/session.py",
        "euler_tpu/training/checkpoint.py",
        "euler_tpu/tools/train.py",
        # the whole-graph analytics lane (ISSUE 12): BSP frontier
        # exchange on the wire, bit-deterministic reductions, and the
        # sweep driver's durable checkpoints — seed-hygiene, ordered-sink
        # and wire-protocol territory
        "euler_tpu/analytics/primitives.py",
        "euler_tpu/analytics/algorithms.py",
        "euler_tpu/analytics/sweeps.py",
        "euler_tpu/tools/analytics.py",
        # the replication lane (ISSUE 13): lease fencing, quorum-ack
        # condition variables, and the WAL-shipping tail loop — lock-
        # discipline and wire-protocol territory
        "euler_tpu/distributed/replication.py",
        # the disaster-recovery lane (ISSUE 15): archive commits must be
        # durable-write clean, and the scrubber's peer repair rides the
        # wire protocol — both checker territories
        "euler_tpu/graph/backup.py",
        "euler_tpu/tools/backup.py",
        # the byte-budget lane (ISSUE 16): the frame codec every
        # compressed stream rides, plus the borrow-mode decode paths the
        # borrowed-buffer-escape checker audits
        "euler_tpu/distributed/codec.py",
        "euler_tpu/distributed/wire.py",
        # the retrieval-serving lane (ISSUE 17): hot-swapped engines,
        # DNF-mask caches and router fan-out state are lock-discipline /
        # unbounded-cache territory, and the retrieve protocol is the
        # wire checker's third domain
        "euler_tpu/retrieval/corpus.py",
        "euler_tpu/retrieval/topk.py",
        "euler_tpu/retrieval/server.py",
        "euler_tpu/retrieval/router.py",
        "euler_tpu/retrieval/client.py",
        "euler_tpu/tools/retrieve.py",
        "bench.py",
    ):
        assert must in rels, f"{must} escaped the lint gate"


# ---------------------------------------------------------------------------
# 2. fixture proofs, one pair per checker
# ---------------------------------------------------------------------------


def test_jit_purity_fixture_trips():
    findings = _check(_fixture_project("jit_bad.py"), "jit-purity")
    ids = _ids(findings)
    assert ids["jit-py-branch"] == 3, findings
    assert ids["jit-np-call"] == 1, findings
    assert ids["jit-host-sync"] == 2, findings
    assert ids["jit-static-arg"] == 2, findings
    assert set(ids) == {
        "jit-py-branch",
        "jit-np-call",
        "jit-host-sync",
        "jit-static-arg",
    }


def test_jit_purity_fixed_form_clean():
    assert _check(_fixture_project("jit_good.py"), "jit-purity") == []


def test_lock_discipline_fixture_trips():
    findings = _check(_fixture_project("lock_bad.py"), "lock-discipline")
    ids = _ids(findings)
    assert ids["lock-racy-init"] == 2, findings
    assert ids["lock-mixed-write"] == 2, findings
    # the PR-4 regression: quarantine timestamps read under the pool lock
    # in the picker, written lock-free in the failure path — graftlint
    # must catch the old RemoteShard.bad_until form; plus the PR-13
    # regression: replica lists rebound lock-free by the topology
    # refresh while the picker iterates them under the lock
    assert ids["lock-unguarded-write"] == 2, findings
    unguarded = {
        f.symbol: f for f in findings if f.check == "lock-unguarded-write"
    }
    assert "bad_until" in unguarded["QuarantineRace.on_failure"].message
    assert "members" in unguarded["TopologySyncRace.on_refresh"].message
    # the regression the ISSUE pins: the pre-PR-2 _jit_cache
    # attribute-injection get-or-build race must be among them
    racy = [f for f in findings if f.check == "lock-racy-init"]
    assert any("_jit_cache" in f.message for f in racy), racy


def test_lock_discipline_fixed_form_clean():
    assert _check(_fixture_project("lock_good.py"), "lock-discipline") == []


def test_unbounded_cache_fixture_trips():
    findings = _check(_fixture_project("cache_bad.py"), "unbounded-cache")
    ids = _ids(findings)
    assert ids["unbounded-cache"] == 3, findings
    # the class-attr memo and the module-global memo are both covered
    symbols = {f.symbol for f in findings}
    assert "ResultCacheUnbounded._handle" in symbols
    assert "_pool_job" in symbols


def test_unbounded_cache_fixed_form_clean():
    # cache_good.py mirrors the shipped ReadCache (LRU eviction under a
    # budget), the epoch reset-by-rebind, and the exempt Counter /
    # WeakKeyDictionary forms
    assert _check(_fixture_project("cache_good.py"), "unbounded-cache") == []


def test_durable_write_fixture_trips():
    findings = _check(_fixture_project("durable_bad.py"), "durable-write")
    ids = _ids(findings)
    assert ids["durable-write"] == 3, findings
    symbols = {f.symbol for f in findings}
    # json-dump via open, np.save, and the path-through-a-local-name form
    # (the async-writer thread target) are all covered
    assert symbols == {
        "CkptWriter.save_meta",
        "CkptWriter.save_arrays",
        "snapshot_writer",
    }, findings


def test_durable_write_fixed_form_clean():
    # durable_good.py mirrors the shipped idiom: tmp + fsync + one
    # atomic os.replace/os.rename (wal.write_snapshot /
    # training/checkpoint.py CheckpointStore.save_leaves)
    assert _check(
        _fixture_project("durable_good.py"), "durable-write"
    ) == []


def test_borrowed_buffer_escape_fixture_trips():
    findings = _check(
        _fixture_project("borrow_bad.py"), "borrowed-buffer-escape"
    )
    ids = _ids(findings)
    assert ids["borrowed-buffer-escape"] == 4, findings
    # the cache-store, the attribute retain, the module-global memo, and
    # the append of a row view are all distinct escape shapes
    messages = sorted(f.message.split(" — ")[0] for f in findings)
    assert any("self._rows" in m for m in messages), messages
    assert any("self._last" in m for m in messages), messages
    assert any("_FRAME_MEMO" in m for m in messages), messages
    assert any("self._pending" in m for m in messages), messages


def test_borrowed_buffer_escape_fixed_form_clean():
    # borrow_good.py mirrors the shipped idiom: copy exactly the rows
    # kept (per-row tobytes, .copy(), np.array) before any store;
    # locals-only views are the fast path and stay unflagged
    assert (
        _check(
            _fixture_project("borrow_good.py"), "borrowed-buffer-escape"
        )
        == []
    )


def test_determinism_fixture_trips():
    findings = _check(_fixture_project("det_bad.py"), "determinism")
    ids = _ids(findings)
    assert ids["det-unseeded-rng"] == 3, findings
    assert ids["det-iter-order"] == 2, findings
    assert ids["det-key-reuse"] == 2, findings


def test_determinism_fixed_form_clean():
    assert _check(_fixture_project("det_good.py"), "determinism") == []


_FIXTURE_DOMAIN_BAD = WireDomain(
    name="fixture",
    clients=("tests/lint_fixtures/wire_bad_client.py",),
    servers=("tests/lint_fixtures/wire_bad_server.py",),
)
_FIXTURE_DOMAIN_GOOD = WireDomain(
    name="fixture",
    clients=("tests/lint_fixtures/wire_good_client.py",),
    servers=("tests/lint_fixtures/wire_good_server.py",),
)


def test_wire_protocol_fixture_trips():
    project = _fixture_project("wire_bad_client.py", "wire_bad_server.py")
    findings = check_domain(project, _FIXTURE_DOMAIN_BAD)
    ids = _ids(findings)
    assert ids["wire-unhandled"] == 1, findings
    assert ids["wire-unreachable"] == 1, findings
    assert ids["wire-table-drift"] == 1, findings
    unhandled = next(f for f in findings if f.check == "wire-unhandled")
    assert "exec_plan" in unhandled.message


def test_wire_protocol_fixed_form_clean():
    project = _fixture_project("wire_good_client.py", "wire_good_server.py")
    assert check_domain(project, _FIXTURE_DOMAIN_GOOD) == []


_WAL_WRITER_SRC = (
    "class W:\n"
    "    WIRE_VERBS = frozenset({\n"
    "        'get_meta', 'upsert_nodes', 'upsert_edges', 'delete_edges',\n"
    "        'publish_epoch',\n"
    "    })\n"
)


def _wal_project(wal_verbs: str) -> Project:
    from euler_tpu.analysis.checkers.wire_protocol import WAL_CLIENT, WAL_TABLE

    wal_src = f"WAL_VERBS = frozenset({{{wal_verbs}}})\n"
    return Project(
        [
            Module(WAL_TABLE[0], WAL_TABLE[0], wal_src),
            Module(WAL_CLIENT, WAL_CLIENT, _WAL_WRITER_SRC),
        ],
        root=".",
    )


def test_wal_lockstep_drift_trips():
    """A mutation verb with no WAL record type (acked but non-durable)
    and a WAL-only record type (unwritable) must both trip."""
    from euler_tpu.analysis.checkers.wire_protocol import check_wal_lockstep

    missing = check_wal_lockstep(
        _wal_project("'upsert_nodes', 'upsert_edges', 'publish_epoch'")
    )
    assert len(missing) == 1 and missing[0].check == "wire-wal-drift"
    assert "delete_edges" in missing[0].message
    assert "non-durable" in missing[0].message
    extra = check_wal_lockstep(
        _wal_project(
            "'upsert_nodes', 'upsert_edges', 'delete_edges',"
            " 'publish_epoch', 'compact_shard'"
        )
    )
    assert len(extra) == 1 and "compact_shard" in extra[0].message


def test_wal_lockstep_fixed_form_clean():
    from euler_tpu.analysis.checkers.wire_protocol import check_wal_lockstep

    assert check_wal_lockstep(
        _wal_project(
            "'upsert_nodes', 'upsert_edges', 'delete_edges',"
            " 'publish_epoch'"
        )
    ) == []
    # the real repo's tables are in lockstep at HEAD (also covered by the
    # gate, but assert it here with the runtime objects so a drift names
    # this test, not a generic lint failure)
    from euler_tpu.distributed import replication
    from euler_tpu.distributed.writer import GraphWriter
    from euler_tpu.graph.wal import WAL_VERBS

    assert WAL_VERBS == (
        GraphWriter.WIRE_VERBS - {"get_meta"} - replication.WIRE_VERBS
    )


def test_wal_lockstep_replication_verbs_exempt():
    """The writer speaks repl_status (primary discovery) — a replication-
    control verb, not a mutation. With the replication module's
    WIRE_VERBS table in the project the lockstep check exempts it; with
    the module absent (older slices, fixtures) the same writer table
    trips as an un-WAL'd mutation — the drift pair that keeps the
    exemption itself honest."""
    from euler_tpu.analysis.checkers.wire_protocol import (
        REPL_TABLE,
        WAL_CLIENT,
        WAL_TABLE,
        check_wal_lockstep,
    )

    writer_src = (
        "class W:\n"
        "    WIRE_VERBS = frozenset({\n"
        "        'get_meta', 'upsert_nodes', 'upsert_edges',\n"
        "        'delete_edges', 'publish_epoch', 'repl_status',\n"
        "    })\n"
    )
    wal_src = (
        "WAL_VERBS = frozenset({'upsert_nodes', 'upsert_edges',"
        " 'delete_edges', 'publish_epoch'})\n"
    )
    repl_src = (
        "WIRE_VERBS = frozenset({'repl_status', 'wal_pos', 'wal_ship'})\n"
    )
    with_repl = Project(
        [
            Module(WAL_TABLE[0], WAL_TABLE[0], wal_src),
            Module(WAL_CLIENT, WAL_CLIENT, writer_src),
            Module(REPL_TABLE[0], REPL_TABLE[0], repl_src),
        ],
        root=".",
    )
    assert check_wal_lockstep(with_repl) == []
    without_repl = Project(
        [
            Module(WAL_TABLE[0], WAL_TABLE[0], wal_src),
            Module(WAL_CLIENT, WAL_CLIENT, writer_src),
        ],
        root=".",
    )
    drift = check_wal_lockstep(without_repl)
    assert len(drift) == 1 and drift[0].check == "wire-wal-drift"
    assert "repl_status" in drift[0].message


def test_executor_deadlock_fixture_trips():
    findings = _check(
        _fixture_project("exec_deadlock_bad.py"), "executor-deadlock"
    )
    ids = _ids(findings)
    assert ids["executor-self-submit"] == 1, findings
    f = findings[0]
    # the PR 17 shape: the pool WORKER flags, the caller-thread fan-out
    # in query() does not
    assert f.symbol == "FanoutRouter._shard_task", findings
    assert "_pool" in f.message


def test_executor_deadlock_fixed_form_clean():
    # the shipped fix shape: inner attempts go to a different, leaf-only
    # executor — same blocking .result(), no self-submission
    assert (
        _check(_fixture_project("exec_deadlock_good.py"), "executor-deadlock")
        == []
    )


def test_blocking_under_lock_fixture_trips():
    findings = _check(
        _fixture_project("lock_blocking_bad.py"), "blocking-under-lock"
    )
    ids = _ids(findings)
    assert ids["lock-blocking-call"] == 3, findings
    msgs = " | ".join(f.message for f in findings)
    assert "time.sleep" in msgs
    assert "wire RPC" in msgs
    assert "future wait" in msgs


def test_blocking_under_lock_fixed_form_clean():
    # fetch-outside-lock, plus the two sanctioned exemptions the good
    # file exercises: Condition.wait on the held condition and os.fsync
    # under a *sync*-named lock
    assert (
        _check(
            _fixture_project("lock_blocking_good.py"), "blocking-under-lock"
        )
        == []
    )


def test_hot_swap_reread_fixture_trips():
    findings = _check(_fixture_project("hot_swap_bad.py"), "hot-swap-reread")
    ids = _ids(findings)
    assert ids["hot-swap-reread"] == 3, findings
    # the three PR 17 shapes: double read on the request path, the
    # post-swap canary re-read, and the replica-rotation re-read through
    # a local shard handle
    assert {f.symbol for f in findings} == {
        "SwapServer.search",
        "SwapServer.reload",
        "probe_shard",
    }, findings


def test_hot_swap_reread_fixed_form_clean():
    assert (
        _check(_fixture_project("hot_swap_good.py"), "hot-swap-reread") == []
    )


def test_typed_error_retry_fixture_trips():
    findings = _check(
        _fixture_project("typed_retry_bad.py"), "typed-error-retry"
    )
    ids = _ids(findings)
    assert ids["typed-error-retry"] == 2, findings
    by_symbol = {f.symbol: f.message for f in findings}
    # both re-issue shapes: `continue` back into the calling loop (the
    # PR 16 long-poll churn) and a direct second call in the handler
    assert "continue" in by_symbol["TailFollower.tail_loop"]
    assert "re-issues" in by_symbol["TailFollower.fetch"]


def test_typed_error_retry_fixed_form_clean():
    # consult-the-verdict, raise-path, and mixed-transport arms are all
    # exempt — the sanctioned idioms from writer.py / client.py / the
    # retrieval router
    assert (
        _check(_fixture_project("typed_retry_good.py"), "typed-error-retry")
        == []
    )


def test_retry_budget_drain_fixture_trips():
    findings = _check(
        _fixture_project("budget_drain_bad.py"), "typed-error-retry"
    )
    ids = _ids(findings)
    assert ids["retry-budget-drain-only"] == 1, findings
    assert "_retry_tokens" in findings[0].message


def test_retry_budget_drain_fixed_form_clean():
    assert (
        _check(_fixture_project("budget_drain_good.py"), "typed-error-retry")
        == []
    )


# ---------------------------------------------------------------------------
# 3. the repo-wide call graph
# ---------------------------------------------------------------------------


def _two_module_project(worker_src, main_src):
    return Project(
        [
            Module(
                "euler_tpu/jobs/worker.py",
                "euler_tpu/jobs/worker.py",
                worker_src,
            ),
            Module(
                "euler_tpu/jobs/main.py", "euler_tpu/jobs/main.py", main_src
            ),
        ],
        root=".",
    )


def test_callgraph_cross_module_alias_edge():
    """`from euler_tpu.jobs.worker import leaf as run_leaf; run_leaf()`
    resolves to the worker module's function through the alias table."""
    project = _two_module_project(
        "def leaf():\n    return 1\n",
        "from euler_tpu.jobs.worker import leaf as run_leaf\n"
        "def caller():\n"
        "    return run_leaf()\n",
    )
    cg = project.callgraph
    assert (
        "euler_tpu/jobs/worker.py::leaf"
        in cg.edges["euler_tpu/jobs/main.py::caller"]
    )


def test_callgraph_executor_entry_propagates_across_modules():
    """A Thread target imported from another module makes that module's
    function an entry, and reachability propagates to its callees."""
    project = _two_module_project(
        "def work(x):\n"
        "    return helper(x)\n"
        "def helper(x):\n"
        "    return x\n",
        "import threading\n"
        "from euler_tpu.jobs.worker import work\n"
        "def spawn():\n"
        "    threading.Thread(target=work).start()\n",
    )
    cg = project.callgraph
    assert "euler_tpu/jobs/worker.py::work" in cg.entries
    assert "euler_tpu/jobs/worker.py::helper" in cg.thread_reachable
    # the spawning function itself is NOT thread-reachable
    assert "euler_tpu/jobs/main.py::spawn" not in cg.thread_reachable


def test_callgraph_pool_worker_facts():
    """Everything transitively submitted into a bounded pool is one of
    its workers, and owning_executors inverts the map."""
    project = _fixture_project("exec_deadlock_bad.py")
    cg = project.callgraph
    rel = "tests/lint_fixtures/exec_deadlock_bad.py"
    token = f"{rel}::FanoutRouter._pool"
    workers = cg.pool_workers(token)
    assert f"{rel}::FanoutRouter._shard_task" in workers
    assert f"{rel}::FanoutRouter._leaf" in workers
    assert f"{rel}::FanoutRouter.query" not in workers
    assert token in cg.owning_executors(f"{rel}::FanoutRouter._shard_task")


def test_callgraph_locks_on_entry_intersection():
    """The `_locked`-suffix calling contract is machine-derived: a
    function whose EVERY call site holds the lock has it on entry; one
    bare call site drops it to the empty set."""
    src = (
        "import threading\n"
        "class Store:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def put(self, k, v):\n"
        "        with self._lock:\n"
        "            self._merge_locked(k, v)\n"
        "    def drop(self, k):\n"
        "        with self._lock:\n"
        "            self._merge_locked(k, None)\n"
        "    def _merge_locked(self, k, v):\n"
        "        pass\n"
    )
    project = Project([Module("s.py", "s.py", src)], root=".")
    assert project.callgraph.locks_on_entry(
        "s.py::Store._merge_locked"
    ) == frozenset({"Store.self._lock"})
    bare = src + "    def oops(self, k):\n        self._merge_locked(k, 0)\n"
    project2 = Project([Module("s.py", "s.py", bare)], root=".")
    assert project2.callgraph.locks_on_entry(
        "s.py::Store._merge_locked"
    ) == frozenset()


def test_module_callgraph_class_method_reference_edges():
    """An explicitly spelled `Class.method` reference is an edge in the
    module-local graph (the `_refs_in` branch both lookups share)."""
    from euler_tpu.analysis.callgraph import CallGraph

    mod = _module_from(
        "class C:\n"
        "    def target(self):\n"
        "        pass\n"
        "def spawn():\n"
        "    return C.target\n"
    )
    cgm = CallGraph(mod.tree, mod.symbols)
    assert "C.target" in cgm.edges["spawn"]
    assert cgm.edges["C.target"] == set()


def test_findings_byte_identical_across_processes():
    """Determinism pin: two fresh processes with DIFFERENT hash seeds
    must emit byte-identical findings in identical order."""
    fixtures = [
        os.path.join(FIXTURES, n)
        for n in (
            "exec_deadlock_bad.py",
            "hot_swap_bad.py",
            "lock_blocking_bad.py",
            "typed_retry_bad.py",
            "budget_drain_bad.py",
        )
    ]
    cmd = [
        sys.executable, "-m", "euler_tpu.tools.lint", "--json",
        "--no-baseline", *fixtures,
    ]
    outs = []
    for seed in ("0", "1"):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED=seed)
        r = subprocess.run(cmd, capture_output=True, text=True, env=env)
        assert r.returncode == 1, r.stdout + r.stderr
        payload = json.loads(r.stdout.strip().splitlines()[-1])
        payload.pop("wall_s")
        outs.append(json.dumps(payload, sort_keys=True))
    assert outs[0] == outs[1]
    assert json.loads(outs[0])["total"] >= 10


# ---------------------------------------------------------------------------
# 4. mechanism proofs
# ---------------------------------------------------------------------------


def _module_from(src: str, relpath="synthetic.py") -> Module:
    return Module(relpath, relpath, src)


def test_suppression_comment_silences_one_check():
    src = (
        "import numpy as np\n"
        "def f(g):\n"
        "    return g.sample(rng=np.random.default_rng())"
        "  # graftlint: disable=det-unseeded-rng -- fixture\n"
    )
    mod = _module_from(src)
    project = Project([mod], root=".")
    report = analysis.run(project, checks=["determinism"])
    assert report.findings == []
    assert len(report.suppressed) == 1
    # and without the comment the same code trips
    mod2 = _module_from(src.replace(
        "  # graftlint: disable=det-unseeded-rng -- fixture", ""
    ))
    report2 = analysis.run(Project([mod2], root="."), checks=["determinism"])
    assert len(report2.findings) == 1


def test_suppression_on_comment_line_applies_to_next_code_line():
    src = (
        "import numpy as np\n"
        "def f(g):\n"
        "    # graftlint: disable=determinism -- checker-group id works too\n"
        "    return g.sample(rng=np.random.default_rng())\n"
    )
    report = analysis.run(
        Project([_module_from(src)], root="."), checks=["determinism"]
    )
    assert report.findings == []
    assert len(report.suppressed) == 1


def test_baseline_matches_by_symbol_not_line():
    src = (
        "import numpy as np\n"
        "\n"
        "def f(g):\n"
        "    return g.sample(rng=np.random.default_rng())\n"
    )
    entry = {
        "check": "det-unseeded-rng",
        "path": "synthetic.py",
        "symbol": "f",
        "reason": "fixture",
    }
    report = analysis.run(
        Project([_module_from(src)], root="."),
        checks=["determinism"],
        baseline=[entry],
    )
    assert report.findings == [] and len(report.baselined) == 1
    # same entry still matches after lines shift
    shifted = "# a new comment\n# another\n" + src
    report2 = analysis.run(
        Project([_module_from(shifted)], root="."),
        checks=["determinism"],
        baseline=[entry],
    )
    assert report2.findings == [] and len(report2.baselined) == 1


def test_stale_baseline_entries_are_reported():
    entry = {
        "check": "det-unseeded-rng",
        "path": "synthetic.py",
        "symbol": "long_gone",
        "reason": "fixture",
    }
    report = analysis.run(
        Project([_module_from("x = 1\n")], root="."),
        checks=["determinism"],
        baseline=[entry],
    )
    assert report.stale_baseline == [entry]


def test_cli_exit_codes_and_json_lane():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # a known-bad file → exit 1, counts per checker in the JSON line
    bad = subprocess.run(
        [
            sys.executable, "-m", "euler_tpu.tools.lint", "--json",
            "--no-baseline", os.path.join(FIXTURES, "det_bad.py"),
        ],
        capture_output=True, text=True, env=env,
    )
    assert bad.returncode == 1, bad.stderr
    payload = json.loads(bad.stdout.strip().splitlines()[-1])
    assert payload["ok"] is False
    assert payload["counts"]["determinism"] == 7
    assert {"check", "path", "line", "symbol", "message", "checker"} <= set(
        payload["findings"][0]
    )
    # a clean file → exit 0
    good = subprocess.run(
        [
            sys.executable, "-m", "euler_tpu.tools.lint", "--json",
            "--no-baseline", os.path.join(FIXTURES, "det_good.py"),
        ],
        capture_output=True, text=True, env=env,
    )
    assert good.returncode == 0, good.stdout + good.stderr
    assert json.loads(good.stdout.strip().splitlines()[-1])["ok"] is True


def test_changed_only_scopes_findings_to_changed_files():
    """--changed-only on a dirty tree: a freshly created (untracked) bad
    file still trips; a tracked-and-unchanged bad fixture is filtered out
    — and the exit code follows the SCOPED findings, not the full set."""
    from euler_tpu.analysis.core import repo_root

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    probe = os.path.join(repo_root(), "euler_tpu", "_lint_changed_probe.py")
    fixture_bad = os.path.join(FIXTURES, "det_bad.py")
    base = [
        sys.executable, "-m", "euler_tpu.tools.lint", "--json",
        "--no-baseline",
    ]
    try:
        with open(probe, "w", encoding="utf-8") as f:
            f.write(
                "import numpy as np\n"
                "\n"
                "def f(g):\n"
                "    return g.sample(rng=np.random.default_rng())\n"
            )
        full = subprocess.run(
            base + [probe, fixture_bad],
            capture_output=True, text=True, env=env,
        )
        scoped = subprocess.run(
            base + ["--changed-only", probe, fixture_bad],
            capture_output=True, text=True, env=env,
        )
        assert full.returncode == 1, full.stdout + full.stderr
        assert scoped.returncode == 1, scoped.stdout + scoped.stderr
        full_paths = {
            f["path"]
            for f in json.loads(full.stdout.strip().splitlines()[-1])[
                "findings"
            ]
        }
        scoped_paths = {
            f["path"]
            for f in json.loads(scoped.stdout.strip().splitlines()[-1])[
                "findings"
            ]
        }
        assert "tests/lint_fixtures/det_bad.py" in full_paths
        assert scoped_paths == {"euler_tpu/_lint_changed_probe.py"}
        # only an unchanged file in scope -> scoped-clean, exit 0
        clean = subprocess.run(
            base + ["--changed-only", fixture_bad],
            capture_output=True, text=True, env=env,
        )
        assert clean.returncode == 0, clean.stdout + clean.stderr
    finally:
        os.remove(probe)


def test_unknown_checker_name_rejected():
    with pytest.raises(ValueError, match="unknown checker"):
        analysis.run(
            Project([_module_from("x = 1\n")], root="."), checks=["nope"]
        )
