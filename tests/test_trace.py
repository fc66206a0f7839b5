"""The layers' names: `euler.*` scopes in both step programs' HLO
metadata, and `utils/trace.py`'s host spans and their record."""

import gc
import hashlib
import json
import os
import re
import threading
import time

import jax
import pytest

from euler_tpu.dataflow import DeviceSageFlow, DeviceWalkFlow
from euler_tpu.dataflow.device import DeviceSequenceFlow
from euler_tpu.datasets.synthetic import random_graph
from euler_tpu.estimator import DeviceFeatureCache, Estimator, EstimatorConfig
from euler_tpu.models import GraphSAGESupervised
from euler_tpu.models.embedding_models import SkipGramModel
from euler_tpu.models.sequence_lm import Lfm2MoeLM, Qwen3NextLM
from euler_tpu.estimator import estimator as estimator_module
from euler_tpu.utils import trace

SCOPES = {
    "sage": ("sample", "hydrate", "embed", "conv", "loss", "optimizer"),
    "skipgram": ("sample", "embed", "loss", "optimizer"),
}
PROGRAMS = ("train_step", "multi_step")


@pytest.fixture(scope="module")
def graph():
    return random_graph(num_nodes=120, out_degree=4, feat_dim=8, seed=5)


def _estimator(kind, graph, tmp_path, **cfg):
    since = time.perf_counter_ns()
    if kind == "sage":
        flow = DeviceSageFlow(
            graph, fanouts=[3, 2], batch_size=8, label_feature="label",
            with_hop_ids=True,
        )
        model = GraphSAGESupervised(
            dims=[8, 8], label_dim=2, encoder_dim=8, max_id=120
        )
        cache = DeviceFeatureCache(graph, ["feat"])
    elif kind == "sequence":
        flow = DeviceSequenceFlow(graph, batch_size=2, seq_len=32, doc_len=8)
        model = Qwen3NextLM(
            vocab_size=120, hidden_size=32, num_layers=2,
            full_attention_interval=2, num_heads=2, num_kv_heads=1,
            head_dim=16, rope_theta=1e4, partial_rotary_factor=0.25,
            attention_block=16, linear_num_key_heads=1,
            linear_num_value_heads=2, linear_key_head_dim=16,
            linear_value_head_dim=16, linear_conv_kernel_dim=4, chunk=8,
            num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, norm_topk_prob=True,
            experts_here=(0, 2), rms_norm_eps=1e-6, loss_chunks=2,
        )
        cache = None
    elif kind == "lfm2":
        flow = DeviceSequenceFlow(graph, batch_size=2, seq_len=32, doc_len=8)
        model = Lfm2MoeLM(
            vocab_size=120, hidden_size=32, num_layers=3,
            layer_types=("conv", "full_attention", "conv"), num_heads=2,
            num_kv_heads=1, head_dim=16, attention_block=16,
            num_dense_layers=1, intermediate_size=48, num_experts=4,
            num_experts_per_tok=2, moe_intermediate_size=16,
            experts_here=(0, 2), loss_chunks=2,
        )
        cache = None
    else:
        flow = DeviceWalkFlow(graph, batch_size=4, walk_len=3, window=1)
        model = SkipGramModel(num_nodes=120, dim=8)
        cache = None
    config = EstimatorConfig(
        model_dir=str(tmp_path / "m"), log_steps=10**9, **cfg
    )
    return Estimator(model, flow, config, feature_cache=cache), since


@pytest.fixture(scope="module")
def op_names(graph, tmp_path_factory):
    """Per (model, program): every `op_name` in the compiled program's
    HLO metadata — what the profiler's trace shows per op."""
    out = {}
    for kind in SCOPES:
        est, _ = _estimator(kind, graph, tmp_path_factory.mktemp(kind))
        est._ensure_init()
        state = (est.params, est.opt_state, est._tables())
        single = (*state, est._rngs(0), *est._next_batch(1))
        stacked = (*state, est._rngs_stacked(0, 2), *est._next_batch(2))
        for program, fn, args in (
            ("train_step", est._train_step(), single),
            ("multi_step", est._train_step_scan(), stacked),
        ):
            lowered = fn.lower(*args)
            text = lowered.compile().as_text()
            out[kind, program] = set(re.findall(r'op_name="([^"]*)"', text))
            out[kind, program, "functions"] = set(
                re.findall(r"func\.func private @(\w+)", lowered.as_text())
            )
    return out


@pytest.mark.parametrize(
    "kind,program,scope",
    [(k, p, s) for k in SCOPES for p in PROGRAMS for s in SCOPES[k]],
)
def test_step_programs_name_every_layer(op_names, kind, program, scope):
    names = op_names[kind, program]
    hits = [n for n in names if f"/euler.{scope}/" in n]
    assert hits, f"no op under euler.{scope} in {kind} {program}"
    assert any(n.startswith(f"jit({program})/") for n in hits)


@pytest.mark.parametrize("kind", list(SCOPES))
@pytest.mark.parametrize("program", PROGRAMS)
def test_backward_ops_keep_the_scope(op_names, kind, program):
    """The table's scatter-add is `transpose(jvp(...))/euler.embed/...`:
    the readers tell forward from backward by that."""
    embed = [n for n in op_names[kind, program] if "/euler.embed/" in n]
    backward = [n for n in embed if "transpose(" in n.split("/euler.embed/")[0]]
    assert backward and len(backward) < len(embed)
    assert not any(
        "transpose(" in n
        for n in op_names[kind, program]
        if "/euler.sample/" in n or "/euler.optimizer/" in n
    )


@pytest.mark.parametrize("kind", list(SCOPES))
@pytest.mark.parametrize("program", PROGRAMS)
def test_the_module_differs_from_an_unscoped_one(op_names, kind, program):
    """The persistent compile cache keys on the module without metadata:
    scopes alone would be served an older, unscoped executable on a hit.
    The optimizer is a function of the module too, so the key differs."""
    assert "optimizer" in op_names[kind, program, "functions"]


def test_only_two_files_touch_the_profiler():
    import pathlib

    import euler_tpu

    root = pathlib.Path(euler_tpu.__file__).parent
    users = {
        str(p.relative_to(root))
        for p in root.rglob("*.py")
        if "jax.profiler" in p.read_text()
    }
    assert users == {"utils/trace.py", "estimator/estimator.py"}


def _mine(since):
    return [s for s in trace.spans() if s.start_ns >= since]


def test_spans_nest_and_name_their_parent():
    since = time.perf_counter_ns()
    with trace.span("t.outer", step=3) as outer:
        with trace.span("t.inner", detail="x") as inner:
            pass
        outer.child("t.timed", outer._t0, outer._t0 + 5)
    got = {s.name: s for s in _mine(since)}
    assert got["t.outer"].parent is None and got["t.outer"].step == 3
    assert got["t.inner"].parent == outer.id == got["t.timed"].parent
    assert got["t.inner"].id == inner.id and got["t.inner"].step is None
    assert got["t.inner"].args == {"detail": "x"}
    assert got["t.outer"].start_ns <= got["t.inner"].start_ns
    assert got["t.inner"].end_ns <= got["t.outer"].end_ns
    assert got["t.timed"].end_ns - got["t.timed"].start_ns == 5


def test_record_is_bounded_and_keeps_set_up_spans():
    since = time.perf_counter_ns()
    with trace.span("stage.t_bounded"):
        pass
    with trace.span("step.first_call", program="t_bounded"):
        pass
    for i in range(trace.MAX_SPANS + 10):
        with trace.span("t.step", step=i):
            pass
    got = trace.spans()
    steps = [s for s in got if not s.name.startswith(trace.SETUP_PREFIXES)]
    assert len(steps) == trace.MAX_SPANS
    # the oldest went: ten, and one more for each run of the collector
    # that the loop set off (a `gc` span of the same kind)
    mine = [s for s in steps if s.name == "t.step"]
    assert mine[0].step == 10 + len(steps) - len(mine)
    kept = [s.name for s in _mine(since) if s.name.startswith(trace.SETUP_PREFIXES)]
    assert kept == ["stage.t_bounded", "step.first_call"]
    assert len(got) <= trace.MAX_SPANS + trace.MAX_SETUP_SPANS


def test_threads_do_not_parent_each_other():
    since = time.perf_counter_ns()
    inside = threading.Event()
    done = threading.Event()

    def other():
        inside.wait(5)
        with trace.span("t.other"):
            pass
        done.set()

    worker = threading.Thread(target=other)
    worker.start()
    with trace.span("t.main"):
        inside.set()
        assert done.wait(5)
    worker.join(5)
    assert not worker.is_alive()
    got = {s.name: s for s in _mine(since)}
    assert got["t.other"].parent is None
    assert got["t.other"].thread != got["t.main"].thread


@pytest.mark.parametrize("kind,k", [("sage", 1), ("skipgram", 2)])
def test_train_records_set_up_and_steps(graph, tmp_path, kind, k):
    est, since = _estimator(kind, graph, tmp_path, steps_per_call=k)
    est.train(2 * k, log=False, save=False)
    est.train(2 * k + (k - 1), log=False, save=False)
    got = _mine(since)
    names = [s.name for s in got]
    by_id = {s.id: s for s in got}

    assert names.count("stage.graph") == 1
    # sage: the feature table and the flow's label table
    assert names.count("stage.features") == (2 if kind == "sage" else 0)
    stage = next(s for s in got if s.name == "stage.graph")
    children = {s.name for s in got if s.parent == stage.id}
    assert {"stage.graph.degrees", "stage.graph.sweep", "stage.graph.planes"} <= children

    firsts = [s for s in got if s.name == "step.first_call"]
    programs = ["multi_step", "train_step"] if k > 1 else ["train_step"]
    assert sorted(s.args["program"] for s in firsts) == programs
    for first in firsts:
        assert by_id[first.parent].name == "train.dispatch"
        kinds = {s.name.rsplit(".", 1)[1] for s in got if s.parent == first.id}
        assert {"trace", "lower", "compile"} <= kinds
        for s in got:
            if s.parent == first.id:
                assert first.start_ns <= s.start_ns <= s.end_ns <= first.end_ns

    trains = [s for s in got if s.name == "train"]
    assert [s.args["steps"] for s in trains] == [2 * k, 3 * k - 1]
    dispatches = [s for s in got if s.name == "train.dispatch"]
    batches = [s for s in got if s.name == "train.next_batch"]
    # k = 1: a dispatch a step; k = 2: two scans of 2, then two scans
    # and a remainder of one single step
    steps = [0, 1, 2, 3] if k == 1 else [0, 2, 4, 6, 8]
    assert [s.step for s in dispatches] == steps
    assert [s.step for s in batches] == steps
    assert all(by_id[s.parent].name == "train.step" for s in dispatches + batches)
    assert all(by_id[by_id[s.parent].parent].name == "train" for s in dispatches)
    drains = [s for s in got if s.name == "train.drain"]
    assert [s.step for s in drains] == [2 * k, est.step]
    assert all(by_id[s.parent].name == "train" for s in drains)
    assert est.step == 5 * k - 1
    assert "train.save" not in names


def test_checkpoint_is_a_span(graph, tmp_path):
    est, since = _estimator("skipgram", graph, tmp_path, checkpoint_steps=2)
    est.train(2, log=False, save=False)
    saves = [s for s in _mine(since) if s.name == "train.save"]
    assert [s.step for s in saves] == [2]


def test_profiled_stretch_has_steps_spans_and_scopes(graph, tmp_path):
    """`profile_dir` through the one helper, in the scan loop: the trace
    holds `euler.step` markers and the program's host spans."""
    from jax.profiler import ProfileData

    est, _ = _estimator(
        "skipgram", graph, tmp_path, steps_per_call=2,
        profile_dir=str(tmp_path / "prof"), profile_start_step=2,
        profile_steps=2,
    )
    est.train(7, log=False, save=False)
    assert est._profiled
    found = list((tmp_path / "prof").rglob("*.xplane.pb"))
    assert len(found) == 1
    host = [
        (ev.name, dict(ev.stats))
        for plane in ProfileData.from_file(str(found[0])).planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("euler.")
    ]
    steps = [a["step_num"] for n, a in host if n == "euler.step"]
    assert steps == [2]  # one dispatch of two steps, then the trace stops
    assert [a["step"] for n, a in host if n == "euler.train.dispatch"] == [2]
    # a second call takes no second trace
    est.train(2, log=False, save=False)
    assert len(list((tmp_path / "prof").rglob("*.xplane.pb"))) == 1


# -- what interrupts the host ----------------------------------------------


@pytest.fixture
def no_collector_of_its_own():
    """Only the runs a test plants: the collector's own are off."""
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


def test_a_counted_span_keeps_only_the_counters_that_moved(monkeypatch):
    since = time.perf_counter_ns()
    readings = iter([(5, 7, 0, 100), (5, 9, 0, 103), (5, 9, 0, 103), (5, 9, 0, 103)])
    monkeypatch.setattr(trace, "_interruptions", lambda: next(readings))
    monkeypatch.setattr(trace, "INTERRUPTIONS_COUNTED", True)
    with trace.counted("t.counted", step=3) as moved:
        pass
    with trace.counted("t.counted", step=4) as idle:
        pass
    assert moved.args == {"step": 3, "nvcsw": 2, "minflt": 3}
    assert idle.args == {"step": 4}
    kept = [s for s in _mine(since) if s.name == "t.counted"]
    assert [s.args for s in kept] == [moved.args, idle.args]
    assert [s.step for s in kept] == [3, 4]


def test_a_counted_span_is_a_plain_one_where_the_host_counts_nothing(monkeypatch):
    """gVisor has `getrusage(RUSAGE_THREAD)` and fills no counter: the
    call is not made there (6 us each on the chip tool's machine)."""
    def never():
        raise AssertionError("getrusage asked on a host that counts nothing")

    monkeypatch.setattr(trace, "_interruptions", never)
    monkeypatch.setattr(trace, "INTERRUPTIONS_COUNTED", False)
    with trace.counted("t.uncounted", step=1) as quiet:
        bytearray(1 << 20)
    assert quiet.args == {"step": 1}


@pytest.mark.skipif(not trace.INTERRUPTIONS_COUNTED, reason="this host counts none")
def test_a_counted_span_sees_its_own_threads_page_faults():
    with trace.counted("t.faults") as touched:
        pages = bytearray(32 << 20)
        pages[::4096] = b"\x01" * len(pages[::4096])
    assert touched.args.get("minflt", 0) >= 1
    assert set(touched.args) <= {"nivcsw", "nvcsw", "majflt", "minflt"}


def test_a_collector_run_is_a_span_under_the_span_it_interrupted(
    no_collector_of_its_own,
):
    since = time.perf_counter_ns()
    with trace.span("t.outer") as outer:
        with trace.span("t.inner") as inner:
            gc.collect()
        gc.collect(0)
    gc.collect()  # under no span: nobody's interruption
    with trace.span("stage.t_gc"):
        gc.collect()  # set-up allocates by the million: not kept
    got = _mine(since)
    by_id = {s.id: s for s in got}
    runs = [s for s in got if s.name == "gc"]
    assert [(r.parent, r.args["generation"]) for r in runs] == [
        (inner.id, 2), (outer.id, 0),
    ]
    for run in runs:
        assert set(run.args) == {"generation"}
        held = by_id[run.parent]
        assert held.start_ns <= run.start_ns <= run.end_ns <= held.end_ns
        assert run.thread == held.thread


def test_a_compile_outside_a_first_call_is_a_late_compile():
    import jax.numpy as jnp

    since = time.perf_counter_ns()
    x = jnp.ones(3)
    with trace.span("t.late") as late:
        jax.jit(lambda v: v * 3 + 1)(x)
    with trace.span("t.collecting") as collecting, trace.compiles() as events:
        jax.jit(lambda v: v * 5 + 2)(x)
    with trace.span("stage.t_compile"):
        jax.jit(lambda v: v * 7 + 3)(x)  # in set-up nothing is late
    jax.jit(lambda v: v * 9 + 4)(x)  # under no span
    found = [s for s in _mine(since) if s.name == "late_compile"]
    assert {s.parent for s in found} == {late.id}
    assert {"trace", "lower", "compile"} <= {s.args["event"] for s in found}
    for s in found:
        assert set(s.args) == {"event"} and late._t0 <= s.start_ns < s.end_ns
    assert {"trace", "lower", "compile"} <= {kind for kind, _, _ in events}
    assert all(collecting._t0 <= lo <= hi for _, lo, hi in events)


def test_innermost_gives_every_instant_to_the_interval_that_began_last():
    nested = [(0, 100, "a"), (10, 60, "b"), (20, 30, "c"), (70, 80, "b"), (10, 15, "d")]
    assert trace.innermost(nested) == [
        (0, 10, "a"), (10, 15, "d"), (15, 20, "b"), (20, 30, "c"), (30, 60, "b"),
        (60, 70, "a"), (70, 80, "b"), (80, 100, "a"),
    ]
    # what no interval holds is nobody's, and one key's neighbours are joined
    assert trace.innermost([(0, 10, "a"), (10, 20, "a"), (30, 40, "b")]) == [
        (0, 20, "a"), (30, 40, "b"),
    ]
    assert trace.innermost([]) == []


def test_self_stretches_of_nested_events_are_disjoint():
    events = [
        ("trace", 10, 30),      # an inner jit, traced inside the outer trace
        ("lower", 30, 40),      # ... lowered
        ("cache_fetch", 42, 58),
        ("compile", 40, 60),    # ... and compiled there
        ("trace", 0, 100),      # the step's own trace
        ("trace", 110, 120),    # a kernel's body, traced while lowering
        ("lower", 100, 150),
        ("cache_fetch", 160, 390),
        ("compile", 150, 400),
    ]
    got = trace.self_stretches(events, ("trace", "lower", "compile"))
    assert got == {
        "trace": [(0, 30), (60, 100), (110, 120)],
        "lower": [(30, 40), (100, 110), (120, 150)],
        "compile": [(40, 60), (150, 400)],
    }
    assert sum(hi - lo for parts in got.values() for lo, hi in parts) == 400
    # the old record, first start to last end a kind, summed to 100 + 120 + 360
    assert trace.self_stretches(events, ("cache_fetch",)) == {
        "cache_fetch": [(42, 58), (160, 390)]
    }
    assert trace.self_stretches([], ("trace",)) == {"trace": []}


def test_first_call_children_cover_disjoint_time():
    """An inner `jax.jit` is traced, lowered and compiled inside the
    outer trace: the children still add up to no more than the call."""
    import jax.numpy as jnp

    since = time.perf_counter_ns()

    @jax.jit
    def outer(v):
        # a value made eagerly inside the trace compiles a program there
        with jax.ensure_compile_time_eval():
            table = jax.jit(lambda n: jnp.arange(n) * 2.5, static_argnums=0)(3)
        return jax.jit(lambda w: w * table)(v)

    with estimator_module._first_call("t_nested", {}):
        jax.block_until_ready(outer(jnp.ones(3)))
    got = _mine(since)
    (call,) = [s for s in got if s.name == "step.first_call"]
    kids = [s for s in got if s.parent == call.id]
    assert {s.name for s in kids} >= {
        "step.first_call.trace", "step.first_call.lower", "step.first_call.compile",
    }
    staged = sorted(
        (s.start_ns, s.end_ns) for s in kids
        if not s.name.endswith("cache_fetch")
    )
    for (_, end), (start, _) in zip(staged, staged[1:]):
        assert end <= start
    total = sum(hi - lo for lo, hi in staged)
    assert 0 < total <= call.end_ns - call.start_ns
    traced = [s for s in kids if s.name.endswith(".trace")]
    assert len(traced) >= 2  # the inner program's compile splits the trace
    assert not [s for s in got if s.name == "late_compile" and s.parent == call.id]


def test_first_call_carries_the_tallies_that_moved_and_no_other():
    """`step.first_call` keeps each `trace.count` name that moved while
    the program was traced, by how much, whoever counted it; a name
    counted before the span opened is not among its args."""
    import jax.numpy as jnp

    trace.count("t_earlier")
    since = time.perf_counter_ns()

    @jax.jit
    def counted(v):
        trace.count("t_moved", 2)
        return v + 1

    with estimator_module._first_call("t_moved", {}):
        jax.block_until_ready(counted(jnp.ones(3)))
    (call,) = [s for s in _mine(since) if s.name == "step.first_call"]
    assert call.args["t_moved"] == 2
    assert "t_earlier" not in call.args


# -- the change is the host's ------------------------------------------------

STEP_HASHES = os.path.join(os.path.dirname(__file__), "step_program_hashes.json")


def step_program_hash(est) -> str:
    est._ensure_init()
    args = (est.params, est.opt_state, est._tables(), est._rngs(0), *est._next_batch(1))
    text = est._train_step().lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("kind", ["sage", "sequence", "lfm2"])
def test_the_step_program_is_the_recorded_one(kind, graph, tmp_path):
    """Spans, counters and the drain's fetch are the host's: the lowered
    `train_step` of a graph model and of two sequence models (a DeltaNet
    / attention decoder with a head of its own; a convolution / attention
    decoder with a tied head, since PR 42) hash as
    `step_program_hashes.json` says (written at PR 38's parent;
    `sequence` again at PR 43, whose delta rule brings its own backward). A PR
    that changes the step program on purpose writes the hashes this
    test's failure shows into that file."""
    with open(STEP_HASHES) as f:
        want = json.load(f)
    if want["jax"] != jax.__version__:
        pytest.skip(f"hashes are of JAX {want['jax']}, this is {jax.__version__}")
    est, _ = _estimator(kind, graph, tmp_path)
    assert step_program_hash(est) == want[kind]
