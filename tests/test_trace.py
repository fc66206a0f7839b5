"""The layers' names: `euler.*` scopes in both step programs' HLO
metadata, and `utils/trace.py`'s host spans and their record."""

import re
import threading
import time

import pytest

from euler_tpu.dataflow import DeviceSageFlow, DeviceWalkFlow
from euler_tpu.datasets.synthetic import random_graph
from euler_tpu.estimator import DeviceFeatureCache, Estimator, EstimatorConfig
from euler_tpu.models import GraphSAGESupervised
from euler_tpu.models.embedding_models import SkipGramModel
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
    assert steps[0].step == 10  # the oldest went
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
    assert all(by_id[s.parent].name == "train" for s in dispatches + batches)
    drains = [s for s in got if s.name == "train.drain"]
    assert [s.step for s in drains] == [2 * k, est.step]
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
