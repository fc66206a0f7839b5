"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name from BENCHMARK.json:
the configuration's file, `traffic/<mix>.json`, `families/<family>.py`
(how the program is handed this kind of model), `reference/<name>.py`
(the plain reference), `counts/<name>.py`, `limits/<cell>.json` and one
`layer_metrics/<metric>.py` per per-layer metric. Nothing here switches
on a cell's name. README.md says how to add each.

One run: build the graph of the configuration, hand it to the program,
make the weights and draw the batches from --seed (or from the seed the
configuration fixes, `weights.run_seed`), drive the program's first
three steps through the very Estimator the window then drives, warm up,
measure for --seconds, read the memory peak, free the program, run the
plain reference over the same three steps and compare.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
ADAM_B1 = 0.9


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, here: str = HERE):
    """`<here>/<kind>/<name>.py`, found by name."""
    path = os.path.join(here, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(base[k], v) if isinstance(v, dict) and isinstance(base.get(k), dict) else v
    return out


def resolve(workload: str, root: str = ROOT) -> dict:
    """The cell's entry with its configuration, mix, limits and the
    metric entries it reports."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    here = os.path.join(root, bench["paths"][0])

    def mine(metric):
        return cell["name"] in metric.get("workloads", [cell["name"]])

    return {
        "cell": cell,
        "here": here,
        "config": load_json(os.path.join(root, entry["file"])),
        "mix": load_json(os.path.join(here, "traffic", f"{cell['traffic']}.json")),
        "limits": load_json(os.path.join(here, "limits", f"{cell['name']}.json")),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def require_tpu(chips: int) -> None:
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"no accelerator: {e}")
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"benchmark needs {chips} TPU chip(s); JAX offers "
            f"{len(devices)} x {devices[0].platform}",
            file=sys.stderr,
        )
        raise SystemExit(2)


def configure_jax() -> None:
    """The program's own compile cache (inside the checkout, or where
    JAX_COMPILATION_CACHE_DIR says), taking every program of a run
    whatever its size or compile time, so that only a checkout's first
    run of a cell compiles. Since PR 27 the tables are the step's
    arguments: its entry is 6-20 MB, not the graph's 2 GB."""
    import jax

    from euler_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def trim() -> None:
    """Hands freed host memory back to the system (glibc keeps each
    thread's arena otherwise): generating and staging the graph leave
    several GB freed, and the reference stages it once more."""
    import ctypes

    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def phase(name: str) -> None:
    """One line on stderr per phase: seconds since start, host memory."""
    with open("/proc/self/statm") as f:
        rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 2**30
    print(
        f"phase {name}: {time.perf_counter() - _T0:.1f} s, host {rss:.1f} GiB",
        file=sys.stderr, flush=True,
    )


class CompileCounter:
    """Counts programs compiled or fetched from the compile cache."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_args, **_kw):
        if name.endswith("backend_compile_duration") or name.endswith(
            "cache_retrieval_time_sec"
        ):
            self.count += 1


def make_estimator(
    built: dict, config: dict, mix: dict, spec: list, seed: int,
    weights_seed: int | None = None,
):
    """The object the first steps and the window both drive: batches,
    sampling keys and weights from `seed`; `proof.py` may make the
    weights from a `weights_seed` of their own."""
    from euler_tpu.estimator import Estimator, EstimatorConfig

    import weights

    cfg = EstimatorConfig(
        model_dir=os.path.join(tempfile.gettempdir(), "bench_never_saved"),
        learning_rate=config["optimizer"]["learning_rate"],
        optimizer=config["optimizer"]["name"],
        log_steps=10**9,
        seed=weights.key_seed(seed),
        steps_per_call=mix["steps_per_call"],
    )
    est = Estimator(
        built["model"], built["flow"], cfg, feature_cache=built["feature_cache"]
    )
    if weights_seed is None:
        weights_seed = seed
    est.params = weights.nest(weights.make_params(spec, weights_seed))
    return est


def program_first_steps(est, spec: list, weights_seed: int) -> dict:
    """Losses of steps 1-3, the first gradient's norm per leaf from
    Adam's first moment after one step, and each leaf's change after
    three (from the weights `weights_seed` made) — through
    `Estimator.train`, as the window calls it."""
    import weights

    losses = est.train(1, log=False, save=False)
    adam = next(s for s in est.opt_state if hasattr(s, "mu"))
    grad = {
        k: float(v) / (1.0 - ADAM_B1)
        for k, v in weights.leaf_norms(weights.flatten(adam.mu)).items()
    }
    losses += est.train(2, log=False, save=False)
    change = weights.change_norms(
        weights.flatten(est.params), weights.make_params(spec, weights_seed)
    )
    return {
        "loss": [float(x) for x in losses],
        "grad_norm": grad,
        "change_norm": {k: float(v) for k, v in change.items()},
    }


def measure(est, mix: dict, seconds: float, trace_dir: str | None) -> dict:
    """The window: `Estimator.train` calls of `steps_per_train_call`
    steps until `seconds` have passed; it closes when the last call that
    began inside has finished on the device."""
    import jax

    per_call = mix["steps_per_train_call"]
    traced_calls = mix["trace_train_calls"] if trace_dir else 0
    calls, losses, traced = [], [], None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        span = jax.profiler.TraceAnnotation("bench.traced")
        span.__enter__()
    start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        if t0 - start >= seconds:
            break
        with jax.profiler.TraceAnnotation("bench.train_call"):
            losses += est.train(per_call, log=False, save=False)
            jax.block_until_ready(est.params)
        calls.append(time.perf_counter() - t0)
        if trace_dir and traced is None and len(calls) >= traced_calls:
            traced = len(calls) * per_call
            # writing the trace out takes seconds: they are not the window's
            t_stop = time.perf_counter()
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            start += time.perf_counter() - t_stop
    elapsed = time.perf_counter() - start
    if trace_dir and traced is None:
        raise RuntimeError("the window closed before the traced calls had run")
    return {
        "steps": len(calls) * per_call,
        "elapsed": elapsed,
        "call_seconds": calls,
        "losses": losses,
        "traced_steps": traced if traced else 0,
    }


def traced_window(events: list) -> tuple:
    """[lo, hi) of the traced stretch on the trace's clock: the
    `bench.traced` host span, else the extent of the device's ops."""
    import tracered

    marks = [e for e in events if e["name"] == "bench.traced"]
    if marks:
        return marks[0]["start_ns"], marks[0]["start_ns"] + marks[0]["dur_ns"]
    ops = tracered.spans(
        [e for e in events if e["plane"].startswith(tracered.DEVICE_PLANE)]
    )
    return min(s for s, _ in ops), max(e for _, e in ops)


def keep_trace(out_dir: str, trace_dir: str, events: list, mix: dict) -> None:
    """For reading a trace by hand: what planes and lines it has, and
    the events of its first two step executions (a recording small
    enough to keep as a test's fixture)."""
    import tracered

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "describe.json"), "w") as f:
        json.dump(tracered.describe(tracered.find_xplane(trace_dir)), f, indent=1)
    runs = tracered.program_runs(events, mix["step_program"])[:2]
    hi = runs[-1][1] if runs else 0
    sample = [e for e in events if e["start_ns"] < hi]
    with open(os.path.join(out_dir, "events.json"), "w") as f:
        json.dump(sample, f)


def read_layer_metrics(resolved: dict, run: dict) -> dict:
    """Each per-layer metric's reader, found by the metric's name. A
    reader that finds nothing returns None and the metric is left out."""
    out = {}
    for metric in resolved["per_layer"]:
        reader = load_module("layer_metrics", metric["name"], resolved["here"])
        value = reader.read(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def reduce_trace(args, resolved, mix, counts, window, memory_peak, trace_dir):
    """The traced run's part: the window's trace to per-layer metrics,
    the device's busy time and the breakdown."""
    import jax

    import tracered

    device = jax.devices()[0]
    peaks = load_json(os.path.join(resolved["here"], "peaks.json"))
    if device.device_kind not in peaks and not args.rehearse:
        raise SystemExit(f"no peaks for device kind {device.device_kind!r}")
    events = tracered.load(tracered.find_xplane(trace_dir))
    lo, hi = traced_window(events)
    if args.keep_trace:
        keep_trace(args.keep_trace, trace_dir, events, mix)
    run_facts = {
        "trace": events,
        "step_program": mix["step_program"],
        "steps_per_program": mix["steps_per_call"],
        "call_seconds": window["call_seconds"],
        "traced_steps": window["traced_steps"],
        "traced_seconds": (hi - lo) / 1e9,
        "busy_s": tracered.busy_seconds(events, lo, hi),
        "memory_peak_bytes": memory_peak,
        "counts": counts,
        "peak": peaks.get(device.device_kind),
        "notes": {},
    }
    layer = {} if args.rehearse else read_layer_metrics(resolved, run_facts)
    breakdown = tracered.breakdown(events, lo, hi)
    breakdown["notes"] = run_facts["notes"]
    extra = {"busy_s": run_facts["busy_s"], "window_s": run_facts["traced_seconds"]}
    return layer, extra, breakdown


def decide(compared: dict, limits: dict) -> tuple:
    """Every compared number beside its limit; correct when none is
    over, and none is missing or not a number."""
    table, ok = {}, True
    for name, limit in limits["limits"].items():
        value = compared.get(name)
        table[name] = {"value": value, "limit": limit}
        if value is None or not value <= limit:
            ok = False
    return table, ok


def stage(workload: str, rehearse: bool) -> dict:
    """What a run and `proof.py` share: the cell resolved, a TPU found,
    the configuration's graph generated and staged by the program, and
    the cell's reference, counts and weight spec loaded by name."""
    resolved = resolve(workload)
    config = resolved["config"]
    if rehearse:
        config = merge(config, config["rehearse"])
    for p in (HERE, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    if not rehearse:
        require_tpu(resolved["cell"]["chips"])
    configure_jax()

    import graphs

    here, mix = resolved["here"], resolved["mix"]
    family = load_module("families", config["family"], here)
    reference = load_module("reference", family.REFERENCE, here)
    graph = graphs.build(config["graph"])
    trim()
    phase("graph generated")
    built = family.build(config, mix, graph)
    trim()
    phase("graph staged by the program")
    return {
        **resolved,
        "config": config,
        "graph": graph,
        "built": built,
        "reference": reference,
        "train": load_module("reference", "train", here),
        "counts": load_module("counts", family.COUNTS, here).per_step(config),
        "spec": reference.param_spec(config, graph),
    }


def run(args, plant=None) -> dict:
    import jax

    compiles = CompileCounter()
    resolved = stage(args.workload, args.rehearse)
    import weights  # `stage` has put this directory on the path

    cell, mix, config = resolved["cell"], resolved["mix"], resolved["config"]
    graph, built, spec = resolved.pop("graph"), resolved.pop("built"), resolved["spec"]
    reference, train, counts = resolved["reference"], resolved["train"], resolved["counts"]
    seed = weights.run_seed(config, args.seed)
    est = make_estimator(built, config, mix, spec, seed)
    if plant is not None:
        plant(est, built)
    got = program_first_steps(est, spec, seed)
    phase("first three steps")
    est.train(mix["steps_per_train_call"], log=False, save=False)
    jax.block_until_ready(est.params)
    setup_s = time.perf_counter() - _T0
    phase("warm")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        before = compiles.count
        window = measure(est, mix, args.seconds, trace_dir)
        window_compiles = compiles.count - before
        phase("window closed")
        device = jax.devices()[0]
        memory_peak = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))
        failed = sum(1 for x in window["losses"] if x != x or abs(x) == float("inf"))
        # the peak is read: the program's state goes, its staged tables
        # (the step's arguments) and the step's executable with it
        lr = config["optimizer"]["learning_rate"]
        facts, examples_per_step = built["facts"], built["examples_per_step"]
        est.params = est.opt_state = None
        del est, built
        jax.clear_caches()
        trim()
        phase("estimator freed")
        layer, device_extra, breakdown = {}, {}, None
        if args.trace:
            layer, device_extra, breakdown = reduce_trace(
                args, resolved, mix, counts, window, memory_peak, trace_dir
            )
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    tables, loss_fn = reference.make(config, mix, graph)
    t_ref = time.perf_counter()
    want = train.first_steps(loss_fn, tables, spec, seed, lr)
    reference_s = time.perf_counter() - t_ref
    phase("reference done")
    compared = train.compare(got, want)
    compared["window_compiles"] = window_compiles
    compared["failed_steps"] = failed
    table, correct = decide(compared, resolved["limits"])

    examples = window["steps"] * examples_per_step
    metrics = {}
    if not args.rehearse:
        if args.trace:
            metrics = layer
        else:
            values = {
                "examples_per_s": examples / window["elapsed"],
                "setup_s": setup_s,
            }
            metrics = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in resolved["end_to_end"]
            }
    d = jax.devices()
    result = {
        "correct": bool(correct),
        "attempted": window["steps"],
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": d[0].platform,
            "kind": d[0].device_kind,
            "count": len(d),
            "memory_peak_bytes": memory_peak,
            **device_extra,
        },
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["run"] = {
        "workload": cell["name"],
        "seed": args.seed,
        "run_seed": seed,
        "window_s": window["elapsed"],
        "train_calls": len(window["call_seconds"]),
        "reference_s": reference_s,
        "setup_s": setup_s,
        "rehearse": bool(args.rehearse),
        "facts": facts,
    }
    result["compared"] = table
    return result


def selftest() -> int:
    import pytest

    return int(pytest.main(["-q", "-p", "no:cacheprovider", os.path.join(HERE, "tests")]))


def main(argv=None, plant=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on whatever JAX offers; prints no metric")
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--keep-trace", default="",
                    help="directory for the trace's description and a sample")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.workload:
        ap.error("--workload is required")
    if args.seconds is None:
        args.seconds = float(load_benchmark()["run_seconds"])
    result = run(args, plant)
    for name, row in result["compared"].items():
        print(f"compared {name}: {row['value']} (limit {row['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
