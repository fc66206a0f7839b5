"""Seconds of set-up in the first call of the window's step program: its
`step.first_call` span. The parts — tracing, lowering, cache fetch or
compile, and what remains, the first run — go to `run["notes"]`."""

import scoped


def read(run: dict):
    spans = scoped.program_spans()
    calls = [
        s for s in spans
        if s.name == "step.first_call"
        and "jit_" + str(s.args.get("program")) == run["step_program"]
    ]
    if not calls:
        return None
    total = sum(s.end_ns - s.start_ns for s in calls) / 1e9
    mine = {s.id for s in calls}
    parts: dict = {}
    for s in spans:
        if s.parent in mine:
            kind = s.name.rsplit(".", 1)[1] + "_s"
            parts[kind] = parts.get(kind, 0.0) + (s.end_ns - s.start_ns) / 1e9
    # `compile` holds `cache_fetch`; tracing, lowering and compile follow
    # one another, and the call's remainder is the first run
    staged = sum(parts.get(k, 0.0) for k in ("trace_s", "lower_s", "compile_s"))
    rest = total - staged
    run["notes"]["step_compile_s"] = {**parts, "first_run_s": rest}
    return total
