"""Seconds of set-up the program spent staging: its `stage.features` and
`stage.graph` spans (euler_tpu/utils/trace.py keeps set-up spans in
memory; no profiler runs that early)."""

import scoped


def read(run: dict):
    spans = scoped.program_spans()
    staged = [
        s for s in spans
        if s.name in ("stage.features", "stage.graph") and s.parent is None
    ]
    if not staged:
        return None
    by_id = {s.id: s for s in staged}
    run["notes"]["stage_s"] = {
        f"{s.name}#{s.id}": (s.end_ns - s.start_ns) / 1e9
        for s in spans
        if s.id in by_id or s.parent in by_id
    }
    return sum(s.end_ns - s.start_ns for s in staged) / 1e9
