"""From a profiler trace to numbers: the one reduction every PR shares.

`load` turns the profiler's `.xplane.pb` into plain events
(plane, line, name, start_ns, dur_ns); everything else works on that
list, so the tests can run it on a small recorded list. On a TPU the
device plane is `/device:TPU:<n>`; its line `XLA Ops` holds one event
per executed HLO op (nested where an op calls others) and `XLA Modules`
one per executed program. Host threads are lines of `/host:CPU`, where
`jax.profiler.TraceAnnotation` spans land. All planes share one clock.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, lines=(OPS_LINE, MODULES_LINE), host_prefix="bench.") -> list:
    """Device events of `lines`, and host events named `host_prefix`*."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and not plane.name.startswith(HOST_PLANE):
            continue
        for line in plane.lines:
            if device and line.name not in lines:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(host_prefix):
                    continue
                events.append(
                    {
                        "plane": plane.name,
                        "line": line.name,
                        "name": ev.name,
                        "start_ns": int(ev.start_ns),
                        "dur_ns": int(ev.duration_ns),
                    }
                )
    return events


def describe(path: str, top: int = 12) -> dict:
    """Planes, lines and their commonest event names: what to read by
    hand before trusting the reduction on a new chip or JAX."""
    from collections import Counter

    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            names = Counter()
            count = 0
            for ev in line.events:
                names[ev.name] += 1
                count += 1
            out[f"{plane.name} | {line.name}"] = {
                "events": count,
                "names": names.most_common(top),
            }
    return out


def device_planes(events: list) -> list:
    return sorted({e["plane"] for e in events if e["plane"].startswith(DEVICE_PLANE)})


def select(events, plane=None, line=None, prefix=None) -> list:
    return [
        e
        for e in events
        if (plane is None or e["plane"] == plane)
        and (line is None or e["line"] == line)
        and (prefix is None or e["name"].startswith(prefix))
    ]


def union(intervals: list) -> list:
    """Merged, sorted (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def spans(events: list) -> list:
    return [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events]


def total(intervals: list) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: list, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_seconds(events: list, lo: int, hi: int) -> float:
    """Seconds inside [lo, hi) in which an op ran, averaged over the
    device planes present."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    busy = [
        total(clip(union(spans(select(events, plane=p, line=OPS_LINE))), lo, hi))
        for p in planes
    ]
    return sum(busy) / len(busy) / 1e9


def program_runs(events: list, program: str) -> list:
    """(start, end) of each execution of the program whose module name
    starts with `program`, on the first device plane, in time order."""
    planes = device_planes(events)
    if not planes:
        return []
    return sorted(
        spans(select(events, plane=planes[0], line=MODULES_LINE, prefix=program))
    )


def busy_inside(events: list, runs: list) -> int:
    """Nanoseconds of op time inside the given program executions."""
    planes = device_planes(events)
    if not planes or not runs:
        return 0
    ops = union(spans(select(events, plane=planes[0], line=OPS_LINE)))
    return sum(total(clip(ops, s, e)) for s, e in runs)


def step_ns(events: list, program: str, steps_per_program: int):
    """Op time inside the program's executions per training step, in
    nanoseconds; None where the trace holds no execution."""
    runs = program_runs(events, program)
    if not runs:
        return None
    return busy_inside(events, runs) / (len(runs) * steps_per_program)


def self_times(events: list, lo: int, hi: int) -> dict:
    """Per op name, the time inside [lo, hi) not covered by ops nested
    in it (first device plane)."""
    planes = device_planes(events)
    if not planes:
        return {}
    ops = sorted(
        (e for e in select(events, plane=planes[0], line=OPS_LINE)
         if e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo),
        key=lambda e: (e["start_ns"], -e["dur_ns"]),
    )
    out: dict = {}
    stack: list = []  # [name, end, self_ns]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0) + own

    for e in ops:
        start = max(e["start_ns"], lo)
        end = min(e["start_ns"] + e["dur_ns"], hi)
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([e["name"], end, end - start])
    close(float("inf"))
    return out


def idle_gaps(events: list, lo: int, hi: int) -> list:
    """(start, end) of the stretches of [lo, hi) with no op running on
    the first device plane, longest first."""
    planes = device_planes(events)
    if not planes:
        return []
    busy = clip(union(spans(select(events, plane=planes[0], line=OPS_LINE))), lo, hi)
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def host_doing(events: list, start: int, end: int) -> str:
    """The innermost of the benchmark's host spans that holds the middle
    of [start, end)."""
    middle = (start + end) // 2
    best, width = "unannotated", None
    for e in events:
        if not e["plane"].startswith(HOST_PLANE):
            continue
        if e["start_ns"] <= middle < e["start_ns"] + e["dur_ns"]:
            if width is None or e["dur_ns"] < width:
                best, width = e["name"], e["dur_ns"]
    return best


def breakdown(events: list, lo: int, hi: int, top: int = 10) -> dict:
    ops = sorted(self_times(events, lo, hi).items(), key=lambda kv: -kv[1])
    named: dict = {}
    for s, e in idle_gaps(events, lo, hi):
        what = host_doing(events, s, e)
        named[what] = named.get(what, 0) + (e - s)
    gaps = sorted(named.items(), key=lambda kv: -kv[1])
    return {
        # on the TPU an op's name is its whole HLO line: keep its head
        "device_ops": [[n[:120], t / 1e9] for n, t in ops[:top]],
        "idle_gaps": [[n, t / 1e9] for n, t in gaps[:top]],
    }
