"""What the readers of a sequence model's per-layer metrics share: the
scope table with XLA's own kernels put down to their layer, device time
under every scope of one layer (`euler.gdn.*`), a kernel's share of its
roofline, and the program's own count of the rows its experts saw in the
traced steps.

A scope's time is forward + backward, and a rematerialised forward runs
under `transpose(`, so `scoped.scope_of` counts it as backward: the time
under a scope is all the time the step spends there, recomputation
included, while the FLOPs and bytes set against it are the needed ones
(`counts/<family>.py:kernels`), counted once.
"""

from __future__ import annotations

import scoped
import tracered as tr

# XLA rewrites a ragged dot into custom calls of its own and names them
# itself (`op_name="ragged-dot-none"`): the scope it was traced under is
# gone. They are the grouped matmuls of the expert layer, and nothing else
# in the program makes one.
KERNEL_KEYS = {"ragged-dot": "moe.experts.kernel"}
LOOSE = scoped.UNSCOPED + ":"

_TABLE: list = []  # [events, (program, steps), (table, loose)]: the last one


def _key(event: dict) -> str:
    op_name = event.get("op_name") or ""
    for prefix, key in KERNEL_KEYS.items():
        if op_name.startswith(prefix):
            return key
    key = scoped.layer_key(event)
    if key == scoped.UNSCOPED:  # told apart, to say what the largest are
        return LOOSE + event["name"].split(" = ")[0]
    return key


def partition(events: list, program: str, steps_per_program: int):
    """`scoped.partition` with the kernels XLA names itself put down to
    their layer: (self time per step by `<scope>.<direction>` and
    `unscoped`, the unscoped time by instruction), nanoseconds. None where
    the trace holds no execution or no op under any scope."""
    runs = tr.program_runs(events, program)
    planes = tr.device_planes(events)
    if not runs or not planes:
        return None
    ops = [
        {**e, "name": _key(e)}
        for e in tr.select(events, plane=planes[0], line=tr.OPS_LINE)
    ]
    if all(e["name"].startswith(LOOSE) for e in ops):
        return None
    steps = len(runs) * steps_per_program
    table: dict = {}
    loose: dict = {}
    for lo, hi in runs:
        for key, ns in tr.self_times(ops, lo, hi).items():
            if key.startswith(LOOSE):
                loose[key[len(LOOSE):]] = loose.get(key[len(LOOSE):], 0) + ns / steps
                key = scoped.UNSCOPED
            table[key] = table.get(key, 0) + ns / steps
    return table, loose


def _partitioned(run: dict):
    events = scoped.events_of()
    key = (run["step_program"], run["steps_per_program"])
    if not _TABLE or _TABLE[0] is not events or _TABLE[1] != key:
        _TABLE[:] = [events, key, partition(events, *key)]
    return _TABLE[2]


def layers(run: dict):
    """The scope table of this run's step program, or None."""
    found = _partitioned(run)
    return None if found is None else found[0]


def notes(run: dict, top: int = 8):
    """What `breakdown.notes.layers` gets in a sequence model's cell: the
    scope table per step in ms, the largest unscoped instructions, and
    the idle gaps by program span as `scoped.notes` reckons them."""
    found = _partitioned(run)
    if found is None:
        return None
    table, loose = found
    by_size = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "scope_ms_per_step": {k: v / 1e6 for k, v in by_size(table)},
        "unscoped_ms_by_op": {k: v / 1e6 for k, v in by_size(loose)[:top]},
        "idle_ms_by_span": (scoped.notes(run) or {}).get("idle_ms_by_span", {}),
    }


def prefix_ms(run: dict, prefix: str):
    """Per-step milliseconds under every `<prefix>.*` scope, both
    directions; None where the trace has no such scope."""
    table = layers(run)
    if table is None:
        return None
    inside = (prefix + ".forward", prefix + ".backward")
    ns = sum(
        v for k, v in table.items() if k in inside or k.startswith(prefix + ".")
    )
    return ns / 1e6 or None


def routed_share():
    """Mean of the model's metric over the steps dispatched while a
    profiler session was live, which are the traced steps: the program
    leaves each one's device scalar in its `train.dispatch` span, and it
    is fetched here. None for a program that records none."""
    values = [
        float(s.args["metric"]) for s in scoped.program_spans()
        if s.name == "train.dispatch" and "metric" in s.args
    ]
    return sum(values) / len(values) if values else None


def roofline_pct(run: dict, scope: str, flops: float, nbytes: float):
    """The least time the chip could take for `flops` and `nbytes` over
    the per-step time under `scope`, in %; `run["notes"]` says which
    bound. None where there is nothing under the scope."""
    ms = prefix_ms(run, scope)
    if not ms or not flops:
        return None
    by_flops = flops / run["peak"]["flops_per_s"]
    by_bytes = nbytes / run["peak"]["bytes_per_s"]
    run["notes"][f"{scope}_roofline_bound"] = (
        "memory" if by_bytes >= by_flops else "compute"
    )
    return 100.0 * max(by_flops, by_bytes) / (ms / 1e3)
