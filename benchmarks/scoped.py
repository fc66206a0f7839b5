"""A traced run by layer: which `euler.*` scope each device op ran under,
and what the program's `euler.*` host spans say the host was doing.

The program names its layers (`euler_tpu/utils/trace.py`): `jax.named_scope`
puts `euler.<layer>` into every op's HLO `op_name`, and host spans named
`euler.*` land on `/host:CPU`. The harness hands readers `run["trace"]`,
which has an op's name and times only, and no host event but `bench.*`;
so this file reads the trace itself, once per process.

Read by hand on the v5e under JAX 0.9.0 before this was written:

- `jax.profiler.ProfileData` gives an event's own stats
  (`device_offset_ps`, `device_duration_ps`, `Time Scale Multiplier`) but
  not the stats of the event's *metadata*, and the `op_name` is there: the
  stat `tf_op` of each `XLA Ops` event's `XEventMetadata`, as
  `<op_name>:<op_type>` — `jit(train_step)/jvp(SkipGramModel)/target/
  euler.embed/gather:`. So the `.xplane.pb` is decoded here, by field
  number (tsl/profiler/protobuf/xplane.proto), with nothing imported.
- That also spares the second pass its cost: the `/host:metadata` plane
  carries the step's HLO with the graph as constants (2 GB in
  `sage-products-id`) and is skipped as one length-prefixed field.
- A fusion has one `op_name`: its root's. Work fused into it from another
  layer is counted under the root's layer.
- Backward ops keep their scope under `transpose(jvp(...))`:
  `jit(train_step)/transpose(jvp(SkipGramModel))/target/euler.embed/
  scatter-add`. Forward or backward is told by a `transpose(` before the
  scope's name.

Times are nanoseconds on the trace's clock, as `tracered.load` has them,
so the two agree on any event they both hold.
"""

from __future__ import annotations

import functools
import glob
import mmap
import os
import re
import statistics
import struct
import tempfile

import tracered as tr

SCOPE = "euler."
UNSCOPED = "unscoped"
HOST_PREFIXES = (SCOPE, "bench.")
OP_NAME_STAT = "tf_op"

_PARSED: dict = {}  # trace path -> events, so eight readers parse once
_TABLE: list = []  # [events, (program, steps), their partition, its `notes`]: the last one


def find_trace() -> str | None:
    """The live trace of this run: `run.py` keeps it under the temporary
    directory as `bench_trace_*` until the readers have returned."""
    found = glob.glob(
        os.path.join(
            tempfile.gettempdir(), "bench_trace_*", "plugins", "profile", "*",
            "*.xplane.pb",
        )
    )
    return max(found, key=os.path.getmtime) if found else None


# -- the protobuf wire format, as far as xplane.proto needs it ------------


def _varint(buf, i: int):
    b = buf[i]
    i += 1
    if b < 0x80:
        return b, i
    value, shift = b & 0x7F, 7
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of one message: an int for a varint, the
    (start, end) of the bytes for everything else."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = (i, i + 8)
            i += 8
        elif wire == 5:
            value = (i, i + 4)
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stats(buf, spans, stat_names: dict) -> dict:
    """XStat messages -> {stat name: value}."""
    out = {}
    for span in spans:
        name, value = None, None
        for no, v in _fields(buf, *span):
            if no == 1:
                name = stat_names.get(v)
            elif no == 2:
                value = struct.unpack("<d", bytes(buf[v[0]:v[1]]))[0]
            elif no == 3:
                value = v
            elif no == 4:
                value = _signed(v)
            elif no == 5:
                value = _text(buf, v)
            elif no == 7:  # a string kept as a stat metadata's name
                value = stat_names.get(v)
        if name is not None:
            out[name] = value
    return out


def _map_entries(buf, spans):
    """map<int64, Message> entries -> (key, (start, end) of the value)."""
    for span in spans:
        key, value = None, None
        for no, v in _fields(buf, *span):
            if no == 1:
                key = v
            elif no == 2:
                value = v
        if key is not None and value is not None:
            yield key, value


def _plane(buf, span) -> dict:
    """One XPlane's fields, sorted by kind; nothing below is decoded."""
    out = {"name": "", "lines": [], "event_metadata": [], "stat_metadata": []}
    for no, v in _fields(buf, *span):
        if no == 2:
            out["name"] = _text(buf, v)
        elif no == 3:
            out["lines"].append(v)
        elif no == 4:
            out["event_metadata"].append(v)
        elif no == 5:
            out["stat_metadata"].append(v)
    return out


def _event_names(buf, plane: dict, stat_names: dict, device: bool) -> dict:
    """metadata id -> (event name, op_name or None) for the events kept:
    every one of a device plane, the `euler.*` and `bench.*` of a host's."""
    out = {}
    for key, span in _map_entries(buf, plane["event_metadata"]):
        name, stats = "", []
        for no, v in _fields(buf, *span):
            if no == 2:
                name = _text(buf, v)
            elif no == 5 and device:
                stats.append(v)
        if device:
            op = _stats(buf, stats, stat_names).get(OP_NAME_STAT)
            out[key] = (name, op.rsplit(":", 1)[0] if op else None)
        elif name.startswith(HOST_PREFIXES):
            out[key] = (name, None)
    return out


def _line_events(buf, span, plane_name, names, stat_names, device, lines):
    line_name, t0, events = "", 0, []
    for no, v in _fields(buf, *span):
        if no == 2:
            line_name = _text(buf, v)
        elif no == 3:
            t0 = _signed(v)
        elif no == 4:
            events.append(v)
    if device and line_name not in lines:
        return
    for span in events:
        meta, offset_ps, duration_ps, stats = None, 0, 0, []
        for no, v in _fields(buf, *span):
            if no == 1:
                meta = v
            elif no == 2:
                offset_ps = v
            elif no == 3:
                duration_ps = v
            elif no == 4 and not device:
                stats.append(v)
        if meta not in names:
            continue
        name, op_name = names[meta]
        # as the profiler's own reader reckons them, then cut to whole
        # nanoseconds as tracered.load cuts them
        event = {
            "plane": plane_name,
            "line": line_name,
            "name": name,
            "start_ns": int(t0 + offset_ps / 1000.0),
            "dur_ns": int(duration_ps / 1000.0),
        }
        if device:
            event["op_name"] = op_name
        else:
            event["args"] = _stats(buf, stats, stat_names)
        yield event


def load(path: str, lines=(tr.OPS_LINE, tr.MODULES_LINE)) -> list:
    """`tracered.load`'s events of the device planes, each `XLA Ops` event
    with its `op_name`, and the host events named `euler.*` or `bench.*`
    with their arguments under `args`."""
    events = []
    with open(path, "rb") as f, mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as buf:
        for no, span in _fields(buf, 0, len(buf)):
            if no != 1:
                continue
            # only a plane's own fields are walked here: one that is not
            # wanted, the 2 GB of `/host:metadata` too, costs its header
            plane = _plane(buf, span)
            plane_name = plane["name"]
            device = plane_name.startswith(tr.DEVICE_PLANE)
            if not device and not plane_name.startswith(tr.HOST_PLANE):
                continue
            stat_names = {}
            for key, meta in _map_entries(buf, plane["stat_metadata"]):
                for n, v in _fields(buf, *meta):
                    if n == 2:
                        stat_names[key] = _text(buf, v)
            names = _event_names(buf, plane, stat_names, device)
            for line in plane["lines"]:
                events.extend(
                    _line_events(
                        buf, line, plane_name, names, stat_names, device, lines
                    )
                )
    return events


def events_of() -> list:
    """The run's trace, parsed once however many readers ask."""
    path = find_trace()
    if path is None:
        return []
    if path not in _PARSED:
        _PARSED[path] = load(path)
    return _PARSED[path]


def program_spans() -> list:
    """The program's in-memory record of host spans, set-up included;
    empty for a program from before it kept one."""
    try:
        from euler_tpu.utils import trace
    except ImportError:
        return []
    return trace.spans()


# -- from events to layers ------------------------------------------------


_SCOPE_RE = re.compile(r"euler\.([A-Za-z0-9_.]+)")


@functools.lru_cache(maxsize=65536)  # a program's op names repeat every step
def scope_of(op_name: str | None):
    """(innermost `euler.*` scope without the prefix, "forward" or
    "backward"), or None for an op outside every scope. JAX wraps a scope
    that is the first inside a transformed function — `jvp(euler.embed)`,
    `transpose(jvp(euler.embed))` — and leaves one under a flax module's
    name bare; both read the same here."""
    found = list(_SCOPE_RE.finditer(op_name or ""))
    if not found:
        return None
    backward = "transpose(" in op_name[: found[-1].start()]
    return found[-1].group(1), "backward" if backward else "forward"


def layer_key(event: dict) -> str:
    found = scope_of(event.get("op_name"))
    return UNSCOPED if found is None else f"{found[0]}.{found[1]}"


def partition(events: list, program: str, steps_per_program: int):
    """Self time (as `tracered.self_times` reckons it) inside the
    executions of `program`, per training step, by `<scope>.<forward |
    backward>` and `unscoped`, in nanoseconds. None where the trace holds
    no execution or no op under any `euler.*` scope: a program from before
    the scopes, or an executable the compile cache kept from then."""
    runs = tr.program_runs(events, program)
    planes = tr.device_planes(events)
    if not runs or not planes:
        return None
    ops = [
        {**e, "name": layer_key(e)}
        for e in tr.select(events, plane=planes[0], line=tr.OPS_LINE)
    ]
    if all(e["name"] == UNSCOPED for e in ops):
        return None
    total: dict = {}
    for lo, hi in runs:
        for key, ns in tr.self_times(ops, lo, hi).items():
            total[key] = total.get(key, 0) + ns
    steps = len(runs) * steps_per_program
    return {key: ns / steps for key, ns in total.items()}


def layers(run: dict):
    """`partition` of this run's step program, or None; worked once for
    the readers that share it."""
    events = events_of()
    key = (run["step_program"], run["steps_per_program"])
    # the list itself is kept, so that `is` cannot meet a recycled id
    if not _TABLE or _TABLE[0] is not events or _TABLE[1] != key:
        _TABLE[:] = [events, key, partition(events, *key), None]
    return _TABLE[2]


def layer_ms(run: dict, *keys: str):
    """Per-step milliseconds under the given `<scope>.<direction>` keys;
    None where the trace has no scopes at all, or none of these."""
    table = layers(run)
    if table is None:
        return None
    return sum(table.get(k, 0.0) for k in keys) / 1e6 or None


def host_spans(events: list, prefix: str = SCOPE) -> list:
    return [
        e for e in events
        if e["plane"].startswith(tr.HOST_PLANE) and e["name"].startswith(prefix)
    ]


# the train loop's body; `train.drain` fetches the call's losses, so it
# is a wait for the device and no work of the host's, and where a call
# is one step (the language-model cells) every step would hold one
HOST_STEP_SPANS = (f"{SCOPE}train.next_batch", f"{SCOPE}train.dispatch")


def host_step_ns(events: list):
    """Median over the traced steps of the host time in the train loop's
    body: the `HOST_STEP_SPANS` that carry the step's number."""
    per_step: dict = {}
    for e in host_spans(events):
        step = e.get("args", {}).get("step")
        if e["name"] in HOST_STEP_SPANS and step is not None:
            per_step[step] = per_step.get(step, 0) + e["dur_ns"]
    return statistics.median(per_step.values()) if per_step else None


def idle_by_span(events: list, lo: int, hi: int) -> dict:
    """Idle nanoseconds of [lo, hi) on the device, each gap put down to
    the innermost `euler.*` host span that holds its middle."""
    mine = host_spans(events)
    out: dict = {}
    for start, end in tr.idle_gaps(events, lo, hi):
        what = tr.host_doing(mine, start, end)
        out[what] = out.get(what, 0) + (end - start)
    return out


def notes(run: dict):
    """What `breakdown.notes` gets: the whole scope table per step in ms,
    and the traced stretch's idle gaps by program span. Worked once: in a
    sequence model's cell `sampler_ms` and `kernel_share.notes` both ask."""
    table = layers(run)
    if table is None:
        return None
    if _TABLE[3] is not None:
        return _TABLE[3]
    events = events_of()
    marks = [e for e in events if e["name"] == "bench.traced"]
    runs = tr.program_runs(events, run["step_program"])
    lo = marks[0]["start_ns"] if marks else runs[0][0]
    hi = marks[0]["start_ns"] + marks[0]["dur_ns"] if marks else runs[-1][1]
    idle = idle_by_span(events, lo, hi)
    _TABLE[3] = {
        "scope_ms_per_step": {
            k: v / 1e6 for k, v in sorted(table.items(), key=lambda kv: -kv[1])
        },
        "idle_ms_by_span": {
            k: v / 1e6 for k, v in sorted(idle.items(), key=lambda kv: -kv[1])
        },
    }
    return _TABLE[3]
