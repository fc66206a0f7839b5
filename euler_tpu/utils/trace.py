"""Names for the program's layers, in a profiler trace and in memory.

Three things, used at the layer boundaries of the train path:

- `scope(name)` for code that runs under `jit`: a `jax.named_scope`, so
  every HLO op traced inside carries `euler.<name>` in its `op_name`.
  Metadata only — the executable and its numbers are the same.
- `span(name, **args)` for host code: a `jax.profiler.TraceAnnotation`
  named `euler.<name>` (it lands on `/host:CPU`, on the device trace's
  own clock, whenever a profiler session is live) and one entry in a
  process-wide in-memory record, which is what sees set-up — that
  happens before any profiler session exists. `spans()` returns the
  record; whoever asks writes it out.
- `count(name)` for a choice made at trace time (which form an
  aggregation took): a tally; `step.first_call` keeps, as its args,
  every name that moved while its program was traced. What a name
  counts is said where it is counted.

What interrupts the host lands in the record too, under the span of its
thread that it interrupted, so that a slow call names its cause (not
inside a set-up span: staging and tracing allocate by the million, and
nothing compiled there is late):

- `counted(name, **args)` is a span that keeps, of the thread's context
  switches and page faults, those that moved while it was open (a
  call's `train` and its `train.drain` are: twice a call, nothing a
  step), where the host counts them at all (`INTERRUPTIONS_COUNTED`);
- every run of Python's collector is a span `gc` (one `gc.callbacks`
  hook), in a profiler's trace too;
- a compile or cache fetch that `jax.monitoring` reports is a
  record-only span `late_compile` — unless the thread is collecting
  them (`compiles()`), as `step.first_call` does for its own.

The record is bounded: per-step spans evict the oldest of their kind,
set-up spans (`stage.*`, `step.first_call*`) are kept apart so a long
run never pushes them out. Times are `time.perf_counter_ns()`.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import threading
import time
from typing import NamedTuple

import jax

try:  # the calling thread's own counters: Linux
    import resource

    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):
    resource = None

PREFIX = "euler."
SETUP_PREFIXES = ("stage.", "step.first_call")
# per-step spans kept; at 4 a step (`train.step`, `.next_batch`,
# `.dispatch` and a share of the call's drain and of the collector's
# runs), the last ~2000 steps
MAX_SPANS = 8192
MAX_SETUP_SPANS = 1024

# `jax.monitoring`'s durations of one program's way from Python to the
# device, by the kind a span names them. `compile` holds the persistent
# cache's lookup (`cache_fetch`, on a hit) or XLA's compile.
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_fetch",
}

# what a `counted` span keeps of `getrusage`: involuntary and voluntary
# context switches, major and minor page faults (`ru_nivcsw`,
# `ru_nvcsw`, `ru_majflt`, `ru_minflt`: fields 15, 14, 7, 6)
_INTERRUPTIONS = ("nivcsw", "nvcsw", "majflt", "minflt")


class Span(NamedTuple):
    name: str  # without the `euler.` prefix
    start_ns: int
    end_ns: int
    parent: int | None  # id of the enclosing span on the same thread
    step: int | None  # the global training step, where the span has one
    id: int
    thread: int
    args: dict


_steps: collections.deque = collections.deque(maxlen=MAX_SPANS)
_setup: collections.deque = collections.deque(maxlen=MAX_SETUP_SPANS)
_counts: collections.Counter = collections.Counter()
_ids = itertools.count(1)
_local = threading.local()


def scope(name: str):
    """`with scope("sample"):` around traced code names its ops
    `euler.sample` in HLO metadata."""
    return jax.named_scope(PREFIX + name)


def profiling() -> bool:
    """Whether a profiler session is live, whoever started it."""
    return jax.profiler.TraceAnnotation.is_enabled()


def _record(name, start_ns, end_ns, parent, ident, args) -> None:
    # plain tuples here, `Span`s when read: building the named tuple
    # would be a fifth of a span's cost. deque.append is atomic under
    # the interpreter lock
    (_setup if name.startswith(SETUP_PREFIXES) else _steps).append(
        (name, start_ns, end_ns, parent, args.get("step"), ident,
         threading.get_ident(), args)
    )


class span:
    """`with span("train.dispatch", step=7):` — a host span in the
    profiler's trace (when one is being taken) and in the record.
    `args` may be added to until the span closes, and by whoever holds
    the dict after: the record keeps it by reference."""

    __slots__ = ("name", "args", "id", "_parent", "_ann", "_t0")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self):
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        self._parent = stack[-1].id if stack else None
        self.id = next(_ids)
        stack.append(self)
        self._ann = ann = jax.profiler.TraceAnnotation(
            PREFIX + self.name, **self.args
        )
        ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _local.stack.pop()
        _record(self.name, self._t0, end, self._parent, self.id, self.args)
        return False

    def child(self, name: str, start_ns: int, end_ns: int, **args) -> None:
        """A finished stretch inside this span that was timed by someone
        else (a `jax.monitoring` duration): record only."""
        _record(name, start_ns, end_ns, self.id, next(_ids), args)


def _interruptions() -> tuple:
    """The calling thread's `_INTERRUPTIONS` so far."""
    usage = resource.getrusage(_RUSAGE_THREAD)
    return usage[15], usage[14], usage[7], usage[6]


# Whether this host counts them. A kernel that does has faulted pages in
# for any thread that got as far as importing this module; gVisor
# (`runsc`) has the call and leaves all four at 0 for ever, at 6 us a
# call. Where this is False a `counted` span is a plain one, and its
# bare `args` say nothing about what interrupted it.
INTERRUPTIONS_COUNTED = resource is not None and any(_interruptions())


class counted(span):
    """A span that says what interrupted its thread: when it closes its
    `args` gain `nivcsw`, `nvcsw`, `majflt`, `minflt` — those of them
    that moved while it was open, so an undisturbed span's `args` are
    what it was given. Nothing where the host does not count them
    (`INTERRUPTIONS_COUNTED`)."""

    __slots__ = ("_before",)

    def __enter__(self):
        self._before = _interruptions() if INTERRUPTIONS_COUNTED else None
        return span.__enter__(self)

    def __exit__(self, *exc):
        if self._before is not None:
            after = _interruptions()
            if after != self._before:
                for name, was, now in zip(_INTERRUPTIONS, self._before, after):
                    if now != was:
                        self.args[name] = now - was
        return span.__exit__(self, *exc)


def _interrupted():
    """The innermost open span of this thread, where an interruption is
    recorded: None under no span, or anywhere inside a set-up span."""
    stack = getattr(_local, "stack", None)
    if not stack or any(s.name.startswith(SETUP_PREFIXES) for s in stack):
        return None
    return stack[-1]


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks`: a run of the collector is the span `gc`, child of
    the span of the collecting thread that it interrupted."""
    if phase == "start":
        if _interrupted() is not None:
            _local.gc = run = span("gc", generation=info["generation"])
            run.__enter__()
    else:
        run = getattr(_local, "gc", None)
        if run is not None:  # its start was seen
            _local.gc = None
            run.__exit__(None, None, None)


def _on_duration(event: str, seconds: float, **_kw) -> None:
    """`jax.monitoring`: a compile step either goes to the thread's open
    `compiles()` list, or is a `late_compile` under its innermost span."""
    kind = COMPILE_EVENTS.get(event)
    if kind is None:
        return
    end = time.perf_counter_ns()
    start = end - int(seconds * 1e9)
    sink = getattr(_local, "compiles", None)
    if sink is not None:
        sink.append((kind, start, end))
        return
    inside = _interrupted()
    if inside is not None:
        inside.child("late_compile", start, end, event=kind)


@contextlib.contextmanager
def compiles():
    """The compile steps `jax.monitoring` reports on this thread while
    the context is open, as `(kind, start_ns, end_ns)` in the order they
    ended: whoever opens it accounts for them, and they are no
    `late_compile`."""
    outer = getattr(_local, "compiles", None)
    events = _local.compiles = []
    try:
        yield events
    finally:
        _local.compiles = outer


def innermost(intervals) -> list:
    """`[(lo, hi, key), ...]`, in order and disjoint, for `(start, end,
    key)` intervals that nest (a span's children; a `jax.jit` traced,
    lowered and compiled inside an outer trace): every instant that any
    interval holds goes to the key of the one that began last among
    those holding it — the innermost — and neighbouring stretches of one
    key are joined. The lengths add up to the time the intervals cover."""
    waiting = sorted(intervals, key=lambda i: (i[0], -i[1]))  # outer first
    points = sorted({t for start, end, _ in waiting for t in (start, end)})
    out: list = []
    active: list = []  # in order of start
    at = 0
    for lo, hi in zip(points, points[1:]):
        while at < len(waiting) and waiting[at][0] <= lo:
            active.append(waiting[at])
            at += 1
        active = [i for i in active if i[1] > lo]
        if not active:
            continue
        key = active[-1][2]
        if out and out[-1][1] == lo and out[-1][2] == key:
            out[-1] = (out[-1][0], hi, key)
        else:
            out.append((lo, hi, key))
    return out


def self_stretches(events: list, kinds: tuple) -> dict:
    """`{kind: [(start_ns, end_ns), ...]}` for `compiles()` events: each
    kind's own time among the events of `kinds` (`innermost`), so the
    stretches of all kinds are disjoint."""
    out: dict = {kind: [] for kind in kinds}
    for lo, hi, kind in innermost(
        (start, end, kind) for kind, start, end in events if kind in kinds
    ):
        out[kind].append((lo, hi))
    return out


def count(name: str, amount: int = 1) -> None:
    """`amount` more of `name` in the process-wide tally: for a choice
    code makes, or a size it is given, while it is traced (once a
    program, not once a step), where a span has nothing to time. Whoever
    opened the span around the tracing reads `counts()` before and after
    and keeps the difference."""
    _counts[name] += amount


def counts() -> dict:
    """The tally so far."""
    return dict(_counts)


def spans() -> list:
    """The record so far, in order of start."""
    return sorted(
        (Span(*entry) for entry in list(_setup) + list(_steps)),
        key=lambda s: (s.start_ns, s.id),
    )


# the record is always on: one hook each, for the life of the process
gc.callbacks.append(_on_gc)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
