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
  aggregation took): a tally, which `step.first_call` turns into args.

The record is bounded: per-step spans evict the oldest of their kind,
set-up spans (`stage.*`, `step.first_call*`) are kept apart so a long
run never pushes them out. Times are `time.perf_counter_ns()`.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

import jax

PREFIX = "euler."
SETUP_PREFIXES = ("stage.", "step.first_call")
MAX_SPANS = 8192  # per-step spans kept; at 3 a step, the last ~2700 steps
MAX_SETUP_SPANS = 1024


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
    `args` may be added to until the span closes; the record keeps them."""

    __slots__ = ("name", "args", "id", "_parent", "_ann", "_t0")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self):
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        self._parent = stack[-1] if stack else None
        self.id = ident = next(_ids)
        stack.append(ident)
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
