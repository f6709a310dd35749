"""Wall-clock spans of the program's host work, collected only while a JAX
profiler session records.

``span(name, **args)`` marks a stretch of host work: the search's ask and
tell, the evaluator's memo, a simulator lane's staging and its wait on the
device.  While ``jax.profiler`` records (``start_trace``/``stop_trace``,
``jax.profiler.trace``, or a capture through ``start_server``), each span
is kept here as a :class:`Record` and also opened as a
``jax.profiler.TraceAnnotation`` of the same name, so it lands on the
profiler's host plane on the same clock as the device's module events.
With no session recording, a span is one enabled check and no record:
there is no switch of its own.

Each record names its parent (the innermost span open on the same thread
when it opened) and a request id.  The id is taken at construction by the
object a user's request creates (``RibbonOptimizer``, ``PoolEvaluator``,
``StreamingSimulator``, through :func:`new_request`) and passed to its
spans; a span opened inside another takes its parent's id, where the
parent has one.

Records live in memory, in a ring of :data:`CAPACITY`; ``dropped()``
counts the records the ring has pushed out since the last ``clear()``,
and a reader that needs every record checks it.  The profiler's own trace
is the export: nothing here writes a file.

Garbage collections are recorded too, as ``host.gc`` spans carrying the
collected generation, through a ``gc.callbacks`` hook.

No span goes inside jitted code: a span times the host, and the device's
time comes from the profiler's module events.
"""

from __future__ import annotations

import gc
import itertools
import threading
from collections import deque
from time import perf_counter_ns
from typing import NamedTuple

from jax.profiler import TraceAnnotation

CAPACITY = 65536

_enabled = TraceAnnotation.is_enabled
_ring: deque = deque(maxlen=CAPACITY)
_dropped = 0
_local = threading.local()
_span_ids = itertools.count(1)
_request_ids = itertools.count(1)


class Record(NamedTuple):
    """One closed span: times are ``perf_counter_ns``; ``parent`` is the
    ``sid`` of the span it opened inside (None at the top)."""

    name: str
    start_ns: int
    end_ns: int
    sid: int
    parent: int | None
    request: int | None
    args: dict


def new_request() -> int:
    """A fresh request id, for the object a user's request creates."""
    return next(_request_ids)


def records() -> list[Record]:
    """The spans collected so far, in the order they closed."""
    return list(_ring)


def dropped() -> int:
    """Records pushed out of the full ring since the last ``clear()``."""
    return _dropped


def clear() -> None:
    global _dropped
    _ring.clear()
    _dropped = 0


def _keep(rec: Record) -> None:
    global _dropped
    if len(_ring) == CAPACITY:
        _dropped += 1
    _ring.append(rec)


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class Span:
    """A span being timed; ``seconds`` is its wall duration once closed.
    It is kept (and annotated) only if a profiler session recorded when it
    opened."""

    __slots__ = ("name", "request", "args", "collect", "sid", "parent",
                 "ann", "t0", "seconds")

    def __init__(self, name: str, request: int | None, args: dict,
                 collect: bool):
        self.name, self.request, self.args = name, request, args
        self.collect = collect
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        if self.collect:
            stack = _stack()
            top = stack[-1] if stack else None
            self.parent = None if top is None else top.sid
            if top is not None and top.request is not None:
                self.request = top.request
            self.sid = next(_span_ids)
            self.ann = TraceAnnotation(self.name, **self.args)
            self.ann.__enter__()
            stack.append(self)
        self.t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = perf_counter_ns()
        self.seconds = (t1 - self.t0) * 1e-9
        if self.collect:
            self.ann.__exit__(*exc)
            _stack().pop()
            _keep(Record(self.name, self.t0, t1, self.sid, self.parent,
                         self.request, self.args))


class _Off:
    """The span of a process no profiler session records: does nothing."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> None:
        return None


_OFF = _Off()


def span(name: str, request: int | None = None, **args):
    """Context manager marking host work as ``name`` (``args`` go into the
    record and the trace event)."""
    if not _enabled():
        return _OFF
    return Span(name, request, args, True)


def timed(name: str, request: int | None = None, **args) -> Span:
    """As ``span``, for a caller that also reads the span's wall duration
    (``.seconds``): it is timed whether or not a session records."""
    return Span(name, request, args, _enabled())


# (start, annotation) of the collection in progress while a session records.
_gc_open = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    if phase == "start":
        if _enabled():
            ann = TraceAnnotation("host.gc", generation=info["generation"])
            ann.__enter__()
            _gc_open = (perf_counter_ns(), ann)
        return
    if _gc_open is None:
        return
    t1 = perf_counter_ns()
    t0, ann = _gc_open
    _gc_open = None
    ann.__exit__(None, None, None)
    stack = _stack()
    top = stack[-1] if stack else None
    _keep(Record("host.gc", t0, t1, next(_span_ids),
                 None if top is None else top.sid,
                 None if top is None else top.request,
                 {"generation": info["generation"]}))


gc.callbacks.append(_on_gc)
