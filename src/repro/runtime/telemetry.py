"""Spans of the serving path: which frontend or engine phase the host was
in, and when, so that a request's latency and the device's idle time can
be put down to the phase that caused them.

Tracing is on exactly while a JAX profiler session runs
(``jax.profiler.start_trace``, or the profiler server); there is no other
switch. Off, ``span`` costs one C++ call and returns a shared null
context: nothing is allocated or recorded. On, each span is also a
``jax.profiler.TraceAnnotation``, so it shows in the profiler's own trace
beside the device operations, and it is appended to ``RECORDER``, a
bounded in-memory ring that counts what it drops.

A span's times are ``time.perf_counter_ns()``: a reader that knows the
offset between that clock and the profiler trace's places the spans among
the device operations. Spans nest on one stack (the serving loop runs on
one thread); each records the id of the span open around it. Attributes
are values the host already has: no span reads a device value or adds a
host transfer. Zero-length spans are events (``request.first_token``,
``compile``).
"""
from __future__ import annotations

import collections
import functools
import itertools
import time
from typing import List

import jax

_enabled = jax.profiler.TraceAnnotation.is_enabled
_now = time.perf_counter_ns

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span:
    """One phase: ``name``, ``start_ns``/``end_ns`` on the perf_counter
    clock, its id ``sid``, the ``parent`` id (0 at the top) and ``attrs``."""

    __slots__ = ("name", "start_ns", "end_ns", "sid", "parent", "attrs",
                 "_ann")

    def __init__(self, name: str, sid: int, parent: int, attrs: dict,
                 start_ns: int = 0, end_ns: int = 0):
        self.name, self.sid, self.parent, self.attrs = name, sid, parent, attrs
        self.start_ns, self.end_ns = start_ns, end_ns
        self._ann = None

    def set(self, **attrs):
        """Attributes known only once the phase has run."""
        self.attrs.update(attrs)

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        RECORDER._stack.append(self.sid)
        self.start_ns = _now()
        return self

    def __exit__(self, *exc):
        self.end_ns = _now()
        RECORDER._stack.pop()
        self._ann.__exit__(*exc)
        self._ann = None
        RECORDER.append(self)
        return False

    def __repr__(self):
        return (f"Span({self.name!r}, sid={self.sid}, parent={self.parent}, "
                f"{self.start_ns}..{self.end_ns}, {self.attrs})")


class _NullSpan:
    """What ``span`` returns while tracing is off."""

    __slots__ = ()

    def set(self, **attrs):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class Recorder:
    """The finished spans, oldest first, in a ring of ``capacity``
    entries; ``dropped`` counts the spans it pushed out."""

    def __init__(self, capacity: int = 1 << 20):
        self.spans = collections.deque(maxlen=capacity)
        self.dropped = 0
        self._stack: List[int] = []
        self._ids = itertools.count(1)

    def append(self, sp: Span):
        if len(self.spans) == self.spans.maxlen:
            self.dropped += 1
        self.spans.append(sp)

    def open(self, name: str, attrs: dict) -> Span:
        return Span(name, next(self._ids),
                    self._stack[-1] if self._stack else 0, attrs)

    def snapshot(self) -> List[Span]:
        return list(self.spans)

    def clear(self):
        self.spans.clear()
        self.dropped = 0


RECORDER = Recorder()


def span(name: str, **attrs):
    """Context manager timing one phase while tracing is on; ``set`` on
    what it returns adds attributes known only at the end."""
    if not _enabled():
        return _NULL
    return RECORDER.open(name, attrs)


def spanned(name: str):
    """Decorator: each call of the function is one span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return call
    return wrap


def event(name: str, **attrs):
    """A zero-length span under whatever span is open, while tracing is
    on."""
    if _enabled():
        sp = RECORDER.open(name, attrs)
        sp.start_ns = sp.end_ns = _now()
        RECORDER.append(sp)


def _on_duration(name: str, seconds: float, **_):
    if name == COMPILE_EVENT:
        event("compile", seconds=seconds)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
