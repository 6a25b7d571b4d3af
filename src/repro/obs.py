"""Spans and counters for the program's phases.

``span(name, **attrs)`` marks a phase on the JAX profiler's host timeline (a
``TraceAnnotation``, on the same clock as the device's ops in a trace).
Inside ``record()`` each span is also kept in memory with its parent, on
``time.perf_counter``; ``count(name, n)`` adds to the recorder's counters,
every backend compile adds to ``compiles`` and ``compile_s``, and each of them
that the persistent compilation cache served adds to ``cache_loads``.

With ``record(device_marks=True)`` each span also launches a tiny program
when it opens and when it closes, whose module is named for the boundary
(``jit_obs_mark_ptq_capture_begin``).  A chip runs its programs in the order
the host launched them, so in a device trace the ops between a span's two
marks are the ones the host launched inside it.  Eager ops cannot be named
any other way: each compiles as its own module, outside any named scope.
The marks run on the default device only: on a mesh, the other devices'
ops have no marks around them.

Nothing here writes a file: the profiler is the exporter.
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
import time
import zlib
from typing import Optional

import jax
import numpy as np

__all__ = ["Recorder", "Span", "active", "count", "record", "span"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Also fires for a program loaded from the persistent compilation cache, which
# then emits COMPILE_EVENT as well (with the load's duration).
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_local = threading.local()  # .recorder: the active Recorder of this thread
_listening = False  # the listeners are process-wide and stay once registered;
# with no recorder active they count nothing
_mark_fns: dict = {}


@dataclasses.dataclass
class Span:
    name: str
    attrs: dict
    t0: float  # time.perf_counter seconds
    t1: float  # nan while the span is open
    parent: Optional[int]  # index of the enclosing span in Recorder.spans


class Recorder:
    """What the spans and counters of one ``record()`` block kept."""

    def __init__(self, device_marks: bool = False):
        self.device_marks = device_marks
        self.spans: list[Span] = []  # in the order they opened
        self.counters: dict[str, float] = {}
        self._open: list[int] = []
        self._mark_x = jax.device_put(np.int32(0)) if device_marks else None

    def snapshot(self) -> tuple:
        return len(self.spans), dict(self.counters)

    def since(self, snap: tuple) -> dict:
        """Host seconds by span name of the spans opened since ``snap``, the
        compiles counted since (``cache_loads`` of them were read from the
        persistent compilation cache), and what every other counter added
        since, under its own name."""
        n, counters = snap
        phase_s: dict[str, float] = {}
        for s in self.spans[n:]:
            phase_s[s.name] = phase_s.get(s.name, 0.0) + (s.t1 - s.t0)

        def grew(k):
            return self.counters.get(k, 0) - counters.get(k, 0)

        out = {"phase_s": phase_s, "compiles": int(grew("compiles")),
               "compile_s": float(grew("compile_s")), "cache_loads": int(grew("cache_loads"))}
        out.update({k: grew(k) for k in self.counters if k not in out})
        return out

    def _mark(self, label: str):
        fn = _mark_fns.get(label)
        if fn is None:
            # Each boundary adds its own constant: programs that differ only
            # in name run as one executable, and the trace then names every
            # mark after whichever boundary compiled first.
            step = zlib.crc32(label.encode()) & 0x7FFFFFFF

            def mark(x):
                return x + step  # real work, so XLA keeps the program

            mark.__name__ = mark.__qualname__ = "obs_mark_" + re.sub(r"\W", "_", label)
            fn = _mark_fns[label] = jax.jit(mark)
        self._mark_x = fn(self._mark_x)


def active() -> Optional[Recorder]:
    return getattr(_local, "recorder", None)


@contextlib.contextmanager
def span(name: str, **attrs):
    """One phase of the program.  ``attrs`` go into the trace annotation and
    the record as they are."""
    rec = getattr(_local, "recorder", None)
    with jax.profiler.TraceAnnotation(name, **attrs):
        if rec is None:
            yield
            return
        i = len(rec.spans)
        rec.spans.append(Span(name, attrs, time.perf_counter(), float("nan"),
                              rec._open[-1] if rec._open else None))
        rec._open.append(i)
        if rec.device_marks:
            rec._mark(f"{name}_begin")
        try:
            yield
        finally:
            if rec.device_marks:
                rec._mark(f"{name}_end")
            rec._open.pop()
            rec.spans[i].t1 = time.perf_counter()


def count(name: str, n: float = 1):
    rec = getattr(_local, "recorder", None)
    if rec is not None:
        rec.counters[name] = rec.counters.get(name, 0) + n


def _on_duration(event: str, duration: float, **_):
    if event == COMPILE_EVENT:
        count("compiles")
        count("compile_s", duration)


def _on_event(event: str, **_):
    if event == CACHE_HIT_EVENT:
        count("cache_loads")


@contextlib.contextmanager
def record(device_marks: bool = False):
    """Keep this thread's spans and counters in a new :class:`Recorder`,
    which it yields; the recorder that was active before comes back after."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    rec, prev = Recorder(device_marks), getattr(_local, "recorder", None)
    _local.recorder = rec
    try:
        yield rec
    finally:
        _local.recorder = prev
