"""Span and counter recorder of the serving runtime.

One process-wide, bounded, in-memory ring of finished spans, always on.
A span is a named interval on ``time.perf_counter_ns`` (the clock the
benchmark harness times requests with) with the span that caused it
(``parent``), its ids and the counters set on it.  The ids are
``sched`` (the scheduler instance), ``round`` and ``rid``; a child span
inherits its parent's ids, so every phase of a scheduling round carries
the round's ``sched`` and ``round``, and the spans of one request share
``sched`` and ``rid``.  Counters are attributes of the span that owns
them (``span.set(tokens_emitted=...)``).

Two ways to open a span:

* ``with span(name, **ids) as s:`` for work that starts and ends in one
  call (the phases of a round); its parent is the innermost ``with``
  span open on this thread;
* ``s = begin(name, **ids)`` and later ``s.end()`` for a lifetime that
  crosses calls (a request queued, prefilling, decoding); it has no
  parent.  ``start_ns``/``end_ns`` let one instant close a span and open
  the next, so a request's spans tile its life with no gaps.

``event(name, **ids)`` records an instant (a span that ends where it
starts).

Every span also opens a ``jax.profiler.TraceAnnotation`` of its name:
with the profiler on, the spans land in the trace's host plane on the
same clock as the device's events; with it off that is one cheap check.
Names start with ``sched.``, ``engine.`` or ``request.``.  Spans are
taken per round and per request, never per token or per layer.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

CAPACITY = 65536          # finished spans kept; the oldest fall out first

now_ns = time.perf_counter_ns


class Span:
    """One named interval; ``end_ns`` is ``None`` while it is open."""

    __slots__ = ("name", "sid", "parent", "ids", "counters", "start_ns",
                 "end_ns", "_rec", "_note")

    def __init__(self, rec, name: str, parent, ids: dict, start_ns):
        self._rec = rec
        self.name = name
        self.sid = next(rec._sids)
        self.parent = None if parent is None else parent.sid
        self.ids = {**parent.ids, **ids} if parent is not None else ids
        self.counters: dict = {}
        self._note = TraceAnnotation(name)
        self._note.__enter__()
        self.start_ns = now_ns() if start_ns is None else start_ns
        self.end_ns = None

    def set(self, **counters) -> "Span":
        self.counters.update(counters)
        return self

    def end(self, end_ns=None) -> int:
        """Close the span and file it in the ring; returns its end."""
        self.end_ns = now_ns() if end_ns is None else end_ns
        self._note.__exit__(None, None, None)
        self._note = None
        self._rec._ring.append(self)
        return self.end_ns

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def __enter__(self):
        self._rec._stack().append(self)
        return self

    def __exit__(self, *exc):
        self._rec._stack().pop()
        self.end()

    def __repr__(self):
        return (f"Span({self.name!r}, sid={self.sid}, parent={self.parent}, "
                f"ids={self.ids}, counters={self.counters}, "
                f"start_ns={self.start_ns}, end_ns={self.end_ns})")


class Recorder:
    """The ring of finished spans and the per-thread stack of open
    ``with`` spans."""

    def __init__(self, capacity: int = CAPACITY):
        self._ring: deque = deque(maxlen=capacity)
        self._sids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, **ids) -> Span:
        """A span to enter with ``with``, child of the open one."""
        st = self._stack()
        return Span(self, name, st[-1] if st else None, ids, None)

    def begin(self, name: str, *, start_ns=None, **ids) -> Span:
        """An open span with no parent; the caller ends it."""
        return Span(self, name, None, ids, start_ns)

    def event(self, name: str, **ids) -> Span:
        s = self.begin(name, **ids)
        s.end(s.start_ns)
        return s

    def snapshot(self) -> list:
        """The finished spans still in the ring, oldest end first."""
        return list(self._ring)


RECORDER = Recorder()
span = RECORDER.span
begin = RECORDER.begin
event = RECORDER.event
snapshot = RECORDER.snapshot
