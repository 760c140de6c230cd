"""Program spans: host time of the engine's and the scheduler's calls.

A span is one timed region of host code: its name, start and end on the
``time.perf_counter`` clock, the span that encloses it (its parent) and,
for a walk request, the request id.  Spans are always on.  With no
profiler session one costs two clock reads, a ring append and an
inactive ``jax.profiler.TraceAnnotation``; under a profiler session the
annotation puts the span on the device trace's clock, so a trace reads
each host call beside the device work it launched.

Names are dotted and start with their layer: ``engine.*``
(``serve/dynwalk.py``), ``guard.*`` (the guard's host accounting) and
``sched.*`` (``serve/scheduler.py``).

Records live in a bounded ring in memory and are written nowhere:
``records()``, ``summary()`` and ``reset()`` are what an operator or a
benchmark reads.  The process keeps one log (``LOG``), like the
profiler's own trace, so that a scheduler's span and the engine call
inside it share one parent chain.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import NamedTuple, Optional

import jax

__all__ = ["Record", "SpanLog", "LOG", "span", "add", "records", "summary",
           "reset"]


class Record(NamedTuple):
    """One finished span; ``parent`` is the ``sid`` of the span that
    enclosed it on the same thread (None at the top)."""
    sid: int
    name: str
    t0: float
    t1: float
    parent: Optional[int]
    rid: Optional[int]


class SpanLog:
    """A bounded ring of span records and per-name count, total and
    longest time (which outlive the ring)."""

    def __init__(self, capacity: int = 65536):
        self._ring: deque = deque(maxlen=capacity)
        self._stats: dict = {}
        self._ids = itertools.count()
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, rid: Optional[int] = None) -> "_Span":
        """A context manager that times the enclosed block as span
        ``name``."""
        return _Span(self, name, rid)

    def add(self, name: str, t0: float, t1: float,
            rid: Optional[int] = None) -> None:
        """Record a span whose ends were taken apart (a request's time
        in a queue): no parent, and no profiler annotation."""
        self._record(Record(next(self._ids), name, t0, t1, None, rid))

    def _record(self, rec: Record) -> None:
        dt = rec.t1 - rec.t0
        with self._lock:
            self._ring.append(rec)
            st = self._stats.get(rec.name)
            if st is None:
                self._stats[rec.name] = [1, dt, dt]
            else:
                st[0] += 1
                st[1] += dt
                if dt > st[2]:
                    st[2] = dt

    def records(self) -> list:
        """The ring's records, oldest first."""
        with self._lock:
            return list(self._ring)

    def summary(self) -> dict:
        """``{name: {"count", "total_s", "max_s"}}`` since the last
        ``reset()``."""
        with self._lock:
            return {n: {"count": c, "total_s": t, "max_s": m}
                    for n, (c, t, m) in self._stats.items()}

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._stats.clear()


class _Span:
    """One open span: a class rather than a generator-based context
    manager, since one is entered on every engine call and this costs
    less."""
    __slots__ = ("log", "name", "rid", "sid", "parent", "t0", "ann")

    def __init__(self, log: SpanLog, name: str, rid: Optional[int]):
        self.log, self.name, self.rid = log, name, rid

    def __enter__(self):
        stack = self.log._stack()
        self.sid = next(self.log._ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.sid)
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.log._stack().pop()
        self.log._record(Record(self.sid, self.name, self.t0, t1,
                                self.parent, self.rid))
        return False


LOG = SpanLog()
span = LOG.span
add = LOG.add
records = LOG.records
summary = LOG.summary
reset = LOG.reset
