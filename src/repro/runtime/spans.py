"""Host spans of the serving path, on while a JAX profiler trace runs.

The Engine (runtime/server.py) and the backends (runtime/backends.py) mark
their phases with ``span(name, **info)``.  A span is on exactly while the
profiler records (``jax.profiler.TraceAnnotation.is_enabled()``): under
``jax.profiler.trace(dir)``, a TensorBoard capture, or any other
``start_trace``.  No option or environment variable turns it on.

* On, a span enters ``TraceAnnotation(name, **info)``, so it lies in the
  device trace on the trace's clock with its counts (a request id, rows)
  as the event's arguments, and keeps one ``Record`` in a bounded ring:
  its name, ``t0``/``t1`` on ``time.perf_counter``, the index of the span
  it ran inside (the serving loop runs in one thread, so a stack of open
  spans gives each its parent), and ``info``.
* Off, ``span`` is one flag check returning a shared no-op context
  manager: no clock read, nothing recorded.

``record`` keeps a span whose start lies in the past (a request's queue
wait, from its submit stamp); it is in the ring only, since the profiler's
trace cannot be written backwards.

The ring keeps records in the order they end.  A full ring drops its
oldest records and counts them; ``Snapshot.covers`` tells a reader whether
any record ending inside its window was dropped, so no number is built
from part of a window.

The ring is process-wide, like the profiler it follows: ``RECORDER`` is
what the serving path writes to and what readers take ``snapshot()`` of.
"""
from __future__ import annotations

import collections
import itertools
import time
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

_enabled = TraceAnnotation.is_enabled
_clock = time.perf_counter


class Record(NamedTuple):
    index: int                  # position in the order spans started
    name: str
    t0: float                   # time.perf_counter seconds
    t1: float
    parent: Optional[int]       # index of the enclosing span, if any
    info: Dict[str, Any]


class Snapshot(NamedTuple):
    records: List[Record]       # kept records, in the order they ended
    dropped: int                # records the full ring let go
    lost_until: float           # latest end among the dropped records

    def covers(self, t: float) -> bool:
        """True when no dropped record ended at or after ``t``: every span
        that ends at ``t`` or later is among ``records``."""
        return self.lost_until < t


class _Off:
    """The span while the profiler is off: does nothing."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc: object) -> None:
        return None


_OFF = _Off()


class _On:
    __slots__ = ("_rec", "_name", "_info", "_ann", "_index", "_parent",
                 "_t0")

    def __init__(self, rec: "Recorder", name: str,
                 info: Dict[str, Any]) -> None:
        self._rec, self._name, self._info = rec, name, info

    def __enter__(self) -> "_On":
        rec = self._rec
        self._ann = TraceAnnotation(self._name, **self._info)
        self._ann.__enter__()
        self._parent = rec._stack[-1] if rec._stack else None
        self._index = next(rec._count)
        rec._stack.append(self._index)
        self._t0 = _clock()
        return self

    def __exit__(self, *exc: object) -> None:
        t1 = _clock()
        rec = self._rec
        rec._stack.pop()
        rec._put(Record(self._index, self._name, self._t0, t1,
                        self._parent, self._info))
        self._ann.__exit__(*exc)


class Recorder:
    """A bounded ring of span records, oldest dropped first."""

    def __init__(self, capacity: int = 1 << 18) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.clear()

    def clear(self) -> None:
        self._ring: collections.deque = collections.deque(
            maxlen=self.capacity)
        self._count = itertools.count()
        self._stack: List[int] = []
        self.dropped = 0
        self.lost_until = float("-inf")

    def span(self, name: str, **info: Any) -> Any:
        """A context manager marking one phase; ``info`` is kept with it.
        While no profiler trace runs, the shared no-op."""
        if not _enabled():
            return _OFF
        return _On(self, name, info)

    def record(self, name: str, t0: float, t1: float) -> None:
        """Keep a span that has already ended (from ``t0`` to ``t1``,
        ``time.perf_counter`` seconds) while a profiler trace runs; it has
        no parent and no counts."""
        if _enabled():
            self._put(Record(next(self._count), name, t0, t1, None, {}))

    def snapshot(self) -> Snapshot:
        return Snapshot(list(self._ring), self.dropped, self.lost_until)

    def _put(self, rec: Record) -> None:
        ring = self._ring
        if len(ring) == self.capacity:      # the append drops ring[0]
            self.dropped += 1
            self.lost_until = max(self.lost_until, ring[0].t1)
        ring.append(rec)


RECORDER = Recorder()
span = RECORDER.span
record = RECORDER.record
snapshot = RECORDER.snapshot
