"""Named host spans and counters: the runtime's one tracing facility.

``with span("taskgraph.replay.dispatch"):`` does two things at once:

* it opens a ``jax.profiler.TraceAnnotation`` of that name, so under
  ``jax.profiler.trace`` the span sits on the host plane of the
  ``.xplane.pb``, on the same clock as the device's ``XLA Ops``;
* on exit it appends one :class:`SpanRecord` to a bounded in-memory ring:
  name, id, parent (the enclosing span on this thread), root (the id shared
  by every span under one outermost span: one replay, record or warmup),
  start and end on ``time.perf_counter_ns`` and its attributes. A span whose
  body raises is recorded too, marked ``error``.

``count(name, n)`` keeps process-wide counters. ``recent()`` returns the
ring oldest first, ``counters()`` the counters, and ``dump(path)`` writes
both as JSON. Recording is always on; a span costs a few microseconds
with the profiler off.

Attributes are ints and strings only. Those given to :func:`span` go to
both the profiler annotation and the record; those added with
:meth:`span.set` inside the body (values known only after the work, like
an output count) go to the record alone.
"""
from __future__ import annotations

import collections
import itertools
import json
import threading
import time
from typing import NamedTuple

import jax

#: Records the ring keeps: a 30 s window of 150 ms replays at six spans each
#: needs about 1,200, and set-up a few hundred more.
CAPACITY = 65_536


class SpanRecord(NamedTuple):
    """One finished span; times on ``time.perf_counter_ns``."""

    name: str
    id: int
    parent: int | None
    root: int
    t0_ns: int
    t1_ns: int
    attrs: dict
    error: bool = False

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9


class Recorder:
    """A bounded ring of span records, counters, and a parent stack per thread."""

    def __init__(self, capacity: int = CAPACITY):
        self._ring: collections.deque[SpanRecord] = collections.deque(
            maxlen=capacity)
        self._counters: dict[str, int] = {}
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()

    def stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def append(self, record: SpanRecord) -> None:
        with self._lock:
            self._ring.append(record)

    def next_id(self) -> int:
        return next(self._ids)

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def recent(self, name: str | None = None) -> list[SpanRecord]:
        with self._lock:
            records = list(self._ring)
        return records if name is None else [r for r in records
                                             if r.name == name]

    def counters(self) -> dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def dump(self, path: str) -> None:
        doc = {"spans": [r._asdict() for r in self.recent()],
               "counters": self.counters()}
        with open(path, "w") as f:
            json.dump(doc, f)


_default = Recorder()


def _check_attrs(attrs: dict) -> None:
    for k, v in attrs.items():
        if type(v) not in (int, str):
            raise TypeError(f"span attribute {k!r} must be an int or a str, "
                            f"got {type(v).__name__}")


class span:
    """Context manager: one named span (see the module docstring)."""

    __slots__ = ("name", "attrs", "record", "_rec", "_ann", "_id", "_parent",
                 "_root", "_t0")

    def __init__(self, name: str, recorder: Recorder | None = None, **attrs):
        _check_attrs(attrs)
        self.name = name
        self.attrs = attrs
        self.record: SpanRecord | None = None
        self._rec = recorder or _default

    def set(self, **attrs) -> None:
        """Add attributes to the record (not to the profiler annotation)."""
        _check_attrs(attrs)
        self.attrs.update(attrs)

    def __enter__(self) -> "span":
        stack = self._rec.stack()
        self._id = self._rec.next_id()
        if stack:
            outer = stack[-1]
            self._parent, self._root = outer._id, outer._root
        else:
            self._parent, self._root = None, self._id
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(exc_type, exc, tb)
        self._rec.stack().pop()
        self.record = SpanRecord(self.name, self._id, self._parent, self._root,
                                 self._t0, t1, dict(self.attrs),
                                 exc_type is not None)
        self._rec.append(self.record)
        return False


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the process counter ``name``."""
    _default.count(name, n)


def recent(name: str | None = None) -> list[SpanRecord]:
    """The ring's records (those named ``name``), oldest first."""
    return _default.recent(name)


def counters() -> dict[str, int]:
    return _default.counters()


def dump(path: str) -> None:
    """Write the ring and the counters to ``path`` as JSON."""
    _default.dump(path)
