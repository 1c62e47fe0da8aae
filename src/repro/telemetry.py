"""Spans of the control plane: where its host time goes, kept in process.

``span(name, **attrs)`` times a block of the program.  It appends one
`Span` record to an in-memory ring, and enters a
``jax.profiler.TraceAnnotation`` of the same name, so that in a profiled
run the span sits in the ``.xplane.pb`` beside the device's operations,
on the same clock.  Recording is always on; a span costs a few
microseconds of host time.

Spans nest through a per-thread stack: each record names its parent and
its root, the outermost span of its call tree.  A span opened on another
thread starts a tree of its own.  Counts ride on the spans as attributes (``bytes=``,
``nodes=``); the body of a ``with`` may add them to the dict it yields.

`records` and `totals` read the spans that lie inside an interval of
``time.perf_counter()``.  The ring keeps the last `CAPACITY` spans and
counts those it drops; a read that reaches back to a dropped span raises
`SpansDropped` rather than undercount.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import math
import threading
import time
from typing import NamedTuple

import jax

__all__ = ["CAPACITY", "Recorder", "Span", "SpansDropped", "Total",
           "RECORDER", "span", "records", "totals"]

CAPACITY = 65_536


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    span_id: int
    parent_id: int | None
    root_id: int
    attrs: dict


@dataclasses.dataclass
class Total:
    count: int = 0
    seconds: float = 0.0
    self_seconds: float = 0.0  # less the time its direct children cover


class SpansDropped(RuntimeError):
    """The ring dropped spans inside the interval a reader asked for."""


def _covered(intervals) -> float:
    """Length of the union of ``(t0, t1)`` intervals."""
    total, end = 0.0, -math.inf
    for t0, t1 in sorted(intervals):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


class Recorder:
    """A ring of the last ``capacity`` spans, and the stacks that nest them."""

    def __init__(self, capacity: int = CAPACITY) -> None:
        self._ring: collections.deque[Span] = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self.dropped = 0
        self._dropped_until = -math.inf  # latest end of a dropped span

    def _stack(self) -> list[tuple[int, int]]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        stack = self._stack()
        parent = stack[-1] if stack else None
        span_id = next(self._ids)
        root_id = parent[1] if parent else span_id
        stack.append((span_id, root_id))
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield attrs
            finally:
                t1 = time.perf_counter()
                stack.pop()
                parent_id = parent[0] if parent else None
                rec = Span(name, t0, t1, span_id, parent_id, root_id, attrs)
                with self._lock:
                    if len(self._ring) == self._ring.maxlen:
                        self.dropped += 1
                        self._dropped_until = max(self._dropped_until, self._ring[0].t1)
                    self._ring.append(rec)

    def records(self, since: float = -math.inf, until: float = math.inf) -> list[Span]:
        """The spans that started at or after ``since`` and ended by ``until``."""
        with self._lock:
            ring, dropped_until = list(self._ring), self._dropped_until
        if self.dropped and dropped_until >= since:
            raise SpansDropped(
                f"{self.dropped} spans dropped, the latest ending at {dropped_until}"
                f" >= {since}"
            )
        return [r for r in ring if r.t0 >= since and r.t1 <= until]

    def totals(self, since: float = -math.inf, until: float = math.inf) -> dict[str, Total]:
        """Per span name over the interval: count, seconds and self seconds."""
        recs = self.records(since, until)
        children: dict[int, list[tuple[float, float]]] = collections.defaultdict(list)
        for r in recs:
            if r.parent_id is not None:
                children[r.parent_id].append((r.t0, r.t1))
        out: dict[str, Total] = {}
        for r in recs:
            t = out.setdefault(r.name, Total())
            t.count += 1
            t.seconds += r.t1 - r.t0
            t.self_seconds += r.t1 - r.t0 - _covered(children.get(r.span_id, ()))
        return out


#: The process's recorder, which the program's spans go to.
RECORDER = Recorder()
span = RECORDER.span
records = RECORDER.records
totals = RECORDER.totals
