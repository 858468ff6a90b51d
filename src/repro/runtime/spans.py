"""Host spans of the serving loop, on the profiler's clock and in memory.

``span(name, rid=None, **attrs)`` times a block of host code with two
sinks. It enters a ``jax.profiler.TraceAnnotation`` of the same name, so
a profiled run shows the span on the thread's host line, on the clock of
the device events; and it appends a :class:`Span` to a bounded ring in
memory, on ``time.monotonic()``, which :func:`spans` reads back.
``record`` adds a span whose start lies in the past (a request's wait in
the queue) to the ring only.

The recorder is always on. Parents come from a stack per thread, so
engines served from different threads keep separate trees; the ring
keeps the newest ``1 << 16`` spans of the process.
"""

from __future__ import annotations

import itertools
import math
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "SpansDropped", "Recorder", "RECORDER", "span", "record",
           "spans"]


class Span(NamedTuple):
    name: str
    start: float                    # time.monotonic() seconds
    end: float
    span_id: int
    parent_id: Optional[int]        # the enclosing span of the same thread
    rid: Any                        # the request it serves, if one
    attrs: Dict[str, Any]

    @property
    def dur(self) -> float:
        return self.end - self.start


class SpansDropped(LookupError):
    """The ring no longer holds every span of the interval asked for."""


class Recorder:
    """A ring of finished spans and the stacks of the open ones."""

    def __init__(self, maxlen: int = 1 << 16):
        self._ring: deque = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._dropped_end = -math.inf   # latest end of a span the ring dropped

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _append(self, s: tuple):
        # plain tuples (Span's fields) here; spans() makes them Spans
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self._dropped_end = max(self._dropped_end, self._ring[0][2])
            self._ring.append(s)

    def span(self, name: str, rid=None, **attrs) -> "_Open":
        """Context manager timing its block as one span."""
        return _Open(self, name, rid, attrs)

    def record(self, name: str, start: float, end: float, rid=None, **attrs):
        """A span that started in the past; the ring only, no profiler."""
        stack = self._stack()
        self._append((name, start, end, next(self._ids),
                      stack[-1] if stack else None, rid, attrs))

    def spans(self, lo: float, hi: float, name: Optional[str] = None
              ) -> List[Span]:
        """Finished spans that overlap ``[lo, hi]``, in the order recorded.
        Raises :class:`SpansDropped` if the ring has dropped one there."""
        with self._lock:
            if self._dropped_end >= lo:
                raise SpansDropped(
                    f"the ring of {self._ring.maxlen} spans dropped spans up "
                    f"to {self._dropped_end:.6f}, inside [{lo:.6f}, {hi:.6f}]")
            ring = list(self._ring)
        return [Span._make(s) for s in ring if s[2] >= lo and s[1] <= hi
                and (name is None or s[0] == name)]


class _Open:
    __slots__ = ("rec", "name", "rid", "attrs", "start", "span_id",
                 "parent_id", "_ann")

    def __init__(self, rec: Recorder, name: str, rid, attrs: dict):
        self.rec, self.name, self.rid, self.attrs = rec, name, rid, attrs

    def __enter__(self) -> "_Open":
        stack = self.rec._stack()
        self.parent_id = stack[-1] if stack else None
        self.span_id = next(self.rec._ids)
        stack.append(self.span_id)
        attrs = (self.attrs if self.rid is None
                 else dict(self.attrs, rid=self.rid))
        self._ann = TraceAnnotation(self.name, **attrs)
        self._ann.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.monotonic()
        self._ann.__exit__(*exc)
        self.rec._stack().pop()
        self.rec._append((self.name, self.start, end, self.span_id,
                          self.parent_id, self.rid, self.attrs))
        return False


RECORDER = Recorder()
span, record, spans = RECORDER.span, RECORDER.record, RECORDER.spans
