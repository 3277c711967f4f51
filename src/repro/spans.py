"""The program's host spans: named phases that a profile shows next to the
device's operations, and that a reader can also get back in memory.

    from repro import spans

    with spans.span("serve.emit") as s:
        ...
        s.args["syncs"] = n          # counts known only at the span's end

Every span enters a ``jax.profiler.TraceAnnotation`` of its name, so any
profile an operator captures carries it.  While the profiler traces, or
while a :func:`recording` scope is open, a span is also kept, as a
:class:`Span` on ``time.perf_counter_ns``, in a bounded buffer that
:func:`recorded` returns; otherwise nothing is kept.  Each kept span has an
id of its own and the id of the kept span it was opened in.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

CAPACITY = 1 << 16


class Span(NamedTuple):
    start_ns: int
    end_ns: int
    name: str
    span_id: int
    parent_id: int | None
    args: dict


_buffer: collections.deque = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_open = threading.local()           # .stack: ids of this thread's open spans
_recording = 0                      # open recording() scopes
_recording_lock = threading.Lock()


class span:
    """A named host span; ``args`` are kept with it."""

    __slots__ = ("name", "args", "_annotation", "_id", "_parent", "_start")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self) -> "span":
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._id = None
        if _recording or TraceAnnotation.is_enabled():
            stack = _open.__dict__.setdefault("stack", [])
            self._parent = stack[-1] if stack else None
            self._id = next(_ids)
            stack.append(self._id)
            self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        if self._id is not None:
            end = time.perf_counter_ns()
            _open.stack.pop()
            _buffer.append(Span(self._start, end, self.name, self._id,
                                self._parent, self.args))
        self._annotation.__exit__(*exc)


@contextlib.contextmanager
def recording():
    """Keep the spans opened inside, with the profiler off too."""
    global _recording
    with _recording_lock:
        _recording += 1
    try:
        yield
    finally:
        with _recording_lock:
            _recording -= 1


def recorded() -> list[Span]:
    """The kept spans, oldest first (at most ``CAPACITY``)."""
    return list(_buffer)


def clear() -> None:
    _buffer.clear()
