"""Host spans of the serving path, kept in memory while recording is on.

A span is a named stretch of host time on the wall clock
(``time.time_ns()``, the clock a profiler trace is placed by), with the
thread it ran on, its parent (the span open on that thread when it
began) and ids: ``request`` and ``batch``.  Recording is off by default;
``record(True)`` turns it on for the process and ``drain()`` hands back
what was recorded.  Off, a span still times its body (the serving
counters read ``t0``/``t1``) and stores nothing.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import List, NamedTuple, Optional

__all__ = ["Span", "span", "add", "record", "drain"]


class Span(NamedTuple):
    id: int
    name: str
    t0_ns: int
    t1_ns: int
    thread: str
    parent: Optional[int]
    ids: dict


_on = False
_spans: List[Span] = []
_lock = threading.Lock()
_next_id = itertools.count()
_open = threading.local()


def record(on: bool) -> None:
    """Switch recording on or off for every thread of the process."""
    global _on
    _on = bool(on)


def drain() -> List[Span]:
    """The spans recorded so far, in the order they ended; clears them."""
    global _spans
    with _lock:
        out, _spans = _spans, []
    return out


def _stack() -> list:
    st = getattr(_open, "stack", None)
    if st is None:
        st = _open.stack = []
    return st


class span:
    """``with span(name, **ids) as s:`` times its body into ``s.t0`` and
    ``s.t1`` (ns) and, while recording is on, records it."""

    __slots__ = ("name", "ids", "t0", "t1", "_id", "_parent")

    def __init__(self, name: str, **ids) -> None:
        self.name = name
        self.ids = ids

    def __enter__(self) -> "span":
        self._id = None
        if _on:
            st = _stack()
            self._parent = st[-1] if st else None
            self._id = next(_next_id)
            st.append(self._id)
        self.t0 = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.time_ns()
        if self._id is not None:
            _stack().pop()
            s = Span(self._id, self.name, self.t0, self.t1,
                     threading.current_thread().name, self._parent, self.ids)
            with _lock:
                _spans.append(s)


def add(name: str, t0_ns: int, t1_ns: int, **ids) -> None:
    """Record a span timed by the caller, such as one that began on
    another thread; it has no parent."""
    if not _on:
        return
    s = Span(next(_next_id), name, int(t0_ns), int(t1_ns),
             threading.current_thread().name, None, ids)
    with _lock:
        _spans.append(s)
