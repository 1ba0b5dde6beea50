"""Host spans and counters of the program, recorded only while a profiler runs.

``span(name, **ids)`` marks one piece of host work at a layer boundary and
``count(name, value)`` adds to a counter there.  With no profiler running
both do nothing: ``span`` returns one shared null context and reads no
clock.  While a profiler runs (``jax.profiler.trace``, ``start_trace`` or a
remote xprof capture), a span enters a ``jax.profiler.TraceAnnotation`` of
the same name and ids, so it lands in the profiler's ``.xplane.pb`` beside
the device operations, and is also kept in memory as a :class:`Span`;
``snapshot()`` returns the spans and counters kept.

A span is kept only if the profiler runs both when it begins and when it
ends, which is the profiler's own rule, so the memory and the trace hold
the same spans.  Records belong to a session: the first record under a new
profiler session, or after a call made while none ran, starts a new one
and drops the old.  A session keeps at most ``MAX_SPANS`` spans and counts
the rest in the counter ``spans.dropped``.  The profiler is one per
process, and so is this recorder.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
from jax._src import profiler as _jax_profiler

MAX_SPANS = 65_536

enabled = jax.profiler.TraceAnnotation.is_enabled
_NULL = contextlib.nullcontext()


class Span(NamedTuple):
    name: str
    start_ns: int               # ``time.time_ns()``, the profiler's clock
    end_ns: int
    parent: Optional[int]       # index of the enclosing span in the snapshot
    ids: Dict[str, object]      # ``request=``, ``wave=``, ``query=`` ...


class _Session:
    def __init__(self, key):
        self.key = key
        self.records: List[list] = []    # [name, start, end, parent, ids]
        self.counters: Dict[str, float] = {}


def _profiler_key():
    """The programmatic profiler session, new with every ``start_trace``;
    None under a remote capture (a call made while off then marks the
    boundary)."""
    state = getattr(_jax_profiler, "_profile_state", None)
    return getattr(state, "profile_session", None)


_lock = threading.Lock()
_session = _Session(None)
_off_seen = True
_open = threading.local()           # per thread: stack of (session, record)


def _current() -> _Session:
    global _session, _off_seen
    key = _profiler_key()
    with _lock:
        if _off_seen or key is not _session.key:
            _session, _off_seen = _Session(key), False
        return _session


def _add(session: _Session, name: str, value) -> None:
    with _lock:
        session.counters[name] = session.counters.get(name, 0) + value


class _Span:
    __slots__ = ("name", "ids", "annotation", "session", "record")

    def __init__(self, name: str, ids: dict):
        self.name, self.ids = name, ids

    def __enter__(self):
        self.annotation = jax.profiler.TraceAnnotation(self.name, **self.ids)
        self.annotation.__enter__()
        session = self.session = _current()
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        parent = stack[-1][1] if stack and stack[-1][0] is session else None
        if len(session.records) < MAX_SPANS:
            self.record = [self.name, time.time_ns(), None, parent, self.ids]
            session.records.append(self.record)
        else:
            self.record = None
            _add(session, "spans.dropped", 1)
        stack.append((session, self.record))
        return self

    def __exit__(self, *exc):
        end = time.time_ns()
        _open.stack.pop()
        if (self.record is not None and enabled()
                and _current() is self.session):
            self.record[2] = end
        return self.annotation.__exit__(*exc)


def span(name: str, **ids):
    """A context manager timing host work as ``name``; ``ids`` tie the
    spans of one request, wave or query together."""
    global _off_seen
    if not enabled():
        _off_seen = True
        return _NULL
    return _Span(name, ids)


def count(name: str, value=1) -> None:
    """Add ``value`` to counter ``name`` while a profiler runs."""
    global _off_seen
    if not enabled():
        _off_seen = True
        return
    _add(_current(), name, value)


def snapshot() -> Tuple[List[Span], Dict[str, float]]:
    """The spans (in the order they began) and counters of the current or
    latest session; a span still open, or one the profiler stopped
    inside, is left out."""
    with _lock:
        session = _session
        records = list(session.records)
        counters = dict(session.counters)
    kept = [r for r in records if r[2] is not None]
    index = {id(r): i for i, r in enumerate(kept)}
    return ([Span(n, s, e, None if p is None else index.get(id(p)), ids)
             for n, s, e, p, ids in kept], counters)
