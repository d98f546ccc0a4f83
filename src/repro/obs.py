"""In-program spans: where the serving path spends its time.

Every layer opens a span around the work it does: the gateway's lane
and dispatch, the runtime's invoke and commit, the group committer's
flush, the pager and its serialization, the tier hierarchy's puts, gets
and lock waits.  A span is one tuple

    (span id, parent id, request id, name, t0, t1, thread, attrs)

appended, when it closes, to one process-wide ring of the most recent
:data:`RING` spans.  Times come from ``time.perf_counter``.  The parent
and request ids come from a thread-local stack, so work on an invoker
thread inherits the request the gateway dispatched; work on another
thread names its request (``span(..., rid=...)`` or :func:`record`).
``attrs`` carries the counts of the span's own work (keys, bytes,
blocks, syncs): the body of a ``with span(...) as attrs`` may add to it.

Recording is always on and costs two clock reads and one tuple a span.
While a profiler trace is being taken, each span is also a
``jax.profiler.TraceAnnotation`` named ``marvel.<name>``, on the device
trace's clock.  The profiler takes no interval measured elsewhere, so a
:func:`record`-ed span appears there as a mark at its end that carries
its length (``dur_ns``).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = [
    "PREFIX",
    "RING",
    "Span",
    "dropped",
    "kept_since",
    "new_request",
    "record",
    "request",
    "span",
    "spans",
]

PREFIX = "marvel."

#: spans the ring keeps.  A decoded token of the paged serving path
#: closes about 16 spans, so at 10 tokens/s the ring holds 6-7 minutes.
RING = 1 << 16


class Span(NamedTuple):
    sid: int
    parent: int
    rid: Optional[int]
    name: str
    t0: float
    t1: float
    thread: int
    attrs: Dict[str, Any]


_ring: Deque[Tuple] = deque(maxlen=RING)
_ids = itertools.count(1)
_rids = itertools.count(1)
#: drawn once per append (``next`` is atomic); :func:`dropped` draws one
#: more per read and counts those apart
_appended = itertools.count()
_reads = 0
_reads_lock = threading.Lock()
_local = threading.local()
_clock = time.perf_counter
_tracing = TraceAnnotation.is_enabled
_thread = threading.get_ident


def _stack() -> List[Tuple[int, Optional[int]]]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def new_request() -> int:
    """A fresh request id (one per gateway invocation)."""
    return next(_rids)


def request() -> Optional[int]:
    """The request of the innermost open span on this thread."""
    st = _stack()
    return st[-1][1] if st else None


class span:
    """``with span(name, **attrs) as attrs:`` records one span around the
    block; ``rid`` names its request, which otherwise comes from the
    enclosing span on this thread."""

    __slots__ = ("name", "rid", "attrs", "sid", "parent", "t0", "ann",
                 "stack")

    def __init__(self, name: str, rid: Optional[int] = None,
                 **attrs: Any) -> None:
        self.name = name
        self.rid = rid
        self.attrs = attrs

    def __enter__(self) -> Dict[str, Any]:
        st = _stack()
        if st:
            self.parent, inherited = st[-1]
            if self.rid is None:
                self.rid = inherited
        else:
            self.parent = 0
        self.sid = next(_ids)
        st.append((self.sid, self.rid))
        self.stack = st
        if _tracing():
            self.ann = TraceAnnotation(PREFIX + self.name, rid=self.rid)
            self.ann.__enter__()
        else:
            self.ann = None
        self.t0 = _clock()
        return self.attrs

    def __exit__(self, *exc: Any) -> None:
        t1 = _clock()
        if self.ann is not None:
            self.ann.set_metadata(**self.attrs)
            self.ann.__exit__(None, None, None)
        self.stack.pop()
        _ring.append((self.sid, self.parent, self.rid, self.name, self.t0,
                      t1, _thread(), self.attrs))
        next(_appended)


def record(name: str, t0: float, t1: float, rid: Optional[int] = None,
           **attrs: Any) -> None:
    """Log an interval measured elsewhere (``t0``, ``t1`` from
    ``time.perf_counter``), as a child of this thread's open span."""
    st = _stack()
    parent = st[-1][0] if st else 0
    if _tracing():
        with TraceAnnotation(PREFIX + name, rid=rid,
                             dur_ns=int((t1 - t0) * 1e9), **attrs):
            pass
    _ring.append((next(_ids), parent, rid, name, t0, t1, _thread(), attrs))
    next(_appended)


def spans(since: float = float("-inf"),
          until: float = float("inf")) -> List[Span]:
    """The kept spans that start at or after ``since`` and end at or
    before ``until``, in the order they closed."""
    # list() copies the ring in one call, which no other thread's append
    # can interleave with
    return [Span._make(e) for e in list(_ring)
            if e[4] >= since and e[5] <= until]


def dropped() -> int:
    """Spans that fell off the ring since the process started."""
    global _reads
    with _reads_lock:
        appended = next(_appended) - _reads
        _reads += 1
    return max(0, appended - len(_ring))


def kept_since(t: float) -> bool:
    """Whether the ring still holds every span that closed at or after
    ``t``: nothing fell off, or what fell off closed before the oldest
    kept span, which closed before ``t``."""
    if not dropped():
        return True
    try:
        return _ring[0][5] < t
    except IndexError:
        return False
