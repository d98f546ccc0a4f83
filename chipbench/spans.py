"""Benchmark-side spans and counters around calls into the program.

The program has no spans of its own yet, so the benchmark wraps the
calls it makes into each layer.  A span is recorded only while
``active`` (the measured window); in a traced run it is also written into
the profiler's trace as a host annotation ``chipbench.<name>``, on the
device trace's clock, so that idle gaps can be labelled by it.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from collections import defaultdict
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax

PREFIX = "chipbench."


class Spans:
    def __init__(self, annotate: bool) -> None:
        self.annotate = annotate
        self.active = False
        self.intervals: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        #: series of values noted by drivers (waits, sizes, positions)
        self.values: Dict[str, List[float]] = defaultdict(list)
        self._lock = threading.Lock()
        self._restore: List[Tuple[Any, str, Any]] = []

    @contextlib.contextmanager
    def span(self, name: str, **meta: Any) -> Iterator[None]:
        """Record span ``name``; in a traced run ``meta`` rides on the
        trace's host event as its stats."""
        ann = (jax.profiler.TraceAnnotation(PREFIX + name, **meta)
               if self.annotate else contextlib.nullcontext())
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            if self.active:
                t1 = time.perf_counter()
                with self._lock:
                    self.intervals[name].append((t0, t1))

    def note(self, name: str, value: float) -> None:
        if self.active:
            with self._lock:
                self.values[name].append(float(value))

    def wrap(self, owner: Any, attr: str, name: str,
             before: Optional[Callable[..., None]] = None,
             meta: Optional[Callable[..., Dict[str, Any]]] = None,
             block: bool = False) -> None:
        """Replace ``owner.attr`` by a call that records span ``name``;
        ``before`` sees the arguments first, ``meta`` makes the span's
        stats from them; with ``block`` the span closes only once the
        call's outputs are ready on the device."""
        orig = getattr(owner, attr)

        @functools.wraps(orig)
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            if before is not None and self.active:
                before(*args, **kwargs)
            with self.span(name, **(meta(*args, **kwargs) if meta else {})):
                out = orig(*args, **kwargs)
                return jax.block_until_ready(out) if block else out

        setattr(owner, attr, wrapper)
        self._restore.append((owner, attr, orig))

    def unwrap(self) -> None:
        for owner, attr, orig in reversed(self._restore):
            setattr(owner, attr, orig)
        self._restore.clear()

    def total(self, name: str) -> float:
        return sum(b - a for a, b in self.intervals.get(name, ()))

    def count(self, name: str) -> int:
        return len(self.intervals.get(name, ()))

    def union(self, names: List[str]) -> float:
        """Seconds in which at least one of the named spans was open."""
        ivs = sorted(iv for n in names for iv in self.intervals.get(n, ()))
        return union_length(ivs)


def union_length(sorted_intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted_intervals:
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total
