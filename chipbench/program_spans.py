"""The program's own spans (``repro.obs``) of the window's decode steps,
for the per-layer metrics that read them.

A decode step is one gateway invocation, and its spans carry one request
id: ``gateway.queue`` (enqueued to taken by an invoker), then
``gateway.dispatch`` (runtime, pager and tiers on the invoker's thread),
then ``gateway.ack`` (until the group commit's flush lands).  A step
counts when its ``gateway.queue`` starts inside the window and it ran
``serve.decode`` but not ``serve.prefill`` (a conversation's start runs
both).
A program without these spans, or a ring that lost spans of the window,
gives nothing to read.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Any, Callable, Iterable, List, Optional, Sequence

from chipbench.spans import union_length


def decode_steps(ctx: Any) -> Optional[List[List[Any]]]:
    """The spans of each decode step enqueued in the window."""
    try:
        from repro import obs
    except ImportError:  # a program without in-program spans
        return None
    windows = ctx.spans.intervals.get("window")
    if not windows:
        return None
    w0, w1 = windows[0]
    if not obs.kept_since(w0):
        return None
    by_request = defaultdict(list)
    for s in obs.spans(since=w0):
        if s.rid is not None:
            by_request[s.rid].append(s)
    steps = [spans for spans in by_request.values()
             if any(s.name == "gateway.queue" and s.t0 <= w1 for s in spans)
             and any(s.name == "serve.decode" for s in spans)
             and not any(s.name == "serve.prefill" for s in spans)]
    return steps or None


def total(step: Iterable[Any], *names: str) -> float:
    """Seconds in the step's spans of these names."""
    return sum(s.t1 - s.t0 for s in step if s.name in names)


def self_time(step: Sequence[Any], name: str, minus: str) -> float:
    """Seconds in the step's ``name`` spans, less their ``minus``
    children."""
    out = 0.0
    for s in step:
        if s.name == name:
            out += s.t1 - s.t0 - sum(c.t1 - c.t0 for c in step
                                     if c.parent == s.sid and c.name == minus)
    return out


def bounds(step: Sequence[Any]) -> tuple:
    """Enqueued to acknowledged: ``gateway.queue``'s start to the end of
    ``gateway.ack``, or of ``gateway.dispatch`` where nothing waited for
    a flush."""
    start = min(s.t0 for s in step if s.name == "gateway.queue")
    ends = [s.t1 for s in step if s.name == "gateway.ack"] or \
        [s.t1 for s in step if s.name == "gateway.dispatch"]
    return start, max(ends)


def unattributed(step: Sequence[Any]) -> float:
    """Seconds from enqueued to acknowledged in none of the step's spans."""
    start, end = bounds(step)
    covered = sorted((max(s.t0, start), min(s.t1, end)) for s in step
                     if s.t1 > start and s.t0 < end)
    return end - start - union_length(covered)


def per_step_ms(ctx: Any, seconds: Callable[[Sequence[Any]], float]
                ) -> Optional[float]:
    """The mean over the window's decode steps of ``seconds(step)``, in
    milliseconds."""
    steps = decode_steps(ctx)
    if steps is None:
        return None
    return 1e3 * sum(seconds(step) for step in steps) / len(steps)
