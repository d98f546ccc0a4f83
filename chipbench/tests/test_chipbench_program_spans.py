"""The per-layer metrics that read the program's own spans (``repro.obs``),
on a traced smoke-size serving run: each reads a finite value, the
enqueue-to-ack time of a decode step is its spans plus what no span
covers, the spans are in the profiler's trace, and a reader gives nothing
where the program has no spans or the ring lost some of the window."""

import math
import sys
import time

import pytest

from chipbench import harness, program_spans
from chipbench.tests import smoke

NEW = ("lane_wait_ms.serve", "commit_ack_ms_per_token.serve",
       "pager_serialize_ms_per_token.serve", "tier_put_ms_per_token.serve",
       "tier_lock_wait_ms_per_token.serve", "unattributed_ms_per_token.serve")


class _Ctx:
    """What the readers take from a run's context: its window."""

    def __init__(self, w0, w1):
        self.spans = type("S", (), {"intervals": {"window": [(w0, w1)]}})()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from repro import obs

    keep = tmp_path_factory.mktemp("trace") / "serve.xplane.pb"
    t = time.perf_counter()
    result = smoke.run(smoke.serve_cell(), trace=True, keep_trace=keep)
    queued = [s.t0 for s in obs.spans(t) if s.name == "gateway.queue"]
    return result, keep, _Ctx(min(queued), max(queued))


def _reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_a_finite_value_in_a_traced_run(traced, name):
    result, _, _ = traced
    assert result["correct"], result["checks"]
    value = result["metrics"][name]["value"]
    assert math.isfinite(value) and value >= 0.0


def test_enqueue_to_ack_is_the_steps_spans_plus_what_none_covers(traced):
    _, _, ctx = traced
    steps = program_spans.decode_steps(ctx)
    assert steps
    for step in steps:
        start, end = program_spans.bounds(step)
        top = program_spans.total(step, "gateway.queue", "gateway.dispatch",
                                  "gateway.ack")
        left = program_spans.unattributed(step)
        assert abs(end - start - (top + left)) < 1e-9
        # and each span's own time, less its children's, adds up the same
        own = sum(s.t1 - s.t0 - sum(c.t1 - c.t0 for c in step
                                    if c.parent == s.sid) for s in step)
        assert abs(end - start - (own + left)) < 1e-9
    per_step = {n: _reader(n).read(None, ctx) for n in NEW}
    assert None not in per_step.values()
    assert per_step["lane_wait_ms.serve"] + per_step["commit_ack_ms_per_token.serve"] \
        + per_step["unattributed_ms_per_token.serve"] < 1e3 * sum(
            program_spans.bounds(s)[1] - program_spans.bounds(s)[0]
            for s in steps) / len(steps)


def test_program_spans_are_in_the_trace_between_its_markers(traced):
    from jax.profiler import ProfileData

    from chipbench.spans import PREFIX
    from repro.obs import PREFIX as PROGRAM

    _, keep, _ = traced
    host = [ev for plane in ProfileData.from_file(str(keep)).planes
            if plane.name.startswith("/host") for line in plane.lines
            for ev in ((line.name, e) for e in line.events)]
    marks = {e.name: e for _, e in host if e.name.startswith(PREFIX + "trace.")}
    w0, w1 = marks[PREFIX + "trace.start"].end_ns, marks[PREFIX + "trace.stop"].start_ns
    ours = [(line, e) for line, e in host if e.name.startswith(PROGRAM)]
    assert ours and all(w0 <= e.start_ns and e.end_ns <= w1 for _, e in ours)
    names = {e.name[len(PROGRAM):] for _, e in ours}
    assert {"gateway.queue", "gateway.dispatch", "gateway.ack", "runtime.invoke",
            "runtime.commit", "journal.flush", "serve.decode", "pager.load",
            "pager.write", "pager.serialize", "tier.put",
            "tier.journal_put"} <= names
    invokes = [(line, e) for line, e in ours if e.name == PROGRAM + "runtime.invoke"]
    writes = [(line, e) for line, e in ours if e.name == PROGRAM + "pager.write"]
    assert writes
    for line, w in writes:
        assert any(line == il and i.start_ns <= w.start_ns and w.end_ns <= i.end_ns
                   for il, i in invokes)


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_without_program_spans(traced, name, monkeypatch):
    import repro

    _, _, ctx = traced
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _reader(name).read(None, ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_when_the_ring_lost_window_spans(traced, name):
    from repro import obs

    _, _, ctx = traced
    w0, w1 = ctx.spans.intervals["window"][0]
    for _ in range(obs.RING):
        obs.record("test.flood", w0, w1)
    assert _reader(name).read(None, ctx) is None
