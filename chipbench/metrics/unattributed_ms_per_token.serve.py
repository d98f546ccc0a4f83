"""Time of a decode step that no span of the program covers: from
enqueued (``gateway.queue``) to acknowledged (``gateway.ack``), less the
union of the step's own spans, averaged over the decode steps enqueued
in the window.  What the program still cannot see."""

from chipbench import program_spans as ps


def read(out, ctx):
    return ps.per_step_ms(ctx, ps.unattributed)
