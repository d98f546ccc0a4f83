"""Time a decode step waits for the tier hierarchy's placement lock,
which other invokers and the committer hold: the program's
``tier.lock_wait`` spans inside the step, over the decode steps enqueued
in the window."""

from chipbench import program_spans as ps


def read(out, ctx):
    return ps.per_step_ms(ctx, lambda step: ps.total(step, "tier.lock_wait"))
