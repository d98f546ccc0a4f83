"""Mean wait of a decode step for its durable ack: the program's
``gateway.ack`` span, from the end of the step's execution to the group
committer's flush of its state landing, over the decode steps enqueued
in the window."""

from chipbench import program_spans as ps


def read(out, ctx):
    return ps.per_step_ms(ctx, lambda step: ps.total(step, "gateway.ack"))
