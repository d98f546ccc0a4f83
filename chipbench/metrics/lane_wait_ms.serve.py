"""Mean wait of a decode step in its gateway lane: the program's
``gateway.queue`` span, from ``Gateway.submit`` to an invoker taking the
step, over the decode steps enqueued in the window."""

from chipbench import program_spans as ps


def read(out, ctx):
    return ps.per_step_ms(ctx, lambda step: ps.total(step, "gateway.queue"))
