"""Time a decode step spends serializing its dirty KV blocks: the
program's ``pager.serialize`` spans (slicing, copy to the host,
``serde.dumps``) inside the step, over the decode steps enqueued in the
window."""

from chipbench import program_spans as ps


def read(out, ctx):
    return ps.per_step_ms(ctx, lambda step: ps.total(step, "pager.serialize"))
