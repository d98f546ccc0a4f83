"""Time a decode step spends in the tier hierarchy's puts once it holds
the placement lock: the program's ``tier.put`` spans inside the step,
less their ``tier.lock_wait``, over the decode steps enqueued in the
window.  The write-back journal's redo writes (``tier.journal_put``)
are part of it."""

from chipbench import program_spans as ps


def read(out, ctx):
    return ps.per_step_ms(
        ctx, lambda step: ps.self_time(step, "tier.put", "tier.lock_wait"))
