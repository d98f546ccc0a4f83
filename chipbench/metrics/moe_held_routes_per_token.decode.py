"""Routed slots of a decode step's token that fell on the experts held
here, summed over the MoE layers: the program's ``held_routes`` attr of
``serve.decode``, averaged over the window's decode steps."""

from chipbench import decode_attrs


def read(out, ctx):
    steps = decode_attrs.decode_steps(ctx)
    if not steps:
        return None
    return sum(s["held_routes"] for s in steps) / len(steps)
