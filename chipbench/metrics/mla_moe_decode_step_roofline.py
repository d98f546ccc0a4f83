"""Roofline share of the jitted decode step of a latent-attention MoE
model: the least time of its runs in the trace (the larger of operations
over peak and bytes over bandwidth, batch 1, from ``counts_mla_moe``: the
held experts its token was routed to and the latent over its context,
averaged over the window's decode steps) over their device time."""

from chipbench import counts_mla_moe as counts
from chipbench import decode_attrs
from chipbench.counts import roofline_seconds


def read(out, ctx):
    steps = decode_attrs.decode_steps(ctx)
    if out.trace is None or out.trace.dropped or not steps or ctx.peak is None:
        return None
    runs, device_s = out.trace.program(out.counters["programs"]["decode"])
    if not runs:
        return None
    cfg = ctx.cell.config
    least = sum(roofline_seconds(*counts.decode_cost(
        cfg, s["position"] + 1, s["held_routes"]), ctx.peak)
        for s in steps) / len(steps)
    return 100.0 * runs * least / device_s
