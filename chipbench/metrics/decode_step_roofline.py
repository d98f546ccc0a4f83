"""Roofline share of the jitted decode step: the least time of its runs
in the trace (the larger of operations over peak and bytes over
bandwidth, batch 1, at the mean context of the window's decode calls)
over their device time.  The weight read bounds it, so the context moves
the least time by under 1%."""

from chipbench import counts


def read(out, ctx):
    positions = ctx.spans.values.get("decode_positions")
    if out.trace is None or out.trace.dropped or not positions or ctx.peak is None:
        return None
    runs, device_s = out.trace.program(out.counters["programs"]["decode"])
    if not runs:
        return None
    cfg = ctx.cell.config
    least = sum(counts.roofline_seconds(*counts.decode_cost(cfg, int(n)), ctx.peak)
                for n in positions) / len(positions)
    return 100.0 * runs * least / device_s
