"""Share of the chip's bf16 peak that the whole decode step fills: the
operations of every decode step of the window (counted from shapes, at
each step's context) over the time in which at least one decode step was
in flight (from the harness's ``pool.step`` to its token: gateway,
pager and device), over the peak."""

from chipbench import counts


def read(out, ctx):
    positions = ctx.spans.values.get("decode_positions", [])
    busy = out.counters.get("decode_busy_s", 0.0)
    if ctx.peak is None or not positions or busy <= 0:
        return None
    cfg = ctx.cell.config
    flops = sum(counts.decode_cost(cfg, int(n))[0] for n in positions)
    return 100.0 * flops / busy / ctx.peak["bf16_flops_per_s"]
