"""Share of the chip's bf16 peak that the whole decode step of a
latent-attention MoE model fills: the operations of the window's decode
steps (``counts_mla_moe``, at each step's context and held routes) over
the time in which at least one decode step was in flight (from the
harness's ``pool.step`` to its token), over the peak.  The steps are
those whose gateway lane wait began in the window, the busy time that of
the steps submitted in it: the two sets differ by the steps at its
edges."""

from chipbench import counts_mla_moe as counts
from chipbench import decode_attrs


def read(out, ctx):
    steps = decode_attrs.decode_steps(ctx)
    busy = out.counters.get("decode_busy_s", 0.0)
    if ctx.peak is None or not steps or busy <= 0:
        return None
    cfg = ctx.cell.config
    flops = sum(counts.decode_cost(cfg, s["position"] + 1, s["held_routes"])[0]
                for s in steps)
    return 100.0 * flops / busy / ctx.peak["bf16_flops_per_s"]
