"""Share of the chip's bf16 peak that the whole prefill step of a
latent-attention MoE model fills: the operations of every prefill of the
window (``counts_mla_moe``, at each prompt's length) over the time in
which at least one conversation's start was in flight (from the
harness's ``pool.start`` to its first token), over the peak."""

from chipbench import counts_mla_moe as counts


def read(out, ctx):
    lengths = ctx.spans.values.get("prefill_tokens", [])
    busy = out.counters.get("prefill_busy_s", 0.0)
    if ctx.peak is None or not lengths or busy <= 0:
        return None
    cfg = ctx.cell.config
    flops = sum(counts.prefill_cost(cfg, int(t))[0] for t in lengths)
    return 100.0 * flops / busy / ctx.peak["bf16_flops_per_s"]
