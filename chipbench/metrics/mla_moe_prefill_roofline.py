"""Roofline share of the jitted prefill of a latent-attention MoE model
over every prompt the window served, each at its own length: the least
time of all of them (``counts_mla_moe``) over the time of their
``prefill`` spans, each of which closes when its outputs are on the
device."""

from chipbench import counts_mla_moe as counts
from chipbench.counts import roofline_seconds


def read(out, ctx):
    spans = ctx.spans.intervals.get("prefill", [])
    lengths = ctx.spans.values.get("prefill_tokens", [])
    if ctx.peak is None or not spans or len(spans) != len(lengths):
        return None
    cfg = ctx.cell.config
    least = sum(roofline_seconds(*counts.prefill_cost(cfg, int(t)), ctx.peak)
                for t in lengths)
    return 100.0 * least / sum(b - a for a, b in spans)
