"""Wall time inside the pager (``load``, ``write``, ``demote``,
``resume``: serde, tier get/put, device-host copies) per decoded token
in the window."""

NAMES = ("pager.load", "pager.write", "pager.demote", "pager.resume")


def read(out, ctx):
    tokens = out.counters.get("decoded_tokens", 0)
    if not tokens:
        return None
    return 1e3 * sum(ctx.spans.total(n) for n in NAMES) / tokens
