"""Mean wait of a decode step at the gateway: from the harness's
``pool.step`` to the start of that step's ``pager.load`` (lane queueing,
invoker hand-off and the runtime's state load), over the window."""


def read(out, ctx):
    waits = ctx.spans.values.get("gateway_wait_s")
    return 1e3 * sum(waits) / len(waits) if waits else None
