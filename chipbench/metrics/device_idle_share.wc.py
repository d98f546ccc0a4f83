"""Share of the traced window in which no operation ran on the device."""


def read(out, ctx):
    if out.trace is None or out.trace.dropped or not out.trace.devices:
        return None
    return 100.0 * out.trace.idle_share
