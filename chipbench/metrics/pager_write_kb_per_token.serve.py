"""Bytes a decode step writes back through the pager: the ``bytes`` of
the program's ``pager.write`` span (the serialized dirty blocks), over
the window's decode steps, in KB of 1,000 bytes."""

from chipbench import decode_attrs


def read(out, ctx):
    steps = decode_attrs.decode_steps(ctx)
    if not steps or any("write_bytes" not in s for s in steps):
        return None
    return sum(s["write_bytes"] for s in steps) / len(steps) / 1e3
