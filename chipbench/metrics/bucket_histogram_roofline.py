"""Roofline share of the ``bucket_histogram`` kernel (the Pallas
``tpu_custom_call`` of the partition program): the least time of its
runs in the trace (keys read and counts written at the chip's memory
bandwidth, at the window's mean key count) over the kernel's device
time."""

from chipbench import counts


def read(out, ctx):
    keys = ctx.spans.values.get("histogram_keys")
    if out.trace is None or out.trace.dropped or not keys or ctx.peak is None:
        return None
    program = out.counters["histogram_program"]
    runs, _ = out.trace.program(program)
    device_s = out.trace.op_seconds(rf"^{program}/.*:tpu_custom_call$")
    if not runs or device_s <= 0:
        return None
    buckets = int(ctx.cell.config["reducers"])
    least = sum(counts.roofline_seconds(*counts.bucket_histogram_cost(int(n), buckets),
                                        ctx.peak) for n in keys) / len(keys)
    return 100.0 * runs * least / device_s
