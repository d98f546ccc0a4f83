"""Share of the jobs' wall time in which the device shuffle
(``device_partition`` or ``device_segment_reduce``, on any thread) was
running."""


def read(out, ctx):
    jobs = ctx.spans.total("job")
    if not jobs:
        return None
    return 100.0 * ctx.spans.union(["device_partition", "device_segment_reduce"]) / jobs
