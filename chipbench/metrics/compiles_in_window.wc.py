"""Programs JAX compiled (or read from its persistent cache) inside the
window: every one is set-up work that leaked into the measurement."""


def read(out, ctx):
    return float(ctx.compiles.compiles)
