"""Process start to the first timed call: JAX start-up, compile-cache
loads, tracing and lowering of the cell's own shapes, row generation
and the warm-up calls."""


def read(ctx):
    return ctx["setup_s"]
