"""Rows given a verdict in the window over the window's wall time: all
the work and all the time, row generation and read-back included."""


def read(ctx):
    rows = sum(len(c["rows"]) for c in ctx["calls"])
    return rows / ctx["window_s"] if rows and ctx["window_s"] > 0 else None
