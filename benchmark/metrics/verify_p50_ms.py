"""Median latency of one call over every call completed in the window:
its own duration in a closed loop, or from its burst's arrival to its
verdict in an open one (``harness.window``)."""

import numpy as np


def read(ctx):
    lat = [c["latency_s"] for c in ctx["calls"]]
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
