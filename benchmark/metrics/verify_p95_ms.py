"""95th percentile of the call latency (as ``verify_p50_ms`` takes
it), over every call completed in the window (numpy's linear
interpolation)."""

import numpy as np


def read(ctx):
    lat = [c["latency_s"] for c in ctx["calls"]]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
