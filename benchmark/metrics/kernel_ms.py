"""Device time of the verify executables per call: the summed
durations of their module events in the profiler trace, found by
their jit names, over the calls of the traced window the device trace
covers."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("kernel_events") or not tr.get("calls"):
        return None
    return tr["kernel_s"] * 1e3 / tr["calls"]
