"""1 - (union of device-op intervals / traced window), from the
profiler trace, averaged over the chips used."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr.get("busy_s") is None or not tr.get("window_s"):
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
