"""``verify.audit.oracle`` span time per call: the host oracle's
re-verify of the sampled rows and the compare with the device's
verdicts."""


def read(ctx):
    calls = len(ctx["calls"])
    tot = ctx["spans"].get("span.verify.audit.oracle")
    if not calls or tot is None:
        return None
    return tot["sum_ms"] / calls
