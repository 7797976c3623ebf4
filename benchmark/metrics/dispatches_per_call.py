"""Device dispatches per call: ``verify.dispatch`` spans (one per
bucket chunk, so one per row on the one-row route) over calls."""


def read(ctx):
    calls = len(ctx["calls"])
    tot = ctx["spans"].get("span.verify.dispatch")
    if not calls or tot is None:
        return None
    return tot["count"] / calls
