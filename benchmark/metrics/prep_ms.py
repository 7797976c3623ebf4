"""``verify.prep`` span time per call, root-attributed: the
``span.attr.verify.prep`` timer, which counts a phase only once its
blocking root has closed."""


def read(ctx):
    calls = len(ctx["calls"])
    tot = ctx["spans"].get("span.attr.verify.prep")
    if not calls or tot is None:
        return None
    return tot["sum_ms"] / calls
