"""``herder.payload`` span time per SCP envelope: the signed payload
``Herder.verify_envelope`` packs and its result-cache probe."""


def read(ctx):
    calls = len(ctx["calls"])
    tot = ctx["spans"].get("span.herder.payload")
    if not calls or tot is None:
        return None
    return tot["sum_ms"] / calls
