"""``verify.place`` span time per call: operand fingerprints, the
resident-cache probe and ``device_put`` of each dispatched operand
tuple (``BatchEngine._place_operands``)."""


def read(ctx):
    calls = len(ctx["calls"])
    tot = ctx["spans"].get("span.verify.place")
    if not calls or tot is None:
        return None
    return tot["sum_ms"] / calls
