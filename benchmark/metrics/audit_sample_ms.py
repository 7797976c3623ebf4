"""``verify.audit.sample`` span time per call: the join of each audited
part's encoded bytes and the content-seeded sample drawn from them."""


def read(ctx):
    calls = len(ctx["calls"])
    tot = ctx["spans"].get("span.verify.audit.sample")
    if not calls or tot is None:
        return None
    return tot["sum_ms"] / calls
