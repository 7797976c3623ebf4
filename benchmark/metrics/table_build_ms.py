"""Time per call in first-sight signer-table builds: the
``span.verify.table_build`` timer (one span per build attempt in
``BatchVerifier.submit``'s partition, inside ``verify.prep.partition``),
summed over the window and divided by the calls."""


def read(ctx):
    calls = len(ctx["calls"])
    tot = ctx["spans"].get("span.verify.table_build")
    if not calls or tot is None:
        return None
    return tot["sum_ms"] / calls
