"""``verify.prep.gather`` span time per call: the ``np.stack`` of the
hot rows' signer tables into the hot kernel's table operand
(``Ed25519HotWorkload.encode``)."""


def read(ctx):
    calls = len(ctx["calls"])
    tot = ctx["spans"].get("span.verify.prep.gather")
    if not calls or tot is None:
        return None
    return tot["sum_ms"] / calls
