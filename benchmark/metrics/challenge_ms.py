"""``verify.prep.challenge`` span time per call: the byte joins into the
on-wire matrices and the native SHA-512 challenge ``h = H(R||A||M) mod
L`` (``Ed25519Workload.encode``)."""


def read(ctx):
    calls = len(ctx["calls"])
    tot = ctx["spans"].get("span.verify.prep.challenge")
    if not calls or tot is None:
        return None
    return tot["sum_ms"] / calls
