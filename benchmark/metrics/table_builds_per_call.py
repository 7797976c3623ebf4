"""First-sight signer-table build attempts per call: the count of the
``span.verify.table_build`` timer over the window, divided by the
calls (a key that cannot be tabled counts too: it is attempted on
every sight)."""


def read(ctx):
    calls = len(ctx["calls"])
    tot = ctx["spans"].get("span.verify.table_build")
    if not calls or tot is None:
        return None
    return tot["count"] / calls
