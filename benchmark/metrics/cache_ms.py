"""Result-cache time per call in ``crypto/keys.py``: the
``span.keys.probe`` (per-row ``sha256`` key and the locked probe) and
``span.keys.seed`` (verdicts written back) timers, summed."""

TIMERS = ("span.keys.probe", "span.keys.seed")


def read(ctx):
    calls = len(ctx["calls"])
    found = [ctx["spans"][t] for t in TIMERS if t in ctx["spans"]]
    if not calls or not found:
        return None
    return sum(t["sum_ms"] for t in found) / calls
