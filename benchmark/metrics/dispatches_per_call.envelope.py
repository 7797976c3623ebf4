"""``dispatches_per_call`` per call, in the cells where a call is one SCP
envelope (those of ``envelope_p50_ms``)."""

from benchmark.metrics.dispatches_per_call import read  # noqa: F401
