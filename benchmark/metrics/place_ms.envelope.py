"""``place_ms`` per call, in the cells where a call is one SCP
envelope (those of ``envelope_p50_ms``)."""

from benchmark.metrics.place_ms import read  # noqa: F401
