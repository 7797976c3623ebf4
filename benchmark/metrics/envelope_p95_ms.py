"""95th percentile of the envelope latency (as ``envelope_p50_ms``
takes it), over every envelope the window served."""

from benchmark.metrics.verify_p95_ms import read  # noqa: F401
