"""Median latency of one SCP envelope, from its burst's arrival to its
verdict, over every envelope the window served: ``verify_p50_ms``'s
reading, under a name and bound of its own, since an envelope is not
a tx set."""

from benchmark.metrics.verify_p50_ms import read  # noqa: F401
