"""tail_p95_ms (ms): 95th percentile of ticket latency in the traced run.
The arithmetic is rkbench/readers.py::tail_p95_ms."""

from rkbench.readers import tail_p95_ms as read  # noqa: F401
