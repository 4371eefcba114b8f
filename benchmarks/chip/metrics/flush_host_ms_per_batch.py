"""flush_host_ms_per_batch (ms): mean host time of the program's rk.flush
spans in the traced window. The arithmetic is
rkbench/span_readers.py::flush_host_ms_per_batch."""

from rkbench.span_readers import flush_host_ms_per_batch as read  # noqa: F401
