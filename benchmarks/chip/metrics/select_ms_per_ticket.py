"""select_ms_per_ticket (ms): device time of the kmips.select stage in the
traced window per ticket completed in it. The arithmetic is
rkbench/span_readers.py::select_ms_per_ticket."""

from rkbench.span_readers import select_ms_per_ticket as read  # noqa: F401
