"""queue_wait_ms (ms): mean wait of a ticket from admission to its
batch's formation, from the program's RuntimeStats. The arithmetic is
rkbench/span_readers.py::queue_wait_ms."""

from rkbench.span_readers import queue_wait_ms as read  # noqa: F401
