"""launches_per_batch (launches): device program launches per rk.flush
span in the traced window. The arithmetic is
rkbench/span_readers.py::launches_per_batch."""

from rkbench.span_readers import launches_per_batch as read  # noqa: F401
