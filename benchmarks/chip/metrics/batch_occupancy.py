"""batch_occupancy (%): the filled share of micro-batch slots over the
traced run. The arithmetic is rkbench/readers.py::batch_occupancy."""

from rkbench.readers import batch_occupancy as read  # noqa: F401
