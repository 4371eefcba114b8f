"""tiles_per_ticket (tiles): execute-phase tile visits per reverse ticket.
The arithmetic is rkbench/readers.py::tiles_per_ticket."""

from rkbench.readers import tiles_per_ticket as read  # noqa: F401
