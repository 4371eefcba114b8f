"""device_idle_share (%): the share of the traced window in which no
operation ran on the device. The arithmetic is
rkbench/readers.py::device_idle_share."""

from rkbench.readers import device_idle_share as read  # noqa: F401
