"""scan_lane_share (%): the share of users the reverse plan leaves to the
execute phase's scan. The arithmetic is
rkbench/readers.py::scan_lane_share."""

from rkbench.readers import scan_lane_share as read  # noqa: F401
