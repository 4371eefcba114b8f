"""hamming_scores_ms_per_ticket (ms): device time of the hamming_scores
kernel in the traced window per ticket completed in it. The arithmetic
is rkbench/readers.py::hamming_scores_ms_per_ticket."""

from rkbench.readers import hamming_scores_ms_per_ticket as read  # noqa: F401
