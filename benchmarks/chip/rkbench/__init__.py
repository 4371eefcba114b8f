"""The on-chip benchmark of the RkMIPS serving path (``BENCHMARK.json``).

Everything that measures lives here, apart from the program it measures:
the corpus generator, the traffic schedule, the plain reference that
decides ``correct``, the trace reduction and the per-layer readers (``metrics/<name>.py``). Cells, configurations and
traffic mixes are data files found by name.
"""
