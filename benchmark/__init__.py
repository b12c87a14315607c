"""The benchmark: one cell, once, on the chip (``python -m benchmark.run``).

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, the trace reduction, the table of
peaks, the FLOP and byte functions, the plain reference and the comparison
that decides ``correct``. From the program it takes only the system under
test (``ray_tpu``) and what that system counts. ``BENCHMARK.json`` at the
root names the cells; each configuration, traffic mix, cell and per-layer
metric is a data file found by that name.
"""
