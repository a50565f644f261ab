"""Chip benchmark of the local-SGD engine (``python3 bench/run.py``).

Everything that measures lives here: the harness (``run.py``), the
token traffic, the weights made from the seed, the plain reference, the
trace reduction, the peaks table, the FLOP and byte counts, and one
reader per metric. ``BENCHMARK.json`` at the checkout root names the
cells; each configuration, traffic mix, set of limits and metric reader
is a file found by its name.
"""
