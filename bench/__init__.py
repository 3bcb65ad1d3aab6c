"""Chip benchmark of Veer's served path: closed-loop analyst sessions over
TPC-DS-shaped deployments, checked against a plain reference.

Everything that defines the yardstick lives in this package: the data
generator, the edit families, the plain reference, the trace reduction,
the peaks table and the per-layer metric readers.  ``bench/run.py`` is
the one command; ``BENCHMARK.json`` at the repository root names the cells.
"""
