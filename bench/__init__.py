"""Benchmark of the paged serving path, driven by the data files beside it.

Every configuration, traffic mix, per-layer metric and reference forward
is a file of its own, found by the name ``BENCHMARK.json`` gives it
(:mod:`bench.spec`).  ``python3 bench/run.py --workload <cell> ...`` runs
one cell once on the chip it is started on.
"""
