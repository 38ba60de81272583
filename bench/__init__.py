"""Chip benchmark of the partitioner: time to rebalance to an ε-equilibrium.

``python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything a cell needs is found by
name under this directory: ``configs/<config>.json`` (the deployment),
``traffic/<traffic>.json`` (the rebalance requests), ``entries/<entry>.py``
(how the deployment's public entry point is called) and
``metrics/<metric>.py`` (one reader per per-layer metric).
"""
