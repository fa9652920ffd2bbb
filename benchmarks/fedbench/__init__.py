"""fedbench: the chip benchmark of the federated-learning simulator.

``run.py`` runs one cell of ``BENCHMARK.json`` once and prints one JSON
line.  Everything a cell needs is found by name: ``configs/<name>.json``
(sizes) with ``configs/<name>.py`` (the plain reference model),
``workloads/<cell>.json`` (traffic, strategy, check limits) and
``metrics/<metric>.py`` (one reader per per-layer metric).
"""
