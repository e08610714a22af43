"""The benchmark harness: one cell of BENCHMARK.json, run once.

Everything that belongs to one configuration, traffic mix, cell or metric
lives in a file of its own, found by name:

* ``configs/<config>.json`` and its plain reference ``configs/<reference>.py``;
* ``traffic/<traffic>.json``, read by :mod:`harness.traffic`;
* ``cells/<workload>.json``, the limits of the cell's correctness check;
* ``metrics/<metric>.py``, one reader per metric.
"""
