"""Chip benchmark of the lock-engine simulator.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the TPU it is started on and
prints one JSON result line. Everything a cell needs is found by name:

* ``bench/configs/<config>.json``: the deployment (shapes, guarantees, source);
* ``bench/traffic/<traffic>.json``: the traffic mix, which names its driver;
* ``bench/drivers/<driver>.py``: the general generator for that kind of mix;
* ``bench/layers/<metric>.py``: one reader per per-layer metric;
* ``bench/limits/<cell>.json``: the limits of the numbers that decide ``correct``.
"""
