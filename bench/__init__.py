"""On-chip benchmark of the serving system: one run of one cell per
process (``python3 bench/run.py --workload <cell> --seed <n> --seconds
<s> --trace <0|1>``).

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file ``bench/configs/<config>.json``, the traffic mix
``bench/traffic/<traffic>.json`` and one reader per per-layer metric in
``bench/metrics/<metric>.py``.  Adding a configuration, a mix or a metric
is adding its file and its entry.
"""
