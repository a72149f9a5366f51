"""The benchmark of ray_tpu: ``python3 perfbench/run.py --workload <cell> ...``.

Everything the yardstick needs lives under this directory (``BENCHMARK.json``
names it under ``paths``); from the program it takes only the system under
test.  Cells, configurations, traffic mixes and per-layer metrics are data
files found by the names in ``BENCHMARK.json``.
"""
