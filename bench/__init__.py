"""The chip benchmark: one harness driven by ``BENCHMARK.json``.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell on the accelerator it is started on. Configurations, traffic
mixes, op tables and per-layer metric readers are files found by name under
this directory; see ``bench/run.py``.
"""
