"""On-chip serving benchmark: a harness driven by data files.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` and prints one JSON result line.  Each
configuration (``configs/``), traffic mix (``traffic/``), generator kind
(``generators/``) and metric (``metrics/``) is a file of its own, found by
the name ``BENCHMARK.json`` gives it; so is each architecture (``archs/``),
found by the name its configuration file gives it.
"""
