"""Chip benchmark of the served search: cells named in ``BENCHMARK.json``.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once on the chips it is started on.  Everything that belongs to
one configuration, traffic mix or per-layer metric is a file of its own under
``bench/configs``, ``bench/traffic`` and ``bench/metrics``, found by the name
that ``BENCHMARK.json`` gives it.
"""
