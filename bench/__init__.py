"""On-chip benchmark of the served KVI path.

One command runs one cell (one entry of ``BENCHMARK.json``'s
``workloads``) once::

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, metric,
runner kind or kernel is a file of its own, found by name:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/metrics/<metric>.py``, ``bench/runners/<kind>.py`` and
``bench/kernels/<kernel>.py``.
"""
