"""Chip benchmark for the MemEC store: open-loop YCSB cells on one TPU.

Run one cell with ``python bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the repository root.  Cells,
configurations, traffic mixes and metrics are named in ``BENCHMARK.json``
and found by name under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``.
"""
