"""Chip benchmark of the serving stack, driven by ``BENCHMARK.json``.

Run one cell with ``python bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.
"""
