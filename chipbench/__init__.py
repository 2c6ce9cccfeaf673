"""Chip benchmark of the ACORN serving path: one cell per run.

Run ``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout.  ``BENCHMARK.json`` names the
cells; each cell's configuration, traffic mix and metrics are data files
and small readers under this directory, found by name.
"""
