"""On-chip benchmark of the serving engine: one cell is one model
configuration under one traffic mix, named in ``BENCHMARK.json``.

Run a cell with ``python3 chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the repository root on a TPU host.
"""
