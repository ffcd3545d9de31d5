"""On-chip benchmark of the query engine: TPC-H query serving and
predicate-pushdown scans, driven from ``BENCHMARK.json``.

Run one cell once with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout on a TPU host.
"""
