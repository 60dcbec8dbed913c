"""The chip benchmark: one cell of ``BENCHMARK.json`` per run, on a TPU.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``.  See ``PERF.md`` for the cells and metrics.
"""
