"""Benchmark of WASGD training and paged serving on TPU chips: run one cell
with ``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``."""
