"""Chip benchmark: one cell (a model configuration under a traffic mix) per
run. ``python bench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``; cells, metrics and bounds are in ``BENCHMARK.json``."""
