"""Chip benchmark of this repo's trainer: one cell per run, from data files.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See PERF.md at the root of the repo for the cells, metrics and limits.
"""
