"""Shared code of the chip benchmark: the yardstick kept with the benchmark.

Configurations (`configs/`), traffic mixes (`workloads/`) and per-layer
metric readers (`metrics/`) are data or one small file each, found by name;
everything here is read by all of them.
"""
