"""Chip benchmark for TOAST: train throughput and time to a plan.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the chips of the
machine it starts on.  Everything that belongs to one configuration,
traffic mix or per-layer metric is a file of its own, found by name
(``catalog``).
"""
