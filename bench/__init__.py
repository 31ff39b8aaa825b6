"""Chip benchmark of PBNG: batch decompositions and forest serving.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON result line.  Configurations (``configs/``), traffic mixes
(``traffic/``) and per-layer metric readers (``metrics/``) are found by
the names ``BENCHMARK.json`` gives them.
"""
