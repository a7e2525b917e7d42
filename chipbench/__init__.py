"""Chip benchmark of the SparKV served path.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON line. Everything that belongs to one
configuration, traffic mix, limit set or per-layer metric is a file of its
own under ``configs/``, ``traffic/``, ``limits/`` or ``metrics/``, found
by the name ``BENCHMARK.json`` gives it; a model family (weight tree,
reference, operation counts) is a module under ``families/``, found by
the configuration's ``"family"``.
"""
