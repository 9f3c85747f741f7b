"""On-chip benchmark of the DynaComm trainers.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything a
cell needs is found by name: its model in ``configs/``, the plain
reference and operation count that the model's file names in
``reference/``, its traffic in ``traffic/``, its runtime settings and
check limits in ``cells/``, and each per-layer metric's reader in
``metrics/``.
"""
