"""Chip benchmark of the approximate-DNN emulator: one cell per run.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the workload in
``BENCHMARK.json``, its configuration in ``configs/<config>.json``, its
traffic mix in ``mixes/<traffic>.json`` (read by the general generator in
``traffic.py`` and driven by the cell kind the mix names), its output
limits in ``limits/<workload>.json`` and each per-layer metric's reader in
``metrics/<metric>.py``.
"""
