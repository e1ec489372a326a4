"""On-chip benchmark harness: cells, traffic, reference and trace reduction.

Run one cell with ``python chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``; ``BENCHMARK.json`` at the repository root
names the cells, their configurations and traffic mixes, and the metrics
each reports.  Everything a cell needs is found by name: its configuration
in ``chipbench/configs/<config>.json``, its traffic mix in
``chipbench/traffic/<traffic>.json`` and each per-layer metric's reader in
``chipbench/metrics/<metric>.py``.
"""
