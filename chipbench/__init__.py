"""On-chip benchmark of FedRF-TCA: a harness driven by data files.

Run one cell once with ``python chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  Cells, configurations, drivers and per-layer
metric readers are files found by name; see ``chipbench/harness.py``.
"""
