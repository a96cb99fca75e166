"""The chip benchmark: open-loop serving through the paged engine.

Run one cell with ``python chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout on a machine
with a TPU.  Everything a cell needs is found by name: its
configuration in ``configs/``, its traffic in ``traffic/``, each
per-layer metric's reader in ``metrics/``, and the architecture module
(the block's shape and counts) and plain reference a configuration names
in ``architectures/`` and ``references/``.
"""
