"""On-chip benchmark of the robust training step (``python3 chipbench/run.py``).

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, found by name: ``configs/<config>.json``,
``workloads/<cell>.json``, ``metrics/<metric>.py`` and, for the kernels
the trace reduction assigns to a layer, ``kernels/<kernel>.json``.
"""
