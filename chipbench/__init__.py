"""On-chip benchmark of the robust training step (``python3 chipbench/run.py``).

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, found by name: ``configs/<config>.json`` (its
``family`` names ``reference/<family>.py``, the plain reference with the
FLOP count), ``workloads/<cell>.json``, ``metrics/<metric>.py`` and, for
the kernels and name scopes the trace reduction assigns to a layer,
``kernels/<kernel>.json`` and ``scopes/<scope>.json``.
"""
