"""Chip benchmark of the LOCO channel objects.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything that belongs to one
configuration, traffic mix, channel entry or per-layer metric sits in a
file of its own, found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``traffic/<mix>.json``, ``entries/<entry>.py``
and ``metrics/<metric>.py``.
"""
