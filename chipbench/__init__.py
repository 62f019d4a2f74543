"""Chip benchmark of the exact top-K serving system.

One run is one cell of ``BENCHMARK.json`` (a configuration under a
traffic mix)::

    python3 chipbench/run.py --workload retrieval_1m.poisson --seed 7 \
        --seconds 20 --trace 0

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by name: ``configs/<name>.json``,
``traffic/<name>.json`` and ``metrics/<name>.py``.
"""
