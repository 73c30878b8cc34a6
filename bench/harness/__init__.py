"""The chip benchmark's harness: everything that is not one cell's data.

``run.py`` finds a cell by name in ``BENCHMARK.json``; the configuration,
the traffic mix and each metric's reader are files of their own under
``bench/`` (see :mod:`harness.layout`).
"""
