"""The benchmark's yardstick: everything a later change may not edit.

``bench/run.py`` is the entry point. The modules here load a cell by its
name in ``BENCHMARK.json`` and find its configuration, traffic mix (with
the arrival shape, input kind and solver it names), correctness limits
and per-layer metric readers as files of their own.
"""
