"""The repo's benchmark: one data-driven command (``run.py``), its
yardstick (traffic, FLOP and spread arithmetic, peaks, trace
reduction, plain references, the ``correct`` comparison) and the drill
that proves a cell. Nothing outside this directory and
``tests/benchmarks`` belongs to it; see ``BENCHMARK.json`` and
``PERF.md``."""
