"""Readers of per-layer metrics. Each takes what the runner observed
(``spans``: host-clock samples in ms by name; ``counters``; ``trace``:
the traced slice, see ``benchmarks/trace.py``) plus the arguments its
metric file gives, and returns a number — or ``None`` where there is
nothing to read, and the harness leaves the metric out."""
