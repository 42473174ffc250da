"""Runners, one module per traffic ``kind``: ``run(run) -> observed``
builds the system under test through the entry points a user calls,
warms it, measures the window and checks the outputs. A new kind is a
new module here, found by name."""
