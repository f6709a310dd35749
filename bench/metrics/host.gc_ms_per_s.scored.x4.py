"""Milliseconds of garbage collection (program span ``host.gc``) per
second collected, in cells that report scored_queries_per_s.x4."""

from bench.program_spans import gc_ms_per_s as read  # noqa: F401
