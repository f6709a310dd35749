"""Milliseconds of the grid lane's host staging (program span
``sim.stage``) per grid dispatch, in cells that report
scored_queries_per_s.x4."""

from bench.program_spans import stage_ms_per_dispatch as read  # noqa: F401
