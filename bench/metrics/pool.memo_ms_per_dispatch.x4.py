"""Milliseconds of the evaluator's memo work (program span ``pool.memo``)
per grid dispatch, in cells that report scored_queries_per_s.x4."""

from bench.program_spans import memo_ms_per_dispatch as read  # noqa: F401
