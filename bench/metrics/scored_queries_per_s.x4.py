"""Candidate-queries scored per second in the capacity sweep on a
four-chip host (candle-sweep-x4), with a bound set from its own spread."""

from bench.readers import scored_queries_per_s as read  # noqa: F401
