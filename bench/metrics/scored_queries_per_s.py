"""Candidate-queries scored per second in the one-chip capacity sweep
(candle-sweep)."""

from bench.readers import scored_queries_per_s as read  # noqa: F401
