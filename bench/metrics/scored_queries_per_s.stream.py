"""Queries of the streamed pool verified per second (mtwnd-stream), with
a bound set from its own spread."""

from bench.readers import scored_queries_per_s as read  # noqa: F401
