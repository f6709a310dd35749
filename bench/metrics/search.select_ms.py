"""Milliseconds per search sample in the acquisition's device round trip
(program span ``ribbon.select``)."""

from bench.program_spans import select_ms as read  # noqa: F401
