"""Milliseconds of a streamed chunk's host part (program span
``sim.stream_draw``) per chunk."""

from bench.program_spans import stream_draw_ms_per_chunk as read  # noqa: F401
