"""Milliseconds per search sample in the optimizer's host work: program
spans ``ribbon.ask`` and ``ribbon.tell`` outside ``ribbon.select``."""

from bench.program_spans import search_host_ms as read  # noqa: F401
