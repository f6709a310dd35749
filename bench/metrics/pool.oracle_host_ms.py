"""Milliseconds per QoS oracle call (program span ``pool.eval``) outside
its waits on the device."""

from bench.program_spans import oracle_host_ms as read  # noqa: F401
