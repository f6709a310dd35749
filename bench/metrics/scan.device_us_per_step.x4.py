"""Device microseconds per scan step of the sharded grid lane, per chip
(candle-sweep-x4)."""

from bench.readers import device_us_per_step as read  # noqa: F401
