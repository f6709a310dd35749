"""Device microseconds per scan step of the one-chip grid lane
(candle-sweep)."""

from bench.readers import device_us_per_step as read  # noqa: F401
