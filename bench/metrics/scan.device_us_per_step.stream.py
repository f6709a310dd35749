"""Device microseconds per scan step of the streamed lane
(mtwnd-stream)."""

from bench.readers import device_us_per_step as read  # noqa: F401
