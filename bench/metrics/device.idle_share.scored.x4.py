"""Device idle share of the traced stretch, mean over the four chips
(candle-sweep-x4)."""

from bench.readers import idle_share as read  # noqa: F401
