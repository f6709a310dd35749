"""Device idle share of the traced stretch in candle-sweep."""

from bench.readers import idle_share as read  # noqa: F401
