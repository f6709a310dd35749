"""Device idle share of the traced stretch in mtwnd-stream."""

from bench.readers import idle_share as read  # noqa: F401
