"""Device idle share of the traced stretch in cells that report
decision_s."""

from bench.readers import idle_share as read  # noqa: F401
